"""``sim_smallthinker``: the ``sim`` driver's round (``fedml_tpu.init`` ->
``FedMLRunner.run()`` -> ``XLASimulator``, packed, one round a unit) with the
SmallThinker decoder, which the program builds itself: ``model: smallthinker``
and ``model_config`` (the configuration file's own keys) through
``fedml_tpu.models.create``.  The reference is
``benchmark/reference_smallthinker.py``; its weight layout names every leaf as
the program's module does, so the map between the two is the regrouping of
``sim_kimi_linear`` (``layers[i]`` <-> ``layer<i>``).

Everything else is ``sim_kimi_linear.Driver``'s: the seed's weights committed to
the mesh, the round program's shapes kept for ``benchmark/scope_times.py``
(``round_fn`` / ``round_shapes``), a unit failed where the round's counters say
an expert assignment was dropped, ``default_attention()`` ``"flash"`` on the
``tpu`` backend (``ops.flash_attention.attention`` dispatches the GQA mixers to
the Pallas kernels there alone)."""

from __future__ import annotations

from benchmark import reference, reference_smallthinker
from benchmark.drivers import sim_kimi_linear


class Driver(sim_kimi_linear.Driver):
    def arguments(self) -> dict:
        arguments = super().arguments()
        arguments["model_args"] = {"model": "smallthinker", "model_config": self.model}
        return arguments

    def weights(self) -> dict:
        return reference_smallthinker.make_weights(self.model, self.seed)

    def reference_readings(self, precision: str = "highest", fault: str | None = None) -> dict:
        out = reference.new_readings()
        current = self.weights()
        cohort = reference.sampled_clients(
            0, len(self.shards), int(self.traffic["clients_per_round"]))
        for _ in range(int(self.traffic["check_units"])):
            current, loss = reference_smallthinker.fedavg_round(
                current, self.shards, self.seed, 0, self.batch, self.lr, self.model,
                precision=precision, fault=fault, clients=cohort)
            reference.record(out, loss, current, self.weights())
        return out
