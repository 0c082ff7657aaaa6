"""``sim_nemotron_h``: the ``sim`` driver's round (``fedml_tpu.init`` -> ``FedMLRunner.run()``
-> ``XLASimulator``, packed, one round a unit) with the ``nemotron_h`` decoder, which the
program builds itself: ``model: nemotron_h`` and ``model_config`` (the configuration file's
own keys) through ``fedml_tpu.models.create``.  The reference is
``benchmark/reference_nemotron_h.py``; its weight layout names every leaf as the program's
module does, so the map between the two is ``sim_kimi_linear``'s regrouping.

Everything else is ``sim_kimi_linear.Driver``'s: the seed's weights committed to the mesh,
the round program's shapes kept for ``benchmark/scope_times.py``, a unit failed where an
expert assignment was dropped, ``default_attention()`` ``"flash"`` on the ``tpu`` backend.
A unit ALSO fails where the round's ``ssm.positions`` is not (Mamba-2 layers) x sequences
x L: a round whose scans did not run over every token is not this cell's."""

from __future__ import annotations

from benchmark import reference, reference_nemotron_h
from benchmark.drivers import sim_kimi_linear


class Driver(sim_kimi_linear.Driver):
    def arguments(self) -> dict:
        arguments = super().arguments()
        arguments["model_args"] = {"model": "nemotron_h", "model_config": self.model}
        return arguments

    def weights(self) -> dict:
        return reference_nemotron_h.make_weights(self.model, self.seed)

    def run_unit(self) -> dict:
        unit = super().run_unit()
        layers = self.model["hybrid_override_pattern"].count("M")
        scanned = layers * unit["sequences"] * int(self.traffic["sequence_length"])
        unit["failed"] = unit["failed"] or self.sim.round_log[-1].get("ssm.positions") != scanned
        return unit

    def default_scan(self) -> str:
        """``kernels`` where the Mamba-2 layers reach ``ops/ssd.py``'s Pallas kernels:
        ``ssd()`` dispatches to them on the ``tpu`` backend alone."""
        import jax

        return "kernels" if jax.default_backend() == "tpu" else "other"

    def reference_readings(self, precision: str = "highest", fault: str | None = None) -> dict:
        out = reference.new_readings()
        current = self.weights()
        cohort = reference.sampled_clients(
            0, len(self.shards), int(self.traffic["clients_per_round"]))
        for _ in range(int(self.traffic["check_units"])):
            current, loss = reference_nemotron_h.fedavg_round(
                current, self.shards, self.seed, 0, self.batch, self.lr, self.model,
                precision=precision, fault=fault, clients=cohort)
            reference.record(out, loss, current, self.weights())
        return out
