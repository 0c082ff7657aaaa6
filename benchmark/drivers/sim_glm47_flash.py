"""``sim_glm47_flash``: the ``sim`` driver's round (``fedml_tpu.init`` ->
``FedMLRunner.run()`` -> ``XLASimulator``, packed, one round a unit) with the
``glm4_moe_lite`` decoder, which the program builds itself: ``model: glm4_moe_lite``
and ``model_config`` (the configuration file's own keys) through
``fedml_tpu.models.create``.  The reference is ``benchmark/reference_glm47_flash.py``;
its weight layout names every leaf as the program's module does, so the map between
the two is the regrouping of ``sim_kimi_linear`` (``layers[i]`` <-> ``layer<i>``) with
the prediction module's leaves (``mtp``) beside the top ones.

Everything else is ``sim_kimi_linear.Driver``'s: the seed's weights committed to the
mesh, the round program's shapes kept for ``benchmark/scope_times.py`` (``round_fn`` /
``round_shapes``), a unit failed where the round's counters say an expert assignment
was dropped, ``default_attention()`` ``"flash"`` on the ``tpu`` backend.  A unit ALSO
fails where the round's ``mtp.positions`` is not sequences x (L - 1): a round that
trained no second loss is not this cell's."""

from __future__ import annotations

from benchmark import reference, reference_glm47_flash
from benchmark.drivers import sim_kimi_linear

TOP = sim_kimi_linear.TOP + ("mtp",)


def to_program(weights: dict) -> dict:
    params = {k: weights[k] for k in TOP if k in weights}
    params.update({f"layer{i}": w for i, w in enumerate(weights["layers"])})
    return {"params": params}


def from_program(variables: dict) -> dict:
    p = variables["params"]
    top = {k: p[k] for k in TOP if k in p}
    return {**top, "layers": [p[f"layer{i}"] for i in range(len(p) - len(top))]}


class Driver(sim_kimi_linear.Driver):
    def arguments(self) -> dict:
        arguments = super().arguments()
        arguments["model_args"] = {"model": "glm4_moe_lite", "model_config": self.model}
        return arguments

    def weights(self) -> dict:
        return reference_glm47_flash.make_weights(self.model, self.seed)

    def setup(self) -> None:
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        super().setup()  # its regrouping knows no ``mtp``: the seed's weights once more, whole
        self.sim.variables = jax.device_put(
            jax.jit(to_program)(self.weights()), NamedSharding(self.sim.mesh, P()))

    def first_units(self) -> None:
        for _ in range(int(self.traffic["check_units"])):
            self.run_unit()
            reference.record(self.program, self.sim.round_losses[-1],
                             from_program(self.sim.variables), self.weights())

    def run_unit(self) -> dict:
        unit = super().run_unit()
        if self.model["num_nextn_predict_layers"]:
            want = unit["sequences"] * (int(self.traffic["sequence_length"]) - 1)
            unit["failed"] = unit["failed"] or self.sim.round_log[-1].get("mtp.positions") != want
        return unit

    def reference_readings(self, precision: str = "highest", fault: str | None = None) -> dict:
        out = reference.new_readings()
        current = self.weights()
        cohort = reference.sampled_clients(
            0, len(self.shards), int(self.traffic["clients_per_round"]))
        for _ in range(int(self.traffic["check_units"])):
            current, loss = reference_glm47_flash.fedavg_round(
                current, self.shards, self.seed, 0, self.batch, self.lr, self.model,
                precision=precision, fault=fault, clients=cohort)
            reference.record(out, loss, current, self.weights())
        return out
