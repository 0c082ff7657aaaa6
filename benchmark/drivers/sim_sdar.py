"""``sim_sdar``: the ``sim`` driver's round (``fedml_tpu.init`` -> ``FedMLRunner.run()`` ->
``XLASimulator``, packed, one round a unit) with the ``sdar_moe`` decoder trained by block
diffusion, which the program builds itself: ``model: sdar_moe`` and ``model_config`` (the
configuration file's own keys) through ``fedml_tpu.models.create``.  The reference is
``benchmark/reference_sdar.py``; its weight layout names every leaf as the program's
module does, so the map between the two is ``sim_kimi_linear``'s regrouping.

The token shards are drawn from the slice's rows below its last, the mask id, which the
traffic never draws.  ``tokens`` counts DATA tokens (L a sequence): the clean
copy the model also runs is the objective's cost, not traffic.

Everything else is ``sim_kimi_linear.Driver``'s: the seed's weights committed to the mesh,
the round program's shapes kept for ``benchmark/scope_times.py``, a unit failed where an
expert assignment was dropped, ``default_attention()`` ``"flash"`` on the ``tpu`` backend.
A unit ALSO fails where the round's ``bd.positions`` is not 2 x sequences x L, or its
``bd.masked`` is 0 or every noised position: a round that trained no block-diffusion
objective is not this cell's."""

from __future__ import annotations

from benchmark import reference, reference_sdar, traffic as traffic_mod
from benchmark.drivers import sim_kimi_linear


class Driver(sim_kimi_linear.Driver):
    def __init__(self, model: dict, traffic: dict, seed: int, chips: int, device_type: str):
        super().__init__(model, traffic, seed, chips, device_type)
        self.shards = traffic_mod.make_shards(traffic, int(model["vocab_size"]) - 1, seed)

    def arguments(self) -> dict:
        arguments = super().arguments()
        arguments["model_args"] = {"model": "sdar_moe", "model_config": self.model}
        return arguments

    def weights(self) -> dict:
        return reference_sdar.make_weights(self.model, self.seed)

    def run_unit(self) -> dict:
        unit = super().run_unit()
        log, noised = self.sim.round_log[-1], unit["sequences"] * int(self.traffic["sequence_length"])
        trained = log.get("bd.positions") == 2 * noised and 0 < log.get("bd.masked", 0) < noised
        unit["failed"] = unit["failed"] or not trained
        return unit

    def reference_readings(self, precision: str = "highest", fault: str | None = None) -> dict:
        out = reference.new_readings()
        current = self.weights()
        cohort = reference.sampled_clients(
            0, len(self.shards), int(self.traffic["clients_per_round"]))
        self.reference_masked = []
        for unit in range(int(self.traffic["check_units"])):
            current, loss, masked = reference_sdar.fedavg_round(
                current, self.shards, self.seed, 0, self.batch, self.lr, self.model,
                precision=precision, fault=fault, clients=cohort, unit=unit)
            self.reference_masked.append(masked)
            reference.record(out, loss, current, self.weights())
        return out
