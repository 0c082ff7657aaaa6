"""``sim_kimi_linear``: the ``sim`` driver's round (``fedml_tpu.init`` ->
``FedMLRunner.run()`` -> ``XLASimulator``, packed, one round a unit) with the
``kimi_linear`` decoder, which the program builds itself: ``model: kimi_linear``
and ``model_config`` (the configuration file's own keys) through
``fedml_tpu.models.create``.  The reference is
``benchmark/reference_kimi_linear.py``; its weight layout names every leaf as
the program's module does, so the map between the two is a regrouping.

A unit also fails where the round's counters say an expert assignment was
dropped (``moe.assignments_dropped`` of ``XLASimulator.round_log``).

``reference_kda`` in the traffic file picks the form in which the reference
evaluates the KDA recurrence: ``per_token`` (the default, as written) or
``by_chunks`` (the same recurrence a chunk at a time, for lengths at which
8,192 sequential steps a layer a pass would take minutes)."""

from __future__ import annotations

import numpy as np

from benchmark import reference, reference_kimi_linear
from benchmark.drivers import sim

TOP = ("embed", "final_norm", "head")


def to_program(weights: dict) -> dict:
    params = {k: weights[k] for k in TOP}
    params.update({f"layer{i}": w for i, w in enumerate(weights["layers"])})
    return {"params": params}


def from_program(variables: dict) -> dict:
    p = variables["params"]
    return {**{k: p[k] for k in TOP},
            "layers": [p[f"layer{i}"] for i in range(len(p) - len(TOP))]}


class Driver(sim.Driver):
    def arguments(self) -> dict:
        arguments = super().arguments()
        arguments["model_args"] = {"model": "kimi_linear", "model_config": self.model}
        return arguments

    def weights(self) -> dict:
        return reference_kimi_linear.make_weights(self.model, self.seed)

    def setup(self) -> None:
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        import fedml_tpu
        from fedml_tpu.arguments import Arguments

        args = fedml_tpu.init(Arguments.from_dict(self.arguments()))
        device = fedml_tpu.device.get_device(args)  # raises unless the backend is device_type
        x_all = np.concatenate([x for x, _ in self.shards])
        y_all = np.concatenate([y for _, y in self.shards])
        dataset = (len(x_all), 0, (x_all, y_all), (x_all[:1], y_all[:1]),
                   {i: len(x) for i, (x, _) in enumerate(self.shards)},
                   dict(enumerate(self.shards)), {}, self.model["vocab_size"])
        self.module = fedml_tpu.models.create(args, self.model["vocab_size"])
        self.runner = fedml_tpu.FedMLRunner(args, device, dataset, self.module)
        self.sim = self.runner.runner.sim
        if self.sim.mesh.devices.size != self.chips:
            raise RuntimeError(f"the simulator's mesh has {self.sim.mesh.devices.size} "
                               f"devices, the cell asks for {self.chips}")
        # the seed's weights in place of the simulator's own init, committed to
        # the sharding the round returns its globals under
        self.sim.variables = jax.device_put(
            jax.jit(to_program)(self.weights()), NamedSharding(self.sim.mesh, P()))
        # the shapes of the round program's first call, for benchmark/scope_times.py;
        # the first call alone passes through here, the window's go straight to the jit
        self.round_fn, self.round_shapes = self.sim._round_fn, None

        def first_call(*inputs):
            self.round_shapes = jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(np.shape(x), jax.numpy.result_type(x),
                                               sharding=getattr(x, "sharding", None)), inputs)
            self.sim._round_fn = self.round_fn
            return self.round_fn(*inputs)

        self.sim._round_fn = first_call

    def first_units(self) -> None:
        for _ in range(int(self.traffic["check_units"])):
            self.run_unit()
            reference.record(self.program, self.sim.round_losses[-1],
                             from_program(self.sim.variables), self.weights())

    def run_unit(self) -> dict:
        unit = super().run_unit()
        dropped = self.sim.round_log[-1].get("moe.assignments_dropped", 0.0)
        unit["failed"] = unit["failed"] or dropped != 0.0
        return unit

    def release(self) -> None:
        super().release()
        self.round_fn = None

    def default_attention(self) -> str:
        """``flash`` where the MLA layers reach ``ops/flash_attention.py``'s Pallas
        kernels: ``attention()`` dispatches to them on the ``tpu`` backend alone."""
        import jax

        return "flash" if jax.default_backend() == "tpu" else "other"

    def reference_readings(self, precision: str = "highest", fault: str | None = None) -> dict:
        out = reference.new_readings()
        current = self.weights()
        cohort = reference.sampled_clients(
            0, len(self.shards), int(self.traffic["clients_per_round"]))
        for _ in range(int(self.traffic["check_units"])):
            current, loss = reference_kimi_linear.fedavg_round(
                current, self.shards, self.seed, 0, self.batch, self.lr, self.model,
                precision=precision, fault=fault, clients=cohort,
                kda_form=self.traffic.get("reference_kda", "per_token"))
            reference.record(out, loss, current, self.weights())
        return out
