"""``sim``: the in-mesh FedAvg round behind the normal entry points —
``fedml_tpu.init`` -> ``FedMLRunner(args, device, dataset, model).run()`` ->
``XLASimulator.train()``, ``backend: XLA``, ``xla_pack: true``, eval off.

A unit is one round: one ``run()`` with ``comm_round: 1``.  The simulator's
cohort and batch order are functions of the round index, its model carries
over between calls, so every call trains the same clients' rows in the same
order from where the last call left the global model.  Nothing compiles after
the first call as long as the first call's weights arrive committed to the
mesh the round returns them on (an uncommitted input made the second call
compile the round program again: 0.8 s at the tiny size on the CPU, PR 24).
"""

from __future__ import annotations

import numpy as np

from benchmark import reference, traffic as traffic_mod
from benchmark.drivers import flax_lm


class Driver:
    unit = "round"

    def __init__(self, model: dict, traffic: dict, seed: int, chips: int, device_type: str):
        self.model, self.traffic, self.seed, self.chips = model, traffic, int(seed), chips
        self.device_type = device_type
        self.shards = traffic_mod.make_shards(traffic, model["vocab_size"], seed)
        self.batch = int(traffic["batch_sequences"])
        self.lr = float(traffic["learning_rate"])
        self.program = reference.new_readings()

    # -- set-up -------------------------------------------------------------
    def arguments(self) -> dict:
        n = len(self.shards)
        compute = {"bfloat16": "bf16", "float32": "fp32"}[self.model["compute_dtype"]]
        return {
            "common_args": {"training_type": "simulation", "random_seed": self.seed,
                            "run_id": "benchmark"},
            "data_args": {"dataset": "benchmark_tokens"},
            "model_args": {"model": "transformer_lm", "compute_dtype": compute},
            "train_args": {"federated_optimizer": "FedAvg", "client_num_in_total": n,
                           "client_num_per_round": int(self.traffic["clients_per_round"]),
                           "xla_pack": True, "comm_round": 1, "epochs": 1,
                           "batch_size": self.batch, "client_optimizer": "sgd",
                           "learning_rate": self.lr},
            "validation_args": {"frequency_of_the_test": 0},
            "device_args": {"device_type": self.device_type},
            "comm_args": {"backend": "XLA"},
            # the obs plane on: obs.compile_seconds_total() counts, and the
            # round span writes its TraceAnnotation into the profiler's trace
            "tracking_args": {"using_mlops": True, "obs_trace": True},
        }

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
        self.module = flax_lm.build_module(self.model)
        self.runner = fedml_tpu.FedMLRunner(args, device, dataset, self.module)
        self.sim = self.runner.runner.sim
        if self.sim.mesh.devices.size != self.chips:
            raise RuntimeError(f"the simulator's mesh has {self.sim.mesh.devices.size} "
                               f"devices, the cell asks for {self.chips}")
        # the seed's weights in place of the simulator's own init, committed to
        # the sharding the round returns its globals under
        weights = traffic_mod.make_weights(self.model, self.seed)
        self.sim.variables = jax.device_put(
            flax_lm.to_program(weights), NamedSharding(self.sim.mesh, P()))
        del weights

    def first_units(self) -> None:
        """Warm-up of the cell's one stream shape, through the window's own
        call; their results are what the reference is held against once the
        window has closed."""
        for _ in range(int(self.traffic["check_units"])):
            self.run_unit()
            reference.record(self.program, self.sim.round_losses[-1],
                             flax_lm.from_program(self.sim.variables),
                             traffic_mod.make_weights(self.model, self.seed))

    # -- the window ---------------------------------------------------------
    def run_unit(self) -> dict:
        self.runner.run()  # blocks until the new global model is ready
        loss = self.sim.round_losses[-1]
        return {"tokens": self.sim.samples_per_round[-1] * int(self.traffic["sequence_length"]),
                "sequences": self.sim.samples_per_round[-1],
                "program_seconds": self.sim.round_times[-1],
                "failed": not np.isfinite(loss)}

    def default_attention(self) -> str:
        """``flash`` where the timed model's blocks reach ``ops/flash_attention.py``'s
        Pallas kernels: its ``attention_fn`` is the module's default,
        ``causal_attention``, which dispatches to them on the ``tpu`` backend
        and nowhere else."""
        import jax

        from fedml_tpu.models import transformer

        default = self.module.attention_fn is transformer.causal_attention
        return "flash" if default and jax.default_backend() == "tpu" else "other"

    def compile_seconds(self) -> float:
        from fedml_tpu.core import obs

        return obs.compile_seconds_total()

    def release(self) -> None:
        from fedml_tpu.core import mlops

        mlops.finish()
        self.sim = self.runner = None

    # -- the reference, once the window has closed ---------------------------
    def reference_readings(self, precision: str = "highest", fault: str | None = None) -> dict:
        out = reference.new_readings()
        current = traffic_mod.make_weights(self.model, self.seed)
        # every call of the window is round 0 of a one-round run: one cohort
        cohort = reference.sampled_clients(
            0, len(self.shards), int(self.traffic["clients_per_round"]))
        for _ in range(int(self.traffic["check_units"])):
            current, loss = reference.fedavg_round(
                current, self.shards, self.seed, 0, self.batch, self.lr, self.model,
                precision=precision, fault=fault, clients=cohort)
            reference.record(out, loss, current, traffic_mod.make_weights(self.model, self.seed))
        return out
