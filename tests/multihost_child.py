"""Child process for the multi-host (2-process jax.distributed) round test.

Run as:  python multihost_child.py <rank> <coordinator_port>
Env must set JAX_PLATFORMS=cpu and XLA_FLAGS device-count BEFORE jax loads
(the parent test does this via the subprocess env).  Prints one final line
``MHOK <round_norm> <defended_norm>`` consumed by the parent.
"""

import os
import sys


def main(rank: int, port: str) -> None:
    os.environ["FEDML_JAX_COORDINATOR"] = f"127.0.0.1:{port}"
    os.environ["FEDML_JAX_NUM_PROCESSES"] = "2"
    os.environ["FEDML_JAX_PROCESS_ID"] = str(rank)

    import numpy as np

    import fedml_tpu
    from fedml_tpu.arguments import Arguments

    def build_args(**over):
        args = Arguments.from_dict({
            "common_args": {"training_type": "simulation", "random_seed": 0,
                            "run_id": "mh"},
            "data_args": {"dataset": "mnist", "data_cache_dir": "",
                          "partition_method": "homo",
                          "synthetic_train_size": 128},
            "model_args": {"model": "lr"},
            "train_args": {"federated_optimizer": "FedAvg",
                           "client_num_in_total": 16,
                           "client_num_per_round": 16, "comm_round": 2,
                           "epochs": 1, "batch_size": 16,
                           "client_optimizer": "sgd", "learning_rate": 0.1},
            "validation_args": {"frequency_of_the_test": 0},
            "comm_args": {"backend": "XLA"},
        })
        for k, v in over.items():
            setattr(args, k, v)
        return args.validate()

    args = fedml_tpu.init(build_args(), should_init_logs=False)
    import jax

    assert jax.process_count() == 2, jax.process_count()
    assert len(jax.devices()) == 8, len(jax.devices())

    from fedml_tpu import data, models
    from fedml_tpu.simulation.xla.fed_sim import XLASimulator

    def norm(sim):
        return sum(float(np.sum(np.abs(np.asarray(l))))
                   for l in jax.tree_util.tree_leaves(sim.variables))

    dataset, out_dim = data.load(args)
    model = models.create(args, out_dim)
    sim = XLASimulator(args, dataset, model)
    sim.train()
    plain = norm(sim)

    # the security path: the per-client update stack stays P('client')-
    # sharded (NOT fully addressable from either process) and the stacked
    # attack + robust-aggregation program consumes it with global
    # semantics — the multi-host-safety claim, executed for real
    from fedml_tpu.core.security.fedml_attacker import FedMLAttacker
    from fedml_tpu.core.security.fedml_defender import FedMLDefender

    args3 = build_args(enable_attack=True,
                       attack_type="byzantine", attack_mode="random",
                       byzantine_client_num=2, enable_defense=True,
                       defense_type="krum")
    FedMLAttacker._attacker_instance = None
    FedMLDefender._defender_instance = None
    args3 = fedml_tpu.init(args3, should_init_logs=False)
    try:
        sim3 = XLASimulator(args3, dataset, model)
        sim3.train()
        defended = norm(sim3)
    finally:
        FedMLAttacker._attacker_instance = None
        FedMLDefender._defender_instance = None

    print(f"MHOK {plain:.6f} {defended:.6f}", flush=True)


if __name__ == "__main__":
    main(int(sys.argv[1]), sys.argv[2])
