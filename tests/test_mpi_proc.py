"""Multi-process MPI-parity simulator (simulation/mpi_proc): OS-process
ranks over the ProcessGroup host plane, reference ``simulation/mpi``
semantics (workers train their strided share, one weighted reduce per
round).  The ranks place themselves on the host CPU (mpi_sim._rank_entry)."""

import numpy as np
import pytest

pytestmark = pytest.mark.heavy  # spawns full jax processes


CFG = {
    "common_args": {"training_type": "simulation", "random_seed": 0,
                    "run_id": "mpiproc"},
    "data_args": {"dataset": "mnist", "data_cache_dir": "",
                  "partition_method": "hetero", "partition_alpha": 0.5,
                  "synthetic_train_size": 640},
    "model_args": {"model": "lr"},
    "train_args": {"federated_optimizer": "FedAvg", "client_num_in_total": 6,
                   "client_num_per_round": 4, "comm_round": 3, "epochs": 1,
                   "batch_size": 32, "client_optimizer": "sgd",
                   "learning_rate": 0.1, "backend": "MPI_PROC"},
    "validation_args": {"frequency_of_the_test": 1},
    "comm_args": {"backend": "MPI_PROC"},
    "tracking_args": {"enable_wandb": False, "log_file_dir": "./log"},
}


def _run_world(world_size):
    import fedml_tpu

    return fedml_tpu.run_mpi_simulation(CFG, world_size)


def test_two_rank_round_learns():
    metrics = _run_world(2)
    assert metrics and metrics["test_acc"] > 0.5, metrics


def test_matches_single_process():
    """The strided-share + weighted-allreduce aggregate must equal the
    1-rank run exactly (same sampling, same trainers, float tolerance)."""
    m1 = _run_world(1)
    m3 = _run_world(3)
    assert m1 and m3
    # metrics are rounded to 4 decimals and float32 summation order differs
    # between 1 and 3 ranks: allow one rounding step of slack
    assert abs(m1["test_loss"] - m3["test_loss"]) <= 2e-4, (m1, m3)
    assert abs(m1["test_acc"] - m3["test_acc"]) <= 1e-3, (m1, m3)


def test_unsupported_configs_fail_loud():
    """Algorithm zoo / security matrix don't run here — fail, don't silently
    degrade to plain FedAvg (reference parity lives on sp / XLA)."""
    import copy

    import fedml_tpu
    from fedml_tpu.arguments import Arguments
    from fedml_tpu.core.security.fedml_defender import FedMLDefender
    from fedml_tpu.simulation.mpi_proc import MPIProcessSimulator

    cfg = copy.deepcopy(CFG)
    cfg["train_args"]["federated_optimizer"] = "SCAFFOLD"
    args = fedml_tpu.init(Arguments.from_dict(cfg).validate(),
                          should_init_logs=False)
    args.mpi_rank, args.mpi_world_size = 0, 1
    dataset, out_dim = fedml_tpu.data.load(args)
    model = fedml_tpu.models.create(args, out_dim)
    with pytest.raises(NotImplementedError, match="FedAvg/FedProx"):
        MPIProcessSimulator(args, dataset, model)

    cfg2 = copy.deepcopy(CFG)
    args2 = fedml_tpu.init(Arguments.from_dict(cfg2).validate(),
                           should_init_logs=False)
    args2.mpi_rank, args2.mpi_world_size = 0, 1
    args2.enable_defense = True
    args2.defense_type = "krum"
    FedMLDefender._defender_instance = None
    FedMLDefender.get_instance().init(args2)
    try:
        with pytest.raises(NotImplementedError, match="attack/defense"):
            MPIProcessSimulator(args2, dataset, model)
    finally:
        FedMLDefender._defender_instance = None
