"""TRUE multi-process execution of the compiled FL round: two
``jax.distributed`` processes (4 virtual CPU devices each) form ONE global
8-device mesh and run the SAME XLASimulator program — psum/all_gather ride
gloo across the process boundary, exactly how a multi-host TPU pod run is
wired (``fedml_tpu.init`` does the ``jax.distributed`` bootstrap from the
FEDML_JAX_* env).  This upgrades the multi-host story from "compiles with
global semantics" (the driver dryrun) to "executes across processes with
identical results"."""

from __future__ import annotations

import os
import subprocess
import sys

import pytest

from netutil import free_port

pytestmark = pytest.mark.heavy

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.abspath(os.path.join(HERE, os.pardir))


def _spawn(rank: int, port: int) -> subprocess.Popen:
    env = {
        **{k: v for k, v in os.environ.items() if k not in ("PYTHONPATH", "XLA_FLAGS")},
        # the children must init the CPU backend with THEIR forced device
        # count, not inherit this process's
        "PYTHONPATH": REPO,
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
    }
    return subprocess.Popen(
        [sys.executable, os.path.join(HERE, "multihost_child.py"), str(rank), str(port)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
    )


def test_two_process_round_executes_and_agrees():
    port = free_port()
    procs = [_spawn(r, port) for r in (0, 1)]
    outs = []
    for p in procs:
        try:
            out, err = p.communicate(timeout=420)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        assert p.returncode == 0, f"rank failed:\n{out}\n{err}"
        line = [l for l in out.splitlines() if l.startswith("MHOK")]
        assert line, f"no MHOK line:\n{out}\n{err}"
        outs.append(tuple(float(x) for x in line[0].split()[1:]))

    # both processes computed the identical global model (the round AND the
    # defended round whose P('client') update stack is not fully addressable
    # from either process)
    assert len(outs[0]) == 2, outs
    assert outs[0] == outs[1], outs


# Single-process oracle in its own test so a multihost failure is
# distinguishable from an oracle failure.
def test_single_process_oracle_matches_two_process():
    port = free_port()
    procs = [_spawn(r, port) for r in (0, 1)]
    mh = None
    for p in procs:
        out, err = p.communicate(timeout=420)
        assert p.returncode == 0, f"rank failed:\n{out}\n{err}"
        line = [l for l in out.splitlines() if l.startswith("MHOK")][0]
        mh = tuple(float(x) for x in line.split()[1:])

    import jax
    import numpy as np

    import fedml_tpu
    from fedml_tpu.arguments import Arguments
    from fedml_tpu.simulation.xla.fed_sim import XLASimulator

    def build(**over):
        args = Arguments.from_dict({
            "common_args": {"training_type": "simulation", "random_seed": 0,
                            "run_id": "mh-oracle"},
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

    def norm(sim):
        return sum(float(np.sum(np.abs(np.asarray(l))))
                   for l in jax.tree_util.tree_leaves(sim.variables))

    args = fedml_tpu.init(build(), should_init_logs=False)
    dataset, out_dim = fedml_tpu.data.load(args)
    model = fedml_tpu.models.create(args, out_dim)
    sim = XLASimulator(args, dataset, model)  # conftest's 8 local devices
    sim.train()
    np.testing.assert_allclose(norm(sim), mh[0], rtol=1e-6)

    # defended (stacked attack + krum) oracle: cross-process agreement alone
    # would also pass for an identically-wrong result — pin it to the
    # single-process run of the same program
    from fedml_tpu.core.security.fedml_attacker import FedMLAttacker
    from fedml_tpu.core.security.fedml_defender import FedMLDefender

    args3 = build(enable_attack=True, attack_type="byzantine",
                  attack_mode="random", byzantine_client_num=2,
                  enable_defense=True, defense_type="krum")
    FedMLAttacker._attacker_instance = None
    FedMLDefender._defender_instance = None
    args3 = fedml_tpu.init(args3, should_init_logs=False)
    try:
        sim3 = XLASimulator(args3, dataset, model)
        sim3.train()
        np.testing.assert_allclose(norm(sim3), mh[1], rtol=1e-6)
    finally:
        FedMLAttacker._attacker_instance = None
        FedMLDefender._defender_instance = None
