"""fedml_tpu — a TPU-native federated / distributed learning framework.

Brand-new implementation of the capability surface of FedML (reference
``python/fedml/__init__.py``), designed for JAX/XLA/pjit/pallas on TPU:

* **Simulation ("Parrot")**: in-process loop (sp) or the XLA in-mesh
  simulator — clients sharded over a ``jax.sharding.Mesh``, aggregation via
  ``lax.psum`` over ICI (successor of the reference's MPI/NCCL simulators).
* **Cross-silo ("Octopus")**: host-side gRPC/loopback message plane driving
  the same round protocol; intra-silo parallelism is a pjit mesh, not DDP.
* **Cross-device ("Beehive")**: server runtime + device protocol harness.
* core/: comm kernel, DP, security (attacks/defenses), MPC (SecAgg), topology,
  scheduling, MLOps-style observability.

Public API parity: ``fedml_tpu.init``, ``fedml_tpu.run_simulation``,
``fedml_tpu.run_cross_silo_server/client``, ``fedml_tpu.FedMLRunner``,
``fedml_tpu.data.load``, ``fedml_tpu.model.create``, ``device.get_device``.
"""

from __future__ import annotations

import logging
import os
import random as _random
import time

import numpy as _np

__version__ = "0.1.0"

from . import constants  # noqa: F401
from .arguments import Arguments, load_arguments
from .runner import FedMLRunner  # noqa: F401
from . import data, device, models  # noqa: E402,F401  (public parity: fedml.data/.model/.device)

_logger = logging.getLogger(__name__)


def init(args: Arguments | None = None, should_init_logs: bool = True) -> Arguments:
    """Bootstrap (reference ``__init__.py:27-93``): load config, seed RNGs,
    init security/DP singletons, per-platform setup."""
    t_init = time.perf_counter()
    if args is None:
        args = load_arguments()
    if hasattr(args, "validate"):
        # validation is part of init, not an optional extra step: config
        # errors must surface HERE, and validate() also injects
        # cross-backend defaults (e.g. FedProx's mu) that every launch
        # path must see.  Idempotent, so pre-validated args are fine.
        args.validate(for_training=bool(getattr(args, "training_type", None)))
    if should_init_logs:
        logging.basicConfig(
            level=logging.INFO, format="[%(asctime)s %(name)s] %(message)s"
        )

    from .core import mlops as _mlops
    from .utils.platform import configure_compilation_cache

    # before the first compile of any entry point; logged so a run says
    # where its compiled programs are kept
    _logger.info("jax compilation cache: %s", configure_compilation_cache())
    _mlops.pre_setup(args)
    if getattr(args, "using_mlops", False):
        _mlops.init(args)

    # multi-host mesh bootstrap (role of reference init_simulation_mpi /
    # torchrun env parsing + NCCL pg init, __init__.py:96,228-246): when a
    # coordinator is configured, join the jax.distributed cluster so
    # jax.devices() spans every host's chips and the same Mesh/shard_map
    # code runs pod-scale — collectives ride ICI within a slice and DCN
    # across hosts, inserted by XLA from the sharding annotations.
    coord = getattr(args, "jax_coordinator_address", None) or os.environ.get(
        "FEDML_JAX_COORDINATOR"
    )
    if coord:
        import jax as _jax

        # explicit args keys win over env (same convention as the cross-silo
        # env parse below) — and 0 is a VALID process id, so test `is None`
        n_proc = getattr(args, "jax_num_processes", None)
        if n_proc is None:
            n_proc = int(os.environ.get("FEDML_JAX_NUM_PROCESSES", 0) or 0)
        n_proc = int(n_proc)
        pid = getattr(args, "jax_process_id", None)
        if pid is None:
            pid = int(os.environ.get("FEDML_JAX_PROCESS_ID", 0) or 0)
        pid = int(pid)
        # idempotent: a process calling init() again (new Arguments, second
        # simulator) must not re-bootstrap the cluster
        if not _jax.distributed.is_initialized():
            _jax.distributed.initialize(
                coordinator_address=str(coord),
                num_processes=n_proc or None,
                process_id=pid if n_proc else None,
            )
            _logger.info("jax.distributed up: proc %d/%s via %s", pid, n_proc, coord)

    # multi-process-silo cross-silo: a launcher (torchrun-style or the
    # example main.py spawner) places each silo process by env — parse it
    # HERE so one config file serves every process of the silo (reference
    # init_cross_silo_hierarchical reads the torchrun env the same way,
    # __init__.py:217,228-246).  Gated on the platform, NOT on
    # scenario=='hierarchical': the adapter's pg plane activates on
    # n_proc_in_silo > 1 for any scenario, and n_proc itself may arrive by
    # env.  Explicit args keys win over env; empty env values are ignored.
    if str(getattr(args, "training_type", "")) == "cross_silo":
        for attr, envs in (
            ("proc_rank_in_silo", ("FEDML_PROC_RANK_IN_SILO", "LOCAL_RANK")),
            ("n_proc_in_silo", ("FEDML_N_PROC_IN_SILO", "LOCAL_WORLD_SIZE")),
        ):
            if getattr(args, attr, None) is None:
                for e in envs:
                    if os.environ.get(e):
                        setattr(args, attr, int(os.environ[e]))
                        break
        if getattr(args, "pg_master_address", None) is None and os.environ.get("MASTER_ADDR"):
            args.pg_master_address = os.environ["MASTER_ADDR"]
        if getattr(args, "pg_master_port", None) is None and os.environ.get("MASTER_PORT"):
            args.pg_master_port = int(os.environ["MASTER_PORT"])

    seed = int(getattr(args, "random_seed", 0))
    _random.seed(seed)
    # run-entry global seeding is the ONE approved global-RNG seam (the
    # reference does the same in fedml.init); library code must use local
    # generators — tools/lint_rng.py enforces this
    _np.random.seed(seed)  # lint_rng: allow

    from .core.dp.fedml_differential_privacy import FedMLDifferentialPrivacy
    from .core.security.fedml_attacker import FedMLAttacker
    from .core.security.fedml_defender import FedMLDefender

    FedMLAttacker.get_instance().init(args)
    FedMLDefender.get_instance().init(args)
    FedMLDifferentialPrivacy.get_instance().init(args)

    if not hasattr(args, "client_id_list"):
        # reference update_client_id_list (:265): synthesize [1..N]
        n = int(getattr(args, "client_num_in_total", 0) or 0)
        args.client_id_list = list(range(1, n + 1))
    # init runs before obs.configure, so no span can time it: the registry
    # is up by now and keeps its seconds (set-up's share of this call)
    from .core import obs as _obs

    _obs.gauge_set("startup.init_seconds", time.perf_counter() - t_init)
    _logger.info("fedml_tpu %s initialized (training_type=%s backend=%s)",
                 __version__, getattr(args, "training_type", None), getattr(args, "backend", None))
    return args


def run_simulation(backend: str = "sp") -> None:
    """One-liner (reference ``launch_simulation.py:9``)."""
    from . import data as _data_mod
    from . import device as _device_mod
    from . import models as _models_mod
    from .constants import FEDML_TRAINING_PLATFORM_SIMULATION

    args = load_arguments(FEDML_TRAINING_PLATFORM_SIMULATION, backend)
    args.training_type = FEDML_TRAINING_PLATFORM_SIMULATION
    args.backend = getattr(args, "backend", None) or backend
    args = init(args)
    device = _device_mod.get_device(args)
    dataset, output_dim = _data_mod.data_loader.load(args)
    model = _models_mod.hub.create(args, output_dim)
    runner = FedMLRunner(args, device, dataset, model)
    runner.run()


def run_mpi_simulation(config, world_size: int, port: int = 0,
                       deadline_s: float = 3600.0, retries: int = 2):
    """``mpirun -np N`` replacement (reference MPI simulator workflow): spawn
    ``world_size`` rank processes over the host-plane ProcessGroup and return
    rank 0's metrics.  ``config``: nested args dict (the YAML shape).

    Call from under ``if __name__ == "__main__":`` — ranks are spawned
    multiprocessing children, which re-import the caller's main module (the
    standard Python spawn contract; an unguarded top-level call would
    recursively re-launch itself in every child)."""
    from .simulation.mpi_proc import run_mpi_simulation as _run

    return _run(config, world_size, port=port, deadline_s=deadline_s,
                retries=retries)


def run_cross_silo_server() -> None:
    from .launch_cross_silo import run_cross_silo

    run_cross_silo(role="server")


def run_cross_silo_client() -> None:
    from .launch_cross_silo import run_cross_silo

    run_cross_silo(role="client")


def run_device_server():
    """Cross-device (Beehive) server one-liner (reference ``run_mnn_server``)."""
    from .launch_cross_device import run_device_server as _run

    return _run()


run_mnn_server = run_device_server
