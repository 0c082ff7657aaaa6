"""Multi-process simulation: the reference's MPI rank plane, process-real.

Parity with reference ``simulation/mpi/fedavg/`` (mpi4py ranks: rank 0
aggregates, workers train their share of each round's clients and reduce
through ``MPI.COMM_WORLD``): here each rank is an OS PROCESS joined through
the host-plane :class:`~fedml_tpu.core.distributed.collective.ProcessGroup`
(TCP star collectives — the transport role torch.distributed/mpi4py play),
and the per-client local training inside each rank is the same compiled
trainer the sp loop uses.

This is the multi-PROCESS counterpart of the in-mesh simulator: Parrot-XLA
(``simulation/xla``) is the TPU path (ranks -> mesh axis, allreduce -> psum
over ICI, zero processes); this module is the reference implementation of
the ``mpirun -np N`` workflow, kept for parity.  Placement is explicit:
every spawned rank trains on the HOST CPU (pinned before the rank's first
backend use), because a chip belongs to one process — N ranks on a chip
host would otherwise all reach for the same chip and every rank after the
first would fail or hang.  Only model-sized blobs ride the host plane, once
per round.

Determinism contract: every rank derives the same per-round client sample
(``core/sampling.client_sampling``), takes the strided slice
``sampled[rank::world]``, and the weighted allreduce-mean reproduces the
single-process FedAvg aggregate exactly (tested in
tests/test_mpi_proc.py::test_matches_single_process).
"""

from __future__ import annotations

import logging
import time
from typing import Any, Dict, List, Tuple

import jax
import numpy as np

from ...core.distributed.collective import ProcessGroup
from ...core.sampling import client_sampling
from ...utils.metrics import MetricsLogger

logger = logging.getLogger(__name__)


class MPIProcessSimulator:
    """One rank of the multi-process round.  ``args`` needs
    ``proc_rank_in_silo``-style fields: ``mpi_rank``, ``mpi_world_size``,
    ``pg_master_address``/``pg_master_port`` (rank 0 hosts the hub)."""

    def __init__(self, args, dataset, model, client_trainer=None):
        self.args = args
        (
            self.train_num, _test_num, train_global, self.test_global,
            self.local_num_dict, self.local_train_dict, _lt, self.class_num,
        ) = dataset
        self.rank = int(getattr(args, "mpi_rank", 0))
        self.world = int(getattr(args, "mpi_world_size", 1))
        # honest surface: this backend implements the weighted-mean family
        # only (FedAvg + the engine's proximal hook); the algorithm zoo
        # (incl. FedSGD, whose server averages GRADIENTS, not parameters)
        # and the attack/defense matrix ride sp or the in-mesh simulator
        opt = str(getattr(args, "federated_optimizer", "FedAvg")).lower()
        if opt not in ("fedavg", "fedprox"):
            raise NotImplementedError(
                f"backend MPI_PROC supports FedAvg/FedProx, not {opt!r}; "
                "use backend 'sp' or 'XLA' for the algorithm zoo"
            )
        from ...core.security.fedml_attacker import FedMLAttacker
        from ...core.security.fedml_defender import FedMLDefender

        if (FedMLAttacker.get_instance().is_attack_enabled()
                or FedMLDefender.get_instance().is_defense_enabled()):
            raise NotImplementedError(
                "backend MPI_PROC has no attack/defense hooks; use 'sp' or 'XLA'"
            )
        addr = (str(getattr(args, "pg_master_address", "127.0.0.1")),
                int(getattr(args, "pg_master_port", 29600)))
        token = str(getattr(args, "pg_token", None)
                    or f"{getattr(args, 'run_id', '0')}-mpi")
        self.pg = ProcessGroup(
            self.rank, self.world, addr=addr, token=token,
            timeout=float(getattr(args, "pg_timeout", 60.0)),
            op_timeout=float(getattr(args, "pg_op_timeout", 1800.0)),
        )
        if client_trainer is None:
            from ...ml.trainer.trainer_creator import create_model_trainer

            client_trainer = create_model_trainer(model, args)
        self.trainer = client_trainer
        if self.rank == 0 and self.trainer.get_model_params() is None:
            # rank 0 owns the round-0 init it broadcasts (reference: the MPI
            # server process initializes the global model)
            import jax.numpy as jnp

            from ...ml.engine.train import init_variables

            self.trainer.set_model_params(init_variables(
                model, jnp.asarray(train_global[0][:1]),
                seed=int(getattr(args, "random_seed", 0)),
            ))
        from ...ml.aggregator.aggregator_creator import create_server_aggregator

        self.aggregator = create_server_aggregator(model, args)
        self.metrics = MetricsLogger(args)

    def train(self) -> Dict[str, Any]:
        args = self.args
        comm_round = int(args.comm_round)
        cpr = int(args.client_num_per_round)
        n_total = int(args.client_num_in_total)
        freq = int(getattr(args, "frequency_of_the_test", 10))
        # rank 0's init is everyone's round-0 model (reference: server
        # broadcasts the global model at round start)
        params = self.pg.broadcast(
            self.trainer.get_model_params() if self.rank == 0 else None
        )
        last: Dict[str, Any] = {}
        for round_idx in range(comm_round):
            # stays on the uniform client_sampling seam (NOT a per-rank
            # PopulationManager): every rank must derive the identical
            # schedule from round_idx alone, and a state-driven policy's
            # rank-local registry would diverge across ranks
            sampled = client_sampling(round_idx, n_total, cpr)
            mine = [int(c) for c in sampled[self.rank :: self.world]]
            acc_tree = None
            n_sum = 0.0
            for cid in mine:
                x, y = self.local_train_dict[cid]
                n_i = int(self.local_num_dict[cid])
                if n_i <= 0:
                    continue
                self.trainer.set_model_params(params)
                self.trainer.set_id(cid)
                self.trainer.round_idx = round_idx
                # the full ClientTrainer hook contract (local DP noise lives
                # in on_after_local_training — skipping it would silently
                # aggregate un-noised updates with DP reported as on)
                self.trainer.on_before_local_training((x, y), None, args)
                self.trainer.train((x, y), None, args)
                self.trainer.on_after_local_training((x, y), None, args)
                w_i = self.trainer.get_model_params()
                w_i = jax.tree_util.tree_map(
                    lambda t: np.asarray(t, np.float32) * n_i, w_i
                )
                acc_tree = w_i if acc_tree is None else jax.tree_util.tree_map(
                    np.add, acc_tree, w_i
                )
                n_sum += n_i
            if acc_tree is None:  # more ranks than sampled clients this round
                local_mean = jax.tree_util.tree_map(
                    lambda t: np.zeros_like(np.asarray(t, np.float32)), params
                )
            else:
                local_mean = jax.tree_util.tree_map(
                    lambda t: t / n_sum, acc_tree
                )
            # every rank learns the round's total weight first (same value
            # everywhere, so the branch below stays collectively consistent);
            # a fully-empty round keeps the previous model instead of letting
            # the zero-weight mean replace it with zeros
            w_tot = float(self.pg.allreduce_sum(np.asarray(n_sum, np.float64)))
            if w_tot > 0:
                # the "MPI reduce": one weighted allreduce-mean on the host plane
                params = self.pg.allreduce_mean(local_mean, weight=n_sum)
                params = self._central_dp(params, round_idx)
            if self.rank == 0 and freq > 0 and (
                round_idx % freq == 0 or round_idx == comm_round - 1
            ):
                self.aggregator.set_model_params(params)
                stats = self.aggregator.test(self.test_global, None, args)
                last = {
                    "round": round_idx,
                    "test_acc": round(stats["test_correct"] / stats["test_total"], 4),
                    "test_loss": round(stats["test_loss"] / stats["test_total"], 4),
                }
                self.metrics.log(last)
                logger.info("mpi_proc eval: %s", last)
        self.trainer.set_model_params(params)
        self.pg.barrier()
        self.pg.close()
        return last

    def _central_dp(self, params, round_idx: int):
        """Central DP on the aggregate: rank 0 noises, then rebroadcasts so
        every rank carries the SAME noised global (per-rank noise would
        diverge the replicas)."""
        from ...core.dp.fedml_differential_privacy import FedMLDifferentialPrivacy

        dp = FedMLDifferentialPrivacy.get_instance()
        if not dp.is_global_dp_enabled():
            return params
        if self.rank == 0:
            params = jax.tree_util.tree_map(np.asarray, dp.add_global_noise(params))
        return self.pg.broadcast(params if self.rank == 0 else None)

    def run(self) -> Dict[str, Any]:
        return self.train()


def _rank_entry(cfg: Dict[str, Any], rank: int, world: int, port: int, q,
                joined) -> None:
    """Child-process entry: pin this rank to the host CPU (see the module
    docstring) before anything initializes a jax backend, rebuild
    args/data/model from the config dict (spawn-safe) and run one rank.
    ``joined`` (mp.Event) is set once this rank's ProcessGroup rendezvous
    succeeded — the parent's retry logic keys on it."""
    from ...utils.platform import force_cpu_backend

    force_cpu_backend()
    import fedml_tpu
    from ...arguments import Arguments

    args = fedml_tpu.init(Arguments.from_dict(cfg).validate(),
                          should_init_logs=False)
    args.mpi_rank = rank
    args.mpi_world_size = world
    args.pg_master_port = port
    dataset, out_dim = fedml_tpu.data.load(args)
    model = fedml_tpu.models.create(args, out_dim)
    sim = MPIProcessSimulator(args, dataset, model)  # PG joins in here
    joined.set()
    metrics = sim.train()
    q.put((rank, metrics))


class _RanksDiedError(RuntimeError):
    def __init__(self, msg: str, rendezvous_done: bool):
        super().__init__(msg)
        self.rendezvous_done = rendezvous_done


def run_mpi_simulation(config: Dict[str, Any], world_size: int, port: int = 0,
                       deadline_s: float = 3600.0,
                       retries: int = 2) -> Dict[str, Any]:
    """The ``mpirun -np N`` replacement: spawn ``world_size`` rank processes
    from one nested config dict and return rank 0's final metrics.

    ``deadline_s`` bounds the whole run (size it to the job — non-toy models
    pay per-rank XLA compiles); per-collective timeouts come from the
    config's ``pg_timeout``/``pg_op_timeout``.  The ranks run on the host
    CPU whatever the parent holds, so a parent that has already initialized
    a jax backend (even the chip) is safe to call this.  Auto-picked ports
    (``port=0``) are probed then released, which is inherently racy against
    other processes on the host — a failed rendezvous retries on a fresh
    port up to ``retries`` times; pass an explicit reserved ``port`` for
    deterministic placement."""
    for attempt in range(int(retries) + 1):
        try:
            return _run_once(config, world_size, port, deadline_s)
        except _RanksDiedError as e:
            # only a crash BEFORE every rank finished rendezvous smells like
            # a port race; a world that died mid-training is a real failure —
            # re-spawning it would triple time-to-failure and bury the
            # actual traceback
            if attempt == retries or port != 0 or e.rendezvous_done:
                raise
            logger.warning("mpi ranks died during rendezvous (possible port "
                           "race); retrying on a fresh port")
    raise AssertionError("unreachable")


def _run_once(config: Dict[str, Any], world_size: int, port: int,
              deadline_s: float) -> Dict[str, Any]:
    import multiprocessing as mp
    import queue as _queue
    import socket
    import time

    if port == 0:
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
    ctx = mp.get_context("spawn")
    q = ctx.Queue()
    joined = [ctx.Event() for _ in range(world_size)]
    procs = [
        ctx.Process(target=_rank_entry,
                    args=(config, r, world_size, port, q, joined[r]))
        for r in range(world_size)
    ]
    for p in procs:
        p.start()
    results: Dict[int, Any] = {}
    deadline = time.time() + float(deadline_s)
    try:
        while len(results) < world_size:
            try:
                rank, metrics = q.get(timeout=5)
                results[rank] = metrics
            except _queue.Empty:
                dead = [p.exitcode for p in procs
                        if not p.is_alive() and p.exitcode not in (0, None)]
                if dead:
                    # fail FAST on a crashed rank instead of starving on the
                    # queue until the deadline
                    raise _RanksDiedError(
                        f"mpi rank process(es) died: {dead}",
                        rendezvous_done=all(e.is_set() for e in joined),
                    )
                if time.time() > deadline:
                    raise TimeoutError("mpi simulation timed out")
    finally:
        for p in procs:
            p.join(timeout=60)
            if p.is_alive():
                p.terminate()
    return results.get(0, {})
