"""Parrot-XLA: the in-mesh federated-learning simulator (north-star component).

TPU-native successor of the reference's NCCL simulator
(``simulation/nccl/base_framework/``): there, rank-0 Server broadcasts the
global model over torch.distributed, per-GPU LocalAggregators sequentially
simulate their scheduled clients (``LocalAggregator.py:69-124``) and reduce
into the server (``common.py:196-210``).  Here the whole round collapses into
ONE compiled XLA program over a ``Mesh``:

* broadcast  -> implicit replication of the global variables;
* per-GPU LocalAggregator loop -> one packed stream of batches per device
  (client axis sharded with shard_map), walked by a ``lax.while_loop`` whose
  trip count is the device's own step count (ml/engine/packed.py, which
  alone knows how a round's client data is laid out and walked);
* local SGD epochs -> steps of that stream; at a client's last step the carry
  flushes into the weighted sum and resets to the round-start state;
* ``fedml_nccl_reduce`` -> weighted on-device accumulation + ``lax.psum``
  over the 'client' axis riding ICI;
* the Server/LocalAggregator role split disappears: no host round-trips
  inside a round, weights never leave HBM.

Client heterogeneity under static shapes: a client contributes
ceil(n_i/B) batches an epoch, so its own padding is at most B-1 samples,
masked from loss and updates; the stream buffers are sized for the worst
case and trimmed to a bucket of the round's real step count; rounds whose
sampled-client count doesn't fill devices evenly pad with weight-0 dummy
slots that add no step.  Static greedy balancing of clients->devices by
step count (core/schedule) evens the devices' trip counts.

The algorithm zoo rides this same compiled round via in-mesh strategies
(algorithms.py): FedAvg/FedProx/FedSGD/FedOpt/FedNova/SCAFFOLD/FedDyn/
buffered-async all compile to ONE XLA program — per-step grad hooks, extra
per-client contributions psum'd alongside the weighted model sum, control
variates in HBM client-state tables, and the server step traced after the
psum (reference ``simulation/mpi/*`` parity, SURVEY.md §2.5).
"""

from __future__ import annotations

import json
import logging
import os
import time
import warnings
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ...core import obs
from ...core.dp.fedml_differential_privacy import FedMLDifferentialPrivacy
from ...core.obs.scopes import table_json
from ...core.schedule import RuntimeEstimator, SeqTrainScheduler
from ...core.security.fedml_attacker import FedMLAttacker
from ...core.security.fedml_defender import FedMLDefender
from ...ml.engine.packed import build_packed_device_fn, pack_round, s_max_for, trim_to_bucket
from ...ml.engine.train import init_variables
from ...parallel.mesh import create_fl_mesh, create_round_mesh
from ...utils.metrics import MetricsLogger
from .algorithms import create_inmesh_algorithm

logger = logging.getLogger(__name__)

# enable_profiler traces the second to fourth rounds a train() call runs
# (rounds 1-3 of a fresh run; the first round compiles) and stops
PROFILED_ROUNDS = (1, 3)


def _abstract(x) -> jax.ShapeDtypeStruct:
    """A round input's shape, dtype and, where it is committed to one, sharding."""
    sharding = x.sharding if getattr(x, "committed", False) else None
    return jax.ShapeDtypeStruct(np.shape(x), jnp.result_type(x), sharding=sharding)


def _compile_anew(lowered):
    """Compile ``lowered`` past both of jax's caches, for the metadata of THIS program:
    an inert option keeps the executable jax holds in memory out of it, and with the
    metadata in its key the persistent cache holds no entry that another program wrote."""
    key = "jax_compilation_cache_include_metadata_in_key"
    before = getattr(jax.config, key)
    jax.config.update(key, True)
    try:
        return lowered.compile(compiler_options={"xla_dump_max_hlo_modules": 1})
    finally:
        jax.config.update(key, before)


class _Phase:
    """One timed phase: ``with _Phase(record, name, parent) as ph`` opens the
    obs span ``name`` (``annotate=True``: on the profiler's host line, on the
    device trace's clock), times the body with ONE pair of
    ``time.perf_counter`` reads and gives that number both to the span's
    ``duration_s`` and to ``record[<last part of name>_s]`` — the record is
    filled whether or not obs is configured.  ``ph.attrs`` collects what
    the body learns, for the span's end record."""

    __slots__ = ("record", "key", "span", "attrs", "t0")

    def __init__(self, record: Dict[str, Any], name: str, parent=None,
                 round_idx: int = None, seq: int = 0):
        self.record, self.key = record, name.rpartition(".")[2] + "_s"
        self.attrs: Dict[str, Any] = {}
        self.span = obs.span(name, parent, round_idx=round_idx, seq=seq,
                             annotate=True)

    @property
    def ctx(self):
        return self.span.ctx

    def __enter__(self) -> "_Phase":
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        dt = time.perf_counter() - self.t0
        self.record[self.key] = dt
        self.span.end(duration_s=dt, **self.attrs)


class _Run(NamedTuple):
    """What one ``train()`` call holds for all of its rounds."""

    comm_round: int
    ckpt: Any         # the checkpointer, or None
    tele_cap: Any     # loop-back client telemetry and its merger, or None
    tele_merger: Any


class _Cohort(NamedTuple):
    """What ``round.select`` decides and the later phases read."""

    sampled: np.ndarray       # the cohort as drawn
    staleness: Dict[int, int]  # fl_mode=async: staleness by client id
    ids: np.ndarray           # [n_dev * slots] scheduled ids, dummy slots too
    counts: np.ndarray        # their sample counts, 0 on a dummy slot
    participated: np.ndarray  # f32: 1 where the compiled round trains the slot
    cex: Any                  # the algorithm's per-slot inputs


class XLASimulator:
    def __init__(self, args, dataset, model, mesh: Mesh = None):
        # start-up's seconds by phase (sim.build and its children), kept like
        # round_log for whoever reads set-up without an obs sink
        self.startup_log: Dict[str, float] = {}
        with _Phase(self.startup_log, "sim.build") as build:
            self._build(args, dataset, model, mesh, build.ctx)

    def _build(self, args, dataset, model, mesh, build_ctx):
        self.args = args
        (
            self.train_num,
            self.test_num,
            self.train_global,
            self.test_global,
            self.local_num_dict,
            self.local_train_dict,
            _local_test_dict,
            self.class_num,
        ) = dataset
        self.module = model
        self.mesh = mesh if mesh is not None else create_fl_mesh()
        self.n_dev = self.mesh.devices.size

        self.num_clients = int(args.client_num_in_total)
        self.clients_per_round = int(args.client_num_per_round)
        self.batch_size = int(getattr(args, "batch_size", 32))

        # Security layer: the round can return the per-client update stack
        # (sharded over the client axis); a second jitted program then runs
        # stacked model attacks + robust aggregation + the algorithm's server
        # step on it (core/security/stacked.py) — updates never touch the
        # host, which also keeps the path multi-host safe (P('client') leaves
        # are not fully addressable under jax.distributed).  Data-poisoning
        # attacks stamp at pack time, where each client's shard is assembled.
        attacker = FedMLAttacker.get_instance()
        defender = FedMLDefender.get_instance()
        self.defended = defender.is_defense_enabled()
        self.model_attacked = attacker.is_model_attack()
        # analysis-primitive attacks (dlg / invert_gradient / revealing
        # labels) read ONE intercepted per-client update off the round's
        # sharded stack — reference fedml_attacker.py:28-30 runs the whole
        # matrix through one simulator path; so does this backend now
        self.analysis_attacked = attacker.is_analysis_attack()
        if (attacker.is_attack_enabled() and not self.model_attacked
                and not self.analysis_attacked
                and not attacker.is_data_poisoning_attack()):
            # fail loud rather than report clean-FedAvg metrics as an
            # attack-experiment result
            raise NotImplementedError(
                f"attack_type {attacker.attack_type!r} has no XLA-backend hook"
            )
        self.needs_stack = (self.defended or self.model_attacked
                            or self.analysis_attacked)
        # every engine loss family runs in-mesh: the loss key is plumbed
        # into the compiled round and eval goes through the task-aware
        # aggregator.  Tag prediction's int->multi-hot conversion happens
        # host-side at pack time (_pack_data), so it rides the bce loss.
        from ...ml.trainer.trainer_creator import _TAG_DATASETS, loss_kind_for_dataset

        ds = str(getattr(args, "dataset", "")).lower()
        self._multihot_labels = ds in _TAG_DATASETS
        self.loss_kind = "bce" if self._multihot_labels else loss_kind_for_dataset(ds)

        with _Phase(self.startup_log, "sim.pack_data", build_ctx):
            self._pack_data()
        with _Phase(self.startup_log, "sim.init_variables", build_ctx):
            sample = jnp.asarray(self.train_global[0][:1])
            self.variables = init_variables(
                model, sample, seed=int(getattr(args, "random_seed", 0)))
        self.algo = create_inmesh_algorithm(args)
        self.server_state = self.algo.init_server_state(self.variables)
        self.client_state = self.algo.init_client_state(self.num_clients, self.variables)
        self.agg_plane = str(getattr(args, "agg_plane", "host") or "host")
        if self.agg_plane not in ("host", "compiled"):
            raise ValueError(
                f"agg_plane must be host|compiled (got {self.agg_plane!r})")
        from ...core.aggregate import server_state_mode

        self.sharded_state = server_state_mode(args) == "sharded"
        self._model_bytes = int(sum(
            l.size * l.dtype.itemsize
            for l in jax.tree_util.tree_leaves(self.variables)))
        # sharded_state composes with the security tail: the round and the
        # security program each end at the psum'd accumulator and the
        # model-sharded GSPMD tail applies the server step
        with _Phase(self.startup_log, "sim.build_round_fn", build_ctx):
            self._build_packed_round_fn()
            if self.needs_stack:
                self._build_security_fn()
            if self.sharded_state:
                self._build_server_tail()

        self._build_population()
        from ...ml.aggregator.aggregator_creator import create_server_aggregator

        self.aggregator = create_server_aggregator(model, args)
        self.metrics = MetricsLogger(args)
        self.round_times: List[float] = []
        self.round_losses: List[float] = []
        self.samples_per_round: List[int] = []
        # one dict a round: the wall time (the round_times entry), the host
        # phases that split it, and what the round carried — filled with obs
        # off too, as round_times is
        self.round_log: List[Dict[str, Any]] = []
        self.samples_trained = 0
        self._rng = jax.random.PRNGKey(int(getattr(args, "random_seed", 0)) + 11)

    def _build_population(self):
        """Who trains when: the scheduler and its runtime model, the
        population's selection policy, and the async arrival queue."""
        args = self.args
        self.runtime_estimator = RuntimeEstimator(self.n_dev, uniform_devices=True)
        self.scheduler = SeqTrainScheduler(self.n_dev, estimator=self.runtime_estimator)
        # population subsystem: fleet registry + selection policy; the
        # uniform policy is bit-identical to the legacy client_sampling
        # schedule (mt19937), so default configs are unchanged
        from ...core.population import PopulationManager, stacked_cohorts

        try:
            samples = [int(self.local_num_dict[i]) for i in range(self.num_clients)]
        except (KeyError, IndexError, TypeError):
            samples = None
        self.population = PopulationManager.from_args(
            self.args, np.arange(self.num_clients), num_samples=samples,
            rng_style="mt19937",
        )
        # opt-in Parrot-scale path: the whole run's cohorts in ONE vectorized
        # draw (10^5-10^6 virtual clients with no per-round host choice) —
        # a different schedule from the per-round seeded draw, hence gated
        self._stacked_schedule = None
        if bool(getattr(args, "population_stacked", False)):
            self._stacked_schedule = stacked_cohorts(
                self.num_clients, self.clients_per_round,
                int(getattr(args, "comm_round", 1)),
                seed=int(getattr(args, "random_seed", 0)),
            )
        # buffered-async execution (fl_mode=async): a host-side virtual
        # arrival queue decides each flush's cohort + staleness; the
        # FedBuffInMesh strategy turns them into discounted weights in-mesh
        self.async_mode = str(
            getattr(args, "fl_mode", "sync") or "sync").lower() == "async"
        if self.async_mode:
            from .async_arrivals import VirtualArrivals

            self._arrivals = VirtualArrivals(
                args, self.num_clients, self.clients_per_round, self._client_sampling(0))

    # ------------------------------------------------------------------
    # data packing: one global HBM-resident array + per-client index table
    # ------------------------------------------------------------------
    def _pack_data(self):
        """Concatenate client shards into one HBM-resident array pair and
        record each client's contiguous row range in a host index table — a
        round uploads row indices only and gathers its batches on device."""
        counts = np.array([self.local_num_dict[i] for i in range(self.num_clients)], np.int32)
        self.max_client_n = int(counts.max())
        xs, ys = [], []
        idx = np.zeros((self.num_clients, max(1, self.max_client_n)), np.int32)
        cursor = 0
        attacker = FedMLAttacker.get_instance()
        poisoning = attacker.is_data_poisoning_attack()
        for i in range(self.num_clients):
            xi, yi = self.local_train_dict[i]
            if poisoning:
                # data side of the attack matrix stamps HERE, where each
                # malicious client's shard is assembled (the XLA round then
                # trains on poisoned HBM rows with zero extra hooks) —
                # reference fedml_attacker.poison_data called per client
                xi, yi = attacker.poison_local_data(i, self.num_clients, xi, yi)
                xi, yi = np.asarray(xi), np.asarray(yi)
            if self._multihot_labels and np.asarray(yi).ndim == 1:
                # tag prediction with int class ids: one-hot for the bce
                # loss (mounted multi-label sets already arrive multi-hot)
                yi = np.eye(self.class_num, dtype=np.float32)[np.asarray(yi)]
            n = len(yi)
            xs.append(np.asarray(xi))
            ys.append(np.asarray(yi))
            idx[i, :n] = np.arange(cursor, cursor + n, dtype=np.int32)
            cursor += n
        self._client_rows = idx  # pack_round reads a client's first n_i entries
        self.client_counts = jnp.asarray(counts)
        from ...models.hub import data_storage_dtype

        # bf16 storage halves the per-step gather traffic (the measured #1
        # round cost) whenever the model casts its input to bf16 anyway —
        # the gathered batch is then bitwise-identical to the fp32 path.
        # Only FLOAT data participates: integer inputs are token/class ids
        # (transformer Embed requires integers) and keep their dtype.
        x_np = np.concatenate(xs, 0)
        if np.issubdtype(x_np.dtype, np.floating):
            x_np = x_np.astype(data_storage_dtype(self.args, self.module))
        # committed ONCE to the mesh-replicated sharding the round takes them
        # under (in_specs P()): a bare jnp.asarray is an uncommitted array on
        # device 0 that every round's call would copy to the other devices
        repl = NamedSharding(self.mesh, P())
        self.x_all = jax.device_put(x_np, repl)
        self.y_all = jax.device_put(np.concatenate(ys, 0), repl)
        logger.info(
            "packed %d clients (max_n=%d) data %s (%s) into HBM",
            self.num_clients, self.max_client_n, self.x_all.shape, self.x_all.dtype,
        )

    # ------------------------------------------------------------------
    # the compiled round
    # ------------------------------------------------------------------
    def _ldp_hook(self):
        """Pure per-client noise fn when local DP is enabled (the mechanism's
        add_noise is jax-traceable), else None."""
        dp = FedMLDifferentialPrivacy.get_instance()
        if not dp.is_local_dp_enabled():
            return None
        mechanism = dp.mechanism
        return lambda tree, key: mechanism.add_noise(tree, key)

    def _build_server_tail(self):
        """server_state=sharded: the algorithm's server step as its own
        GSPMD jit program on a ``(client=1, model)`` round mesh.  Global
        variables and server-optimizer state live between rounds as
        ``NamedSharding`` arrays partitioned along the ``model`` axis (the
        :func:`~fedml_tpu.parallel.sharding.param_spec` heuristic picks the
        largest divisible dim per leaf); the psum'd accumulator is resharded
        onto the same layout and variables/state/acc buffers are DONATED, so
        the tail updates the globals in place with no replicated copy.  The
        training round itself is untouched (client-axis shard_map) — only
        the memory-bound round tail is model-sharded."""
        from ...parallel.sharding import param_spec

        devices = list(np.asarray(self.mesh.devices).flat)
        smp = int(getattr(self.args, "server_model_parallel", 0) or 0)
        if smp:
            if smp > len(devices):
                # degrade-to-replicate, mirroring the message plane's
                # round_mesh_for: a request the surviving mesh can't satisfy
                # runs the tail replicated instead of refusing the round
                logger.warning(
                    "server_model_parallel=%d exceeds the %d mesh devices; "
                    "degrading to a replicated (model=1) server tail",
                    smp, len(devices))
                obs.counter_inc("mesh.degraded_total")
                smp = 1
            devices = devices[:smp]
        rmesh = create_round_mesh(clients=1, model=len(devices),
                                  devices=devices)
        model = int(rmesh.shape["model"])
        repl = NamedSharding(rmesh, P())

        def shard_of(tree):
            return jax.tree_util.tree_map(
                lambda l: NamedSharding(
                    rmesh, param_spec(tuple(np.shape(l)), model, axis="model")),
                tree)

        var_sh = shard_of(self.variables)
        state_sh = shard_of(self.server_state)
        # the round fn replicates its inputs; when the tail runs on a device
        # subset its outputs must hop back to the full mesh between rounds
        self._tail_subset = len(devices) != self.n_dev
        self._tail_shardings = (var_sh, state_sh, repl)
        algo = self.algo

        def tail(variables, server_state, acc, wsum, ext):
            with jax.named_scope("fed.server_step"):
                return algo.server_update(acc, wsum, ext, variables, server_state)

        self._server_tail = jax.jit(
            tail, donate_argnums=(0, 1, 2),
            in_shardings=(var_sh, state_sh, var_sh, repl, repl),
            out_shardings=(var_sh, state_sh))

    def _build_packed_round_fn(self):
        """The round program (one per simulator): ml/engine/packed.py's
        per-device stream under shard_map, the psum over the client axis and,
        unless another program takes the round from there (the security
        program, the model-sharded tail), the algorithm's server step.
        benchmark/tests build it on a bare object: it reads ``args``,
        ``module``, ``mesh``, ``n_dev``, ``clients_per_round``, ``batch_size``,
        ``max_client_n``, ``needs_stack``, ``sharded_state``, ``loss_kind`` and
        ``algo`` and sets ``slots``, ``s_max`` and ``_round_fn``."""
        mesh = self.mesh
        algo = self.algo
        self.slots = -(-self.clients_per_round // self.n_dev)
        self.s_max = s_max_for(
            self.max_client_n, self.slots, self.batch_size,
            int(getattr(self.args, "epochs", 1)),
        )
        self._seen_buckets = set()  # stream buckets a round has compiled
        # the abstract call of the newest bucket's first round, and the table
        # round_scopes() makes of it on request
        self._round_signature = self._round_scopes = None
        stacked = self.needs_stack
        sharded = self.sharded_state
        device_fn = build_packed_device_fn(
            self.module, self.args, algo, self.batch_size, self.slots,
            loss=self.loss_kind,
            post_train=self._ldp_hook(),
            capture_updates=stacked,
        )

        def fedml_round_packed(variables, server_state, x_all, y_all, idx, mask,
                               boundary, weight, slot, n_steps, rngs, cex):
            # arrays with a [n_dev, ...] leading axis arrive as [1, ...]
            acc, wsum, lsum, cnt, ext, outs, counters = device_fn(
                variables, server_state, x_all, y_all, idx[0], mask[0],
                boundary[0], weight[0], slot[0], n_steps[0], rngs[0], cex,
            )
            with jax.named_scope("fed.exchange"):
                lsum = jax.lax.psum(lsum, "client")
                cnt = jax.lax.psum(cnt, "client")
                ext = jax.lax.psum(ext, "client")
                # the round's sums of what the module counts a step: a module
                # that names any (``round_counters``) gets them back last,
                # beside the loss; for every other the results are as they were
                counted = (jax.lax.psum(counters, "client"),) if counters else ()
            mean_loss = lsum / jnp.maximum(cnt, 1.0)
            if stacked:
                return (mean_loss, outs, ext) + counted
            with jax.named_scope("fed.exchange"):
                acc = jax.lax.psum(acc, "client")
                wsum = jax.lax.psum(wsum, "client")
            if sharded:
                # program ends at the reduced accumulator; the model-sharded
                # tail applies the server step
                return (acc, wsum, ext, mean_loss, outs) + counted
            with jax.named_scope("fed.server_step"):
                new_global, new_state = algo.server_update(
                    acc, wsum, ext, variables, server_state
                )
            return (new_global, new_state, mean_loss, outs) + counted

        if stacked:
            out_specs = (P(), P("client"), P())
        elif sharded:
            out_specs = (P(), P(), P(), P(), P("client"))
        else:
            out_specs = (P(), P(), P(), P("client"))
        if getattr(self.module, "round_counters", ()):
            out_specs += (P(),)
        self._round_fn = jax.jit(
            shard_map(
                fedml_round_packed,
                mesh=mesh,
                in_specs=(P(), P(), P(), P(), P("client"), P("client"), P("client"),
                          P("client"), P("client"), P("client"), P("client"), P("client")),
                out_specs=out_specs,
                check_vma=False,
            )
        )

    def _build_security_fn(self):
        """ONE jitted program for the round's security tail: stacked model
        attacks -> robust aggregation -> the algorithm's server step, consuming
        the round's sharded per-client update stack directly (no host
        materialization; multi-host safe under jax.distributed because jit
        handles the non-addressable P('client') leaves with global semantics).
        Mirrors ServerAggregator.on_before_aggregation/aggregate/
        defend_after_aggregation (reference fedml_attacker.py:28-30 +
        fedml_defender.py hook order)."""
        from jax.flatten_util import ravel_pytree

        from ...core.security import defense_funcs as DF
        from ...core.security.stacked import (
            build_stacked_attack,
            build_stacked_defense,
            stack_to_mat,
        )

        algo = self.algo
        via_acc = algo.aggregates_via_acc
        sharded = self.sharded_state
        use_plane = self.agg_plane == "compiled"
        attacker = FedMLAttacker.get_instance()
        defender = FedMLDefender.get_instance()
        attack_fn = (build_stacked_attack(self.args, attacker.attack_type)
                     if self.model_attacked else None)
        defend_fn = None
        if self.defended:
            probe_mask = None
            probe = getattr(defender, "_soteria_probe", None)
            if probe is not None:
                feature_fn, xs = probe
                probe_mask = DF.soteria_mask(
                    DF.soteria_scores(feature_fn, xs),
                    float(getattr(self.args, "soteria_percentile", 10.0)),
                )
            defend_fn = build_stacked_defense(
                self.args, defender.defense_type, probe_mask=probe_mask,
                rows=not via_acc,
            )
        self._defense_type = defender.defense_type if self.defended else None
        self._defense_state = None
        self._defense_n = -1

        def security_round(stack, weights, real_idx, mal_mask, meta, prev_global,
                           server_state, ext, key, dstate):
            sub = jax.tree_util.tree_map(lambda t: t[real_idx], stack)
            w = weights
            ka, kd = jax.random.split(key)
            g32 = jax.tree_util.tree_map(
                lambda v: v.astype(jnp.float32), prev_global
            )
            if via_acc:
                if attack_fn is not None:
                    g_vec, unravel = ravel_pytree(g32)
                    mat = attack_fn(stack_to_mat(sub), w, g_vec, mal_mask, ka)
                    sub = jax.vmap(unravel)(mat)
                if defend_fn is not None:
                    agg, dstate = defend_fn(sub, w, g32, kd, dstate)
                elif use_plane:
                    # the plane's sequential fold — same left-to-right order
                    # as the host weighted_mean, so the simulator's compiled
                    # security tail matches the server paths bit-for-bit
                    from ...parallel.agg_plane import stacked_reduce

                    agg = stacked_reduce(
                        sub, w / jnp.maximum(jnp.sum(w), 1e-9))
                else:
                    agg = jax.tree_util.tree_map(
                        lambda s: jnp.tensordot(w, s.astype(jnp.float32), axes=1)
                        / jnp.maximum(jnp.sum(w), 1e-9),
                        sub,
                    )
                # hand the robust aggregate to the algorithm's server step as
                # a weighted sum (every acc strategy divides by wsum)
                wsum = jnp.sum(w)
                acc = jax.tree_util.tree_map(lambda t: t * wsum, agg)
                if sharded:
                    # model-sharded state: the defended reduce stops at the
                    # accumulator and the GSPMD server tail applies the step
                    # (same two-program split as the undefended sharded round)
                    return acc, wsum, ext, dstate
                new_global, new_server_state = algo.server_update(
                    acc, wsum, ext, prev_global, server_state
                )
                return new_global, new_server_state, dstate
            # ext-aggregating strategies (FedNova, async): the attacked/
            # defended row space replaces the round's in-stream contribution
            # accumulation — ext is recomputed from the defended rows via the
            # strategy's own per-client math (sp composition: defenses filter
            # the update list, THEN the aggregator runs on the survivors)
            g_vec, unravel = ravel_pytree(g32)
            mat = stack_to_mat(sub)
            if attack_fn is not None:
                mat = attack_fn(mat, w, g_vec, mal_mask, ka)
            w2 = w
            if defend_fn is not None:
                sub2 = jax.vmap(unravel)(mat) if attack_fn is not None else sub
                mat, w2, dstate = defend_fn(sub2, w, g32, kd, dstate)
            ext2 = algo.ext_from_rows(mat, w2, w, meta, g_vec, unravel)
            # contract-complete acc (the defended weighted sum); strategies
            # that only read ext leave it to XLA's dead-code elimination
            acc = unravel(w2 @ mat)
            if sharded:
                return acc, jnp.sum(w2), ext2, dstate
            new_global, new_server_state = algo.server_update(
                acc, jnp.sum(w2), ext2, prev_global, server_state
            )
            return new_global, new_server_state, dstate

        self._security_fn = jax.jit(security_round)

    def _ensure_defense_state(self, n_real: int):
        if not self.defended:
            return {}
        if self._defense_state is None or self._defense_n != n_real:
            from ...core.security.stacked import flat_dim, init_defense_state

            # cross-round per-slot state (foolsgold history, wbc prev) is
            # positional; a changed participant count resets it, matching the
            # host dispatcher's shape-mismatch reset
            self._defense_state = init_defense_state(
                self._defense_type, n_real, flat_dim(self.variables)
            )
            self._defense_n = n_real
        return self._defense_state

    def _packed_inputs(self, ids: np.ndarray, counts: np.ndarray, round_idx: int):
        """The round's stream, trimmed to its bucket and uploaded: the six
        arrays between ``y_all`` and the device keys in the round program's
        arguments."""
        sched = trim_to_bucket(pack_round(
            ids.reshape(self.n_dev, self.slots), counts.reshape(self.n_dev, self.slots),
            lambda cid: self._client_rows[cid],
            self.batch_size, int(getattr(self.args, "epochs", 1)),
            int(getattr(self.args, "random_seed", 0)), round_idx, self.s_max), self.s_max)
        self._s_bucket = sched.idx.shape[1]
        self._steps_max = max(int(sched.n_steps.max()), 1)
        # first round at a new bucket shape pays an XLA recompile: flag it so
        # the round keeps that wall time out of the runtime model's fit
        self._bucket_compiling = self._s_bucket not in self._seen_buckets
        self._seen_buckets.add(self._s_bucket)
        self._h2d_bytes = sum(int(a.nbytes) for a in sched)
        # how often the stream's inner loop runs and how often it ends a client
        self._stream_counts = {"round.local_steps": int(sched.n_steps.sum()),
                               "round.client_boundaries": int(sched.boundary.sum())}
        return tuple(jnp.asarray(a) for a in sched)

    def _client_steps(self, n: int) -> int:
        """A client's cost in the round's native unit: compiled steps
        (ceil(n/B) per epoch) — the quantity the while_loop actually runs."""
        if n <= 0:
            return 0
        return -(-int(n) // self.batch_size) * int(getattr(self.args, "epochs", 1))

    def _schedule(self, sampled: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """Balance sampled clients across mesh slots via core/schedule
        (SeqTrainScheduler; runtime-model-aware once rounds have been
        observed).  Returns (client_ids [C_pad], is_real [C_pad]) laid out so
        that reshape(n_dev, -1) gives each device its contiguous schedule.

        Cost units match what the round executes: the stream runs
        ceil(n/B)*E steps per client (a 1-sample client costs a whole batch
        step), so LPT balances on STEP counts and the runtime model is fed
        the same unit (see the record() call in _close)."""
        sizes = [self._client_steps(self.local_num_dict[int(c)]) for c in sampled]
        ids2d, mask2d, _ = self.scheduler.schedule(sampled, sizes)
        return ids2d.reshape(-1), mask2d.reshape(-1)

    def _client_sampling(self, round_idx: int) -> np.ndarray:
        if self._stacked_schedule is not None:
            return self._stacked_schedule[round_idx % len(self._stacked_schedule)]
        return np.asarray(
            self.population.select(round_idx, self.clients_per_round), np.int64
        )

    def train(self) -> Dict[str, Any]:
        # train()'s own preamble and tail on the same line as the rounds: the
        # benchmark pays them once a round (a unit is one run())
        with obs.span("sim.train", None, seq=len(self.round_times),
                      annotate=True):
            return self._train()

    def _train(self) -> Dict[str, Any]:
        from ...core.checkpoint import maybe_checkpointer

        comm_round = int(self.args.comm_round)
        last: Dict[str, Any] = {}
        ckpt = maybe_checkpointer(self.args)
        start_round = 0
        if ckpt is not None and ckpt.latest_step() is not None:
            from flax import serialization

            step, state = ckpt.restore()
            self.variables = state["variables"]
            self._rng = jnp.asarray(state["rng"])
            if "server_state" in state:
                self.server_state = serialization.from_state_dict(
                    self.server_state, state["server_state"]
                )
            if self.client_state is not None and "client_state" in state:
                self.client_state = serialization.from_state_dict(
                    self.client_state, state["client_state"]
                )
            if "algo_host_state" in state:
                self.algo.restore_host_state(state["algo_host_state"])
            if self.defended and state.get("defense_state"):
                # cross-round defense state (foolsgold history, wbc prev):
                # without it a resumed run silently re-pardons attenuated
                # sybils / loses the perturbation baseline
                self._defense_state = {
                    k: jnp.asarray(v) for k, v in state["defense_state"].items()
                }
                self._defense_n = int(state.get("defense_n", -1))
            start_round = step + 1
            logger.info("resumed from checkpoint round %d", step)
        # enable_profiler: a bounded device trace (TensorBoard/XProf-viewable)
        # of PROFILED_ROUNDS, with the round.* phase spans on its host line
        prof_dir = None
        if bool(getattr(self.args, "enable_profiler", False)):
            prof_dir = str(getattr(self.args, "profiler_dir", "")
                           or os.path.join(
                               str(getattr(self.args, "log_file_dir", ".") or "."),
                               "xla_trace"))
        prof_first, prof_last = (start_round + r for r in PROFILED_ROUNDS)
        profiling = False
        # in-process loopback telemetry (cohort-level: the in-mesh round has
        # no per-client wall times, so the remote "client.train" leg covers
        # the whole cohort's execute time) — keeps the trace_report shape
        # identical between simulation and distributed runs
        run = _Run(comm_round, ckpt, obs.make_client_telemetry(0),
                   obs.make_telemetry_merger())
        try:
            for round_idx in range(start_round, comm_round):
                if prof_dir is not None and round_idx == prof_first:
                    jax.profiler.start_trace(prof_dir)
                    profiling = True
                    logger.info("jax profiler trace of rounds %d-%d -> %s",
                                prof_first, prof_last, prof_dir)
                evaluated = self._run_round(round_idx, run)
                if evaluated is not None:
                    last = evaluated
                if profiling and round_idx == prof_last:
                    self._stop_profiler(prof_dir)
                    profiling = False
        finally:
            if profiling:  # the run ended, or raised, inside the traced rounds
                self._stop_profiler(prof_dir)
        if prof_dir is not None and comm_round <= prof_first:
            logger.warning(
                "enable_profiler traces rounds %d-%d of a run and this one ended "
                "at round %d: nothing was traced", prof_first, prof_last,
                comm_round - 1)
        return last

    def _run_round(self, round_idx: int, run: _Run):
        """One round, split into the host phases ``round.select`` / ``pack`` /
        ``dispatch`` / ``wait`` / ``close`` (children of the ``round`` root,
        each timed once by :class:`_Phase` into the span and into
        ``round_log``).  Returns the eval record where the round evaluated."""
        rec: Dict[str, Any] = {"round": round_idx}
        t0 = time.perf_counter()
        compile_s0 = obs.compile_seconds_total()
        rsp = obs.round_span(
            round_idx, annotate=True,
            mode="simulation_xla_async" if self.async_mode else "simulation_xla")
        with _Phase(rec, "round.select", rsp.ctx, round_idx) as ph:
            cohort = self._select(round_idx)
            ph.attrs["n_sampled"] = len(cohort.sampled)
        with _Phase(rec, "round.pack", rsp.ctx, round_idx) as ph:
            sub, round_inputs = self._pack(round_idx, cohort, ph)
            rec.update(ph.attrs)
        with _Phase(rec, "round.dispatch", rsp.ctx, round_idx) as ph:
            mean_loss, outs, counters = self._dispatch(round_idx, cohort, sub, round_inputs, ph.ctx)
        with _Phase(rec, "round.wait", rsp.ctx, round_idx) as ph:
            self._wait(round_idx, cohort, outs, ph.ctx)
        # the round's wall time: its start to the new global model being ready
        dt = time.perf_counter() - t0
        with _Phase(rec, "round.close", rsp.ctx, round_idx) as ph:
            # compile-vs-execute attribution: the jax.monitoring listener accumulated every
            # backend compile this round triggered (round fn, security fn); the rest of the
            # wall time is execute + host orchestration
            compile_s = max(0.0, obs.compile_seconds_total() - compile_s0)
            loss = float(mean_loss)
            # what the host packed into the stream and what the module counted in the compiled
            # round: into round_log and the registry, the module's under its own names
            for name, value in (*self._stream_counts.items(), *counters.items()):
                rec[name] = float(value)
                obs.counter_inc(name, rec[name])
            samples, evaluated = self._close(
                round_idx, run, cohort, dt, compile_s, loss, rsp.ctx, ph.ctx)
        rec.update(wall_s=dt, compile_s=compile_s, samples=samples, loss=loss)
        self.round_log.append(rec)
        # the root ends after round.close, so the tree nests; compile_s and
        # execute_s keep their meaning (start of the round to the new model)
        rsp.end(reason="closed", loss=loss, compile_s=round(compile_s, 6),
                execute_s=round(max(0.0, dt - compile_s), 6))
        return evaluated

    def _select(self, round_idx: int) -> _Cohort:
        """``round.select``: draw the cohort, lay it over the mesh slots and
        gather what the algorithm keeps per client."""
        stal_map: Dict[int, int] = {}
        if self.async_mode:
            sampled, stal_map = self._arrivals.next_flush()
            self.algo.set_staleness(stal_map)
        else:
            sampled = self._client_sampling(round_idx)
        ids, real = self._schedule(sampled)
        counts = np.where(real > 0, np.asarray(self.client_counts)[ids], 0)
        # participation mask as the compiled round sees it: a sampled
        # client with zero local samples contributes nothing in-mesh
        participated = (counts > 0).astype(np.float32)
        cex = self.algo.gather_client_extras(self.client_state, ids, participated, round_idx)
        dp = FedMLDifferentialPrivacy.get_instance()
        if dp.is_local_dp_enabled():
            # account BEFORE the round releases anything (matching the sp path, where
            # add_noise spends before producing the noised update): budget exhaustion must
            # abort the round, not trail it
            dp.spend_budget(int(participated.sum()))
        return _Cohort(sampled, stal_map, ids, counts, participated, cex)

    def _pack(self, round_idx: int, cohort: _Cohort, ph: _Phase):
        """``round.pack``: the round's one rng split and its packed stream.
        Returns (the round's sub-key, the round program's twelve arguments)."""
        self._rng, sub = jax.random.split(self._rng)
        packed = self._packed_inputs(np.asarray(cohort.ids), cohort.counts, round_idx)
        dev_rngs = jax.random.split(jax.random.fold_in(sub, round_idx), self.n_dev)
        round_inputs = (self.variables, self.server_state, self.x_all, self.y_all,
                        *packed, dev_rngs, cohort.cex)
        ph.attrs.update(s_bucket=self._s_bucket, steps_max=self._steps_max,
                        h2d_bytes=self._h2d_bytes)
        if self._bucket_compiling:
            obs.span_event("bucket_compile", ph.ctx, round_idx=round_idx, s_bucket=self._s_bucket)
        return sub, round_inputs

    def _dispatch(self, round_idx: int, cohort: _Cohort, sub, round_inputs, ctx):
        """``round.dispatch``: call the round program and whichever program
        takes the round from where it ends — none (the server step is inside
        it), the model-sharded server tail, or the security program.  Returns
        (mean loss, per-slot algorithm outs, the module's round counters)."""
        if self._bucket_compiling:
            # a bucket's first round: keep what round_scopes() lowers the program from
            self._round_signature = jax.tree_util.tree_map(_abstract, round_inputs)
            self._round_scopes = None
        if self.needs_stack:
            # the round returns the sharded per-client update stack
            mean_loss, outs, ext, *counters = self._round_fn(*round_inputs)
            self._secure_aggregate(round_idx, cohort, sub, outs, ext, ctx)
            outs = outs["algo"]
        elif self.sharded_state:
            # the client-axis training round ends at the psum'd accumulator
            acc, wsum, ext, mean_loss, outs, *counters = self._round_fn(*round_inputs)
            with obs.span("round.server_update", ctx, round_idx=round_idx,
                          n_clients=int(cohort.participated.sum()),
                          mode="inmesh", policy=type(self.algo).__name__):
                self._apply_server_tail(acc, wsum, ext)
        else:
            self.variables, self.server_state, mean_loss, outs, *counters = self._round_fn(
                *round_inputs)
        return mean_loss, outs, (counters[0] if counters else {})

    def round_scopes(self) -> Optional[Dict[str, str]]:
        """{instruction name: op_name} of the compiled round program (the newest
        stream bucket's): what joins a device trace's op line, which names an
        event by its HLO instruction, to the program's ``jax.named_scope``s
        (``core/obs/scopes.py``).  Made on the first request, by lowering and
        compiling the round once more from its first call's signature (a hit of
        the compilation cache), and kept; a run that reads no trace never asks.
        None before the first round and where the lowering fails."""
        if self._round_scopes is None and self._round_signature is not None:
            try:
                lowered = self._round_fn.lower(*self._round_signature)
                table = obs.program_scopes(lowered.compile().as_text())
                if not any("fed.sgd" in op_name for op_name in table.values()):
                    # every packed step opens fed.sgd: this executable came out of a compile
                    # cache (its key leaves metadata out) that a program without the scope filled
                    logger.warning("round_scopes: the compilation cache handed the round an "
                                   "executable compiled under other scopes; compiling it anew")
                    table = obs.program_scopes(_compile_anew(lowered).as_text())
                self._round_scopes = table
            except Exception:  # noqa: BLE001 - telemetry: never raised into a round
                logger.warning("round_scopes: the round program could not be lowered again",
                               exc_info=True)
                self._round_signature = None  # logged once
        return self._round_scopes

    def _stop_profiler(self, prof_dir: str) -> None:
        """Stop the trace and leave ``round_scopes.json`` beside the xplane it wrote."""
        jax.profiler.stop_trace()
        path = os.path.join(prof_dir, "round_scopes.json")
        try:
            with open(path, "w") as f:
                json.dump(table_json(self.round_scopes()), f)
            logger.info("the traced round's instruction-to-scope table -> %s", path)
        except OSError:
            logger.warning("round_scopes.json could not be written to %s", prof_dir,
                           exc_info=True)

    def _apply_server_tail(self, acc, wsum, ext):
        """server_state=sharded: the algorithm's server step, by the GSPMD tail
        program on donated resident buffers, from the accumulator at which
        the round program or the security program stopped."""
        var_sh, state_sh, repl = self._tail_shardings
        t_tail = time.perf_counter()
        with warnings.catch_warnings():
            # donation is a no-op on CPU backends; expected there
            warnings.filterwarnings("ignore", message="Some donated buffers were not usable")
            self.variables, self.server_state = self._server_tail(
                jax.device_put(self.variables, var_sh),
                jax.device_put(self.server_state, state_sh),
                jax.device_put(acc, var_sh),
                jax.device_put(wsum, repl),
                jax.device_put(ext, repl),
            )
        jax.block_until_ready(self.variables)
        obs.histogram_observe(
            "server_opt.step_seconds", time.perf_counter() - t_tail,
            labels={"policy": type(self.algo).__name__, "mode": "inmesh"})
        if self._tail_subset:
            full = NamedSharding(self.mesh, P())
            self.variables = jax.device_put(self.variables, full)
            self.server_state = jax.device_put(self.server_state, full)

    def _secure_aggregate(self, round_idx: int, cohort: _Cohort, sub, outs, ext, ctx):
        """The security path: the second jitted program runs stacked model
        attacks + robust aggregation + the server step on the round's sharded
        per-client update stack, on device."""
        ids, counts = cohort.ids, cohort.counts
        stack = outs["update"]
        real_sel = np.where(counts > 0)[0]
        if real_sel.size == 0:
            return
        prev_global = self.variables  # the analysis attacks' reference
        attacker = FedMLAttacker.get_instance()
        # the malicious clients: whom a model attack corrupts, whom an analysis attack reads
        attacked = self.model_attacked or self.analysis_attacked
        bad = set(attacker.get_byzantine_idxs(self.num_clients)) if attacked else set()
        mal = np.array([float(self.model_attacked and int(ids[i]) in bad) for i in real_sel],
                       np.float32)
        dstate = self._ensure_defense_state(int(real_sel.size))
        # derive the security key from the round's sub-key, NOT by splitting the main
        # stream: the round-r data/rng layout must be identical with and without the
        # security tail (one split per round is the replayable invariant)
        skey = jax.random.fold_in(sub, 999331)
        meta = self.algo.security_meta(outs["tau"], cohort.cex, jnp.asarray(real_sel))
        sec_inputs = (stack, jnp.asarray(counts[real_sel], jnp.float32), jnp.asarray(real_sel),
                      jnp.asarray(mal), meta, self.variables, self.server_state, ext, skey, dstate)
        with obs.span("aggregate.reduce", ctx, round_idx=round_idx,
                      n_clients=int(real_sel.size), mode="inmesh"):
            if self.sharded_state:
                # defended + model-sharded: the security program stops at the robust
                # accumulator (the same two-program split the undefended sharded round uses)
                acc, wsum, ext, self._defense_state = self._security_fn(*sec_inputs)
                self._apply_server_tail(acc, wsum, ext)
            else:
                self.variables, self.server_state, self._defense_state = (
                    self._security_fn(*sec_inputs))
                jax.block_until_ready(self.variables)
        dlg_every = max(1, int(getattr(self.args, "dlg_frequency", 1)))
        if self.analysis_attacked and round_idx % dlg_every == 0:
            # privacy/analysis attack (dlg, invert_gradient, revealing_labels): run on ONE
            # intercepted update (a single model-size host pull; dlg_frequency gates the
            # per-round gradient-matching cost)
            victims = [int(i) for i in real_sel if int(ids[i]) in bad] or [int(real_sel[0])]
            row = jax.tree_util.tree_map(lambda t: t[victims[0]], stack)
            attacker.analyze_update(
                self.module, prev_global, row,
                (int(getattr(self.args, "dlg_batch_size", 1)),) + tuple(self.x_all.shape[1:]),
                self.class_num)

    def _wait(self, round_idx: int, cohort: _Cohort, outs, ctx):
        """``round.wait``: fold the per-slot outs into the client state and
        wait for the new global model."""
        ids = cohort.ids
        self.client_state = self.algo.apply_client_outs(self.client_state, ids, outs)
        self.algo.host_round_end(ids, cohort.participated, round_idx)
        if self.async_mode:
            # the flush's record span (the aggregation itself ran inside the compiled round):
            # staleness distribution + buffer shape for trace_report's async columns
            svals = list(cohort.staleness.values()) or [0]
            with obs.span("buffer.flush", ctx, round_idx=round_idx, n_deltas=len(cohort.sampled),
                          reason="full", capacity=self._arrivals.cap,
                          staleness_min=int(min(svals)),
                          staleness_mean=round(float(np.mean(svals)), 4),
                          staleness_max=int(max(svals))):
                pass
            self._arrivals.flushed()
        # central DP applies here, on the host
        dp = FedMLDifferentialPrivacy.get_instance()
        if dp.is_global_dp_enabled():
            self.variables = dp.add_global_noise(self.variables)
        jax.block_until_ready(self.variables)

    def _close(self, round_idx: int, run: _Run, cohort: _Cohort, dt: float,
               compile_s: float, loss: float, round_ctx, ctx):
        """``round.close``: the round's bookkeeping once the new global model
        is ready.  Returns (samples trained, the eval record or None)."""
        from ...core import mlops
        from ...core.checkpoint import checkpoint_frequency

        counts, participated = cohort.counts, cohort.participated
        if obs.enabled() and len(self.round_times) >= 3:
            med = float(np.median(self.round_times))
            if dt > obs.slow_round_factor() * med:
                obs.span_event("slow_round", round_ctx, round_idx=round_idx,
                               dt_s=round(dt, 4), median_s=round(med, 4))
        obs.histogram_observe("round.seconds", float(dt))
        obs.counter_inc("agg.bytes_reduced", int(participated.sum()) * self._model_bytes,
                        labels={"path": "inmesh"})
        if run.tele_cap is not None and run.tele_merger is not None:
            tctx = run.tele_cap.record_span(
                "client.train", max(0.0, dt - compile_s), parent=round_ctx,
                round_idx=round_idx, cohort=int(participated.sum()))
            if compile_s > 0.0:
                run.tele_cap.record_span(
                    "client.train.compile", compile_s, parent=tctx, round_idx=round_idx)
            run.tele_cap.sample_resources()
            tele_blob = run.tele_cap.drain()
            if tele_blob:
                run.tele_merger.merge(tele_blob)
        obs.maybe_export_metrics()
        self.round_times.append(dt)
        self.round_losses.append(loss)
        # round 0 is dominated by XLA compile, and so is the first round at a new bucket
        # shape: either would poison the runtime model's fit
        if round_idx > 0 and not self._bucket_compiling:
            # The round's wall time is set by the heaviest mesh slot: record max device
            # STEPS — the while_loop's actual trip count, so round time is genuinely
            # load-dependent and the fitted slope drives next rounds' LPT balancing (in the
            # same step units _schedule passes as costs).
            self.runtime_estimator.record(0, self._steps_max, dt)
        samples = int(counts.sum()) * int(getattr(self.args, "epochs", 1))
        self.samples_per_round.append(samples)
        self.samples_trained += samples
        self.metrics.log({"round": round_idx, "round_time_s": round(dt, 4), "train_loss": loss})
        mlops.log_round_info(run.comm_round, round_idx)
        # population accounting for the synchronous round: everyone sampled was invited and
        # reported; emits cohort_stats
        self.population.observe_round(round_idx, cohort.sampled, seconds=dt)
        last = round_idx == run.comm_round - 1
        if run.ckpt is not None and (round_idx % checkpoint_frequency(self.args) == 0 or last):
            with obs.span("round.checkpoint", ctx, round_idx=round_idx):
                self._checkpoint(round_idx, run.ckpt)
        freq = int(getattr(self.args, "frequency_of_the_test", 10))
        # freq <= 0 disables eval (throughput benches)
        if freq > 0 and (round_idx % freq == 0 or last):
            with obs.span("round.eval", ctx, round_idx=round_idx):
                return samples, self._test_global(round_idx)
        return samples, None

    def _checkpoint(self, round_idx: int, ckpt):
        from flax import serialization

        state = {"variables": self.variables, "rng": self._rng,
                 "server_state": serialization.to_state_dict(self.server_state)}
        if self.client_state is not None:
            state["client_state"] = serialization.to_state_dict(self.client_state)
        host = self.algo.host_state()
        if host:
            state["algo_host_state"] = host
        if self.defended and self._defense_state:
            state["defense_state"] = {k: np.asarray(v) for k, v in self._defense_state.items()}
            state["defense_n"] = self._defense_n
        ckpt.save(round_idx, state)

    def _test_global(self, round_idx: int) -> Dict[str, Any]:
        self.aggregator.set_model_params(self.variables)
        stats = self.aggregator.test(self.test_global, None, self.args)
        out = {
            "round": round_idx,
            "test_acc": round(stats["test_correct"] / stats["test_total"], 4),
            "test_loss": round(stats["test_loss"] / stats["test_total"], 4),
        }
        # task-specific extras (mean IoU, exact match, RMSE, ...) pass through
        for k, v in stats.items():
            if k.startswith("test_") and k not in ("test_correct", "test_total", "test_loss"):
                out[k] = round(float(v), 4)
        self.metrics.log(out)
        logger.info("eval: %s", out)
        return out

    # exposed for benchmarking
    def throughput(self) -> Dict[str, float]:
        """Steady-state throughput.  Round 0 is XLA compile and the first
        executed round pays the one-time host->HBM dataset upload, so the
        representative per-round cost is the MEDIAN over post-compile rounds
        (one-time costs amortize to nothing over a real run's hundreds of
        rounds).  NOTE: the median only isolates steady state when >= 3
        post-compile rounds ran (bench.py uses comm_round=6); with fewer,
        the upload round still weighs in.  mean_round_s keeps the
        warmup-inclusive average for comparison.  All zeros if no round ran.
        """
        times = self.round_times[1:] if len(self.round_times) > 1 else self.round_times
        samples = (
            self.samples_per_round[1:]
            if len(self.samples_per_round) > 1
            else self.samples_per_round
        )
        if not times:
            return {"rounds_per_sec": 0.0, "mean_round_s": 0.0,
                    "median_round_s": 0.0, "samples_per_sec": 0.0}
        med = float(np.median(times))
        # per-round pairing preserved: median of the per-round ratios
        sps = float(np.median([s / max(t, 1e-9) for s, t in zip(samples, times)]))
        return {
            "rounds_per_sec": 1.0 / max(med, 1e-9),
            "mean_round_s": sum(times) / len(times),
            "median_round_s": med,
            "samples_per_sec": sps,
        }
