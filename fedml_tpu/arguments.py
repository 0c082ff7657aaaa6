"""Argument / configuration system.

Capability parity with the reference's ``python/fedml/arguments.py`` (argparse
flags ``--cf --run_id --rank --local_rank --node_rank --role`` + a YAML config
whose sections are flattened onto a single ``Arguments`` object,
reference ``arguments.py:34-196``), with two native improvements:

* ``Arguments`` can be constructed programmatically from a plain dict
  (``Arguments.from_dict``) — no YAML file required, which is what the
  in-process test harness uses.
* A light validation pass (`validate()`) that checks type/enum constraints the
  reference only probes with ``hasattr`` at use sites.

The canonical YAML shape is unchanged::

    common_args:   { training_type, random_seed, ... }
    data_args:     { dataset, data_cache_dir, partition_method, partition_alpha, ... }
    model_args:    { model, ... }
    train_args:    { federated_optimizer, client_num_in_total, client_num_per_round,
                     comm_round, epochs, batch_size, client_optimizer, learning_rate, ... }
    validation_args: { frequency_of_the_test }
    device_args:   { device_type }   # cpu | gpu | tpu; device.get_device enforces it
    comm_args:     { backend, ... }
    tracking_args: { enable_wandb, log_file_dir, ... }
    fault_args:    { fault_plan, ... }

Transport-reliability knobs (``train_args`` or ``comm_args``; consumed by
``core/distributed/comm_manager.py``):

* ``comm_reliability`` (default True) — stamp every outbound message with a
  monotonic ``msg_id``, ack stamped inbound messages, and drop re-deliveries
  (idempotent receive).  Turning it off restores the raw reference wire.
* ``comm_max_retries`` (default 0) — send-side retry budget.  0 keeps the
  reference's synchronous-raise semantics; > 0 retries failed sends with
  exponential backoff + jitter AND runs a background retransmitter that
  re-sends unacked messages until acked or the budget is spent.
* ``comm_backoff_base_s`` (default 0.2) / ``comm_backoff_max_s`` (default
  2.0) / ``comm_backoff_jitter`` (default 0.25) — backoff schedule:
  ``min(base * 2^attempt, max) * (1 + jitter * U[0,1))``.
* ``comm_dedup_window`` (default 8192) — LRU size of the receive-side
  message-id dedup window.
* ``comm_backoff_seed`` (int, default = ``random_seed``, unset = legacy
  per-incarnation nonce) — seeds the retransmit jitter stream per
  ``(seed, rank)`` so schedules are deterministic ACROSS incarnations
  (a restarted cohort must not re-draw identical fresh-nonce schedules
  and synchronize its retry storm) yet distinct per rank.
* ``fault_plan`` (default None; ``fault_args`` section) — a deterministic
  chaos plan injected at the transport seam; schema in
  ``core/distributed/faults.py``.

Chunked resumable-upload knobs (``train_args`` or ``comm_args``; consumed
by ``core/distributed/chunking.py``, wire format + resume protocol in
``docs/INGEST.md``):

* ``upload_chunk_bytes`` (int >= 0, default 0 = whole-message sends) —
  split payload-bearing messages larger than this into crc32-framed
  chunks, each acked/deduped/retransmitted individually by the
  reliability layer, so a reconnecting sender resumes from its last
  acked chunk instead of restarting the upload.  Requires
  ``comm_max_retries > 0`` for the resume semantics to engage.
* ``chunk_window`` (int >= 1, default 8) — max unacked chunks in flight
  per stream; bounds both sender memory and the bytes a mid-stream link
  cut can waste.
* ``chunk_resume`` (bool, default True) — journal each accepted chunk
  before its transport ack (journal-before-ack one level down) so a
  server/edge kill mid-upload replays partial streams from disk; off
  keeps reassembly memory-only (a receiver crash re-collects from
  retransmits).
* ``chunk_buffer_bytes`` (int >= 1, default 64 MiB) — receiver-side
  reassembly budget; over it the OLDEST incomplete stream is shed (its
  sender told to restart via ``comm_chunk_reset``, the over-budget
  chunk's ack withheld).
* ``chunk_receive`` (bool, default True) — advertise chunk-receive
  capability on outbound messages.  Chunking negotiates DOWN per link:
  senders only chunk toward peers seen advertising, so legacy peers keep
  whole-message uploads in both directions.

Backend-specific resilience knobs: ``trpc_connect_retries`` /
``trpc_retry_interval_s`` (TCP), ``grpc_send_retries`` /
``grpc_send_backoff_base_s`` (gRPC), ``mqtt_reconnect_retries`` /
``mqtt_reconnect_base_s`` (broker client auto-reconnect).

Population / pacing knobs (``train_args`` or ``population_args``; consumed
by ``core/population``, semantics in ``docs/POPULATION.md``):

* ``selection_policy`` (default ``uniform``) — per-round cohort policy:
  ``uniform`` (bit-identical to the legacy schedules) | ``stratified``
  (speed strata) | ``importance`` (sample-count/staleness weighted).
* ``pacing_overcommit`` (float >= 1.0, default 1.0) — invite
  ``ceil(K * overcommit)`` clients per round.
* ``pacing_quorum`` (int >= 0, default 0 = the target ``K``) — reports
  needed to close the round when pacing is on; the deadline is the
  existing ``round_timeout_s`` timer.
* ``population_blocklist`` (list of client ids, default none) — never
  selected; must leave >= ``client_num_per_round`` clients eligible.
* ``population_strata`` (int >= 1, default 4) — stratified policy's
  stratum count.
* ``importance_alpha`` / ``importance_staleness`` (floats) — importance
  policy weights.
* ``population_stacked`` (bool, default False) — XLA simulator only:
  draw the whole run's cohorts in one vectorized call (a different,
  single-seed schedule — NOT parity with the per-round draw).

Checkpoint / crash-recovery knobs (``train_args``; consumed by
``core/checkpoint.py``, recovery semantics in ``docs/FAULT_TOLERANCE.md``):

* ``checkpoint_dir`` (default unset = disabled) — simulator round
  checkpoint/resume directory (``sp`` / ``xla``).
* ``checkpoint_keep`` (int >= 1, default 3) — keep-last-N retention for
  both simulator checkpoints and server state snapshots.
* ``checkpoint_frequency`` (int >= 1, default 1) — simulator rounds
  between saves.  The message-plane server snapshots every round open
  regardless: journal replay is only correct against that round's
  snapshot.
* ``server_checkpoint_dir`` (default unset = disabled) — enables
  message-plane server crash recovery: a per-round state snapshot plus an
  update journal of accepted uploads; a restarted server resumes the
  in-flight round instead of restarting the run.
* ``server_journal_fsync`` (``always`` | ``never``, default ``always``) —
  whether each journal append fsyncs before the upload is acked.
  ``never`` trades the power-loss guarantee for upload-path latency
  (process crashes are still covered by the OS page cache).
* ``journal_group_commit_ms`` (float >= 0, default 0 = per-record
  commits) — group-commit window for the update journal: concurrent
  appends within the window coalesce into ONE write+fsync batch and
  their transport acks are released together once the batch is durable
  ("ack implies journaled" amortized, see ``docs/INGEST.md``).
* ``journal_group_commit_max`` (int >= 1, default 32) — records per
  group-commit batch before the committer stops waiting out the window.

Server ingest-pipeline knobs (``train_args`` or ``comm_args``; consumed
by ``core/distributed/comm_manager.py`` + ``core/ingest.py``, stage
anatomy in ``docs/INGEST.md``):

* ``ingest_pipeline`` (bool, default False) — stage the server receive
  path: framing/crc/dedup stay on the transport (io) thread, handler
  dispatch moves to a bounded-queue worker, and upload acks are released
  by the journal's group-commit thread.  Off keeps the synchronous
  receive loop bit-identically.
* ``ingest_queue_depth`` (int >= 1, default 64) — bound of the io→
  dispatch queue; a full queue backpressures the transport thread
  instead of growing an unbounded handler backlog.

Hierarchical fan-in knobs (``train_args`` or ``comm_args``; consumed by
``core/hierarchy``, topology contract in ``docs/HIERARCHY.md``):

* ``fan_in_tree`` (1 | 2 | 3, default 1 = flat) — aggregation tree
  depth: 2 inserts an edge-aggregator tier between leaf clients and the
  root, 3 adds a mid tier above the edges.  The BLOCKED fold the tree
  evaluates is the canonical arithmetic — a flat deployment of the same
  plan computes the identical bits at the root.
* ``edge_fanout`` (int >= 0, default 0 = one block of everything) —
  children per tree node: leaves per edge block, and edges per mid in a
  3-level tree.
* ``edge_flush`` (``all`` | seconds > 0, default ``all``) — when an edge
  flushes its block upward.  ``all`` is the bit-exactness barrier (wait
  for every child); a number flushes whatever arrived after that many
  seconds, trading bit-identity against the full-cohort plan for
  liveness under lost leaves.
* ``edge_checkpoint_dir`` (path, default unset; falls back to
  ``server_checkpoint_dir``) — root for per-edge update journals.  With
  neither set, edges keep no durable state and a killed edge's uploads
  must be retransmitted by its leaves.
* ``edge_codec_offers`` / ``edge_codec_accept`` (comma-separated scheme
  lists from ``none|topk|eftopk|quantize|qsgd``, default ``none``) — the
  per-link codec negotiation inputs: a child offers what it can encode
  (with honest byte estimates), a parent picks the cheapest scheme it
  accepts.  Lossy schemes trade the bit-identity contract for bytes.
* ``edge_codec_ratio`` / ``edge_codec_bits`` (defaults 0.05 / 8) —
  parameters for the negotiated scheme, when one applies.

Observability knobs (``tracking_args`` or ``obs_args``; consumed by
``core/obs``, semantics in ``docs/OBSERVABILITY.md``):

* ``obs_trace`` (bool, default False) — emit the per-round span tree
  (deterministic ids, cross-process ``traceparent`` propagation) through
  the mlops sink fan.  Off keeps the wire and the sink stream
  bit-identical to the pre-obs build.
* ``obs_metrics_export_interval`` (float seconds >= 0, default 0) —
  rate limit for periodic MetricsRegistry exports at round close; 0
  exports only the final snapshot at ``mlops.finish()``.
* ``obs_slow_round_factor`` (float >= 1.0, default 2.0) — a round slower
  than ``factor * median(previous rounds)`` gets a ``slow_round`` span
  event (straggler flagging in ``tools/trace_report.py`` uses the same
  factor).
* ``enable_profiler`` (bool, default False) / ``profiler_dir`` (path,
  default ``<log_file_dir>/xla_trace``) — the XLA simulator's bounded
  device trace: ``jax.profiler`` records rounds 1-3 of the run (round 0
  compiles; the second to fourth rounds after a resume) into
  ``profiler_dir`` and stops.  With ``obs_trace`` on, the host line of
  that trace carries the ``sim.train`` / ``round`` / ``round.select`` /
  ``round.pack`` / ``round.dispatch`` / ``round.wait`` / ``round.close``
  spans on the device's clock; the ``fed.*`` scopes and the ``flash_*``
  kernel names are in the programs either way.
* ``obs_flight_capacity`` (int >= 0, default 2048) — size of the flight
  recorder's in-memory ring of recent telemetry records; 0 disables the
  recorder entirely.
* ``obs_flight_dir`` (path, default unset) — where crc-framed flight
  dumps land on ``server_kill`` / ``server_restore`` / ``slow_round`` /
  unhandled handler exceptions.  Unset keeps the ring (inspectable via
  ``obs.flight_recorder()``) but writes no dumps.
* ``obs_export_port`` (int 0..65535, default 0) — localhost port for the
  OpenMetrics pull endpoint (``GET /metrics``); 0 disables HTTP.
* ``obs_export_path`` (path, default unset) — file that receives atomic
  OpenMetrics snapshots on each rate-limited export and at shutdown.
* ``obs_telemetry`` (bool, default False) — the cross-host telemetry
  plane: clients buffer span/metric records into a bounded ring and
  piggyback one msgpack blob per upload/report (strictly best-effort:
  duplicates dedup by sequence number, gaps are counted, nothing is ever
  retried, and training stays bit-identical on or off).  Requires
  ``obs_trace``.
* ``obs_telemetry_ring`` (int >= 1, default 512) — per-client telemetry
  ring capacity; overflow drops the oldest records (surfacing as
  sequence gaps at the server).
* ``obs_telemetry_flush_s`` (float seconds >= 0, default 0) — minimum
  interval between standalone ``telemetry`` flush messages in async
  mode; 0 restricts telemetry to piggybacked blobs only.
* ``obs_health`` (bool, default False) — the live health & SLO plane
  (``core/obs/health.py``): watchdogs over every long-lived worker,
  EWMA/z-score anomaly windows over the SLO series, a ``/healthz``
  status state machine, and health-triggered flight dumps.  Telemetry
  only: rounds are bit-identical on or off.
* ``obs_health_watchdog_s`` (float > 0, default 30) — default heartbeat
  deadline: an armed watchdog with no beat for this long raises
  ``health.watchdog_expired`` (subsystems may register tighter or looser
  per-worker deadlines).
* ``obs_health_z`` (float > 0, default 4.0) — z-score firing threshold
  for the rolling anomaly windows.
* ``obs_health_ewma_alpha`` (float in (0, 1], default 0.3) — EWMA decay
  for the window mean/variance estimates.
* ``obs_health_warmup`` (int >= 2, default 8) — samples a window must
  see before it may fire (cold distributions would z-fire on noise).

Async / buffered-FL knobs (``train_args`` or ``async_args``; consumed by
``core/async_fl``, execution model in ``docs/ASYNC.md``):

* ``fl_mode`` (``sync`` | ``async``, default ``sync``) — ``async`` turns
  off quorum-gated rounds: the server buffers client deltas (tagged with
  the global-model version they trained against) and flushes the buffer
  through the aggregation plane; ``comm_round`` then counts flushes.
* ``async_buffer_size`` (int >= 1, default = ``client_num_per_round``) —
  deltas per flush.  Must not exceed ``client_num_per_round`` (a buffer
  the active cohort can never fill would only flush by deadline).
  ``async_buffer_size == client_num_per_round`` with the ``constant``
  policy reproduces synchronous FedAvg bit-exactly.
* ``async_staleness_policy`` (``constant`` | ``polynomial`` | ``hinge``,
  default ``constant``) — per-delta aggregation-weight discount as a
  function of staleness (closed forms in ``core/async_fl/staleness.py``).
* ``async_staleness_alpha`` (float > 0, default 0.5) — decay exponent /
  slope of the polynomial and hinge policies.
* ``async_hinge_b`` (int >= 0, default 4) — the hinge policy's no-decay
  grace window.
* ``async_max_staleness`` (int >= 0, default 0) — inclusive staleness
  bound: a delta staler than this is dropped (``async.dropped_stale``)
  and its client immediately re-dispatched on the current global.  0
  accepts only same-version deltas (the sync-equivalence setting); >= 1
  also unlocks the scheduler's immediate re-dispatch of fast clients.
* ``async_flush_deadline_s`` (float >= 0, default 0 = none) — flush a
  non-empty buffer after this many seconds even below capacity (the
  relative-delay timer seam from ``round_timeout_s``; no wall-clock math).

Aggregation-plane knobs (``train_args``; consumed by
``parallel/agg_plane``, semantics in ``docs/AGGREGATION.md``):

* ``agg_plane`` (``host`` | ``compiled``, default ``host``) — where the
  server reduces client updates.  ``compiled`` runs ONE donated-buffer
  GSPMD program over the device mesh; in f32 mode it is bit-exact vs.
  the host path.
* ``agg_wire_dtype`` (``f32`` | ``bf16``, default ``f32``) — dtype for
  staging float client deltas onto the mesh.  ``bf16`` halves wire
  traffic; accumulation stays f32 either way.
* ``agg_microbatch_clients`` (int >= 0, default 0 = all at once) — fold
  K clients at a time into the running accumulator so huge cohorts
  aggregate without materializing the full client stack in HBM.
* ``server_state`` (``replicated`` | ``sharded``, default ``replicated``)
  — where global params + server-optimizer state live between rounds.
  ``sharded`` keeps them as model-axis ``NamedSharding`` device arrays on
  the 2-D (client x model) round mesh and runs the whole round tail
  (reduce -> FedOpt/FedAdam/FedYogi step -> new-params materialization)
  as one donated-buffer compiled program; bit-exact vs. the replicated
  host path in f32 mode.
* ``server_model_parallel`` (int >= 1, default 0 = all devices) — size of
  the round mesh's model axis (the XLA simulator splits its device set
  into client x model with this).  When the live device count can no
  longer satisfy the request (device loss, shrunken restart) the mesh
  degrades to a replicated model=1 layout instead of refusing to serve
  (docs/ELASTICITY.md).
* ``remesh_max_retries`` (int >= 1, default 3) / ``remesh_backoff_s``
  (float >= 0, default 0.05) — retry/backoff for the elastic resume
  handshake: each attempt re-enumerates the live devices before
  re-sharding, so a topology change racing the remesh settles instead of
  failing the round.
* ``broadcast_shards`` (int >= 1, default 1) — number of addressable
  slices the new global params are split into for shard-addressable
  broadcast; each slice is memoized per round as its own
  ``CachedPayload``.

Security/privacy plane knobs (``train_args``; consumed by
``parallel/sec_plane`` and ``core/mpc``, semantics in
``docs/SECURITY.md``):

* ``defense_plane`` (``host`` | ``compiled``, default ``host``) — where
  Byzantine-robust filtering runs when ``enable_defense`` is set.
  ``compiled`` fuses norm-clipping / coordinate-wise trimmed-mean /
  (multi-)Krum into the sharded round program as a pre-reduce stage
  (one program per (mesh, treedef, policy, defense) key); bit-exact
  vs. the retained host defender.
* ``dp_plane`` (``host`` | ``compiled``, default ``host``) — where
  per-client clipping + DP noise runs when ``enable_dp`` is set.
  ``compiled`` draws counter-based noise keyed on (round, client id)
  inside the round program — seed-deterministic and replay/remesh
  stable; the ``core/dp`` budget accountant still drives the noise
  scale (a runtime scalar, never part of the program cache key).
* ``secagg_plane`` (``host`` | ``compiled``, default ``host``) — where
  the secure-aggregation finite-field fold runs.  ``compiled`` sums
  masked residues as sharded uint32 lane ops (``core/mpc/inmesh``);
  exact field math makes any reduction order bit-identical, so the
  knob is a pure perf choice.
"""

from __future__ import annotations

import argparse
import os
from os import path
from typing import Any, Dict, List, Optional

import yaml

from .constants import (
    FEDML_SIMULATION_TYPE_SP,
    FEDML_TRAINING_PLATFORM_SIMULATION,
)

_CONFIG_SECTIONS = (
    "common_args",
    "data_args",
    "model_args",
    "train_args",
    "validation_args",
    "device_args",
    "comm_args",
    "tracking_args",
    "attack_args",
    "defense_args",
    "dp_args",
    "parallel_args",
    # algorithm-family knob sections used by the example configs — an
    # unlisted section would be kept as a dict attr and its knobs silently
    # ignored (the value would quietly fall back to the in-code default)
    "ta_args",
    "vfl_args",
    "fault_args",
    "population_args",
    "obs_args",
    "async_args",
)


def add_args(parser: Optional[argparse.ArgumentParser] = None) -> argparse.Namespace:
    """CLI surface of the reference (``arguments.py:34-60``): five flags."""
    parser = parser or argparse.ArgumentParser(description="fedml_tpu")
    parser.add_argument(
        "--yaml_config_file", "--cf", help="yaml configuration file", type=str, default=""
    )
    parser.add_argument("--run_id", type=str, default="0")
    parser.add_argument("--rank", type=int, default=0)
    parser.add_argument("--local_rank", type=int, default=0)
    parser.add_argument("--node_rank", type=int, default=0)
    parser.add_argument("--role", type=str, default="client")
    args, _ = parser.parse_known_args()
    return args


class Arguments:
    """Flat attribute bag loaded from YAML sections (reference ``arguments.py:63-171``).

    Every key of every section becomes a top-level attribute; section names are
    conventional.  Unknown sections/keys are preserved verbatim.
    """

    def __init__(
        self,
        cmd_args: Optional[argparse.Namespace] = None,
        training_type: Optional[str] = None,
        comm_backend: Optional[str] = None,
    ):
        if cmd_args is not None:
            for k, v in cmd_args.__dict__.items():
                setattr(self, k, v)
        self.training_type = getattr(self, "training_type", None) or training_type
        self.backend = getattr(self, "backend", None) or comm_backend
        config_file = getattr(self, "yaml_config_file", "")
        if config_file:
            self.load_yaml_config(config_file)

    # -- construction -------------------------------------------------------
    @classmethod
    def from_dict(cls, config: Dict[str, Any]) -> "Arguments":
        """Build from a nested (sectioned) or already-flat dict."""
        args = cls()
        args.set_attr_from_config(config)
        return args

    def load_yaml_config(self, yaml_path: str) -> None:
        with open(yaml_path, "r") as f:
            config = yaml.safe_load(f)
        self.set_attr_from_config(config or {})
        self.yaml_paths = [yaml_path]

    def set_attr_from_config(self, configuration: Dict[str, Any]) -> None:
        """Flatten sections onto self (reference ``arguments.py:168-171``)."""
        for section, content in configuration.items():
            if section in _CONFIG_SECTIONS and isinstance(content, dict):
                for k, v in content.items():
                    setattr(self, k, v)
            else:
                setattr(self, section, content)

    # -- access -------------------------------------------------------------
    def get(self, key: str, default: Any = None) -> Any:
        return getattr(self, key, default)

    def __contains__(self, key: str) -> bool:
        return hasattr(self, key)

    def to_dict(self) -> Dict[str, Any]:
        return {k: v for k, v in self.__dict__.items() if not k.startswith("_")}

    def __repr__(self) -> str:  # pragma: no cover
        return f"Arguments({self.to_dict()!r})"

    # -- validation ---------------------------------------------------------
    REQUIRED_FOR_TRAINING: List[str] = [
        "training_type",
        "dataset",
        "model",
        "federated_optimizer",
        "client_num_in_total",
        "client_num_per_round",
        "comm_round",
    ]

    # the in-mesh round's retired levers: (key, whether a value still means
    # the packed while-loop stream), each value read as its lever read it
    RETIRED_ROUND_KEYS = (
        ("xla_pack", bool),
        ("xla_stream", lambda v: str(v) == "while"),
        ("xla_pregather", lambda v: not bool(v)),
        ("xla_client_chunk", lambda v: int(v or 0) <= 1),
    )

    def validate(self, for_training: bool = True) -> "Arguments":
        if for_training:
            missing = [k for k in self.REQUIRED_FOR_TRAINING if not hasattr(self, k)]
            if missing:
                raise ValueError(f"missing required config keys: {missing}")
            if int(self.client_num_per_round) > int(self.client_num_in_total):
                raise ValueError(
                    "client_num_per_round must be <= client_num_in_total "
                    f"({self.client_num_per_round} > {self.client_num_in_total})"
                )
            bl = getattr(self, "population_blocklist", None)
            if bl:
                eligible = int(self.client_num_in_total) - len(set(int(c) for c in bl))
                if eligible < int(self.client_num_per_round):
                    raise ValueError(
                        "population_blocklist leaves only "
                        f"{eligible} eligible clients (< client_num_per_round="
                        f"{self.client_num_per_round})"
                    )
            # selecting FedProx without a mu means "use the default", on
            # EVERY backend — the engine's proximal hook only installs when
            # mu > 0, so injecting here (the one chokepoint all backends
            # pass through) keeps sp/XLA/MPI_PROC training the same objective
            opt = str(getattr(self, "federated_optimizer", "")).lower()
            if opt == "fedprox" and not float(getattr(self, "proximal_mu", 0) or 0):
                from .constants import FEDPROX_DEFAULT_MU

                self.proximal_mu = FEDPROX_DEFAULT_MU
        # a model built from a published config.json (models/hub.py): a dict of
        # its keys, or the path of a JSON file that holds one
        mc = getattr(self, "model_config", None)
        if mc is not None:
            if isinstance(mc, (str, os.PathLike)):
                if not os.path.isfile(mc):
                    raise ValueError(f"model_config names no file: {mc!r}")
            elif not isinstance(mc, dict):
                raise ValueError(
                    "model_config must be a dict of the published config.json's keys "
                    f"or the path of a JSON file (got {type(mc).__name__})")
        # a config from outside must not silently train another round than
        # the one it names
        for key, means_packed in self.RETIRED_ROUND_KEYS:
            v = getattr(self, key, None)
            if v is not None and not means_packed(v):
                raise ValueError(
                    f"{key}={v!r} asks for an in-mesh round that no longer exists: "
                    "the packed stream is the only round (drop the key)")
        # population / pacing knobs fail at config time, not as a traceback
        # mid-run when the first round opens (core/population semantics)
        oc = getattr(self, "pacing_overcommit", None)
        if oc is not None and float(oc) < 1.0:
            raise ValueError(f"pacing_overcommit must be >= 1.0 (got {oc})")
        q = getattr(self, "pacing_quorum", None)
        if q is not None and int(q) < 0:
            raise ValueError(f"pacing_quorum must be >= 0 (got {q})")
        pol = str(getattr(self, "selection_policy", "uniform") or "uniform").lower()
        if pol not in ("uniform", "stratified", "importance"):
            raise ValueError(
                f"unknown selection_policy {pol!r} "
                "(expected uniform|stratified|importance)"
            )
        strata = getattr(self, "population_strata", None)
        if strata is not None and int(strata) < 1:
            raise ValueError(f"population_strata must be >= 1 (got {strata})")
        # checkpoint / server-recovery knobs (core/checkpoint.py) — a typo'd
        # value must fail here, not be silently ignored by the bare getattr
        # defaults at the use sites
        for knob in ("checkpoint_dir", "server_checkpoint_dir"):
            d = getattr(self, knob, None)
            if d is not None and not isinstance(d, (str, os.PathLike)):
                raise ValueError(
                    f"{knob} must be a path string (got {type(d).__name__}); "
                    "empty/unset disables checkpointing")
        for knob, floor in (("checkpoint_keep", 1), ("checkpoint_frequency", 1)):
            v = getattr(self, knob, None)
            if v is None:
                continue
            try:
                iv = int(v)
            except (TypeError, ValueError):
                raise ValueError(f"{knob} must be an integer >= {floor} (got {v!r})")
            if iv < floor:
                raise ValueError(f"{knob} must be >= {floor} (got {iv})")
        fsync = getattr(self, "server_journal_fsync", None)
        if fsync is not None:
            from .core.checkpoint import JOURNAL_FSYNC_POLICIES

            if str(fsync).lower() not in JOURNAL_FSYNC_POLICIES:
                raise ValueError(
                    "server_journal_fsync must be one of "
                    f"{JOURNAL_FSYNC_POLICIES} (got {fsync!r})")
        # ingest-pipeline knobs (core/ingest + comm_manager staged path)
        pipe = getattr(self, "ingest_pipeline", None)
        if pipe is not None and not isinstance(pipe, bool):
            if (not isinstance(pipe, str) or pipe.strip().lower() not in
                    ("1", "true", "on", "yes", "0", "false", "off", "no")):
                raise ValueError(
                    "ingest_pipeline must be a bool or on/off string "
                    f"(got {pipe!r})")
        gc_ms = getattr(self, "journal_group_commit_ms", None)
        if gc_ms is not None:
            try:
                gv = float(gc_ms)
            except (TypeError, ValueError):
                raise ValueError(
                    "journal_group_commit_ms must be a number >= 0 "
                    f"(got {gc_ms!r})")
            if gv < 0:
                raise ValueError(
                    f"journal_group_commit_ms must be >= 0 (got {gv})")
        for knob in ("journal_group_commit_max", "ingest_queue_depth"):
            v = getattr(self, knob, None)
            if v is None:
                continue
            try:
                iv = int(v)
            except (TypeError, ValueError):
                raise ValueError(
                    f"{knob} must be an integer >= 1 (got {v!r})")
            if iv < 1:
                raise ValueError(f"{knob} must be >= 1 (got {iv})")
        # chunked resumable-upload knobs (core/distributed/chunking)
        chunk_bytes = getattr(self, "upload_chunk_bytes", None)
        if chunk_bytes is not None:
            try:
                cb = int(chunk_bytes)
            except (TypeError, ValueError):
                raise ValueError(
                    "upload_chunk_bytes must be an integer >= 0 "
                    f"(got {chunk_bytes!r})")
            if cb < 0:
                raise ValueError(
                    f"upload_chunk_bytes must be >= 0 (got {cb})")
        for knob in ("chunk_window", "chunk_buffer_bytes"):
            v = getattr(self, knob, None)
            if v is None:
                continue
            try:
                iv = int(v)
            except (TypeError, ValueError):
                raise ValueError(
                    f"{knob} must be an integer >= 1 (got {v!r})")
            if iv < 1:
                raise ValueError(f"{knob} must be >= 1 (got {iv})")
        # hierarchical fan-in knobs (core/hierarchy) — the plan derives the
        # tree shape from these, so a bad value must fail before any node
        # is built with a different grouping than its peers
        tree = getattr(self, "fan_in_tree", None)
        if tree is not None:
            from .core.hierarchy.plan import FAN_IN_TREE_LEVELS

            try:
                tv = int(tree)
            except (TypeError, ValueError):
                raise ValueError(
                    f"fan_in_tree must be one of {FAN_IN_TREE_LEVELS} "
                    f"(got {tree!r})")
            if tv not in FAN_IN_TREE_LEVELS:
                raise ValueError(
                    f"fan_in_tree must be one of {FAN_IN_TREE_LEVELS} "
                    f"(got {tv})")
        fanout = getattr(self, "edge_fanout", None)
        if fanout is not None:
            try:
                fo = int(fanout)
            except (TypeError, ValueError):
                raise ValueError(
                    "edge_fanout must be an integer >= 0 "
                    f"(got {fanout!r})")
            if fo < 0:
                raise ValueError(f"edge_fanout must be >= 0 (got {fo})")
        flush_k = getattr(self, "edge_flush", None)
        if flush_k is not None:
            ok = (isinstance(flush_k, str)
                  and flush_k.strip().lower() == "all")
            if not ok:
                try:
                    fs = float(flush_k)
                    ok = fs > 0
                except (TypeError, ValueError):
                    ok = False
            if not ok:
                raise ValueError(
                    "edge_flush must be 'all' or a positive number of "
                    f"seconds (got {flush_k!r})")
        # observability knobs (core/obs) — bad values fail here so a typo'd
        # interval doesn't silently disable the periodic metrics export
        interval = getattr(self, "obs_metrics_export_interval", None)
        if interval is not None:
            try:
                fv = float(interval)
            except (TypeError, ValueError):
                raise ValueError(
                    f"obs_metrics_export_interval must be a number >= 0 "
                    f"(got {interval!r})")
            if fv < 0:
                raise ValueError(
                    f"obs_metrics_export_interval must be >= 0 (got {fv})")
        slow = getattr(self, "obs_slow_round_factor", None)
        if slow is not None:
            try:
                sv = float(slow)
            except (TypeError, ValueError):
                raise ValueError(
                    f"obs_slow_round_factor must be a number >= 1.0 "
                    f"(got {slow!r})")
            if sv < 1.0:
                raise ValueError(
                    f"obs_slow_round_factor must be >= 1.0 (got {sv})")
        cap = getattr(self, "obs_flight_capacity", None)
        if cap is not None:
            try:
                cv = int(cap)
            except (TypeError, ValueError):
                raise ValueError(
                    f"obs_flight_capacity must be an integer >= 0 "
                    f"(got {cap!r})")
            if cv < 0:
                raise ValueError(
                    f"obs_flight_capacity must be >= 0 (got {cv})")
        port = getattr(self, "obs_export_port", None)
        if port is not None:
            try:
                pv = int(port)
            except (TypeError, ValueError):
                raise ValueError(
                    f"obs_export_port must be an integer in 0..65535 "
                    f"(got {port!r})")
            if not 0 <= pv <= 65535:
                raise ValueError(
                    f"obs_export_port must be in 0..65535 (got {pv})")
        ring = getattr(self, "obs_telemetry_ring", None)
        if ring is not None:
            try:
                rv = int(ring)
            except (TypeError, ValueError):
                raise ValueError(
                    f"obs_telemetry_ring must be an integer >= 1 "
                    f"(got {ring!r})")
            if rv < 1:
                raise ValueError(
                    f"obs_telemetry_ring must be >= 1 (got {rv})")
        flush = getattr(self, "obs_telemetry_flush_s", None)
        if flush is not None:
            try:
                fs = float(flush)
            except (TypeError, ValueError):
                raise ValueError(
                    f"obs_telemetry_flush_s must be a number >= 0 "
                    f"(got {flush!r})")
            if fs < 0:
                raise ValueError(
                    f"obs_telemetry_flush_s must be >= 0 (got {fs})")
        # health-plane knobs (core/obs/health) — a typo'd threshold must
        # fail here, not silently run with the default
        wds = getattr(self, "obs_health_watchdog_s", None)
        if wds is not None:
            try:
                wv = float(wds)
            except (TypeError, ValueError):
                raise ValueError(
                    f"obs_health_watchdog_s must be a number > 0 "
                    f"(got {wds!r})")
            if wv <= 0:
                raise ValueError(
                    f"obs_health_watchdog_s must be > 0 (got {wv})")
        hz = getattr(self, "obs_health_z", None)
        if hz is not None:
            try:
                zv = float(hz)
            except (TypeError, ValueError):
                raise ValueError(
                    f"obs_health_z must be a number > 0 (got {hz!r})")
            if zv <= 0:
                raise ValueError(f"obs_health_z must be > 0 (got {zv})")
        alpha = getattr(self, "obs_health_ewma_alpha", None)
        if alpha is not None:
            try:
                av = float(alpha)
            except (TypeError, ValueError):
                raise ValueError(
                    f"obs_health_ewma_alpha must be a number in (0, 1] "
                    f"(got {alpha!r})")
            if not 0 < av <= 1:
                raise ValueError(
                    f"obs_health_ewma_alpha must be in (0, 1] (got {av})")
        warm = getattr(self, "obs_health_warmup", None)
        if warm is not None:
            try:
                wv = int(warm)
            except (TypeError, ValueError):
                raise ValueError(
                    f"obs_health_warmup must be an integer >= 2 "
                    f"(got {warm!r})")
            if wv < 2:
                raise ValueError(
                    f"obs_health_warmup must be >= 2 (got {wv})")
        # async / buffered-FL knobs (core/async_fl) — a typo'd mode or policy
        # must fail here, not silently run the sync state machine
        mode = getattr(self, "fl_mode", None)
        if mode is not None:
            from .core.async_fl import FL_MODES

            if str(mode).lower() not in FL_MODES:
                raise ValueError(
                    f"fl_mode must be one of {FL_MODES} (got {mode!r})")
        bs = getattr(self, "async_buffer_size", None)
        if bs is not None:
            try:
                bv = int(bs)
            except (TypeError, ValueError):
                raise ValueError(
                    f"async_buffer_size must be an integer >= 1 (got {bs!r})")
            if bv < 1:
                raise ValueError(f"async_buffer_size must be >= 1 (got {bv})")
            k = getattr(self, "client_num_per_round", None)
            if k is not None and bv > int(k):
                raise ValueError(
                    f"async_buffer_size ({bv}) must not exceed "
                    f"client_num_per_round ({k}): a buffer the active cohort "
                    "cannot fill would only ever flush by deadline")
        spol = getattr(self, "async_staleness_policy", None)
        if spol is not None:
            from .core.async_fl import ASYNC_STALENESS_POLICIES

            if str(spol).lower() not in ASYNC_STALENESS_POLICIES:
                raise ValueError(
                    "async_staleness_policy must be one of "
                    f"{ASYNC_STALENESS_POLICIES} (got {spol!r})")
        for knob, floor, kind in (
                ("async_max_staleness", 0, int),
                ("async_hinge_b", 0, int),
                ("async_flush_deadline_s", 0.0, float)):
            v = getattr(self, knob, None)
            if v is None:
                continue
            try:
                cv = kind(v)
            except (TypeError, ValueError):
                raise ValueError(
                    f"{knob} must be a {kind.__name__} >= {floor} (got {v!r})")
            if cv < floor:
                raise ValueError(f"{knob} must be >= {floor} (got {cv})")
        sa = getattr(self, "async_staleness_alpha", None)
        if sa is not None:
            try:
                sav = float(sa)
            except (TypeError, ValueError):
                raise ValueError(
                    f"async_staleness_alpha must be a number > 0 (got {sa!r})")
            if sav <= 0:
                raise ValueError(
                    f"async_staleness_alpha must be > 0 (got {sav})")
        # aggregation-plane knobs (parallel/agg_plane) — a typo'd plane name
        # must not silently fall back to the host loop
        plane = getattr(self, "agg_plane", None)
        if plane is not None:
            from .parallel.agg_plane import AGG_PLANES

            if str(plane).lower() not in AGG_PLANES:
                raise ValueError(
                    f"agg_plane must be one of {AGG_PLANES} (got {plane!r})")
        wire = getattr(self, "agg_wire_dtype", None)
        if wire is not None:
            from .parallel.agg_plane import AGG_WIRE_DTYPES

            if str(wire).lower() not in AGG_WIRE_DTYPES:
                raise ValueError(
                    f"agg_wire_dtype must be one of {AGG_WIRE_DTYPES} "
                    f"(got {wire!r})")
        mb = getattr(self, "agg_microbatch_clients", None)
        if mb is not None:
            try:
                mv = int(mb)
            except (TypeError, ValueError):
                raise ValueError(
                    f"agg_microbatch_clients must be an integer >= 0 "
                    f"(got {mb!r})")
            if mv < 0:
                raise ValueError(
                    f"agg_microbatch_clients must be >= 0 (got {mv})")
        state = getattr(self, "server_state", None)
        if state is not None:
            from .parallel.agg_plane import SERVER_STATES

            if str(state).lower() not in SERVER_STATES:
                raise ValueError(
                    f"server_state must be one of {SERVER_STATES} "
                    f"(got {state!r})")
        # security/privacy stage planes (parallel/sec_plane, core/mpc) — same
        # fail-loud contract: a typo must not silently stay on the host path
        for knob in ("defense_plane", "dp_plane", "secagg_plane"):
            sp = getattr(self, knob, None)
            if sp is not None:
                from .parallel.sec_plane import SEC_PLANES

                if str(sp).lower() not in SEC_PLANES:
                    raise ValueError(
                        f"{knob} must be one of {SEC_PLANES} (got {sp!r})")
        if (str(getattr(self, "defense_plane", "host") or "host").lower()
                == "compiled" and getattr(self, "enable_defense", False)):
            from .parallel.sec_plane import defense_spec

            defense_spec(self)  # raises on defenses the plane can't compile
        for knob, floor in (("server_model_parallel", 0),
                            ("broadcast_shards", 1),
                            ("remesh_max_retries", 1)):
            v = getattr(self, knob, None)
            if v is None:
                continue
            try:
                cv = int(v)
            except (TypeError, ValueError):
                raise ValueError(
                    f"{knob} must be an integer >= {floor} (got {v!r})")
            if cv < floor:
                raise ValueError(f"{knob} must be >= {floor} (got {cv})")
        # a malformed chaos plan should fail at config time, not mid-run when
        # the backend factory first tries to wrap the transport
        plan = getattr(self, "fault_plan", None)
        if plan:
            from .core.distributed.faults import FaultPlan

            FaultPlan.from_dict(plan)
        return self


def _default_yaml_path(training_type: str, comm_backend: str) -> str:
    base = path.join(path.dirname(__file__), "config")
    if training_type == FEDML_TRAINING_PLATFORM_SIMULATION:
        sub = "simulation_sp" if comm_backend == FEDML_SIMULATION_TYPE_SP else "simulation_xla"
    else:
        sub = training_type
    return path.join(base, sub, "fedml_config.yaml")


def load_arguments(
    training_type: Optional[str] = None, comm_backend: Optional[str] = None
) -> Arguments:
    """Reference ``arguments.py:174-196``: parse CLI, then load YAML config."""
    cmd_args = add_args()
    if not cmd_args.yaml_config_file:
        candidate = _default_yaml_path(
            training_type or FEDML_TRAINING_PLATFORM_SIMULATION,
            comm_backend or FEDML_SIMULATION_TYPE_SP,
        )
        if os.path.exists(candidate):
            cmd_args.yaml_config_file = candidate
    args = Arguments(cmd_args, training_type, comm_backend)
    if not hasattr(args, "rank"):
        args.rank = 0
    return args
