"""North-star benchmark: FedAvg ResNet-56 CIFAR-10, 100 simulated clients,
Parrot-XLA simulator (BASELINE.json).

Runs on the TPU and nowhere else: jax is initialised once, in this process,
and when the backend that answers is not ``tpu`` the bench emits one
``mode: "failed"`` line, measures nothing and exits 1.  One process per
chip; nothing is spawned.  The compile cache is placed by
``fedml_tpu.init`` (``JAX_COMPILATION_CACHE_DIR``, else ``.jax_cache``
beside the package).  ``chip_smoke.py`` is the quick proof that the same
path starts on the chip; this file is the measurement.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...extras},
stamped with the schema-2 provenance fields {"bench_schema", "mode":
full|failed, "degraded_reason" (failed only), "git_rev"} that
``tools/perf_gate.py`` validates, plus the device it ran on.  The one-line
contract holds on EVERY path: a crash or early exit still emits a
``mode: "failed"`` record before the nonzero rc.  A phase that raises is
named under ``failed_phases`` and makes the exit code 1; a phase that
cannot run on this host's device count says so under its ``*_skipped`` key.

value = local-training samples/sec/chip (the throughput half of the
north-star; accuracy parity is tracked in PARITY.md and the test suite).

vs_baseline divides by a MEASURED eager baseline: the same ResNet-56/CIFAR-10
b=64 fp32 local training executed the way the reference's NCCL simulator
executes it — a host loop dispatching one step per batch (per-batch kernel
launches, no cross-batch compilation) — on the SAME chip, measured in this
process right before the main run.  The reference publishes no wall-clock
numbers (BASELINE.md), so hardware-identical architecture-vs-architecture is
the honest comparison; the old hardcoded A100 estimate (2000 samples/s) is
kept as `vs_a100_estimate`.

Read vs_baseline as a CEILING ratio, not an apples-to-apples FL race: the
eager loop is pure back-to-back steps on two resident alternating batches —
no ragged clients, no per-client state resets, no aggregation, no per-step
data gather — i.e. the throughput ceiling of this chip for this model.
What the full in-mesh FL round reaches against it has no driver record yet
(PERF.md: not measured).

Also reported: achieved model TFLOP/s and MFU, from an analytic ResNet-56
cost (0.126 GFLOP forward x3 for training) — model FLOPs, not hardware
FLOPs, so MFU is comparable across implementations.  MFU divides by the
bf16 peak DEVICE_PEAKS holds for the device_kind that ran.

The main run uses bf16 compute (fp32 params).
"""

from __future__ import annotations

import json
import os
import sys
import time

A100_NCCL_SPS = 2000.0  # comparison constant (estimated, never measured)
# Published per-chip peaks keyed by jax's ``device_kind``.  Source: Google
# Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 819 GB/s HBM).  A device
# that is not here is an error, not a default (see _device_peaks).
DEVICE_PEAKS = {
    "TPU v5 lite": {"bf16_tflops": 197.0, "hbm_gbps": 819.0},
}
RESNET56_TRAIN_GFLOPS = 0.378  # analytic fallback: 0.126 GFLOP fwd x3

# record format version; tools/perf_gate.py validates stamped records and
# tests/test_perf_gate.py pins the two constants together so they can't
# drift.  Schema 2 = {bench_schema, mode: full|failed, degraded_reason
# (failed only), git_rev} on every metric line.
BENCH_SCHEMA = 2


def _device_peaks(device_kind: str) -> dict:
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise RuntimeError(
            f"no published peak for device_kind {device_kind!r}: add it to "
            "bench.DEVICE_PEAKS with its source") from None


def _device_stamp() -> dict:
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def _git_rev() -> str:
    """Short rev of the measured tree, stamped into every metric line so a
    BENCH artifact is attributable without the driver's wrapper context."""
    import subprocess

    try:
        r = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10,
            cwd=os.path.dirname(os.path.abspath(__file__)))
        if r.returncode == 0 and r.stdout.strip():
            return r.stdout.strip()
    except Exception:
        pass
    return "unknown"


_emitted = False


def _emit(out: dict, mode: str) -> None:
    """THE stdout seam: every metric line leaves through here, stamped with
    the schema fields.  ``degraded_reason`` rides in ``out`` when the mode
    needs one."""
    global _emitted
    rec = dict(out)
    rec["bench_schema"] = BENCH_SCHEMA
    rec["mode"] = mode
    rec["git_rev"] = _git_rev()
    print(json.dumps(rec))  # lint_obs: allow — this IS the bench contract
    _emitted = True


def _bench_args(n_chips: int, compute_dtype: str = "bf16"):
    from fedml_tpu.arguments import Arguments

    return Arguments.from_dict(
        {
            "common_args": {"training_type": "simulation", "random_seed": 0, "run_id": "bench"},
            "data_args": {
                "dataset": "cifar10",
                "data_cache_dir": "./fedml_data",
                "partition_method": "hetero",
                "partition_alpha": 0.5,
            },
            "model_args": {"model": "resnet56", "compute_dtype": compute_dtype},
            "train_args": {
                # 32 clients/round: the fixed per-round dispatch cost
                # amortized over 4x the round compute of 8
                "federated_optimizer": "FedAvg",
                "client_num_in_total": 100,
                "client_num_per_round": min(100, max(32, n_chips * 8)),
                "comm_round": 6,  # round 0 compiles, round 1 uploads data; 2-5 are steady state
                "epochs": 1,
                "batch_size": 64,
                "client_optimizer": "sgd",
                "learning_rate": 0.001,
            },
            "validation_args": {"frequency_of_the_test": 0},  # 0 disables eval
            "comm_args": {"backend": "XLA"},
        }
    ).validate()


def _measure_eager_baseline(args, dataset, n_batches: int = 24) -> float:
    """Reference-architecture baseline on the same chip: fp32, one jitted
    step per batch dispatched from a python loop (how a torch/NCCL per-batch
    trainer executes), no cross-batch compilation, batch 64."""
    import jax
    import jax.numpy as jnp
    import optax

    import fedml_tpu
    from fedml_tpu.ml.engine.train import init_variables, softmax_ce_loss

    model = fedml_tpu.models.create(args, 10)  # fp32: args copy has fp32 dtype
    x_glob, y_glob = dataset[2]
    b = int(args.batch_size)
    x = jnp.asarray(x_glob[: b * 2])
    y = jnp.asarray(y_glob[: b * 2])
    variables = init_variables(model, x[:1], seed=0)
    tx = optax.sgd(float(args.learning_rate))
    opt_state = tx.init(variables["params"])

    def step(variables, opt_state, bx, by):
        def loss_fn(params):
            out = model.apply(dict(variables, params=params), bx, train=True,
                              rngs={"dropout": jax.random.PRNGKey(0)})
            loss, _ = softmax_ce_loss(out, by, jnp.ones(by.shape[0]))
            return loss

        grads = jax.grad(loss_fn)(variables["params"])
        updates, opt_state = tx.update(grads, opt_state, variables["params"])
        params = optax.apply_updates(variables["params"], updates)
        return dict(variables, params=params), opt_state

    jstep = jax.jit(step)
    # warmup/compile
    variables, opt_state = jstep(variables, opt_state, x[:b], y[:b])
    jax.block_until_ready(variables)
    t0 = time.time()
    for i in range(n_batches):
        off = (i % 2) * b
        variables, opt_state = jstep(variables, opt_state, x[off:off + b], y[off:off + b])
    jax.block_until_ready(variables)
    dt = time.time() - t0
    return n_batches * b / max(dt, 1e-9)


def main() -> int | None:
    """Exactly-one-JSON-line wrapper: whatever ``_main`` does — return,
    raise, find no TPU — stdout carries at least (and on the primary path
    exactly) one schema-stamped metric line.  A crash leaves a ``mode:
    "failed"`` record naming the exception, and the nonzero exit still
    marks the round dark for ``tools/perf_gate.py``."""
    try:
        rc = _main()
    except BaseException as e:
        if not _emitted:
            _emit({"metric": "bench_failed", "value": None, "unit": "none",
                   "degraded_reason": f"unhandled {type(e).__name__}: {e}"},
                  "failed")
        raise
    if rc and not _emitted:
        _emit({"metric": "bench_failed", "value": None, "unit": "none",
               "degraded_reason": f"bench exited rc={rc} without a metric "
                                  "line"}, "failed")
    return rc


def _run_phases(out: dict, phases) -> list:
    """Run each ``(name, fn)`` measurement phase, merging its keys into
    ``out``.  One broken phase must not cost the others their chip time, so
    a raise is caught HERE — and returned by name: the caller writes the
    names into the record and exits nonzero."""
    import traceback

    failed = []
    for name, fn in phases:
        try:
            out.update(fn())
        except Exception as e:
            traceback.print_exc()
            failed.append(f"{name}: {type(e).__name__}: {e}")
    return failed


def _main() -> int | None:
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        # nothing is measured off the chip: a CPU timing under a device
        # metric's name is worse than no number
        _emit({"metric": "bench_failed", "value": None, "unit": "none",
               "degraded_reason": f"jax backend is {backend!r}, not 'tpu'",
               "device": _device_stamp()}, "failed")
        return 1

    import fedml_tpu
    from fedml_tpu.simulation.xla.fed_sim import XLASimulator

    n_chips = len(jax.devices())
    peaks = _device_peaks(jax.devices()[0].device_kind)
    args = fedml_tpu.init(_bench_args(n_chips), should_init_logs=False)
    from fedml_tpu import data

    dataset, out_dim = data.load(args)

    # measured same-chip eager (reference-architecture) baseline, fp32
    base_args = _bench_args(n_chips, compute_dtype="fp32")
    eager_sps = _measure_eager_baseline(base_args, dataset)

    model = fedml_tpu.models.create(args, out_dim)
    sim = XLASimulator(args, dataset, model)
    sim.train()

    # median per-round throughput over post-compile rounds: the steady-state
    # rate (compile + one-time dataset upload amortized out; see
    # XLASimulator.throughput for the exact semantics)
    sps = sim.throughput()["samples_per_sec"]
    sps_per_chip = sps / max(n_chips, 1)

    gflops_sample = RESNET56_TRAIN_GFLOPS
    achieved_tflops = sps_per_chip * gflops_sample / 1e3
    out = {
        "metric": "fedavg_resnet56_cifar10_100clients_samples_per_sec_per_chip",
        "value": round(sps_per_chip, 2),
        "unit": "samples/s/chip",
        "device": _device_stamp(),
        "dataset_is_synthetic": bool(getattr(args, "dataset_is_synthetic", False)),
        "vs_baseline": round(sps_per_chip / max(eager_sps, 1e-9), 4),
        "eager_baseline_sps": round(eager_sps, 2),
        "vs_a100_estimate": round(sps_per_chip / A100_NCCL_SPS, 4),
        "achieved_tflops": round(achieved_tflops, 3),
        "mfu": round(achieved_tflops / peaks["bf16_tflops"], 5),
        "compute_dtype": "bf16",
    }
    phases = [
        ("obs_overhead", lambda: _measure_obs_overhead(sim)),
        ("telemetry_overhead", _measure_telemetry_overhead),
        ("agg_step", _measure_agg_step),
        ("round_update", _measure_round_update),
        ("defended_round", _measure_defended_round),
        ("secagg", _measure_secagg),
        ("remesh", _measure_remesh),
        ("upload_saturation", _measure_upload_saturation),
        ("fanin", _measure_fanin),
        ("async_throughput", _measure_async_throughput),
        ("chunked", _measure_chunked),
        ("health_overhead", _measure_health_overhead),
        ("round_throughput", _measure_round_throughput),
    ]
    if os.environ.get("BENCH_SP"):
        phases.append(("sp", lambda: {
            "sp_samples_per_sec": round(_measure_sp(args, dataset), 2)}))
    failed = _run_phases(out, phases)
    if failed:
        out["failed_phases"] = failed
    _emit(out, "full")
    if os.environ.get("BENCH_TRANSFORMER"):
        # second opt-in metric line: the transformer stack (TransformerLM
        # through its default attention, i.e. the pallas flash kernel)
        _emit(_measure_transformer(), "full")
    return 1 if failed else 0


def _synthetic_updates(n_clients: int, seed: int = 0):
    """Seeded synthetic client deltas shaped like a small MLP — enough
    structure (matrices, vectors, a scalar) to exercise the partition rules
    while staying cheap to generate."""
    import jax.numpy as jnp
    import numpy as np

    shapes = {
        "layer1/kernel": (256, 256), "layer1/bias": (256,),
        "layer2/kernel": (256, 256), "layer2/bias": (256,),
        "head/kernel": (256, 10), "head/bias": (10,),
        "scale": (),
    }
    rng = np.random.default_rng(seed)
    updates = []
    for _ in range(n_clients):
        tree = {k: jnp.asarray(rng.standard_normal(s), jnp.float32)
                for k, s in shapes.items()}
        updates.append((float(rng.integers(16, 256)), tree))
    return updates


def _measure_agg_step() -> dict:
    """The aggregation-plane relative keys: median host-loop vs compiled
    reduction time over the same seeded synthetic deltas."""
    import numpy as np

    import jax

    from fedml_tpu.core.aggregate import weighted_mean
    from fedml_tpu.parallel.agg_plane import CompiledAggPlane

    n = int(os.environ.get("BENCH_AGG_CLIENTS", "32"))
    reps = int(os.environ.get("BENCH_AGG_REPS", "5"))
    updates = _synthetic_updates(n)

    def timed(fn):
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            ts.append(time.perf_counter() - t0)
        return float(np.median(ts))

    host_s = timed(lambda: weighted_mean(updates))
    plane = CompiledAggPlane()
    plane.aggregate(updates)  # pay the compile outside the timing
    comp_s = timed(lambda: plane.aggregate(updates))
    return {
        "agg_step_host_s": round(host_s, 6),
        "agg_step_compiled_s": round(comp_s, 6),
        "agg_speedup": round(host_s / max(comp_s, 1e-9), 4),
        "agg_clients": n,
    }


def _measure_round_update() -> dict:
    """The sharded-server-state relative keys (server_state=sharded): median
    host-oracle round tail (reduce + FedAdam server step) vs the ONE-program
    sharded round update over the same seeded synthetic deltas, plus the
    broadcast wire cost of the full tree vs its largest shard slice."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from fedml_tpu.core.aggregate import (host_server_round_update,
                                          make_host_round_step)
    from fedml_tpu.core.distributed.communication.serialization import (
        CachedPayload)
    from fedml_tpu.parallel.agg_plane import (ShardedRoundPlane,
                                              _policy_tx,
                                              broadcast_shards)

    n = int(os.environ.get("BENCH_AGG_CLIENTS", "32"))
    reps = int(os.environ.get("BENCH_AGG_REPS", "5"))
    n_shards = int(os.environ.get("BENCH_BCAST_SHARDS", "4"))
    updates = _synthetic_updates(n)
    rng = np.random.default_rng(7)
    params = {k: jnp.asarray(rng.standard_normal(np.shape(v)), jnp.float32)
              for k, v in updates[0][1].items()}
    policy = ("adam", 0.1, 0.9)  # the FedAdam default server optimizer
    tx = _policy_tx(policy)
    opt_state = tx.init([v for v in jax.tree_util.tree_leaves(params)])
    step = make_host_round_step(tx)
    host_server_round_update(params, updates, tx, opt_state,
                             step=step)  # pay the jit outside the timing

    def timed(fn):
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            ts.append(time.perf_counter() - t0)
        return float(np.median(ts))

    host_s = timed(lambda: host_server_round_update(
        params, updates, tx, opt_state, step=step))
    plane = ShardedRoundPlane(policy=policy)
    out_tree = plane.round_update(params, updates)  # compile
    state = {"tree": out_tree}

    def compiled_once():
        state["tree"] = plane.round_update(state["tree"], updates)
        return state["tree"]

    comp_s = timed(compiled_once)
    bytes_full = len(CachedPayload(state["tree"]).wire_bytes())
    bytes_sharded = max(
        len(CachedPayload(s).wire_bytes())
        for s in broadcast_shards(state["tree"], n_shards))
    return {
        "round_update_host_s": round(host_s, 6),
        "round_update_compiled_s": round(comp_s, 6),
        "round_update_speedup": round(host_s / max(comp_s, 1e-9), 4),
        "broadcast_bytes_full": bytes_full,
        "broadcast_bytes_sharded": bytes_sharded,
        "broadcast_shrink": round(bytes_full / max(bytes_sharded, 1), 4),
        "round_update_policy": policy[0],
    }


def _measure_defended_round() -> dict:
    """The defense/privacy-plane keys (PR 17) over the same seeded
    synthetic deltas —

    * ``defended_round_speedup``: median host-oracle defended round
      (multi-Krum + Gaussian DP via ``host_secure_round_update``) vs the
      ONE staged compiled program (``ShardedRoundPlane`` with the fused
      defense + DP stages).  Higher is better (RELATIVE band).
    * ``dp_overhead_frac``: the compiled round with the DP stage on vs
      the identical round without it — what per-client clip + noise
      costs inside the fused program.  Lower is better (budget cap)."""
    import numpy as np

    out = {}
    import jax
    import jax.numpy as jnp

    from fedml_tpu.parallel.agg_plane import ShardedRoundPlane
    from fedml_tpu.parallel.sec_plane import host_secure_round_update

    n = int(os.environ.get("BENCH_AGG_CLIENTS", "32"))
    reps = int(os.environ.get("BENCH_AGG_REPS", "5"))
    updates = _synthetic_updates(n)
    rng = np.random.default_rng(7)
    params = {k: jnp.asarray(rng.standard_normal(np.shape(v)), jnp.float32)
              for k, v in updates[0][1].items()}
    policy = ("adam", 0.1, 0.9)
    defense = ("krum", 1, max(1, n // 2))  # multi-Krum, half cohort
    dp = ("gaussian", 1.0, 0)
    sigma = 0.5

    def timed(fn):
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            ts.append(time.perf_counter() - t0)
        return float(np.median(ts))

    host_secure_round_update(params, updates, policy=policy,
                             defense=defense, dp=dp,
                             dp_sigma=sigma)  # compile outside the timing
    host_s = timed(lambda: host_secure_round_update(
        params, updates, policy=policy, defense=defense, dp=dp,
        dp_sigma=sigma)[0])

    plane = ShardedRoundPlane(policy=policy, defense=defense, dp=dp)
    state = {"tree": plane.round_update(params, updates,
                                        dp_sigma=sigma), "round": 1}

    def staged_once():
        state["tree"] = plane.round_update(
            state["tree"], updates, round_idx=state["round"],
            dp_sigma=sigma)
        state["round"] += 1
        return state["tree"]

    comp_s = timed(staged_once)
    out.update({
        "defended_round_host_s": round(host_s, 6),
        "defended_round_compiled_s": round(comp_s, 6),
        "defended_round_speedup": round(host_s / max(comp_s, 1e-9), 4),
        "defended_round_defense": "multi_krum+gaussian_dp",
    })

    # DP stage overhead inside the fused program: same plane with and
    # without the stage
    plain = ShardedRoundPlane(policy=policy)
    pstate = {"tree": plain.round_update(params, updates)}

    def plain_once():
        pstate["tree"] = plain.round_update(pstate["tree"], updates)
        return pstate["tree"]

    plain_s = timed(plain_once)
    dp_plane = ShardedRoundPlane(policy=policy, dp=dp)
    dstate = {"tree": dp_plane.round_update(params, updates,
                                            dp_sigma=sigma), "round": 1}

    def dp_once():
        dstate["tree"] = dp_plane.round_update(
            dstate["tree"], updates, round_idx=dstate["round"],
            dp_sigma=sigma)
        dstate["round"] += 1
        return dstate["tree"]

    dp_s = timed(dp_once)
    out.update({
        "dp_round_s": round(dp_s, 6),
        "dp_overhead_frac": round(
            max(dp_s - plain_s, 0.0) / max(plain_s, 1e-9), 4),
    })
    return out


def _measure_secagg() -> dict:
    """``secagg_mask_s``: one full SecAgg cycle — quantize + pairwise mask,
    submit, finite-field fold, unmask — on the compiled field plane.  Lower
    is better (LATENCY band)."""
    import numpy as np

    from fedml_tpu.core.mpc.dropout import SecAggRound

    reps = int(os.environ.get("BENCH_AGG_REPS", "5"))
    k = int(os.environ.get("BENCH_SECAGG_CLIENTS", "8"))
    rng = np.random.default_rng(11)
    vec = rng.standard_normal(int(
        os.environ.get("BENCH_SECAGG_DIM", "65536"))).astype(np.float64)

    def secagg_cycle():
        rnd = SecAggRound(n_clients=k, seed=3, plane="compiled")
        for i in range(k):
            rnd.submit(i, rnd.client_payload(i, vec))
        return rnd.unmask()

    secagg_cycle()  # pay the field-kernel compile outside the timing
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        secagg_cycle()
        ts.append(time.perf_counter() - t0)
    return {
        "secagg_mask_s": round(float(np.median(ts)), 6),
        "secagg_clients": k,
    }


def _measure_remesh() -> dict:
    """The elastic-resize keys (PR 16): total downtime of an in-place
    ``ShardedRoundPlane.remesh`` — host-gather the resident params +
    optimizer state, re-shard onto a mesh with half the model axis, and
    warm-recompile the round program — plus the recompile slice alone.
    Lower is better (banded as ceilings in tools/perf_gate.py)."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from fedml_tpu.parallel.agg_plane import ShardedRoundPlane
    from fedml_tpu.parallel.mesh import create_round_mesh

    devs = jax.devices()
    if len(devs) < 2:
        # the shrink goes from a model=2^k axis to half of it; one device
        # has nothing to shrink — said in the record, not swallowed
        return {"remesh_skipped": f"needs >= 2 devices, have {len(devs)}"}
    model = 1 << (len(devs).bit_length() - 1)  # largest pow2
    mesh_a = create_round_mesh(clients=1, model=model,
                               devices=devs[:model])
    mesh_b = create_round_mesh(clients=1, model=model // 2,
                               devices=devs[:model // 2])
    n = int(os.environ.get("BENCH_AGG_CLIENTS", "32"))
    updates = _synthetic_updates(n)
    rng = np.random.default_rng(7)
    params = {k: jnp.asarray(rng.standard_normal(np.shape(v)), jnp.float32)
              for k, v in updates[0][1].items()}
    plane = ShardedRoundPlane(policy=("adam", 0.1, 0.9), mesh=mesh_a)
    plane.round_update(params, updates)  # resident state + program
    info = plane.remesh(mesh_b)
    if not (info and info.get("changed")):
        raise RuntimeError(
            f"remesh to half the model axis changed nothing: {info!r}")
    return {
        "resize_downtime_s": round(float(info["seconds"]), 6),
        "remesh_recompile_s": round(float(info["recompile_s"]), 6),
        "remesh_reshard_bytes": int(info["reshard_bytes"]),
    }


def _measure_upload_saturation() -> dict:
    """The "heavy traffic" numbers: sustained server ingest rate over the
    accept loop, measured twice (PR 10) —

    * **host leg** (``uploads_per_s_host``, also kept as the legacy
      ``uploads_per_s`` key for band continuity): the serial dispatcher
      path — per-sender dedup, msgpack payload decode, length+crc32-framed
      journal append with a PER-UPLOAD fsync before the ack (the PR 4
      crash-safety contract, paid at full price), ack frame encode.
    * **pipelined leg** (``uploads_per_s_pipelined``): the staged ingest
      path — zero-copy decode into per-sender arenas, zero-copy blob
      append into the group-commit journal (one fsync per batch), acks
      released only once the batch is durable; the clock stops after the
      LAST ack is released, so the contract is identical, only amortized.

    Both legs are driven by the same synthetic firehose (~11% retransmits)
    and both report their ``journal.fsync_seconds`` observation-count delta
    (``journal_fsync_count_*``), making the fsync amortization a first-class
    banded fact.  No sockets: this saturates the server-side loop itself,
    not loopback plumbing.  Pure host work."""
    import shutil
    import tempfile

    import numpy as np

    from flax import serialization

    from fedml_tpu.core import obs
    from fedml_tpu.core.checkpoint import UpdateJournal
    from fedml_tpu.core.ingest import ZeroCopyDecoder

    n_uploads = int(os.environ.get("BENCH_UPLOADS", "240"))
    n_senders = 16
    fsync = os.environ.get("BENCH_JOURNAL_FSYNC", "always")
    gc_ms = float(os.environ.get("BENCH_GROUP_COMMIT_MS", "5"))
    gc_max = int(os.environ.get("BENCH_GROUP_COMMIT_MAX", "32"))
    rng = np.random.default_rng(0)
    deltas = [
        {"w/kernel": rng.standard_normal((64, 64)).astype(np.float32),
         "w/bias": rng.standard_normal(64).astype(np.float32),
         "head/kernel": rng.standard_normal((64, 10)).astype(np.float32)}
        for _ in range(n_senders)
    ]
    # the wire blobs: each sender's upload payload in the exact record
    # layout the journal stores, so the pipelined leg can append the
    # received bytes verbatim (UpdateJournal.append_blob_async)
    blobs = [serialization.msgpack_serialize(
        {"sender": s, "n_samples": 32, "version": 0,
         "model_params": deltas[s]}) for s in range(n_senders)]
    payload_bytes = len(blobs[0])

    def fsync_count() -> int:
        h = obs.registry().get_histogram("journal.fsync_seconds")
        return int(h["count"]) if h else 0

    def firehose():
        """Yield (key, version, is_dup) over the shared upload schedule."""
        seen = set()
        for i in range(n_uploads):
            sender = i % n_senders
            version = i // n_senders
            if i % 9 == 8:  # firehose retransmit: an already-sent key
                key = ((sender - 1) % n_senders, version)
            else:
                key = (sender, version)
            dup = key in seen
            seen.add(key)
            yield key, version, dup

    def host_leg():
        tmp = tempfile.mkdtemp(prefix="bench_journal_")
        try:
            journal = UpdateJournal(tmp, fsync=fsync)
            deduped = 0
            t0 = time.perf_counter()
            for key, version, dup in firehose():
                if dup:
                    deduped += 1  # journaled already: discard, no ack
                    continue
                if key[0] == 0 and version:
                    journal.prune_before(version)  # flushed-cycle cleanup
                record = serialization.msgpack_restore(blobs[key[0]])
                journal.append(version, record)
                serialization.msgpack_serialize(  # the ack frame
                    {"sender": key[0], "version": version, "ok": True})
            dt = time.perf_counter() - t0
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
        return (n_uploads - deduped) / max(dt, 1e-9), deduped

    def pipelined_leg():
        tmp = tempfile.mkdtemp(prefix="bench_journal_")
        try:
            journal = UpdateJournal(tmp, fsync=fsync,
                                    group_commit_ms=gc_ms,
                                    group_commit_max=gc_max)
            decoder = ZeroCopyDecoder()
            for s in range(n_senders):  # learning pass outside the clock
                decoder.decode(s, blobs[s])
            deduped = 0
            pending = []
            t0 = time.perf_counter()
            for key, version, dup in firehose():
                if dup:
                    deduped += 1
                    continue
                if key[0] == 0 and version:
                    journal.prune_before(version)
                decoder.decode(key[0], blobs[key[0]])  # arena-backed tree
                pending.append((key[0], version,
                                journal.append_blob_async(version,
                                                          blobs[key[0]])))
            journal.flush(timeout=60.0)
            for sender, version, ticket in pending:
                if not ticket.durable:  # ack withheld: leg is invalid
                    raise RuntimeError("journal batch never went durable")
                serialization.msgpack_serialize(  # the deferred ack frame
                    {"sender": sender, "version": version, "ok": True})
            dt = time.perf_counter() - t0
            journal.close()
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
        return (n_uploads - deduped) / max(dt, 1e-9), deduped

    f0 = fsync_count()
    host_rate, deduped = host_leg()
    host_fsyncs = fsync_count() - f0
    f0 = fsync_count()
    pipe_rate, _ = pipelined_leg()
    pipe_fsyncs = fsync_count() - f0
    return {
        "uploads_per_s": round(host_rate, 2),  # legacy band continuity
        "uploads_per_s_host": round(host_rate, 2),
        "uploads_per_s_pipelined": round(pipe_rate, 2),
        "journal_fsync_count_host": host_fsyncs,
        "journal_fsync_count_pipelined": pipe_fsyncs,
        "upload_payload_bytes": payload_bytes,
        "uploads_deduped": deduped,
        "journal_fsync": fsync,
        "group_commit_ms": gc_ms,
        "group_commit_max": gc_max,
    }


def _measure_fanin() -> dict:
    """Hierarchical fan-in relative keys (PR 18): the same 512-leaf round
    ingested two ways, both evaluating the SAME
    :class:`~fedml_tpu.core.hierarchy.plan.HierarchyPlan` so the
    arithmetic is identical and only the topology moves —

    * **flat leg** (``fanin_uploads_per_s_flat``): one root serially
      journals every leaf upload (decode + length/crc32-framed append,
      the PR 4 durability contract) then folds the whole plan in-process.
    * **edge leg** (``fanin_uploads_per_s_edge``): the plan's leaf-edge
      blocks run concurrently — each edge thread journals ITS block's
      uploads into its own journal and folds its block partial; the clock
      stops after the root combines the edge partials in block order.

    ``edge_forward_bytes`` is the wire size of one edge's fused forward
    delta (the O(model) payload an edge sends regardless of fanout) —
    the number that makes "edge memory/egress is O(model), not
    O(clients)" a banded fact.  Pure host work (journals + host fold)."""
    import concurrent.futures
    import shutil
    import tempfile

    import numpy as np

    from flax import serialization

    from fedml_tpu.core.checkpoint import UpdateJournal
    from fedml_tpu.core.compression import wire_bytes
    from fedml_tpu.core.hierarchy.plan import HierarchyPlan

    n_leaves = int(os.environ.get("BENCH_FANIN_LEAVES", "512"))
    fanout = int(os.environ.get("BENCH_FANIN_FANOUT", "64"))
    fsync = os.environ.get("BENCH_JOURNAL_FSYNC", "always")
    plan = HierarchyPlan(n_leaves=n_leaves, levels=2, edge_fanout=fanout)
    rng = np.random.default_rng(7)
    # a handful of distinct payload templates; each leaf's wire blob is
    # pre-encoded so both legs pay decode + journal + fold, nothing
    # else.  ~4KB frames: million-client leaves ship compressed deltas
    # (docs/COMPRESSION.md), and at this size the per-upload cost is the
    # durability round-trip itself — exactly what the edge tier shards.
    templates = [
        {"w/kernel": rng.standard_normal((32, 32)).astype(np.float32),
         "w/bias": rng.standard_normal(32).astype(np.float32),
         "head/kernel": rng.standard_normal((32, 10)).astype(np.float32)}
        for _ in range(16)
    ]
    blobs = [serialization.msgpack_serialize(
        {"sender": i, "n_samples": 16 + (i % 48), "version": 0,
         "model_params": templates[i % len(templates)]})
        for i in range(n_leaves)]

    def ingest(journal, leaf_indices):
        """Decode + journal each upload; return the block's updates in
        leaf-index order (the plan's fold order)."""
        updates = []
        for i in leaf_indices:
            rec = serialization.msgpack_restore(blobs[i])
            journal.append(0, rec)
            updates.append((float(rec["n_samples"]),
                            rec["model_params"]))
        return updates

    def flat_leg():
        tmp = tempfile.mkdtemp(prefix="bench_fanin_flat_")
        try:
            journal = UpdateJournal(tmp, fsync=fsync)
            t0 = time.perf_counter()
            updates = ingest(journal, range(n_leaves))
            plan.aggregate(updates, mode="mean")
            dt = time.perf_counter() - t0
            journal.close()
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
        return n_leaves / max(dt, 1e-9)

    def edge_leg():
        tmp = tempfile.mkdtemp(prefix="bench_fanin_edge_")
        total = float(sum(16 + (i % 48) for i in range(n_leaves)))

        def run_edge(e):
            journal = UpdateJournal(os.path.join(tmp, f"edge_{e}"),
                                    fsync=fsync)
            updates = ingest(journal, plan.blocks[e])
            partial = plan.block_partial(updates, total, mode="mean")
            journal.close()
            return partial

        try:
            with concurrent.futures.ThreadPoolExecutor(
                    max_workers=plan.n_edges) as pool:
                t0 = time.perf_counter()
                partials = list(pool.map(run_edge,
                                         range(plan.n_edges)))
                plan.combine(partials)
                dt = time.perf_counter() - t0
            fwd_bytes = wire_bytes(partials[0])
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
        return n_leaves / max(dt, 1e-9), fwd_bytes

    # median of reps: fsync latency on shared storage is noisy and the
    # first rep pays cold-start (page cache, allocator) — the median
    # drops it without a separate warmup pass
    reps = int(os.environ.get("BENCH_FANIN_REPS", "3"))
    flat_rate = float(np.median([flat_leg() for _ in range(reps)]))
    edge_runs = [edge_leg() for _ in range(reps)]
    edge_rate = float(np.median([r for r, _ in edge_runs]))
    fwd_bytes = edge_runs[0][1]
    return {
        "fanin_uploads_per_s_flat": round(flat_rate, 2),
        "fanin_uploads_per_s_edge": round(edge_rate, 2),
        "fanin_edge_speedup": round(edge_rate / max(flat_rate, 1e-9), 3),
        "edge_forward_bytes": fwd_bytes,
        "fanin_leaves": n_leaves,
        "fanin_edges": plan.n_edges,
    }


def _measure_async_throughput() -> dict:
    """Buffered-async round-throughput keys: a small sp FedBuff run
    (synthetic data, lr model) timed end-to-end — flushes (the async
    'round') and accepted deltas per second.  Host-loop work, cheap on
    purpose."""
    import fedml_tpu
    from fedml_tpu.arguments import Arguments
    from fedml_tpu.simulation.sp.async_fedavg.fedbuff_api import FedBuffAPI

    cfg = {
        "common_args": {"training_type": "simulation", "random_seed": 0,
                        "run_id": "bench_async"},
        "data_args": {"dataset": "mnist", "data_cache_dir": "",
                      "partition_method": "hetero", "partition_alpha": 0.5,
                      "synthetic_train_size": 480},
        "model_args": {"model": "lr"},
        "train_args": {
            "federated_optimizer": "FedAvg",
            "client_num_in_total": 8,
            "client_num_per_round": 4,
            "comm_round": 6,
            "epochs": 1,
            "batch_size": 32,
            "client_optimizer": "sgd",
            "learning_rate": 0.1,
            "fl_mode": "async",
            "async_buffer_size": 2,
            "async_max_staleness": 2,
            "async_staleness_policy": "polynomial",
        },
        "validation_args": {"frequency_of_the_test": 100},
        "comm_args": {"backend": "sp"},
    }
    args = fedml_tpu.init(Arguments.from_dict(cfg).validate(),
                          should_init_logs=False)
    dataset, out_dim = fedml_tpu.data.load(args)
    model = fedml_tpu.models.create(args, out_dim)
    api = FedBuffAPI(args, None, dataset, model)
    t0 = time.perf_counter()
    api.train()
    dt = time.perf_counter() - t0
    flushes = int(args.comm_round)
    # the flush loop drains exactly `capacity` deltas per flush
    deltas = flushes * api.buffer.capacity
    return {
        "async_flushes_per_s": round(flushes / max(dt, 1e-9), 3),
        "async_deltas_per_s": round(deltas / max(dt, 1e-9), 3),
        "async_buffer_size": api.buffer.capacity,
    }


def _measure_chunked() -> dict:
    """Chunked-upload streaming keys (the resumable-upload plane), pure
    host arithmetic over the REAL framing seam:

    * ``chunk_overhead_frac`` — wire framing cost: the serialized chunk
      frames of a representative 4 MiB upload at 64 KiB chunks, relative
      to the raw payload bytes.  Lower-is-better with an absolute cap —
      headers eating the payload would eat the resumability win too.
    * ``chunked_goodput_frac_lossy`` — payload bytes over total wire
      bytes for an upload whose link dies at 90% of the stream: the
      resumable sender replays only its unacked window (the acked prefix
      survives the cut), where a whole-message sender replays everything.
      Higher-is-better, banded against the trajectory; the whole-message
      figure rides along unbanded for scale.

    Pure host work."""
    import pickle

    import numpy as np

    from fedml_tpu.core.distributed.chunking import _KEY_DATA, build_chunks
    from fedml_tpu.core.distributed.communication.message import Message

    chunk_bytes = int(os.environ.get("BENCH_CHUNK_BYTES", str(64 * 1024)))
    window = int(os.environ.get("BENCH_CHUNK_WINDOW", "8"))
    rng = np.random.default_rng(0)
    payload = rng.standard_normal(4 * 1024 * 1024 // 8).tobytes()
    inner = Message("bench_upload", 1, 0)
    inner.add_params("round_idx", 0)
    frames = build_chunks("bench:0:1", inner, payload, chunk_bytes)
    sizes = [len(f.get(_KEY_DATA)) for f in frames]
    assert b"".join(f.get(_KEY_DATA) for f in frames) == payload
    wire = sum(len(pickle.dumps(f.get_params(),
                                protocol=pickle.HIGHEST_PROTOCOL))
               for f in frames)
    overhead = wire / len(payload) - 1.0

    # the lossy replay model: the link dies after 90% of the chunks
    # are on the wire; everything acked before the cut stays acked
    # (journal-before-ack), so the resumed stream re-sends only the
    # in-flight window plus the untransmitted tail
    n = len(frames)
    cut = max(1, int(0.9 * n))
    sent_before = sum(sizes[:cut])
    resumed_total = sent_before + sum(sizes[max(0, cut - window):])
    restart_total = sent_before + len(payload)
    return {
        "chunk_overhead_frac": round(overhead, 5),
        "chunked_goodput_frac_lossy": round(
            len(payload) / resumed_total, 4),
        "whole_message_goodput_frac_lossy": round(
            len(payload) / restart_total, 4),
        "chunk_bytes": chunk_bytes,
        "chunk_window": window,
    }


def _measure_telemetry_overhead() -> dict:
    """Telemetry-plane relative keys: a synthetic federated round — the
    server's real per-round work (one compiled agg step over N client
    deltas) plus N client report messages — timed with the plane ON
    (every client records its train sub-spans + a resource sample,
    attaches the blob to its upload ``Message``, the server-side merger
    absorbs) vs the IDENTICAL loop with ``obs_telemetry`` off, where the
    facade hands out no capture/merger, so the off leg pays exactly what
    a telemetry-off run pays.  Anchoring both legs on the agg step keeps
    ``telemetry_overhead_frac`` comparable to ``obs_overhead_frac``'s
    budget (telemetry vs real round cost, not vs an empty loop).  Also
    prices the wire: mean blob bytes per round."""
    import numpy as np

    from fedml_tpu.core import obs
    from fedml_tpu.core.distributed.communication.message import Message
    from fedml_tpu.parallel.agg_plane import CompiledAggPlane

    import jax

    n_clients = 8
    rounds = int(os.environ.get("BENCH_TELEMETRY_ROUNDS", "15"))

    def _loop(enabled: bool, plane, updates):
        class _Args:
            run_id = "bench_telemetry"
            obs_telemetry = 1 if enabled else 0

        obs.configure(_Args(), lambda topic, rec: None)
        try:
            caps = [obs.make_client_telemetry(i + 1)
                    for i in range(n_clients)]
            merger = obs.make_telemetry_merger()
            wire_bytes = 0
            ts = []
            for r in range(rounds):
                t0 = time.perf_counter()
                for i, cap in enumerate(caps):
                    msg = Message("send_model_to_server", i + 1, 0)
                    if cap is not None:
                        tctx = cap.record_span(
                            "client.train", 0.01, round_idx=r,
                            client_index=i)
                        cap.record_span("client.train.step", 0.01,
                                        parent=tctx, round_idx=r)
                        cap.record_counter("comm.bytes_sent", 1024.0)
                        cap.sample_resources()
                        wire_bytes += cap.attach(msg)
                    if merger is not None:
                        merger.absorb(msg)
                jax.block_until_ready(plane.aggregate(updates))
                ts.append(time.perf_counter() - t0)
            return float(np.median(ts)), wire_bytes
        finally:
            obs.shutdown()

    updates = _synthetic_updates(n_clients)
    plane = CompiledAggPlane()
    plane.aggregate(updates)  # compile outside the timed legs
    on_s, wire_bytes = _loop(True, plane, updates)
    off_s, _ = _loop(False, plane, updates)
    if on_s <= 0 or off_s <= 0:
        return {}
    return {
        "telemetry_rounds_per_s": round(1.0 / on_s, 2),
        "telemetry_rounds_per_s_off": round(1.0 / off_s, 2),
        "telemetry_overhead_frac": round(
            max(on_s - off_s, 0.0) / off_s, 4),
        "telemetry_bytes_per_round": round(wire_bytes / rounds, 1),
    }


def _measure_health_overhead() -> dict:
    """Health-plane relative key: the telemetry benchmark's synthetic round
    (compiled agg step + round span + ``maybe_export_metrics``, which is
    where the health plane ticks) with ``obs_health`` ON vs the identical
    loop with it off.  The on leg pays the tap (one dict peek per record),
    the per-tick registry pulls, and the window/watchdog checks — i.e. the
    whole liveness plane on the round path.  ``health_overhead_frac``
    rides the shared obs overhead budget."""
    import numpy as np

    from fedml_tpu.core import obs
    from fedml_tpu.parallel.agg_plane import CompiledAggPlane

    import jax

    rounds = int(os.environ.get("BENCH_HEALTH_ROUNDS", "15"))

    def _loop(enabled: bool, plane, updates):
        class _Args:
            run_id = "bench_health"
            obs_health = 1 if enabled else 0

        obs.configure(_Args(), lambda topic, rec: None)
        try:
            wd = obs.health_watchdog("bench.round_loop")
            ts = []
            for r in range(rounds):
                t0 = time.perf_counter()
                wd.beat()
                with obs.round_span(r, mode="bench_health"):
                    jax.block_until_ready(plane.aggregate(updates))
                    obs.health_observe("bench.round_seconds",
                                       time.perf_counter() - t0)
                obs.maybe_export_metrics()
                obs.health_tick()
                ts.append(time.perf_counter() - t0)
            return float(np.median(ts))
        finally:
            obs.shutdown()

    updates = _synthetic_updates(8)
    plane = CompiledAggPlane()
    plane.aggregate(updates)  # compile outside the timed legs
    on_s = _loop(True, plane, updates)
    off_s = _loop(False, plane, updates)
    if on_s <= 0 or off_s <= 0:
        return {}
    return {
        "health_round_s_on": round(on_s, 6),
        "health_round_s_off": round(off_s, 6),
        "health_overhead_frac": round(max(on_s - off_s, 0.0) / off_s, 4),
    }


def _measure_round_throughput() -> dict:
    """Round-throughput trajectory keys: a small SYNC sp FedAvg run
    (synthetic data, lr model) timed per round — full federated rounds
    per second and clients simulated per second: the round-orchestration
    trend (sampling, dispatch, aggregate, eval gating) of the host loop,
    not a device metric."""
    import numpy as np

    import fedml_tpu
    from fedml_tpu.arguments import Arguments
    from fedml_tpu.simulation.sp.fedavg.fedavg_api import FedAvgAPI

    clients_per_round = 4
    cfg = {
        "common_args": {"training_type": "simulation", "random_seed": 0,
                        "run_id": "bench_rounds"},
        "data_args": {"dataset": "mnist", "data_cache_dir": "",
                      "partition_method": "hetero", "partition_alpha": 0.5,
                      "synthetic_train_size": 480},
        "model_args": {"model": "lr"},
        "train_args": {
            "federated_optimizer": "FedAvg",
            "client_num_in_total": 8,
            "client_num_per_round": clients_per_round,
            "comm_round": 6,
            "epochs": 1,
            "batch_size": 32,
            "client_optimizer": "sgd",
            "learning_rate": 0.1,
        },
        "validation_args": {"frequency_of_the_test": 100},
        "comm_args": {"backend": "sp"},
    }
    args = fedml_tpu.init(Arguments.from_dict(cfg).validate(),
                          should_init_logs=False)
    dataset, out_dim = fedml_tpu.data.load(args)
    model = fedml_tpu.models.create(args, out_dim)
    api = FedAvgAPI(args, None, dataset, model)
    api.train()
    # median over post-compile rounds: round 0 pays jit + first dispatch
    times = list(api.round_times)
    times = times[1:] or times
    round_s = float(np.median(times))
    rps = 1.0 / max(round_s, 1e-9)
    return {
        "rounds_per_s": round(rps, 3),
        "clients_simulated_per_s": round(rps * clients_per_round, 3),
        "round_clients": clients_per_round,
    }


def _measure_obs_overhead(sim) -> dict:
    """Round-trace overhead proof: re-run the already-compiled simulator
    with ``core/obs`` tracing enabled (spans emitted to an in-memory sink)
    and compare median round latency against the tracing-off rounds just
    measured.  The acceptance budget is < 2% — the span layer is a handful
    of hash+dict records per round next to an XLA program that trains all
    clients.

    The obs-on leg also runs the metrics EXPORTER (file-snapshot mode), so
    ``obs_overhead_frac`` prices spans + registry + OpenMetrics rendering
    together — the whole observability plane, not just the span layer."""
    import shutil
    import tempfile

    import numpy as np

    from fedml_tpu.core import obs
    from fedml_tpu.core.mlops.sinks import InMemorySink

    # post-compile tracing-off rounds: drop the first recorded round
    mark = len(sim.round_times)
    off = [t for t in sim.round_times[1:mark]]
    export_dir = tempfile.mkdtemp(prefix="bench_export_")
    sim.args.obs_export_path = os.path.join(export_dir, "metrics.prom")
    try:
        obs.configure(sim.args, InMemorySink().emit)
        sim.train()  # appends comm_round more rounds, same compiled program
    finally:
        obs.shutdown()
        sim.args.obs_export_path = None
        shutil.rmtree(export_dir, ignore_errors=True)
    on = sim.round_times[mark:]
    if not off or not on:
        raise RuntimeError(
            f"no rounds to compare ({len(off)} tracing-off, {len(on)} on)")
    off_s = float(np.median(off))
    on_s = float(np.median(on))
    return {
        "round_s_obs_off": round(off_s, 4),
        "round_s_obs_on": round(on_s, 4),
        "obs_overhead_frac": round(on_s / max(off_s, 1e-9) - 1.0, 4),
    }


def _measure_transformer(
    d_model: int = 1024, n_layers: int = 8, n_heads: int = 16, d_ff: int = 4096,
    vocab: int = 32000, seq_len: int = 1024, batch: int = 8, n_steps: int = 20,
):
    """Opt-in (BENCH_TRANSFORMER=1): single-chip training throughput + MFU of
    the in-repo TransformerLM (models/transformer.py) — bf16 compute, fp32
    params, causal LM loss, back-to-back jitted steps.

    MFU uses the standard analytic cost: 6*N*tokens for the parameter math
    (fwd+bwd) plus 12*L^2*d*layers*batch for attention, over the bf16 peak
    of the device_kind that ran (DEVICE_PEAKS).
    Override shapes via BENCH_TF_* env vars (CPU smoke: BENCH_TF_DMODEL=64
    BENCH_TF_LAYERS=2 BENCH_TF_SEQ=128 BENCH_TF_BATCH=2)."""
    import time as _time

    import jax
    import jax.numpy as jnp
    import optax

    from fedml_tpu.models.transformer import TransformerConfig, TransformerLM

    d_model = int(os.environ.get("BENCH_TF_DMODEL", d_model))
    n_layers = int(os.environ.get("BENCH_TF_LAYERS", n_layers))
    n_heads = int(os.environ.get("BENCH_TF_HEADS", n_heads))
    d_ff = int(os.environ.get("BENCH_TF_DFF", d_ff))
    seq_len = int(os.environ.get("BENCH_TF_SEQ", seq_len))
    batch = int(os.environ.get("BENCH_TF_BATCH", batch))
    n_steps = int(os.environ.get("BENCH_TF_STEPS", n_steps))

    peak_tflops = _device_peaks(jax.devices()[0].device_kind)["bf16_tflops"]
    cfg = TransformerConfig(
        vocab_size=vocab, d_model=d_model, n_heads=n_heads, n_layers=n_layers,
        d_ff=d_ff, max_seq_len=seq_len, dtype=jnp.bfloat16,
    )
    model = TransformerLM(cfg)
    key = jax.random.PRNGKey(0)
    tokens = jax.random.randint(key, (batch, seq_len), 0, vocab, jnp.int32)
    params = model.init(key, tokens[:, :8])
    n_params = sum(int(p.size) for p in jax.tree_util.tree_leaves(params))
    tx = optax.sgd(1e-3)
    opt_state = tx.init(params)

    def step(params, opt_state, tok):
        def loss_fn(p):
            logits = model.apply(p, tok[:, :-1])
            per = optax.softmax_cross_entropy_with_integer_labels(
                logits.astype(jnp.float32), tok[:, 1:]
            )
            return jnp.mean(per)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    jstep = jax.jit(step)
    params, opt_state, _ = jstep(params, opt_state, tokens)  # compile
    jax.block_until_ready(params)
    t0 = _time.time()
    for _ in range(n_steps):
        params, opt_state, loss = jstep(params, opt_state, tokens)
    jax.block_until_ready(params)
    dt = _time.time() - t0

    tokens_per_step = batch * (seq_len - 1)
    tok_per_s = n_steps * tokens_per_step / max(dt, 1e-9)
    # analytic training FLOPs: 6*N per token + attention 12*L*d per token-layer
    flops_step = (6.0 * n_params * tokens_per_step
                  + 12.0 * n_layers * d_model * (seq_len - 1) * tokens_per_step)
    achieved_tflops = flops_step * n_steps / max(dt, 1e-9) / 1e12
    # no vs_baseline key on this line: the file-header contract defines
    # vs_baseline as "divided by a MEASURED eager baseline", and this run IS
    # the eager loop — mfu (vs chip peak) is the headline ratio here
    return {
        "metric": "transformer_lm_training_tokens_per_sec_per_chip",
        "value": round(tok_per_s, 1),
        "unit": "tokens/s/chip",
        "device": _device_stamp(),
        "mfu": round(achieved_tflops / peak_tflops, 5),
        "achieved_tflops": round(achieved_tflops, 2),
        "n_params": n_params,
        "config": {"d_model": d_model, "n_layers": n_layers, "n_heads": n_heads,
                   "d_ff": d_ff, "seq_len": seq_len, "batch": batch},
        "compute_dtype": "bf16",
    }


def _measure_sp(args, dataset) -> float:
    """Opt-in (BENCH_SP=1): host-loop sp FedAvg throughput for comparison."""
    import copy

    import fedml_tpu
    from fedml_tpu.simulation.sp.fedavg.fedavg_api import FedAvgAPI

    sp_args = copy.deepcopy(args)
    sp_args.backend = "sp"
    sp_args.comm_round = 3
    sp_args.frequency_of_the_test = 100
    model = fedml_tpu.models.create(sp_args, 10)
    api = FedAvgAPI(sp_args, None, dataset, model)
    api.train()
    import numpy as np

    # pair each round's ACTUAL trained-sample count with its wall time
    # (per-round client sampling varies sizes under the Dirichlet partition)
    pairs = list(zip(api.samples_per_round, api.round_times))
    pairs = pairs[1:] or pairs  # drop the compile round
    return float(np.median([s / max(t, 1e-9) for s, t in pairs]))


if __name__ == "__main__":
    sys.exit(main())
