"""chip_smoke.py — the quickest proof that fedml_tpu starts, compiles and
steps on the chip.  Not a benchmark: every number it prints is a smoke
observation on the device named beside it.

    python chip_smoke.py            # on a TPU host; exits 0 and ends with
                                    # {"ok": true, "device": {...}}

ONE process holds the chip and runs every leg in it; nothing is spawned.
It refuses (nonzero exit, no result line) unless jax's default backend is
``tpu`` — the refusal comes from the normal entry point,
``fedml_tpu.device.get_device`` honouring ``device_args.device_type: tpu``,
before any data is generated.

* leg A — the north-star path through the entry points a user calls:
  ``fedml_tpu.init`` -> ``device.get_device`` -> ``data.load`` ->
  ``models.create`` -> ``FedMLRunner(...).run()`` with ``backend: "XLA"``
  at full width (ResNet-56, CIFAR-10 shapes, 100 Dirichlet(0.5) clients,
  32/round, packed round, batch 64, bf16 compute / fp32 params), depth cut
  to ROUNDS rounds, final-round eval on, on a ONE-device mesh.  The data is
  the seed-generated synthetic set (no network, no files).
* leg B — the Pallas kernels, compiled (never interpreted): ``flash_attention``
  forward and ``jax.grad`` against ``reference_attention``, and
  ``flash_shard_update`` against ``shard_update_reference``, within
  TOLERANCE; at the ``kimi-linear-48b-a3b-sim`` cell's shapes flash at q/k
  192 != v 128 (and at ``glm-4.7-flash-sim``'s 256 / 256 over 20 heads), KDA
  through the entry the model calls (the kernels ``kda_fwd``
  / ``kda_bwd``, or the leg fails) forward and ``jax.grad`` against the
  per-token recurrence (``kda_errors``) and the expert layer's grouped products
  against a dense masked loop (``kimi_linear_ops``); then TransformerLM
  training steps at the bench
  transformer shapes through its DEFAULT attention.
* leg C — only when the host has >= 4 devices: leg A again on the
  four-device ``client`` mesh (per-round loss must agree with leg A within
  LOSS_RTOL) and ``ring_attention(..., block_fn=pallas_block_attend)`` over
  ``sp=4``.

A leg that raises or fails a check is reported with its traceback, the
other legs still run (chip calls are budgeted), and the exit code is 1 with
no result line.
"""

from __future__ import annotations

import json
import sys
import time
import traceback

ROUNDS = 4  # round 0 compiles; three more run
ROUND_CONFIG = {
    "common_args": {"training_type": "simulation", "random_seed": 0,
                    "run_id": "chip_smoke"},
    "data_args": {"dataset": "cifar10", "data_cache_dir": "",  # "" = synthetic
                  "partition_method": "hetero", "partition_alpha": 0.5},
    "model_args": {"model": "resnet56", "compute_dtype": "bf16"},
    "train_args": {"federated_optimizer": "FedAvg",
                   "client_num_in_total": 100, "client_num_per_round": 32,
                   "xla_pack": True, "comm_round": ROUNDS, "epochs": 1,
                   "batch_size": 64, "client_optimizer": "sgd",
                   "learning_rate": 0.001},
    # eval fires on round 0 and on the final round
    "validation_args": {"frequency_of_the_test": ROUNDS},
    "device_args": {"device_type": "tpu"},
    "comm_args": {"backend": "XLA"},
    # the obs plane on, so obs.compile_seconds_total() counts
    "tracking_args": {"using_mlops": True, "obs_trace": True},
}
TRANSFORMER = dict(vocab_size=32000, d_model=1024, n_heads=16, n_layers=8,
                   d_ff=4096, max_seq_len=1024)  # bench._measure_transformer
TRANSFORMER_BATCH, TRANSFORMER_STEPS = 8, 3
# kernel-vs-reference bound on max|a - ref| / max|ref|.  One bound for both
# input dtypes: at the chip's default matmul precision the MXU multiplies
# bf16-rounded operands whatever the input dtype (f32 inputs measured 3.5e-3
# forward on the v5e, this PR), and both sides round probabilities to the
# input dtype before the PV matmul — so the bound is a few bf16 ulps (eps
# 7.8e-3).  The references run at "highest" matmul precision: they are the
# truth, not a second approximation.
TOLERANCE = 2e-2
# (B, L, H, D, dtype): the bench transformer's attention shapes (ragged L,
# D=64), the TransformerConfig default head dim (D=32), and the benchmark
# cells' own shape (dsllm7b-sim: 32 heads of 128, L 2,048, batch 2)
KERNEL_SHAPES = [(8, 1023, 16, 64, "bfloat16"), (2, 256, 8, 32, "float32"),
                 (2, 2048, 32, 128, "bfloat16")]
# the kimi-linear-48b-a3b-sim cell's shapes: one sequence of 8,192 tokens, 32
# heads, q/k 192 and v 128 (MLA), 128 / 128 (KDA), hidden 2,304, 8 held of 256
# experts of 1,024 at top 8.  The references are computed ORACLE_HEADS heads at
# a time (a head's attention and a head's recurrence know no other head), so
# that the float32 scores and the per-token scan's saved states fit the chip.
KIMI = dict(L=8192, H=32, qk=192, v=128, kda=128, d=2304, f=1024, held=8, routed=256, top=8)
ORACLE_HEADS = 4
# the glm-4.7-flash-sim cell's latent attention at the same length: (heads, q/k, v)
GLM47_FLASH = (20, 256, 256)
# one- vs four-device runs of the same seed train the same clients on the
# same batches; they differ in summation order under bf16 compute (measured
# 7.2e-5 over these four rounds on the v5e, this PR)
LOSS_RTOL = 1e-3


def _memory_stats():
    import jax

    out = []
    for d in jax.devices():
        s = d.memory_stats() or {}
        out.append({"id": d.id,
                    "bytes_in_use": s.get("bytes_in_use"),
                    "peak_bytes_in_use": s.get("peak_bytes_in_use")})
    return out


def _rel_err(a, ref):
    import jax.numpy as jnp

    a, ref = a.astype(jnp.float32), ref.astype(jnp.float32)
    return float(jnp.max(jnp.abs(a - ref)) / jnp.maximum(jnp.max(jnp.abs(ref)), 1e-30))


def _check(cond, what):
    if not cond:
        raise AssertionError(what)


def _qkvw(seed, shape, dtype):
    """Seeded q, k, v in ``dtype`` and an f32 cotangent weight, all ``shape``."""
    import jax
    import jax.numpy as jnp

    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    q, k, v, w = (jax.random.normal(key, shape, jnp.float32) for key in keys)
    return q.astype(dtype), k.astype(dtype), v.astype(dtype), w


def _attention_errors(attn, q, k, v, w):
    """Forward and ``jax.grad`` error of ``attn`` against causal
    ``reference_attention``.  ``attn`` runs as the program runs it (default
    precision); only the reference is pinned to "highest", so it is the
    truth and not a second approximation."""
    import jax
    import jax.numpy as jnp

    from fedml_tpu.ops.flash_attention import reference_attention

    def grads(fn):
        loss = lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) * w)
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)

    out, got = attn(q, k, v), grads(attn)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(lambda q, k, v: reference_attention(q, k, v, causal=True))
        errors = {"fwd": _rel_err(out, ref(q, k, v))}
        exp = grads(ref)
    errors.update({f"d{name}": _rel_err(g, e) for name, g, e in zip("qkv", got, exp)})
    return errors


def _within_tolerance(errors, what):
    """Round ``errors`` for the report; raise, listing all of them, if any
    exceeds TOLERANCE (a NaN does)."""
    errors = {k: float(f"{e:.3e}") for k, e in errors.items()}
    bad = {k: e for k, e in errors.items() if not e <= TOLERANCE}
    _check(not bad, f"{what} vs reference beyond {TOLERANCE}: {bad} (all: {errors})")
    return errors


def kda_errors(L, H, D):
    """{name: error} of KDA through the entry the model calls
    (``ops/kda.kda``), forward and all five gradients, every head in one call
    against the per-token recurrence by groups of heads; plus ``kda_worst``.  On
    the chip the entry has to have lowered to the kernels ``kda_fwd`` /
    ``kda_bwd``: it is they that are held to TOLERANCE here."""
    import jax
    import jax.numpy as jnp

    from fedml_tpu.ops import kda

    keys = jax.random.split(jax.random.PRNGKey(64), 6)
    shape = (1, L, H, D)
    q, k = (jax.random.normal(key, shape, jnp.float32) for key in keys[:2])
    q, k = (x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True)) for x in (q, k))
    v, w = (jax.random.normal(key, shape, jnp.float32) for key in keys[2:4])
    g = -0.1 * jax.nn.softplus(jax.random.normal(keys[4], shape, jnp.float32))
    beta = jax.nn.sigmoid(jax.random.normal(keys[5], shape[:3], jnp.float32))
    args = (q.astype(jnp.bfloat16), k.astype(jnp.bfloat16), v.astype(jnp.bfloat16), g, beta)

    def value_and_grads(fn, w):
        return jax.jit(jax.value_and_grad(
            lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * w), argnums=(0, 1, 2, 3, 4)))

    lowered = value_and_grads(kda.kda, w).lower(*args)
    text = lowered.as_text()
    _check("kda_fwd" in text and "kda_bwd" in text,
           "ops/kda.kda did not dispatch to the pallas kernels kda_fwd / kda_bwd")
    _, got = lowered.compile()(*args)
    out = jax.jit(kda.kda)(*args)
    errors = {}
    for h in range(0, H, ORACLE_HEADS):
        hs = slice(h, h + ORACLE_HEADS)
        part = [x[:, :, hs] for x in args]
        with jax.default_matmul_precision("highest"):
            _, exp = value_and_grads(kda.kda_recurrent, w[:, :, hs])(*part)
            pairs = [("fwd_kda", out, jax.jit(kda.kda_recurrent)(*part))]
        pairs += [(f"d{name}_kda", g_, e) for name, g_, e in zip(("q", "k", "v", "g", "beta"),
                                                               got, exp)]
        for name, a, e in pairs:
            errors[name] = max(errors.get(name, 0.0), _rel_err(a[:, :, hs], e))
    errors["kda_worst"] = max(errors.values())
    return errors


def kimi_linear_ops():
    """{name: error} of the ops the ``kimi_linear`` decoder adds, compiled at the
    cell's shapes, each against its reference at "highest" precision."""
    import jax
    import jax.numpy as jnp

    from fedml_tpu.models import expert_lm
    from fedml_tpu.ops.flash_attention import flash_attention, reference_attention

    L, H, errors = KIMI["L"], KIMI["H"], {}

    def worst(name, value):
        errors[name] = max(errors.get(name, 0.0), value)

    # flash at q/k 192 != v 128 and at glm-4.7-flash-sim's 256 / 256 over 20 heads, every
    # head in one call; the oracle by groups of heads
    for tag, n_heads, qk, dv in (("mla", H, KIMI["qk"], KIMI["v"]), ("mla256", *GLM47_FLASH)):
        keys = jax.random.split(jax.random.PRNGKey(qk), 4)
        q, k = (jax.random.normal(key, (1, L, n_heads, qk), jnp.bfloat16) for key in keys[:2])
        v = jax.random.normal(keys[2], (1, L, n_heads, dv), jnp.bfloat16)
        w = jax.random.normal(keys[3], (1, L, n_heads, dv), jnp.float32)
        flash = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True))
        out = flash(q, k, v)
        got = jax.jit(jax.grad(lambda q, k, v: jnp.sum(flash(q, k, v).astype(jnp.float32) * w),
                               argnums=(0, 1, 2)))(q, k, v)
        for hs in (slice(h, h + ORACLE_HEADS) for h in range(0, n_heads, ORACLE_HEADS)):
            part = [x[:, :, hs] for x in (q, k, v)]
            with jax.default_matmul_precision("highest"):
                ref = lambda q, k, v: reference_attention(q, k, v, causal=True)
                worst(f"fwd_{tag}_flash", _rel_err(out[:, :, hs], jax.jit(ref)(*part)))
                exp = jax.jit(jax.grad(
                    lambda *a: jnp.sum(ref(*a).astype(jnp.float32) * w[:, :, hs]),
                    argnums=(0, 1, 2)))(*part)
            for name, g, e in zip("qkv", got, exp):
                worst(f"d{name}_{tag}_flash", _rel_err(g[:, :, hs], e))

    errors.update(kda_errors(L, H, KIMI["kda"]))

    # the expert layer's grouped products against a dense loop over the held experts
    keys = jax.random.split(jax.random.PRNGKey(256), 6)
    d, f, held = KIMI["d"], KIMI["f"], KIMI["held"]
    h = jax.random.normal(keys[0], (L, d), jnp.bfloat16)
    w_gate, w_up = (jax.random.normal(key, (held, d, f), jnp.bfloat16) * d ** -0.5
                    for key in keys[1:3])
    w_down = jax.random.normal(keys[3], (held, f, d), jnp.bfloat16) * f ** -0.5
    scores = jax.nn.sigmoid(jax.random.normal(keys[4], (L, KIMI["routed"]), jnp.float32))
    chosen, weights = expert_lm.route(scores, jnp.zeros(KIMI["routed"]), KIMI["top"], 2.446, True)
    cot = jax.random.normal(keys[5], (L, d), jnp.float32)

    def grouped(h, w_gate, w_up, w_down):
        return expert_lm.grouped_experts(h, chosen, weights, (0, held), w_gate, w_up, w_down,
                                         KIMI["routed"])[0]

    def dense(h, w_gate, w_up, w_down):
        h, w_gate, w_up, w_down = (x.astype(jnp.float32) for x in (h, w_gate, w_up, w_down))
        out = jnp.zeros_like(h)
        for e in range(held):
            weight = jnp.sum(jnp.where(chosen == e, weights, 0.0), -1)
            out = out + weight[:, None] * expert_lm.swiglu(h, w_gate[e], w_up[e], w_down[e])
        return out

    def value_and_grads(fn):
        return jax.jit(jax.value_and_grad(
            lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * cot), argnums=(0, 1, 2, 3)))(
                h, w_gate, w_up, w_down)

    # the grouped products are traced outside the oracle's precision: it is read at
    # trace time and would change the kernels under test
    _, got = value_and_grads(grouped)
    out = jax.jit(grouped)(h, w_gate, w_up, w_down)
    with jax.default_matmul_precision("highest"):
        _, exp = value_and_grads(dense)
        errors["fwd_grouped_experts"] = _rel_err(out, jax.jit(dense)(h, w_gate, w_up, w_down))
    for name, g_, e in zip(("h", "w_gate", "w_up", "w_down"), got, exp):
        errors[f"d{name}_grouped_experts"] = _rel_err(g_, e)
    counters = expert_lm.grouped_experts(h, chosen, weights, (0, held), w_gate, w_up, w_down,
                                         KIMI["routed"])[1]
    _check(float(counters["moe.assignments_dropped"]) == 0.0, f"assignments dropped: {counters}")
    return errors


def round_leg(args, device, dataset, out_dim, n_dev):
    """The in-mesh round on an ``n_dev``-device client mesh.  On the mesh
    that spans the host this goes through ``FedMLRunner`` exactly as a user
    would; a sub-mesh of a larger host has no config key, so it is handed to
    ``XLASimulator`` (the class the runner builds) directly."""
    import jax
    import numpy as np

    import fedml_tpu
    from fedml_tpu.core import obs

    compile_s0 = obs.compile_seconds_total()
    model = fedml_tpu.models.create(args, out_dim)
    if n_dev == len(jax.devices()):
        entry = "FedMLRunner"
        runner = fedml_tpu.FedMLRunner(args, device, dataset, model)
        sim, run = runner.runner.sim, runner.run
    else:
        from fedml_tpu.parallel.mesh import create_fl_mesh
        from fedml_tpu.simulation.xla.fed_sim import XLASimulator

        entry = f"XLASimulator(mesh={n_dev} of {len(jax.devices())} devices)"
        sim = XLASimulator(args, dataset, model, mesh=create_fl_mesh(n_dev))
        run = sim.train
    _check(sim.mesh.devices.size == n_dev, f"mesh has {sim.mesh.devices.size} devices")
    initial = jax.tree_util.tree_map(np.asarray, sim.variables)

    # the round's per-client outputs are consumed inside train(); look at
    # the last round's through the jitted round function's return value
    seen = {}
    round_fn = sim._round_fn

    def spy(*a):
        out = round_fn(*a)
        seen["outs"] = out[-1]
        return out

    sim._round_fn = spy
    final_eval = run()
    sim._round_fn = round_fn

    _check(len(sim.round_losses) == ROUNDS, f"{len(sim.round_losses)} rounds ran")
    _check(all(np.isfinite(sim.round_losses)), f"loss not finite: {sim.round_losses}")
    _check(np.isfinite(final_eval["test_loss"]) and 0.0 <= final_eval["test_acc"] <= 1.0,
           f"final eval: {final_eval}")
    moved = max(float(np.max(np.abs(np.asarray(new, np.float32) - old.astype(np.float32))))
                for new, old in zip(jax.tree_util.tree_leaves(sim.variables),
                                    jax.tree_util.tree_leaves(initial)))
    _check(np.isfinite(moved) and moved > 0.0, f"global variables did not change ({moved})")
    placement = {
        "dataset": str(sim.x_all.sharding),
        "dataset_devices": len(sim.x_all.sharding.device_set),
        "dataset_bytes_per_device": int(sim.x_all.nbytes + sim.y_all.nbytes),
        "globals_devices": len(
            jax.tree_util.tree_leaves(sim.variables)[0].sharding.device_set),
        "client_outs": str(seen["outs"].sharding),
        "client_outs_devices": len(seen["outs"].sharding.device_set),
        "client_outs_shards": [str(s.data.shape) for s in seen["outs"].addressable_shards],
    }
    for key in ("dataset_devices", "globals_devices", "client_outs_devices"):
        _check(placement[key] == n_dev, f"{key} = {placement[key]}, mesh has {n_dev}")
    _check(not seen["outs"].is_fully_replicated or n_dev == 1,
           "per-client outputs are replicated, not sharded over the client axis")
    memory = _memory_stats()
    mesh_ids = {d.id for d in sim.mesh.devices.flat}
    for m in memory:
        if m["id"] in mesh_ids:
            _check((m["bytes_in_use"] or 0) >= placement["dataset_bytes_per_device"],
                   f"device {m['id']} holds {m['bytes_in_use']} bytes, less than the dataset")
    return {
        "entry": entry,
        "mesh_devices": n_dev,
        "dataset_is_synthetic": bool(args.dataset_is_synthetic),
        "compile_seconds": round(obs.compile_seconds_total() - compile_s0, 2),
        "round_seconds": [round(t, 3) for t in sim.round_times],
        "round_loss": [round(x, 6) for x in sim.round_losses],
        "final_eval": final_eval,
        "max_abs_param_change": moved,
        "placement": placement,
        "memory": memory,
    }


def kernel_leg():
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from fedml_tpu.core import obs
    from fedml_tpu.models.transformer import TransformerConfig, TransformerLM
    from fedml_tpu.ops.flash_attention import (flash_attention,
                                               flash_shard_update,
                                               shard_update_reference)

    compile_s0 = obs.compile_seconds_total()
    errors = {}
    flash = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True))
    upd = jax.jit(lambda *a: flash_shard_update(*a, causal=True))
    upd_ref = jax.jit(lambda q, k, v, qp, kp, m, l, o: shard_update_reference(
        q, k, v, qp, kp, True, m, l, o))
    for B, L, H, D, dtype in KERNEL_SHAPES:
        tag = f"B{B}_L{L}_H{H}_D{D}_{dtype}"
        q, k, v, w = _qkvw(L + D, (B, L, H, D), dtype)
        for name, err in _attention_errors(flash, q, k, v, w).items():
            errors[f"{name}_{tag}"] = err
        # the ring's per-chip shard update, mid-stream: fold two K/V shards
        # (global positions 0..L and L..2L) into queries at L..2L
        q_pos = L + jnp.arange(L)
        shards = ((k, jnp.arange(L)), (_qkvw(L, (B, L, H, D), dtype)[1], q_pos))
        state = state_ref = (jnp.full((B, H, L), -jnp.inf, jnp.float32),
                             jnp.zeros((B, H, L), jnp.float32),
                             jnp.zeros((B, L, H, D), jnp.float32))
        for k_shard, k_pos in shards:
            state = upd(q, k_shard, v, q_pos, k_pos, *state)
            with jax.default_matmul_precision("highest"):
                state_ref = upd_ref(q, k_shard, v, q_pos, k_pos, *state_ref)
        for name, g, e in zip("mlo", state, state_ref):
            errors[f"shard_update_{name}_{tag}"] = _rel_err(g, e)
    errors.update(kimi_linear_ops())
    errors = _within_tolerance(errors, "kernel")

    # TransformerLM through its default attention (the flash kernel on tpu)
    cfg = TransformerConfig(dtype=jnp.bfloat16, **TRANSFORMER)
    model = TransformerLM(cfg)
    key = jax.random.PRNGKey(0)
    tokens = jax.random.randint(
        key, (TRANSFORMER_BATCH, cfg.max_seq_len), 0, cfg.vocab_size, jnp.int32)
    params = model.init(key, tokens[:, :8])
    tx = optax.sgd(1e-3)
    opt_state = tx.init(params)

    def step(params, opt_state, tok):
        def loss_fn(p):
            logits = model.apply(p, tok[:, :-1])
            return jnp.mean(optax.softmax_cross_entropy_with_integer_labels(
                logits.astype(jnp.float32), tok[:, 1:]))

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    lowered = jax.jit(step).lower(params, opt_state, tokens)
    _check("tpu_custom_call" in lowered.as_text(),
           "the default attention did not dispatch to the pallas kernel")
    compiled = lowered.compile()
    first = jax.tree_util.tree_leaves(params)[0]
    losses, seconds = [], []
    for _ in range(TRANSFORMER_STEPS):
        t0 = time.time()
        params, opt_state, loss = compiled(params, opt_state, tokens)
        losses.append(float(jax.block_until_ready(loss)))
        seconds.append(round(time.time() - t0, 3))
    _check(all(np.isfinite(losses)), f"transformer loss not finite: {losses}")
    _check(bool(jnp.any(jax.tree_util.tree_leaves(params)[0] != first)),
           "transformer params did not change")
    return {
        "tolerance": TOLERANCE,
        "kernel_rel_err": errors,
        "transformer": {"config": TRANSFORMER, "batch": TRANSFORMER_BATCH,
                        "attention": "default (pallas flash kernel, compiled)",
                        "step_loss": losses, "step_seconds": seconds},
        "compile_seconds": round(obs.compile_seconds_total() - compile_s0, 2),
        "memory": _memory_stats(),
    }


def ring_leg():
    import jax

    from fedml_tpu.core import obs
    from fedml_tpu.parallel.mesh import create_mesh
    from fedml_tpu.parallel.ring_attention import (pallas_block_attend,
                                                   ring_attention)

    compile_s0 = obs.compile_seconds_total()
    B, L, H, D, dtype = 1, 4096, 16, 64, "bfloat16"  # 1024 positions per chip
    mesh = create_mesh((4,), ("sp",))
    q, k, v, w = _qkvw(4, (B, L, H, D), dtype)
    ring = jax.jit(lambda q, k, v: ring_attention(
        q, k, v, mesh, block_fn=pallas_block_attend))
    out_sharding = ring(q, k, v).sharding
    _check(len(out_sharding.device_set) == 4, f"ring output on {out_sharding}")
    errors = _within_tolerance(_attention_errors(ring, q, k, v, w), "ring")
    return {
        "shape": [B, L, H, D, dtype], "sp": 4, "tolerance": TOLERANCE,
        "rel_err": errors,
        "output_sharding": str(out_sharding),
        "compile_seconds": round(obs.compile_seconds_total() - compile_s0, 2),
    }


def main() -> int:
    import jax

    import fedml_tpu
    from fedml_tpu.arguments import Arguments
    from fedml_tpu.core import mlops
    from fedml_tpu.utils.platform import configure_compilation_cache

    args = fedml_tpu.init(Arguments.from_dict(ROUND_CONFIG))
    device = fedml_tpu.device.get_device(args)  # raises unless the backend is tpu
    import jaxlib

    try:
        import libtpu
        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = None
    n_devices = len(jax.devices())
    stamp = {"platform": device.platform, "kind": device.device_kind,
             "count": n_devices}
    report = {
        "what": "smoke observations, not benchmark numbers",
        "device": stamp,
        "versions": {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                     "libtpu": libtpu_version},
        "compilation_cache_dir": configure_compilation_cache(),  # idempotent
        "legs": {},
    }
    failed = []

    def run_leg(name, fn, *a):
        t0 = time.time()
        try:
            report["legs"][name] = fn(*a)
            report["legs"][name]["leg_seconds"] = round(time.time() - t0, 1)
        except Exception:
            failed.append(name)
            report["legs"][name] = {"failed": traceback.format_exc()}
            traceback.print_exc()

    try:
        dataset, out_dim = fedml_tpu.data.load(args)
        run_leg("A_round_1dev", round_leg, args, device, dataset, out_dim, 1)
        run_leg("B_kernels", kernel_leg)
        if n_devices >= 4:
            run_leg("C_round_4dev", round_leg, args, device, dataset, out_dim, 4)
            run_leg("C_ring_sp4", ring_leg)
            one, four = (report["legs"][n].get("round_loss")
                         for n in ("A_round_1dev", "C_round_4dev"))
            if one and four:
                worst = max(abs(a - b) / abs(a) for a, b in zip(one, four))
                report["legs"]["C_round_4dev"]["loss_rel_diff_vs_1dev"] = worst
                if not worst <= LOSS_RTOL:
                    failed.append(f"C_round_4dev: loss differs from 1-dev by {worst:.3e}")
    finally:
        mlops.finish()
    report["legs_run"] = sorted(report["legs"])
    report["failed"] = failed
    print(json.dumps({"smoke_report": report}))
    if failed:
        print(f"chip_smoke: FAILED legs: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": stamp}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
