"""Plain reference: the decoder-only LM of the configuration files, its loss
and gradients, local SGD, and the FedAvg round, in straightforward
``jax.numpy`` and float32.  No kernel, no cache, no packing, no mesh.

It imports nothing from ``fedml_tpu`` and takes nothing the program made:
weights and token shards come from ``benchmark/traffic.py`` (the seed), and
the round's cohort and the order in which a client's rows are fed are
re-derived here from the rules the program documents (``sampled_clients``,
``feed_order_packed_round`` below).

Architecture as published for deepseek-ai/deepseek-llm-7b-base (pre-norm
RMSNorm eps 1e-6, multi-head attention without biases, rotary positions
base 10000 applied as rotate-half, SwiGLU MLP, untied embedding and head).
Departures: none in the block; depth and vocabulary are the configuration
file's (``reduced``).

``precision`` picks the arithmetic of every matrix product:

* ``"highest"`` — float32 operands, ``jax.lax.Precision.HIGHEST`` (six bf16
  passes on the MXU).  This is the reference.
* ``"bfloat16"`` — operands rounded to bfloat16, float32 accumulation: the
  precision the configuration states.  Used to read planted faults at the
  program's own precision.
* ``"int8"`` — operands and, on the way back, their gradients rounded to 8-bit
  integers with one scale a tensor: the control, one step below what the
  configuration states.
* ``"float8"`` — the same with e4m3 operands and e5m2 gradients: the other
  8-bit step, read beside the control.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

PRECISIONS = ("highest", "bfloat16", "int8", "float8")


def _scaled_round(x, dtype, top):
    """Round to an 8-bit float after scaling the tensor's largest entry to the
    format's largest, as an fp8 recipe does: rounding, not overflow."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / s).astype(dtype).astype(jnp.float32) * s


@jax.custom_vjp
def _fp8(x):
    """Operands in e4m3 on the way forward, their gradients in e5m2 on the
    way back (the usual fp8 training recipe)."""
    return _scaled_round(x, jnp.float8_e4m3fn, 448.0)


_fp8.defvjp(lambda x: (_fp8(x), None),
            lambda _, g: (_scaled_round(g, jnp.float8_e5m2, 57344.0),))


def _int8_round(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
    return jnp.clip(jnp.round(x / s), -127.0, 127.0) * s


@jax.custom_vjp
def _int8(x):
    """Symmetric 8-bit integers, one scale a tensor, forward and backward."""
    return _int8_round(x)


_int8.defvjp(lambda x: (_int8(x), None), lambda _, g: (_int8_round(g),))


def _einsum(spec, a, b, precision):
    """One matrix product in the named arithmetic, accumulated in float32."""
    if precision == "highest":
        return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32)
    if precision == "float8":
        a, b = _fp8(a), _fp8(b)
    elif precision == "int8":
        a, b = _int8(a), _int8(b)
    elif precision != "bfloat16":
        raise ValueError(f"precision must be one of {PRECISIONS}, got {precision!r}")
    # operands that bfloat16 holds exactly: one MXU pass multiplies them exactly
    return jnp.einsum(spec, a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)


def rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def rotate_half(x, theta):
    """x: [B, L, H, D].  Rotary positions, the two halves of a head paired."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(angles)[None, :, None, :], jnp.sin(angles)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def block(x, w, model, precision):
    """One decoder layer.  ``w``: wq, wk, wv [d, H, D], wo [H, D, d],
    w_gate, w_up [d, f], w_down [f, d], attn_norm, mlp_norm [d]."""
    eps, theta = model["rms_norm_eps"], model["rope_theta"]
    h = rms_norm(x, w["attn_norm"], eps)
    q = rotate_half(_einsum("bld,dhk->blhk", h, w["wq"], precision), theta)
    k = rotate_half(_einsum("bld,dhk->blhk", h, w["wk"], precision), theta)
    v = _einsum("bld,dhk->blhk", h, w["wv"], precision)
    scores = _einsum("blhk,bmhk->bhlm", q, k, precision) / np.sqrt(q.shape[-1])
    L = x.shape[1]
    scores = jnp.where(jnp.tril(jnp.ones((L, L), bool))[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    attn = _einsum("bhlm,bmhk->blhk", probs, v, precision)
    x = x + _einsum("blhk,hkd->bld", attn, w["wo"], precision)
    h = rms_norm(x, w["mlp_norm"], eps)
    gate = _einsum("bld,df->blf", h, w["w_gate"], precision)
    up = _einsum("bld,df->blf", h, w["w_up"], precision)
    return x + _einsum("blf,fd->bld", jax.nn.silu(gate) * up, w["w_down"], precision)


def loss_fn(weights, tokens, targets, row_mask, model, precision):
    """Mean next-token cross-entropy over the tokens of the rows in ``row_mask``."""
    x = weights["embed"][tokens]
    layer = jax.checkpoint(functools.partial(block, model=model, precision=precision))
    for w in weights["layers"]:
        x = layer(x, w)
    x = rms_norm(x, weights["final_norm"], model["rms_norm_eps"])
    logits = _einsum("bld,dv->blv", x, weights["head"], precision)
    logp = jax.nn.log_softmax(logits, axis=-1)
    per = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    mask = row_mask[:, None] * jnp.ones_like(per)
    return jnp.sum(per * mask) / jnp.maximum(jnp.sum(mask), 1.0)


@functools.partial(jax.jit, static_argnames=("model_key", "precision", "fault"),
                   donate_argnums=(0,))
def _sgd_step(weights, tokens, targets, row_mask, lr, *, model_key, precision, fault):
    model = dict(model_key)
    if fault == "half_batch":  # half of the batch left out, the mean over the rest
        row_mask = row_mask * (jnp.arange(row_mask.shape[0]) < row_mask.shape[0] // 2)
    loss, grads = jax.value_and_grad(loss_fn)(weights, tokens, targets, row_mask,
                                              model, precision)
    if fault == "state_unchanged":
        return weights, loss
    return jax.tree_util.tree_map(lambda p, g: p - lr * g, weights, grads), loss


def model_key(model: dict) -> tuple:
    """The shape-deciding numbers of a configuration file, hashable for jit."""
    keys = ("hidden_size", "intermediate_size", "num_attention_heads",
            "num_key_value_heads", "num_hidden_layers", "vocab_size",
            "rms_norm_eps", "rope_theta")
    if model["num_key_value_heads"] != model["num_attention_heads"]:
        raise NotImplementedError("the reference writes multi-head attention only")
    return tuple((k, model[k]) for k in keys)


def local_sgd(weights, x, y, order, batch, lr, model, precision="highest", fault=None):
    """Plain SGD over the rows of ``x``/``y`` in ``order`` (row indices; -1 is
    padding, left out of the mean), ``batch`` rows a step.  Returns the new
    weights and the mean loss over the rows fed, weighted as the engines do
    (a step's loss times its valid rows).  ``weights`` is consumed."""
    order = np.asarray(order).reshape(-1, batch)
    loss_sum = rows = 0.0
    for idx in order:
        valid = (idx >= 0).astype(np.float32)
        take = np.maximum(idx, 0)
        weights, loss = _sgd_step(
            weights, jnp.asarray(x[take]), jnp.asarray(y[take]), jnp.asarray(valid),
            jnp.float32(lr), model_key=model_key(model), precision=precision, fault=fault)
        loss_sum += float(loss) * float(valid.sum())
        rows += float(valid.sum())
    return weights, loss_sum / max(rows, 1.0)


def copy_tree(tree):
    return jax.tree_util.tree_map(jnp.copy, tree)


def feed_order_packed_round(seed, round_idx, client, n_rows, batch, epochs=1):
    """Rows of one client in the order the packed in-mesh round trains them
    (``ml/engine/packed.py`` docstring: "Shuffling is host-side (numpy, seeded
    per (round, client, epoch))"; a short last batch is padded and masked)."""
    out = []
    for e in range(epochs):
        perm = np.random.default_rng((seed, round_idx, client, e)).permutation(n_rows)
        steps = -(-n_rows // batch)
        padded = np.full(steps * batch, -1, np.int64)
        padded[:n_rows] = perm
        out.append(padded)
    return np.concatenate(out)


def sampled_clients(round_idx, n_clients, per_round):
    """The round's cohort by FedML's documented rule (upstream
    ``FedAvgAPI._client_sampling``; here ``core/sampling.py``): everyone
    under full participation, else ``per_round`` of ``n_clients`` drawn
    without replacement from a Mersenne twister seeded with the round index."""
    if per_round >= n_clients:
        return np.arange(n_clients)
    return np.random.RandomState(round_idx).choice(n_clients, per_round, replace=False)


def fedavg_round(global_w, shards, seed, round_idx, batch, lr, model,
                 precision="highest", fault=None, clients=None):
    """One FedAvg round: every client in ``clients`` (default: all) trains
    from ``global_w``; the new global is the mean weighted by rows.  Returns
    (new global, mean loss weighted by rows).  ``fault="no_exchange"`` keeps
    the first quarter of the clients only, as a device that never heard from
    the other three would."""
    clients = list(range(len(shards))) if clients is None else list(clients)
    if fault == "no_exchange":
        clients, fault = clients[: max(1, len(clients) // 4)], None
    acc, wsum, loss_sum = None, 0.0, 0.0
    for c in clients:
        x, y = shards[c]
        order = feed_order_packed_round(seed, round_idx, c, len(x), batch)
        local, loss = local_sgd(copy_tree(global_w), x, y, order, batch, lr, model,
                                precision, fault)
        w = float(len(x))
        acc = _scale(local, w) if acc is None else _add_scaled(acc, local, w)
        wsum += w
        loss_sum += loss * w
        del local
    return _scale(acc, 1.0 / wsum), loss_sum / wsum


@functools.partial(jax.jit, donate_argnums=(0,))
def _scale(tree, w):
    return jax.tree_util.tree_map(lambda p: w * p, tree)


@functools.partial(jax.jit, donate_argnums=(0,))
def _add_scaled(acc, tree, w):
    return jax.tree_util.tree_map(lambda a, p: a + w * p, acc, tree)


SKETCH_KEY, SKETCH_ROWS = 20240924, 16  # fixed patterns of signs, the same on both sides


@jax.jit
def _leaf_readings(new, old):
    flat_new, treedef = jax.tree_util.tree_flatten(new)
    flat_old = jax.tree_util.tree_leaves(old)
    norms, sketches = [], []
    for i, (p, q) in enumerate(zip(flat_new, flat_old)):
        d = p.astype(jnp.float32) - q.astype(jnp.float32)
        d = d.reshape(d.shape[0], -1)
        ka, kb = jax.random.split(jax.random.fold_in(jax.random.PRNGKey(SKETCH_KEY), i))
        a = jax.random.rademacher(ka, (SKETCH_ROWS, d.shape[0]), jnp.float32)
        b = jax.random.rademacher(kb, (SKETCH_ROWS, d.shape[1]), jnp.float32)
        norms.append(jnp.sqrt(jnp.sum(jnp.square(d))))
        sketches.append(jnp.sum(
            jnp.matmul(a, d, precision=jax.lax.Precision.HIGHEST) * b, axis=-1))
    return (jax.tree_util.tree_unflatten(treedef, norms),
            jax.tree_util.tree_unflatten(treedef, sketches))


def leaf_readings(new, old) -> tuple[dict, dict]:
    """Leaf by leaf, keyed by the leaf's path: the L2 norm of (new - old), and
    its sketch: SKETCH_ROWS sums of (new - old), as a matrix D, under fixed
    patterns of random signs, sum_ij a_i b_j D_ij.  A sketch is linear, so the
    difference of two sides' sketches is the sketch of their difference, and
    each entry's square is, in expectation, that difference's squared norm —
    read without holding both sides' tensors at once."""
    norms, sketches = _leaf_readings(new, old)

    def keyed(tree, to):
        return {jax.tree_util.keystr(path): to(v)
                for path, v in jax.tree_util.tree_flatten_with_path(tree)[0]}

    return keyed(norms, float), keyed(sketches, lambda v: [float(x) for x in v])


def new_readings() -> dict:
    """What benchmark/compare.py takes from either side, one entry a unit."""
    return {"loss": [], "change": [], "sketch": []}


def record(readings: dict, loss: float, new, start) -> None:
    """Append one unit: its mean loss and the leaf readings of (new - start)."""
    norms, sketch = leaf_readings(new, start)
    readings["loss"].append(float(loss))
    readings["change"].append(norms)
    readings["sketch"].append(sketch)
