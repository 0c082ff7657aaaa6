"""Plain reference of SDAR-30B-A3B's block-diffusion training (JetLM/SDAR-30B-A3B-Chat,
``model_type: sdar_moe``; BD3-LM, arXiv:2503.09573, sections 3-4): the forward pass over
a clean and a noised copy of each row, the weighted masked loss, gradients, local SGD and
the FedAvg round, in straightforward ``jax.numpy`` and float32 at ``Precision.HIGHEST``.
No kernel, no cache, no packing, no mesh, no grouped product.

It imports nothing from ``fedml_tpu``.  From ``benchmark/reference.py`` it takes the parts
that know no model: the products' arithmetic (``_einsum``, so the float8 / int8 controls
and the bfloat16 reading exist here too), the rotation, the feed order and the cohort, the
weighted sums of the FedAvg round, and the readings.  The noise of every step is drawn
again here by the rule the program documents (``noise_keys`` below).

A row ``x`` of L tokens, blocks of ``block_length`` (B):

1. Noise: ``t_b = lo + (hi - lo) u_b``; token i is masked where its uniform draw is below
   ``t_{i // B}``; ``x_noisy = where(masked, V - 1, x)`` (the embedding's last row, which
   the traffic never draws).
2. The layers run over ``[x_noisy ; x]`` (2L positions, rotary positions 0..L-1 in both
   halves).  Layer (pre-norm residual; every norm an RMSNorm with ``rms_norm_eps``):
   ``a = RMSNorm_in(x)``; ``q = RMSNorm_q(a W_q)``, ``k = RMSNorm_k(a W_k)`` per head of
   ``head_dim``, ``v = a W_v``; q and k rotated (rotate-half at ``rope_theta``); query
   head h reads kv head ``h // (heads / kv heads)`` (the repeat written out); softmax of
   ``q.k / sqrt(head_dim)`` under the mask written out over the 2L keys, a block of 256
   query rows at a time: a clean query sees the clean keys of blocks <= its own; a noised
   query the clean keys of blocks < its own and the noised keys of its own block;
   ``x <- x + W_o concat(o)``.  ``m = RMSNorm_post(x)``; the router's logits ``m W_r`` over
   all ``n_router_outputs`` (float32, HIGHEST whatever the precision), the top
   ``num_experts_per_tok`` and the softmax over those; ``x <- x + sum over the chosen AND
   held experts of w_e (silu(m W_gate,e) * (m W_up,e)) W_down,e``, a loop over the held
   experts with a mask.  What the absent experts would add is left out.
3. Final RMSNorm and the head over the noised half; the loss of a row is
   ``(1 / L) sum over masked i of -log p(x_i) / t_{i // B}``, the step's the mean over
   the batch's live rows.

Departures from the published description: none in the equations as far as the config
and BD3-LM give them; the configuration file's ``assumed`` lists what neither gives.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import reference
from benchmark.reference import _einsum, rms_norm, rotate_half

ATTENTION_ROWS = 256  # query rows a block of the softmax (32 heads x 256 x 16,384 float32 scores: 512 MiB)
NOISE_STREAM = 0xBD  # the program's rule (ml/engine/train.py): the step's key folded with this


# -- weights -----------------------------------------------------------------

def weight_shapes(model: dict) -> dict:
    d, v = model["hidden_size"], model["vocab_size"]
    hq, hkv, dk = model["num_attention_heads"], model["num_key_value_heads"], model["head_dim"]
    lo, hi = model["experts_held"]
    f = model["moe_intermediate_size"]

    def layer():
        return {"attn_norm": (d,), "ffn_norm": (d,), "router": (d, model["n_router_outputs"]),
                "attn": {"wq": (d, hq, dk), "wk": (d, hkv, dk), "wv": (d, hkv, dk),
                         "wo": (hq, dk, d), "q_norm": (dk,), "k_norm": (dk,)},
                "moe": {"e_gate": (hi - lo, d, f), "e_up": (hi - lo, d, f),
                        "e_down": (hi - lo, f, d)}}

    return {"embed": (v, d), "final_norm": (d,), "head": (d, v),
            "layers": [layer() for _ in range(model["num_hidden_layers"])]}


def _fan_in(name: str, shape: tuple) -> int:
    if name == "wo":
        return shape[0] * shape[1]
    if name in ("embed", "e_gate", "e_up", "e_down"):
        return shape[1]
    return shape[0]


@functools.partial(jax.jit, static_argnames=("shapes_key",))
def _make(key, *, shapes_key):
    out = []
    for i, (name, shape) in enumerate(shapes_key):
        if name.endswith("norm"):
            out.append(jnp.ones(shape, jnp.float32))
        else:
            out.append(jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
                       / np.sqrt(_fan_in(name, shape)))
    return out


def make_weights(model: dict, seed: int) -> dict:
    """Float32 weights on the device, one jitted call from the seed: normal with
    variance 1/fan_in, norm scales 1."""
    from benchmark.traffic import _key

    flat, treedef = jax.tree_util.tree_flatten_with_path(
        weight_shapes(model), is_leaf=lambda x: isinstance(x, tuple))
    names = tuple((path[-1].key, shape) for path, shape in flat)
    return jax.tree_util.tree_unflatten(treedef, _make(_key(seed, 0), shapes_key=names))


# -- the noise -----------------------------------------------------------------

def noise_keys(seed: int, unit: int, n_steps: int) -> list:
    """The noise key of each stream step of the ``unit``-th round (each a one-round
    run) on a one-device mesh, by the program's documented rules: the simulator's key
    ``PRNGKey(random_seed + 11)`` is split once a round (the round's sub-key is the
    second half), a device's key is ``split(fold_in(sub, round 0), devices)[0]``, a
    step's ``fold_in(device key, stream step)`` (ml/engine/packed.py) and its noise
    key that folded with ``NOISE_STREAM`` (ml/engine/train.py)."""
    key = jax.random.PRNGKey(int(seed) + 11)
    for _ in range(unit + 1):
        key, sub = jax.random.split(key)
    device = jax.random.split(jax.random.fold_in(sub, 0), 1)[0]
    return [jax.random.fold_in(jax.random.fold_in(device, step), NOISE_STREAM)
            for step in range(n_steps)]


def stream_order(steps) -> list:
    """Clients of a one-device round in the order its stream trains them: the
    scheduler's longest-processing-time rule, heaviest first by step count
    (``np.argsort`` of the negated counts, as core/schedule does it)."""
    return [int(c) for c in np.argsort(-np.asarray(steps, np.float64))]


def draw_noise(key, rows: int, length: int, block: int, t_range):
    """(t [rows, L / block], masked [rows, L]) of one step's key."""
    k_t, k_m = jax.random.split(key)
    lo, hi = t_range
    t = lo + (hi - lo) * jax.random.uniform(k_t, (rows, length // block), jnp.float32)
    u = jax.random.uniform(k_m, (rows, length), jnp.float32)
    return t, u < jnp.repeat(t, block, axis=1)


# -- the model ---------------------------------------------------------------

def _rotate_halves(x, theta):
    """Rotary positions 0..L-1 in each half of [B, 2L, H, D]."""
    L = x.shape[1] // 2
    return jnp.concatenate([rotate_half(x[:, :L], theta), rotate_half(x[:, L:], theta)], axis=1)


def bd_attention(q, k, v, block, precision, fault=None, rows: int = ATTENTION_ROWS):
    """q: [B, 2L, Hq, D]; k, v: [B, 2L, Hkv, D], the noised half first.  Softmax
    attention with k and v repeated to the query heads' count under the block-diffusion
    mask, written out for a block of query rows at a time (``lax.map``, each recomputed
    on the way back).  ``fault="noised_context"``: a noised query sees the NOISED keys of
    the earlier blocks in place of the clean ones."""
    B, L2, Hq, D = q.shape
    L = L2 // 2
    group = Hq // k.shape[2]
    k, v = (jnp.repeat(x, group, axis=2) for x in (k, v))
    rows = min(rows, L2)
    key_pos = jnp.arange(L2)
    k_noised, k_blk = key_pos < L, (key_pos % L) // block

    @jax.checkpoint
    def one(x):
        q_rows, pos = x
        q_noised, q_blk = (pos < L)[:, None], ((pos % L) // block)[:, None]
        if fault == "noised_context":
            noised_row = k_noised & (k_blk <= q_blk)
        else:
            noised_row = jnp.where(k_noised, k_blk == q_blk, k_blk < q_blk)
        seen = jnp.where(q_noised, noised_row, ~k_noised & (k_blk <= q_blk))
        scores = _einsum("blhk,bmhk->bhlm", q_rows, k, precision) / np.sqrt(D)
        scores = jnp.where(seen, scores, -jnp.inf)
        return _einsum("bhlm,bmhk->blhk", jax.nn.softmax(scores, axis=-1), v, precision)

    q_blocks = q.reshape(B, L2 // rows, rows, Hq, D)
    out = jax.lax.map(one, (jnp.moveaxis(q_blocks, 1, 0), jnp.arange(L2).reshape(-1, rows)))
    return jnp.moveaxis(out, 0, 1).reshape(B, L2, Hq, D)


def gqa_mixer(a, w, model, precision, fault=None):
    eps, theta = model["rms_norm_eps"], model["rope_theta"]
    q = rms_norm(_einsum("bld,dhk->blhk", a, w["wq"], precision), w["q_norm"], eps)
    k = rms_norm(_einsum("bld,dhk->blhk", a, w["wk"], precision), w["k_norm"], eps)
    v = _einsum("bld,dhk->blhk", a, w["wv"], precision)
    q, k = _rotate_halves(q, theta), _rotate_halves(k, theta)
    o = bd_attention(q, k, v, model["block_length"], precision, fault)
    return _einsum("blhk,hkd->bld", o, w["wo"], precision)


def router(m, w_r, model):
    """(chosen, weights): the largest logits of a token and the softmax over them."""
    logits = jnp.einsum("bld,de->ble", m, w_r, precision=jax.lax.Precision.HIGHEST)
    picked, chosen = jax.lax.top_k(logits, model["num_experts_per_tok"])
    return chosen, jax.nn.softmax(picked, axis=-1)


def swiglu(h, w_gate, w_up, w_down, precision):
    gate = _einsum("bld,df->blf", h, w_gate, precision)
    up = _einsum("bld,df->blf", h, w_up, precision)
    return _einsum("blf,fd->bld", jax.nn.silu(gate) * up, w_down, precision)


def expert_layer(m, w, model, precision, held=None):
    """Router and the held experts' part; ``held``: the range of experts whose part is
    added (default: the configuration's ``experts_held``)."""
    chosen, weights = router(m, w["router"], model)
    lo, hi = model["experts_held"] if held is None else held
    first = model["experts_held"][0]  # w["moe"]["e_*"][i] is expert first + i

    @jax.checkpoint
    def add_expert(out, x):  # every token through expert e, weighted 0 where e was not chosen
        e, w_gate, w_up, w_down = x
        weight = jnp.sum(jnp.where(chosen == e, weights, 0.0), -1)
        return out + weight[..., None] * swiglu(m, w_gate, w_up, w_down, precision), None

    out, _ = jax.lax.scan(add_expert, jnp.zeros_like(m), (jnp.arange(lo, hi), *(
        w["moe"][name][lo - first:hi - first] for name in ("e_gate", "e_up", "e_down"))))
    return out


def block(x, w, model, precision, fault=None, held=None):
    eps = model["rms_norm_eps"]
    x = x + gqa_mixer(rms_norm(x, w["attn_norm"], eps), w["attn"], model, precision, fault)
    return x + expert_layer(rms_norm(x, w["ffn_norm"], eps), w, model, precision, held)


def loss_fn(weights, tokens, row_mask, key, model, precision, fault=None):
    """The step's block-diffusion loss (module docstring); ``fault="no_weights"``
    drops the 1 / t_b weights, ``"noised_context"`` is ``bd_attention``'s."""
    rows, L = tokens.shape
    t, masked = draw_noise(key, rows, L, model["block_length"], model["noise_t_range"])
    noisy = jnp.where(masked, model["vocab_size"] - 1, tokens)
    x = weights["embed"][jnp.concatenate([noisy, tokens], axis=1)]
    for w in weights["layers"]:
        x = jax.checkpoint(functools.partial(block, model=model, precision=precision,
                                             fault=fault))(x, w)
    x = rms_norm(x[:, :L], weights["final_norm"], model["rms_norm_eps"])
    logits = _einsum("bld,dv->blv", x, weights["head"], precision)
    logp = jax.nn.log_softmax(logits, axis=-1)
    per = -jnp.take_along_axis(logp, tokens[..., None], axis=-1)[..., 0]
    weight = masked * row_mask[:, None]
    if fault != "no_weights":
        weight = weight / jnp.repeat(t, model["block_length"], axis=1)
    return jnp.sum(per * weight) / L / jnp.maximum(jnp.sum(row_mask), 1.0)


def masked_count(tokens, row_mask, key, model) -> float:
    """Noised positions masked in one step (the program's ``bd.masked``)."""
    rows, L = tokens.shape
    _, masked = draw_noise(key, rows, L, model["block_length"], model["noise_t_range"])
    return float(jnp.sum(masked * row_mask[:, None]))


# -- SGD and the round (as benchmark/reference.py does them) -------------------

MODEL_KEYS = ("hidden_size", "num_hidden_layers", "vocab_size", "rms_norm_eps",
              "num_attention_heads", "num_key_value_heads", "head_dim", "rope_theta",
              "moe_intermediate_size", "num_experts_per_tok", "n_router_outputs",
              "experts_held", "block_length", "noise_t_range")


def model_key(model: dict) -> str:
    return json.dumps({k: model[k] for k in MODEL_KEYS}, sort_keys=True)


@functools.partial(jax.jit, static_argnames=("model_key", "precision", "fault"),
                   donate_argnums=(0,))
def _sgd_step(weights, tokens, row_mask, key, lr, *, model_key, precision, fault):
    model = json.loads(model_key)
    if fault == "half_batch":  # half of the batch left out, the mean over the rest
        row_mask = row_mask * (jnp.arange(row_mask.shape[0]) < row_mask.shape[0] // 2)
    loss, grads = jax.value_and_grad(loss_fn)(weights, tokens, row_mask, key, model, precision,
                                              fault)
    if fault == "state_unchanged":
        return weights, loss
    return jax.tree_util.tree_map(lambda p, g: p - lr * g, weights, grads), loss


def local_sgd(weights, x, order, keys, batch, lr, model, precision="highest", fault=None):
    """Plain SGD over the rows of ``x`` in ``order`` (-1 is padding, left out of the
    mean), ``batch`` rows a step, step i under noise key ``keys[i]``.  Returns the new
    weights and the mean loss over the rows fed.  ``weights`` is consumed."""
    order = np.asarray(order).reshape(-1, batch)
    loss_sum = rows = 0.0
    for idx, key in zip(order, keys, strict=True):
        valid = (idx >= 0).astype(np.float32)
        weights, loss = _sgd_step(
            weights, jnp.asarray(x[np.maximum(idx, 0)]), jnp.asarray(valid), key,
            jnp.float32(lr), model_key=model_key(model), precision=precision, fault=fault)
        loss_sum += float(loss) * float(valid.sum())
        rows += float(valid.sum())
    return weights, loss_sum / max(rows, 1.0)


def fedavg_round(global_w, shards, seed, round_idx, batch, lr, model, precision="highest",
                 fault=None, clients=None, unit=0):
    """One FedAvg round, as ``reference.fedavg_round``, the ``unit``-th one-round run of
    a one-device simulator: the clients in the stream's order, each from ``global_w``,
    its steps under the noise keys of their stream positions; the new global is the mean
    weighted by rows.  Returns (new global, mean loss weighted by rows, masked positions)."""
    clients = list(range(len(shards))) if clients is None else list(clients)
    steps = [-(-len(shards[c][0]) // batch) for c in clients]
    keys = noise_keys(seed, unit, sum(steps))
    acc, wsum, loss_sum, masked, cursor = None, 0.0, 0.0, 0.0, 0
    for i in stream_order(steps):
        c = clients[i]
        x, _ = shards[c]
        order = reference.feed_order_packed_round(seed, round_idx, c, len(x), batch)
        own = keys[cursor:cursor + steps[i]]
        for idx, key in zip(np.asarray(order).reshape(-1, batch), own):
            masked += masked_count(jnp.asarray(x[np.maximum(idx, 0)]),
                                   jnp.asarray((idx >= 0).astype(np.float32)), key, model)
        local, loss = local_sgd(reference.copy_tree(global_w), x, order, own, batch, lr, model,
                                precision, fault)
        cursor += steps[i]
        w = float(len(x))
        acc = reference._scale(local, w) if acc is None else reference._add_scaled(acc, local, w)
        wsum += w
        loss_sum += loss * w
        del local
    return reference._scale(acc, 1.0 / wsum), loss_sum / wsum, masked
