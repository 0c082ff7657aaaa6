"""Plain reference of the SmallThinker decoder (PowerInfer/SmallThinker-21BA3B-
Instruct): its forward pass, loss, gradients, local SGD and the FedAvg round, in
straightforward ``jax.numpy`` and float32 at ``Precision.HIGHEST``.  No kernel,
no cache, no packing, no mesh, no grouped product.

It imports nothing from ``fedml_tpu``.  From ``benchmark/reference.py`` it takes
the parts that know no model: the products' arithmetic (``_einsum``, so the
float8 / int8 controls and the bfloat16 reading exist here too), the rotation,
the feed order and the cohort, the weighted sums of the FedAvg round, and the
readings.

Layer ``i``, with ``x`` its input ``[T, hidden]`` (pre-norm residual; every norm an
RMSNorm with ``rms_norm_eps`` and a scale; no bias anywhere):

1. ``a = RMSNorm_in(x)``.
2. Router, before the attention, on ``a``: ``logits = a W_r`` over all
   ``n_routed_experts`` (float32, ``Precision.HIGHEST`` whatever ``precision`` the
   other products run in); the ``moe_num_active_primary_experts`` largest logits
   of a token are chosen; ``w = softmax`` over those chosen logits.
3. Attention on ``a``: ``q = a W_q`` (``num_attention_heads`` heads of ``head_dim``),
   ``k = a W_k``, ``v = a W_v`` (``num_key_value_heads`` heads); query head ``h``
   reads kv head ``h // (heads / kv heads)`` (the repeat of k and v is written
   out); scores scaled by ``head_dim ** -0.5``.  Where ``rope_layout[i]`` is 1, q
   and k are rotated at ``rope_theta`` (halves paired); where
   ``sliding_window_layout[i]`` is 1, query ``t`` sees the keys ``s`` with ``0 <= t -
   s < sliding_window_size``, else every ``s <= t``.  ``x <- x + W_o concat(o)``.
   The softmax runs a block of query rows at a time so that 16,384 fit.
4. ``m = RMSNorm_post(x)``; ``x <- x + sum over the chosen AND held experts of w_e
   (relu(m W_gate,e) * (m W_up,e)) W_down,e``, as a dense loop over the held
   experts with a mask.  What the absent experts would add is left out (the
   chip's share of a deployment).

Final RMSNorm, untied head.

Departures from the published description: none in the equations as far as the
catalog's row gives them.  What the row does not give is the configuration
file's ``assumed``: that the router reads ``RMSNorm_in(x)``, the softmax AFTER the
choice, the window's convention, no q/k norm, no secondary experts, the pairing
of the rotation.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import reference
from benchmark.reference import _einsum, rms_norm, rotate_half

ATTENTION_ROWS = 256  # query rows a block of the softmax (28 heads x 256 x 16,384 float32 scores: 448 MiB)


# -- weights -----------------------------------------------------------------

def weight_shapes(model: dict) -> dict:
    d, v = model["hidden_size"], model["vocab_size"]
    hq, hkv, dk = model["num_attention_heads"], model["num_key_value_heads"], model["head_dim"]
    lo, hi = model["experts_held"]
    f = model["moe_ffn_hidden_size"]

    def layer():
        return {"attn_norm": (d,), "ffn_norm": (d,), "router": (d, model["n_routed_experts"]),
                "attn": {"wq": (d, hq, dk), "wk": (d, hkv, dk), "wv": (d, hkv, dk),
                         "wo": (hq, dk, d)},
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


# -- the model ---------------------------------------------------------------

def softmax_attention(q, k, v, window, precision, rows: int = ATTENTION_ROWS):
    """q: [B, L, Hq, D]; k, v: [B, L, Hkv, D].  Plain softmax attention with k
    and v repeated to the query heads' count, ``window`` (None: causal alone) as a
    mask, a block of query rows at a time, one block after the other (``lax.map``),
    each recomputed on the way back.  Under a window a block of rows is given the
    ``window + rows`` keys that hold every key its rows see, and masks those: the
    keys outside that span are masked for all of the block's rows (at 16,384
    tokens the span is a quarter of the keys; the mask decides, the span only
    spares the softmax three quarters of its zeros)."""
    B, L, Hq, D = q.shape
    group = Hq // k.shape[2]
    k, v = (jnp.repeat(x, group, axis=2) for x in (k, v))
    rows = min(rows, L)
    pad = (-L) % rows
    span = L if window is None else min(L, window + rows)

    @jax.checkpoint
    def block(x):
        q_rows, pos_rows = x
        # the block's first row sees no key before ``its position - window + 1``
        first = 0 if span == L else jnp.clip(pos_rows[0] - window + 1, 0, L - span)
        k_span, v_span = (jax.lax.dynamic_slice_in_dim(x, first, span, axis=1) for x in (k, v))
        scores = _einsum("blhk,bmhk->bhlm", q_rows, k_span, precision) / np.sqrt(D)
        behind = pos_rows[:, None] - (first + jnp.arange(span))[None, :]  # t - s
        seen = behind >= 0 if window is None else (behind >= 0) & (behind < window)
        scores = jnp.where(seen, scores, -jnp.inf)
        return _einsum("bhlm,bmhk->blhk", jax.nn.softmax(scores, axis=-1), v_span, precision)

    # padded query rows take the last position: they see keys and are cut off
    q_blocks = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0))).reshape(B, -1, rows, Hq, D)
    pos_blocks = jnp.pad(jnp.arange(L), (0, pad), constant_values=L - 1).reshape(-1, rows)
    out = jax.lax.map(block, (jnp.moveaxis(q_blocks, 1, 0), pos_blocks))  # [n, B, rows, Hq, D]
    return jnp.moveaxis(out, 0, 1).reshape(B, -1, Hq, D)[:, :L]


def gqa_mixer(a, w, model, layer: int, precision):
    q = _einsum("bld,dhk->blhk", a, w["wq"], precision)
    k = _einsum("bld,dhk->blhk", a, w["wk"], precision)
    v = _einsum("bld,dhk->blhk", a, w["wv"], precision)
    if model["rope_layout"][layer]:
        q, k = rotate_half(q, model["rope_theta"]), rotate_half(k, model["rope_theta"])
    window = model["sliding_window_size"] if model["sliding_window_layout"][layer] else None
    o = softmax_attention(q, k, v, window, precision)
    return _einsum("blhk,hkd->bld", o, w["wo"], precision)


def router(a, w_r, model):
    """(chosen [B, L, k], weights [B, L, k]): the largest logits of a token and
    the softmax over them."""
    logits = jnp.einsum("bld,de->ble", a, w_r, precision=jax.lax.Precision.HIGHEST)
    picked, chosen = jax.lax.top_k(logits, model["moe_num_active_primary_experts"])
    return chosen, jax.nn.softmax(picked, axis=-1)


def reglu(h, w_gate, w_up, w_down, precision):
    gate = _einsum("bld,df->blf", h, w_gate, precision)
    up = _einsum("bld,df->blf", h, w_up, precision)
    return _einsum("blf,fd->bld", jax.nn.relu(gate) * up, w_down, precision)


def expert_layer(m, chosen, weights, w, model, precision, held=None):
    """``held``: the range of experts whose part is added (default: the
    configuration's ``experts_held``)."""
    lo, hi = model["experts_held"] if held is None else held
    first = model["experts_held"][0]  # w["e_*"][i] is expert first + i

    @jax.checkpoint
    def add_expert(out, x):  # every token through expert e, weighted 0 where e was not chosen
        e, w_gate, w_up, w_down = x
        weight = jnp.sum(jnp.where(chosen == e, weights, 0.0), -1)
        return out + weight[..., None] * reglu(m, w_gate, w_up, w_down, precision), None

    # one expert after the other (a loop the compiler sees once, not 16 copies of it)
    out, _ = jax.lax.scan(add_expert, jnp.zeros_like(m), (jnp.arange(lo, hi), *(
        w[name][lo - first:hi - first] for name in ("e_gate", "e_up", "e_down"))))
    return out


def block(x, w, model, layer: int, precision, held=None):
    eps = model["rms_norm_eps"]
    a = rms_norm(x, w["attn_norm"], eps)
    chosen, weights = router(a, w["router"], model)
    x = x + gqa_mixer(a, w["attn"], model, layer, precision)
    m = rms_norm(x, w["ffn_norm"], eps)
    return x + expert_layer(m, chosen, weights, w["moe"], model, precision, held)


def loss_fn(weights, tokens, targets, row_mask, model, precision):
    """Mean next-token cross-entropy over the tokens of the rows in ``row_mask``."""
    x = weights["embed"][tokens]
    for i, w in enumerate(weights["layers"]):
        x = jax.checkpoint(functools.partial(block, model=model, layer=i,
                                             precision=precision))(x, w)
    x = rms_norm(x, weights["final_norm"], model["rms_norm_eps"])
    logits = _einsum("bld,dv->blv", x, weights["head"], precision)
    logp = jax.nn.log_softmax(logits, axis=-1)
    per = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    mask = row_mask[:, None] * jnp.ones_like(per)
    return jnp.sum(per * mask) / jnp.maximum(jnp.sum(mask), 1.0)


# -- SGD and the round (as benchmark/reference.py does them) -------------------

MODEL_KEYS = ("hidden_size", "num_hidden_layers", "vocab_size", "rms_norm_eps",
              "num_attention_heads", "num_key_value_heads", "head_dim", "rope_theta",
              "rope_layout", "sliding_window_layout", "sliding_window_size",
              "moe_ffn_hidden_size", "moe_num_active_primary_experts", "n_routed_experts",
              "experts_held")


def model_key(model: dict) -> str:
    """The shape- and equation-deciding entries of a configuration file,
    hashable for jit (as JSON text)."""
    return json.dumps({k: model[k] for k in MODEL_KEYS}, sort_keys=True)


@functools.partial(jax.jit, static_argnames=("model_key", "precision", "fault"),
                   donate_argnums=(0,))
def _sgd_step(weights, tokens, targets, row_mask, lr, *, model_key, precision, fault):
    model = json.loads(model_key)
    if fault == "half_batch":  # half of the batch left out, the mean over the rest
        row_mask = row_mask * (jnp.arange(row_mask.shape[0]) < row_mask.shape[0] // 2)
    loss, grads = jax.value_and_grad(loss_fn)(weights, tokens, targets, row_mask,
                                              model, precision)
    if fault == "state_unchanged":
        return weights, loss
    return jax.tree_util.tree_map(lambda p, g: p - lr * g, weights, grads), loss


def local_sgd(weights, x, y, order, batch, lr, model, precision="highest", fault=None):
    """Plain SGD over the rows of ``x``/``y`` in ``order`` (-1 is padding, left
    out of the mean), ``batch`` rows a step.  Returns the new weights and the
    mean loss over the rows fed.  ``weights`` is consumed."""
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


def fedavg_round(global_w, shards, seed, round_idx, batch, lr, model, precision="highest",
                 fault=None, clients=None):
    """One FedAvg round, as ``reference.fedavg_round``: every client in
    ``clients`` trains from ``global_w``; the new global is the mean weighted by
    rows.  ``fault="no_exchange"`` keeps the first quarter of the clients."""
    clients = list(range(len(shards))) if clients is None else list(clients)
    if fault == "no_exchange":
        clients, fault = clients[: max(1, len(clients) // 4)], None
    acc, wsum, loss_sum = None, 0.0, 0.0
    for c in clients:
        x, y = shards[c]
        order = reference.feed_order_packed_round(seed, round_idx, c, len(x), batch)
        local, loss = local_sgd(reference.copy_tree(global_w), x, y, order, batch, lr, model,
                                precision, fault)
        w = float(len(x))
        acc = reference._scale(local, w) if acc is None else reference._add_scaled(acc, local, w)
        wsum += w
        loss_sum += loss * w
        del local
    return reference._scale(acc, 1.0 / wsum), loss_sum / wsum
