"""Plain reference of the ``glm4_moe_lite`` decoder (zai-org/GLM-4.7-Flash): its
forward pass, both losses, gradients, local SGD and the FedAvg round, in
straightforward ``jax.numpy`` and float32 at ``Precision.HIGHEST``.  No kernel, no
cache, no packing, no mesh, no grouped product.

It imports nothing from ``fedml_tpu``.  From ``benchmark/reference.py`` it takes the
parts that know no model: the products' arithmetic (``_einsum``, so the float8 / int8
controls and the bfloat16 reading exist here too), the norm, the rotation, the feed
order and the cohort, the weighted sums of the FedAvg round, and the readings; from
``benchmark/reference_kimi_linear.py`` the plain causal softmax in blocks of query
rows and the gated MLP, which know no configuration either.

``x`` is a layer's input ``[L, hidden]``; every norm is an RMSNorm with ``rms_norm_eps``
and a scale; no bias anywhere; H = ``num_attention_heads``.

1. Latent attention, every layer, on ``a = RMSNorm_in(x)``:
   ``c_q = RMSNorm_q(a W_dq)`` (``q_lora_rank``); ``q = c_q W_uq`` -> per head ``[q_nope
   (qk_nope_head_dim) | q_pe (qk_rope_head_dim)]``; ``[c_kv (kv_lora_rank) | k_pe] = a
   W_dkv``; ``[k_nope | v (v_head_dim)]`` per head ``= RMSNorm_kv(c_kv) W_ukv``; ``q_pe`` and
   the ONE shared ``k_pe`` are rotated (rotary positions over all ``qk_rope_head_dim``,
   ``rope_theta``, halves paired) and ``k_pe`` is broadcast over the heads; ``q = [q_nope |
   R(q_pe)]``, ``k = [k_nope | R(k_pe)]``; causal softmax of ``q k^T (nope + rope)^-1/2``, a
   block of query rows at a time so that 8,192 fit; ``x <- x + concat_heads(o) W_o``.
2. Layer ``i < first_k_dense_replace``: ``x <- x + SwiGLU(RMSNorm_post(x))`` at
   ``intermediate_size``.
3. The other layers, on ``m = RMSNorm_post(x)``: ``s = sigmoid(m W_r)`` over all
   ``n_router_outputs`` (float32 at ``HIGHEST`` whatever ``precision`` the other products
   run in); the ``num_experts_per_tok`` experts of largest ``s + b`` (``router_bias``, the
   ``e_score_correction_bias``, in the choice alone; one group); ``w = s[chosen] / (sum
   s[chosen] + 1e-20) x routed_scaling_factor``; ``x <- x + sum over the chosen AND held
   experts of w_e SwiGLU_e(m) + SwiGLU_shared(m)``, as a dense loop over the held experts
   with a mask.  What the absent experts would add is left out (the chip's share).
4. ``logits = RMSNorm_f(x) W_head`` (untied); ``L_main`` = mean cross-entropy of
   ``logits_i`` against ``t_{i+1}`` over the rows of the batch's mask.
5. The multi-token-prediction module (one; DeepSeek-V3 report, arXiv:2412.19437, section
   2.2) reads ``x``, the last block's output, not ``RMSNorm_f(x)``: ``u_i = [RMSNorm_h(x_i) ;
   RMSNorm_e(Emb(t_{i+1}))] W_eh`` -> one whole block of kind 1 + 3 (its own weights, the
   same share of experts) -> ``logits'_i = RMSNorm_m(.) W_head`` (the main embedding and
   head) predicts ``t_{i+2}``; ``L_mtp`` = mean cross-entropy over the positions that have a
   ``t_{i+2}`` (L - 1 a row).  ``L = L_main + mtp_loss_weight x L_mtp`` is what a step trains.

Departures from the published description, each also under the configuration file's
``assumed``: the config.json gives only ``num_nextn_predict_layers``, so the module's form,
that it shares embedding and head, the order of ``W_eh``'s two halves and that it reads
the un-normed ``x`` are the report's; the module runs over all L positions with the last
one (whose ``t_{i+1}`` is the row's label) masked out of ``L_mtp``, as the program does, so
its expert layer routes L tokens a row; ``mtp_loss_weight`` is the file's; the router's
correction bias is seeded and never trained.  ``fault="no_mtp"`` (a planted fault for
``benchmark/tests``) trains ``L_main`` alone.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import reference
from benchmark.reference import _einsum, rms_norm, rotate_half
from benchmark.reference_kimi_linear import causal_softmax_attention, swiglu


# -- weights -----------------------------------------------------------------

def weight_shapes(model: dict) -> dict:
    d, v = model["hidden_size"], model["vocab_size"]
    h, r_q, r_kv = model["num_attention_heads"], model["q_lora_rank"], model["kv_lora_rank"]
    nope, pe, dv = model["qk_nope_head_dim"], model["qk_rope_head_dim"], model["v_head_dim"]
    lo, hi = model["experts_held"]
    f, fd = model["moe_intermediate_size"], model["intermediate_size"]
    fs = f * model["n_shared_experts"]

    def layer(i):
        w = {"mixer_norm": (d,), "ffn_norm": (d,),
             "mla": {"w_q_down": (d, r_q), "q_norm": (r_q,), "w_q_up": (r_q, h, nope + pe),
                     "w_kv_down": (d, r_kv + pe), "kv_norm": (r_kv,),
                     "w_kv_up": (r_kv, h, nope + dv), "wo": (h, dv, d)}}
        if i < model["first_k_dense_replace"]:
            w["mlp"] = {"w_gate": (d, fd), "w_up": (d, fd), "w_down": (fd, d)}
        else:
            w["moe"] = {"router": (d, model["n_router_outputs"]),
                        "router_bias": (model["n_router_outputs"],),
                        "e_gate": (hi - lo, d, f), "e_up": (hi - lo, d, f), "e_down": (hi - lo, f, d),
                        "shared": {"w_gate": (d, fs), "w_up": (d, fs), "w_down": (fs, d)}}
        return w

    n = model["num_hidden_layers"]
    shapes = {"embed": (v, d), "final_norm": (d,), "head": (d, v),
              "layers": [layer(i) for i in range(n)]}
    if model["num_nextn_predict_layers"]:
        shapes["mtp"] = {"h_norm": (d,), "e_norm": (d,), "w_eh": (2 * d, d), "block": layer(n),
                         "norm": (d,)}
    return shapes


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
        k = jax.random.fold_in(key, i)
        if name.endswith("norm"):
            w = jnp.ones(shape, jnp.float32)
        elif name == "router_bias":  # the score-correction bias: seeded, never trained
            w = 0.02 * jax.random.normal(k, shape, jnp.float32)
        else:
            w = jax.random.normal(k, shape, jnp.float32) / np.sqrt(_fan_in(name, shape))
        out.append(w)
    return out


def make_weights(model: dict, seed: int) -> dict:
    """Float32 weights on the device, one jitted call from the seed: normal with
    variance 1/fan_in, norm scales 1, the router's correction bias at std 0.02."""
    from benchmark.traffic import _key

    flat, treedef = jax.tree_util.tree_flatten_with_path(
        weight_shapes(model), is_leaf=lambda x: isinstance(x, tuple))
    names = tuple((path[-1].key, shape) for path, shape in flat)
    return jax.tree_util.tree_unflatten(treedef, _make(_key(seed, 0), shapes_key=names))


# -- the model ---------------------------------------------------------------

def mla_mixer(a, w, model, precision):
    eps, rank, nope = model["rms_norm_eps"], model["kv_lora_rank"], model["qk_nope_head_dim"]
    theta = model["rope_theta"]
    c_q = rms_norm(_einsum("bld,dr->blr", a, w["w_q_down"], precision), w["q_norm"], eps)
    q = _einsum("blr,rhk->blhk", c_q, w["w_q_up"], precision)
    kv = _einsum("bld,dr->blr", a, w["w_kv_down"], precision)
    up = _einsum("blr,rhk->blhk", rms_norm(kv[..., :rank], w["kv_norm"], eps), w["w_kv_up"],
                 precision)
    q = jnp.concatenate([q[..., :nope], rotate_half(q[..., nope:], theta)], -1)
    k_pe = rotate_half(kv[..., None, rank:], theta)  # one rotated key part for all heads
    k = jnp.concatenate([up[..., :nope], jnp.broadcast_to(
        k_pe, kv.shape[:2] + (q.shape[2], k_pe.shape[-1]))], -1)
    o = causal_softmax_attention(q, k, up[..., nope:], precision)
    return _einsum("blhk,hkd->bld", o, w["wo"], precision)


def expert_layer(m, w, model, precision, held=None):
    """``held``: the range of experts whose part is added (default: the
    configuration's ``experts_held``); the shared expert is always added.  Returns
    (result, chosen [B, L, k])."""
    lo, hi = model["experts_held"] if held is None else held
    first = model["experts_held"][0]  # w["e_*"][i] is expert first + i
    scores = jax.nn.sigmoid(jnp.einsum("bld,de->ble", m, w["router"],
                                       precision=jax.lax.Precision.HIGHEST))
    _, chosen = jax.lax.top_k(scores + w["router_bias"], model["num_experts_per_tok"])
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    if model["norm_topk_prob"]:
        picked = picked / (jnp.sum(picked, -1, keepdims=True) + 1e-20)
    picked = picked * model["routed_scaling_factor"]
    out = swiglu(m, w["shared"]["w_gate"], w["shared"]["w_up"], w["shared"]["w_down"], precision)

    @jax.checkpoint
    def add_expert(out, x):  # every token through expert e, weighted 0 where e was not chosen
        e, w_gate, w_up, w_down = x
        weight = jnp.sum(jnp.where(chosen == e, picked, 0.0), -1)
        return out + weight[..., None] * swiglu(m, w_gate, w_up, w_down, precision), None

    # one expert after the other (a loop the compiler sees once, not 8 copies of it)
    out, _ = jax.lax.scan(add_expert, out, (jnp.arange(lo, hi), *(
        w[name][lo - first:hi - first] for name in ("e_gate", "e_up", "e_down"))))
    return out, chosen


def block(x, w, model, precision):
    eps = model["rms_norm_eps"]
    x = x + mla_mixer(rms_norm(x, w["mixer_norm"], eps), w["mla"], model, precision)
    m = rms_norm(x, w["ffn_norm"], eps)
    if "mlp" in w:
        return x + swiglu(m, w["mlp"]["w_gate"], w["mlp"]["w_up"], w["mlp"]["w_down"], precision)
    return x + expert_layer(m, w["moe"], model, precision)[0]


def _cross_entropy(x, scale, head, targets, mask, model, precision):
    """Sum over ``mask`` of the cross-entropy of ``RMSNorm(x) W_head`` against
    ``targets``, and the mask's count; the logits are recomputed on the way back."""
    @jax.checkpoint
    def total(x, scale, head):
        logits = _einsum("bld,dv->blv", rms_norm(x, scale, model["rms_norm_eps"]), head, precision)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return jnp.sum(-jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0] * mask)

    return total(x, scale, head), jnp.sum(mask)


def losses(weights, tokens, targets, row_mask, model, precision):
    """(L_main, L_mtp, positions of L_mtp).  ``targets[i] = t_{i+1}``."""
    eps = model["rms_norm_eps"]
    layer = jax.checkpoint(functools.partial(block, model=model, precision=precision))
    x = weights["embed"][tokens]
    for w in weights["layers"]:
        x = layer(x, w)
    rows = row_mask[:, None] * jnp.ones(tokens.shape, jnp.float32)
    total, count = _cross_entropy(x, weights["final_norm"], weights["head"], targets, rows,
                                  model, precision)
    main = total / jnp.maximum(count, 1.0)
    if "mtp" not in weights:
        return main, jnp.zeros(()), jnp.zeros(())
    w = weights["mtp"]
    L = tokens.shape[1]
    # t_{i+1}: the row's own next token; targets hold it for every i, the last included
    u = _einsum("blc,cd->bld", jnp.concatenate(
        [rms_norm(x, w["h_norm"], eps), rms_norm(weights["embed"][targets], w["e_norm"], eps)],
        -1), w["w_eh"], precision)
    y = layer(u, w["block"])
    # position i predicts t_{i+2} = targets[i + 1]; the last position has none
    after_next = jnp.concatenate([targets[:, 1:], targets[:, -1:]], axis=1)
    total, count = _cross_entropy(y, w["norm"], weights["head"], after_next,
                                  rows * (jnp.arange(L) < L - 1), model, precision)
    return main, total / jnp.maximum(count, 1.0), count


def loss_fn(weights, tokens, targets, row_mask, model, precision, fault=None):
    """What a step trains: ``L_main + mtp_loss_weight x L_mtp``."""
    main, mtp, _ = losses(weights, tokens, targets, row_mask, model, precision)
    weight = 0.0 if fault == "no_mtp" else model.get("mtp_loss_weight", 0.0)
    return main + weight * mtp


# -- SGD and the round (as benchmark/reference.py does them) -------------------

MODEL_KEYS = ("hidden_size", "num_hidden_layers", "vocab_size", "rms_norm_eps",
              "num_attention_heads", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
              "qk_rope_head_dim", "v_head_dim", "rope_theta", "intermediate_size",
              "first_k_dense_replace", "moe_intermediate_size", "n_router_outputs",
              "experts_held", "num_experts_per_tok", "n_shared_experts",
              "routed_scaling_factor", "norm_topk_prob", "num_nextn_predict_layers",
              "mtp_loss_weight")


def model_key(model: dict) -> str:
    """The shape- and equation-deciding entries of a configuration file,
    hashable for jit (as JSON text)."""
    return json.dumps({k: model[k] for k in MODEL_KEYS if k in model}, sort_keys=True)


@functools.partial(jax.jit, static_argnames=("model_key", "precision", "fault"),
                   donate_argnums=(0,))
def _sgd_step(weights, tokens, targets, row_mask, lr, *, model_key, precision, fault):
    model = json.loads(model_key)
    if fault == "half_batch":  # half of the batch left out, the mean over the rest
        row_mask = row_mask * (jnp.arange(row_mask.shape[0]) < row_mask.shape[0] // 2)
    loss, grads = jax.value_and_grad(loss_fn)(weights, tokens, targets, row_mask,
                                              model, precision, fault)
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
