"""Plain reference of the ``kimi_linear`` decoder (moonshotai/Kimi-Linear-48B-A3B-
Instruct): its forward pass, loss, gradients, local SGD and the FedAvg round,
in straightforward ``jax.numpy`` and float32 at ``Precision.HIGHEST``.  No
kernel, no cache, no packing, no mesh, no grouped product.

It imports nothing from ``fedml_tpu``.  From ``benchmark/reference.py`` it
takes the parts that know no model: the products' arithmetic (``_einsum``, so
the float8 / int8 controls and the bfloat16 reading exist here too), the feed
order and the cohort, the weighted sums of the FedAvg round, and the readings.

Layer equations (pre-norm residual block, RMSNorm eps ``rms_norm_eps``, final
RMSNorm, untied head):

* KDA mixer, per head of ``linear_attn_config.num_heads`` x ``head_dim``:
  ``q, k, v = SiLU(conv(W x))`` with a causal depthwise convolution of
  ``short_conv_kernel_size`` over time; q, k L2-normalised; ``g = -exp(A_log)
  softplus(W_f_up W_f_down x + dt_bias)`` per channel; ``beta = sigmoid(W_beta x)``;
  state ``S_0 = 0`` at the start of every sequence, ``S~_t = Diag(exp(g_t))
  S_{t-1}``, ``S_t = S~_t + beta_t k_t (v_t - S~_t^T k_t)^T``, ``o_t = S_t^T q_t
  d_k^-1/2``; output ``W_o(RMSNorm_head(o) * sigmoid(W_g_up W_g_down x))``.
  ``kda_per_token`` is that recurrence token by token.  ``kda_by_chunks``
  evaluates the SAME recurrence a chunk of 32 tokens at a time with the
  per-channel decay of every pair written out (``tests/`` tie the two); it is
  what the chip runs at 8,192 tokens, where the per-token scan's 8,192 steps a
  layer a pass would take minutes.
* MLA mixer, NoPE: ``[c ; k_pe] = W_kv_down x``, ``c <- RMSNorm(c)``, ``[k_nope ;
  v] = W_kv_up c`` per head, ``[q_nope ; q_pe] = W_q x``, ``k = [k_nope ; k_pe]``
  (k_pe shared by the heads, no rotation), causal softmax of ``q.k / sqrt(192)``,
  ``W_o concat(P v)``; the softmax in blocks of query rows so that 8,192 fit.
* Expert layer: ``s = sigmoid(W_r x)`` over all ``n_routed_experts``; the top
  ``num_experts_per_token`` of ``s + b``; weights ``routed_scaling_factor s_e /
  sum_chosen s``; ``Shared(x) + sum over chosen AND held experts of w_e
  Expert_e(x)``, as a dense loop over the held experts with a mask.  What the
  absent experts would add is left out (the chip's share of a deployment).

Departures from the published model: none in the equations; the low-rank
width of the two KDA gate projections, ``A_log`` per head and ``dt_bias`` per
channel are the configuration file's ``assumed``.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import reference
from benchmark.reference import _einsum

KDA_CHUNK = 32  # tokens a chunk of ``kda_by_chunks``
ATTENTION_ROWS = 256  # query rows a block of the softmax (32 heads x 256 x 8,192 float32 scores: 256 MiB)


# -- weights -----------------------------------------------------------------

def is_kda(model: dict, layer: int) -> bool:
    """``layer`` counts from 0; the published lists count from 1."""
    lin = model["linear_attn_config"]
    if layer + 1 in lin["kda_layers"]:
        return True
    if layer + 1 in lin["full_attn_layers"]:
        return False
    raise ValueError(f"layer {layer + 1} is in neither list of linear_attn_config")


def weight_shapes(model: dict) -> dict:
    d, v = model["hidden_size"], model["vocab_size"]
    lin = model["linear_attn_config"]
    hk, dk, conv = lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"]
    r = model.get("kda_gate_rank", dk)
    h, rank = model["num_attention_heads"], model["kv_lora_rank"]
    nope, pe, dv = model["qk_nope_head_dim"], model["qk_rope_head_dim"], model["v_head_dim"]
    lo, hi = model["experts_held"]
    f, fs = model["moe_intermediate_size"], model["moe_intermediate_size"] * model["num_shared_experts"]
    layers = []
    for i in range(model["num_hidden_layers"]):
        w = {"mixer_norm": (d,), "ffn_norm": (d,)}
        if is_kda(model, i):
            w["kda"] = {"wq": (d, hk, dk), "wk": (d, hk, dk), "wv": (d, hk, dk),
                        "conv_q": (conv, hk, dk), "conv_k": (conv, hk, dk), "conv_v": (conv, hk, dk),
                        "f_down": (d, r), "f_up": (r, hk, dk), "A_log": (hk,), "dt_bias": (hk, dk),
                        "w_beta": (d, hk), "g_down": (d, r), "g_up": (r, hk, dk),
                        "o_norm": (dk,), "wo": (hk, dk, d)}
        else:
            w["mla"] = {"wq": (d, h, nope + pe), "w_kv_down": (d, rank + pe), "kv_norm": (rank,),
                        "w_kv_up": (rank, h, nope + dv), "wo": (h, dv, d)}
        if i < model["first_k_dense_replace"]:
            fd = model["intermediate_size"]
            w["mlp"] = {"w_gate": (d, fd), "w_up": (d, fd), "w_down": (fd, d)}
        else:
            w["moe"] = {"router": (d, model["n_routed_experts"]),
                        "router_bias": (model["n_routed_experts"],),
                        "e_gate": (hi - lo, d, f), "e_up": (hi - lo, d, f), "e_down": (hi - lo, f, d),
                        "shared": {"w_gate": (d, fs), "w_up": (d, fs), "w_down": (fs, d)}}
        layers.append(w)
    return {"embed": (v, d), "final_norm": (d,), "head": (d, v), "layers": layers}


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
        elif name == "A_log":  # exp(A_log) in 1..16, as the family's linear layers start
            w = jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
        elif name == "dt_bias":  # softplus(dt_bias) in 1e-3..1e-1, log-uniform
            dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32, np.log(1e-3), np.log(1e-1)))
            w = jnp.log(jnp.expm1(dt))
        elif name == "router_bias":  # the score-correction bias: seeded, never trained
            w = 0.02 * jax.random.normal(k, shape, jnp.float32)
        else:
            w = jax.random.normal(k, shape, jnp.float32) / np.sqrt(_fan_in(name, shape))
        out.append(w)
    return out


def make_weights(model: dict, seed: int) -> dict:
    """Float32 weights on the device, one jitted call from the seed: normal
    with variance 1/fan_in, norm scales 1, the gates' ``A_log`` and ``dt_bias``
    and the router's correction bias as ``_make`` says."""
    from benchmark.traffic import _key

    flat, treedef = jax.tree_util.tree_flatten_with_path(
        weight_shapes(model), is_leaf=lambda x: isinstance(x, tuple))
    names = tuple((path[-1].key, shape) for path, shape in flat)
    return jax.tree_util.tree_unflatten(treedef, _make(_key(seed, 0), shapes_key=names))


# -- the model ---------------------------------------------------------------

def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def causal_conv(x, w):
    """x: [B, L, H, D]; w: [K, H, D]: y_t = sum_i w[i] x_{t-(K-1)+i}."""
    K, L = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0), (0, 0)))
    return sum(padded[:, i:i + L] * w[i] for i in range(K))


def kda_per_token(q, k, v, g, beta):
    """The recurrence as written, one token a step.  q, k, g: [B, L, H, dk];
    v: [B, L, H, dv]; beta: [B, L, H].  The scan is cut into blocks of 64 steps
    whose insides are recomputed on the way back, so that 8,192 steps' states
    are not all kept."""
    B, L, H, dk = q.shape
    hi = jax.lax.Precision.HIGHEST

    def step(S, x):
        q_t, k_t, v_t, g_t, b_t = x
        S = jnp.exp(g_t)[..., None] * S
        u = b_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", S, k_t, precision=hi))
        S = S + k_t[..., :, None] * u[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, q_t, precision=hi) * dk ** -0.5

    @jax.checkpoint
    def block(S, xs):
        return jax.lax.scan(step, S, xs)

    span = 64
    pad = (-L) % span
    xs = [jnp.pad(jnp.moveaxis(x, 1, 0), ((0, pad),) + ((0, 0),) * (x.ndim - 1))
          for x in (q, k, v, g, beta)]  # zero keys, values and gates leave the state alone
    xs = [x.reshape((-1, span) + x.shape[1:]) for x in xs]
    _, o = jax.lax.scan(block, jnp.zeros((B, H, dk, v.shape[-1]), jnp.float32), xs)
    return jnp.moveaxis(o.reshape((-1,) + o.shape[2:])[:L], 0, 1)


def kda_by_chunks(q, k, v, g, beta, chunk: int = KDA_CHUNK):
    """The same recurrence, a chunk at a time.  Inside a chunk that starts from
    the state S_0, with G the running sum of the gates,

        u_t = beta_t (v_t - S_0^T (k_t exp(G_t)) - sum_{j<t} A_tj u_j),
        A_tj = sum_d k_t[d] k_j[d] exp(G_t[d] - G_j[d]),
        o_t = (S_0^T (q_t exp(G_t)) + sum_{j<=t} B_tj u_j) d_k^-1/2,  B as A with q_t,
        S_C = Diag(exp(G_C)) S_0 + sum_j (k_j exp(G_C - G_j)) u_j^T,

    with every pair's decay ``exp(G_t - G_j)`` written out per channel (a
    [C, C, d_k] array a head: no factorisation, no overflow) and ``u`` from
    one triangular solve a chunk."""
    B, L, H, dk = q.shape
    hi = jax.lax.Precision.HIGHEST
    pad = (-L) % chunk
    xs = [jnp.pad(jnp.moveaxis(x, 1, 0), ((0, pad),) + ((0, 0),) * (x.ndim - 1))
          for x in (q, k, v, g, beta)]
    xs = [jnp.moveaxis(x.reshape((-1, chunk) + x.shape[1:]), 1, 3) for x in xs]  # [N, B, H, C, ..]
    t = jnp.arange(chunk)

    @jax.checkpoint
    def one(S, x):
        q_c, k_c, v_c, g_c, b_c = x  # [B, H, C, d]; b_c [B, H, C]
        G = jnp.cumsum(g_c, axis=-2)
        later = t[:, None] >= t[None, :]
        decay = jnp.exp(jnp.where(later[..., None], G[..., :, None, :] - G[..., None, :, :], -jnp.inf))
        A = jnp.sum(k_c[..., :, None, :] * k_c[..., None, :, :] * decay, -1)
        Bq = jnp.sum(q_c[..., :, None, :] * k_c[..., None, :, :] * decay, -1)
        A = jnp.where(t[:, None] > t[None, :], A, 0.0)
        rhs = b_c[..., None] * (v_c - jnp.einsum("bhck,bhkv->bhcv", k_c * jnp.exp(G), S, precision=hi))
        # u_t + beta_t sum_{j<t} A_tj u_j = rhs_t: a unit lower-triangular system
        U = jax.scipy.linalg.solve_triangular(
            jnp.eye(chunk) + b_c[..., None] * A, rhs, lower=True, unit_diagonal=True)
        o = (jnp.einsum("bhck,bhkv->bhcv", q_c * jnp.exp(G), S, precision=hi)
             + jnp.einsum("bhcj,bhjv->bhcv", Bq, U, precision=hi)) * dk ** -0.5
        G_end = G[..., -1:, :]
        S = jnp.exp(G_end[..., 0, :])[..., None] * S + jnp.einsum(
            "bhck,bhcv->bhkv", k_c * jnp.exp(G_end - G), U, precision=hi)
        return S, o

    _, o = jax.lax.scan(one, jnp.zeros((B, H, dk, v.shape[-1]), jnp.float32), xs)
    o = jnp.moveaxis(o, 3, 1).reshape((-1,) + o.shape[1:3] + o.shape[4:])[:L]  # [L, B, H, dv]
    return jnp.moveaxis(o, 0, 1)


KDA_FORMS = {"per_token": kda_per_token, "by_chunks": kda_by_chunks}


def kda_mixer(h, w, model, precision, kda_form):
    eps = model["rms_norm_eps"]

    def conv_proj(name):
        return jax.nn.silu(causal_conv(_einsum("bld,dhk->blhk", h, w["w" + name], precision),
                                       w["conv_" + name]))

    def l2(x):
        return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + 1e-6)

    def low_rank(down, up):
        return _einsum("blr,rhk->blhk", _einsum("bld,dr->blr", h, w[down], precision),
                       w[up], precision)

    q, k, v = l2(conv_proj("q")), l2(conv_proj("k")), conv_proj("v")
    g = -jnp.exp(w["A_log"])[:, None] * jax.nn.softplus(low_rank("f_down", "f_up") + w["dt_bias"])
    beta = jax.nn.sigmoid(_einsum("bld,dh->blh", h, w["w_beta"], precision))
    o = KDA_FORMS[kda_form](q, k, v, g, beta)
    o = rms_norm(o, w["o_norm"], eps) * jax.nn.sigmoid(low_rank("g_down", "g_up"))
    return _einsum("blhk,hkd->bld", o, w["wo"], precision)


def causal_softmax_attention(q, k, v, precision, rows: int = ATTENTION_ROWS):
    """q, k: [B, L, H, D]; v: [B, L, H, Dv].  Plain softmax attention, a block
    of query rows at a time, one block after the other (``lax.map``: blocks
    written as a Python loop are independent and get scheduled side by side,
    scores and all), each recomputed on the way back."""
    B, L, H, D = q.shape
    rows = min(rows, L)
    pad = (-L) % rows
    positions = jnp.arange(L)

    @jax.checkpoint
    def block(x):
        q_rows, pos_rows = x
        scores = _einsum("blhk,bmhk->bhlm", q_rows, k, precision) / np.sqrt(D)
        scores = jnp.where(pos_rows[:, None] >= positions[None, :], scores, -jnp.inf)
        return _einsum("bhlm,bmhk->blhk", jax.nn.softmax(scores, axis=-1), v, precision)

    # padded query rows take the last position: they see every key and are cut off
    q_blocks = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0))).reshape(B, -1, rows, H, D)
    pos_blocks = jnp.pad(positions, (0, pad), constant_values=L - 1).reshape(-1, rows)
    out = jax.lax.map(block, (jnp.moveaxis(q_blocks, 1, 0), pos_blocks))  # [n, B, rows, H, Dv]
    return jnp.moveaxis(out, 0, 1).reshape(B, -1, H, v.shape[-1])[:, :L]


def mla_mixer(h, w, model, precision):
    rank, nope = model["kv_lora_rank"], model["qk_nope_head_dim"]
    q = _einsum("bld,dhk->blhk", h, w["wq"], precision)
    kv = _einsum("bld,dr->blr", h, w["w_kv_down"], precision)
    c = rms_norm(kv[..., :rank], w["kv_norm"], model["rms_norm_eps"])
    up = _einsum("blr,rhk->blhk", c, w["w_kv_up"], precision)
    k_pe = jnp.broadcast_to(kv[..., None, rank:], kv.shape[:2] + (q.shape[2], kv.shape[-1] - rank))
    k = jnp.concatenate([up[..., :nope], k_pe], -1)  # NoPE: k_pe is not rotated
    o = causal_softmax_attention(q, k, up[..., nope:], precision)
    return _einsum("blhk,hkd->bld", o, w["wo"], precision)


def swiglu(h, w_gate, w_up, w_down, precision):
    gate = _einsum("bld,df->blf", h, w_gate, precision)
    up = _einsum("bld,df->blf", h, w_up, precision)
    return _einsum("blf,fd->bld", jax.nn.silu(gate) * up, w_down, precision)


def expert_layer(h, w, model, precision, held=None):
    """``held``: the range of experts whose part is added (default: the
    configuration's ``experts_held``).  Returns (result, chosen [B, L, k])."""
    lo, hi = model["experts_held"] if held is None else held
    first = model["experts_held"][0]  # w["e_*"][i] is expert first + i
    scores = jax.nn.sigmoid(jnp.einsum("bld,de->ble", h, w["router"],
                                       precision=jax.lax.Precision.HIGHEST))
    _, chosen = jax.lax.top_k(scores + w["router_bias"], model["num_experts_per_token"])
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    if model["moe_renormalize"]:
        picked = picked / jnp.sum(picked, -1, keepdims=True)
    picked = picked * model["routed_scaling_factor"]
    out = swiglu(h, w["shared"]["w_gate"], w["shared"]["w_up"], w["shared"]["w_down"], precision)

    @jax.checkpoint
    def add_expert(out, x):  # every token through expert e, weighted 0 where e was not chosen
        e, w_gate, w_up, w_down = x
        weight = jnp.sum(jnp.where(chosen == e, picked, 0.0), -1)
        return out + weight[..., None] * swiglu(h, w_gate, w_up, w_down, precision), None

    # one expert after the other (a loop the compiler sees once, not 8 copies of it)
    out, _ = jax.lax.scan(add_expert, out, (jnp.arange(lo, hi), *(
        w[name][lo - first:hi - first] for name in ("e_gate", "e_up", "e_down"))))
    return out, chosen


def block(x, w, model, precision, kda_form):
    eps = model["rms_norm_eps"]
    h = rms_norm(x, w["mixer_norm"], eps)
    x = x + (kda_mixer(h, w["kda"], model, precision, kda_form) if "kda" in w
             else mla_mixer(h, w["mla"], model, precision))
    h = rms_norm(x, w["ffn_norm"], eps)
    if "mlp" in w:
        return x + swiglu(h, w["mlp"]["w_gate"], w["mlp"]["w_up"], w["mlp"]["w_down"], precision)
    return x + expert_layer(h, w["moe"], model, precision)[0]


def loss_fn(weights, tokens, targets, row_mask, model, precision, kda_form="per_token"):
    """Mean next-token cross-entropy over the tokens of the rows in ``row_mask``."""
    x = weights["embed"][tokens]
    layer = jax.checkpoint(functools.partial(block, model=model, precision=precision,
                                             kda_form=kda_form))
    for w in weights["layers"]:
        x = layer(x, w)
    x = rms_norm(x, weights["final_norm"], model["rms_norm_eps"])
    logits = _einsum("bld,dv->blv", x, weights["head"], precision)
    logp = jax.nn.log_softmax(logits, axis=-1)
    per = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    mask = row_mask[:, None] * jnp.ones_like(per)
    return jnp.sum(per * mask) / jnp.maximum(jnp.sum(mask), 1.0)


# -- SGD and the round (as benchmark/reference.py does them) -------------------

MODEL_KEYS = ("hidden_size", "num_hidden_layers", "vocab_size", "rms_norm_eps",
              "linear_attn_config", "kda_gate_rank", "num_attention_heads", "kv_lora_rank",
              "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "intermediate_size",
              "first_k_dense_replace", "moe_intermediate_size", "n_routed_experts",
              "experts_held", "num_experts_per_token", "num_shared_experts",
              "routed_scaling_factor", "moe_renormalize")


def model_key(model: dict) -> str:
    """The shape- and equation-deciding entries of a configuration file,
    hashable for jit (as JSON text)."""
    return json.dumps({k: model[k] for k in MODEL_KEYS if k in model}, sort_keys=True)


@functools.partial(jax.jit, static_argnames=("model_key", "precision", "fault", "kda_form"),
                   donate_argnums=(0,))
def _sgd_step(weights, tokens, targets, row_mask, lr, *, model_key, precision, fault, kda_form):
    model = json.loads(model_key)
    if fault == "half_batch":  # half of the batch left out, the mean over the rest
        row_mask = row_mask * (jnp.arange(row_mask.shape[0]) < row_mask.shape[0] // 2)
    loss, grads = jax.value_and_grad(loss_fn)(weights, tokens, targets, row_mask,
                                              model, precision, kda_form)
    if fault == "state_unchanged":
        return weights, loss
    return jax.tree_util.tree_map(lambda p, g: p - lr * g, weights, grads), loss


def local_sgd(weights, x, y, order, batch, lr, model, precision="highest", fault=None,
              kda_form="per_token"):
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
            jnp.float32(lr), model_key=model_key(model), precision=precision, fault=fault,
            kda_form=kda_form)
        loss_sum += float(loss) * float(valid.sum())
        rows += float(valid.sum())
    return weights, loss_sum / max(rows, 1.0)


def fedavg_round(global_w, shards, seed, round_idx, batch, lr, model, precision="highest",
                 fault=None, clients=None, kda_form="per_token"):
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
                                precision, fault, kda_form)
        w = float(len(x))
        acc = reference._scale(local, w) if acc is None else reference._add_scaled(acc, local, w)
        wsum += w
        loss_sum += loss * w
        del local
    return reference._scale(acc, 1.0 / wsum), loss_sum / wsum
