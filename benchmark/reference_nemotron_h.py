"""Plain reference of the ``nemotron_h`` decoder (nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16):
its forward pass, loss, gradients, local SGD and the FedAvg round, in straightforward
``jax.numpy`` and float32 at ``Precision.HIGHEST``.  No kernel, no packing, no mesh, no
grouped product.

It imports nothing from ``fedml_tpu``.  From ``benchmark/reference.py`` it takes the parts
that know no model (the products' arithmetic ``_einsum``, so the float8 / int8 controls
and the bfloat16 reading exist here too; the feed order, the cohort, the weighted sums
of the round, the readings), from ``benchmark/reference_kimi_linear.py`` the causal
softmax in blocks of query rows and the float32 RMSNorm.

A layer is one sublayer, ``x <- x + Sublayer(RMSNorm(x))``, chosen by its character of
``hybrid_override_pattern``:

* ``M``, Mamba-2: ``[z | xBC | dt] = h W_in``; ``xBC = SiLU(conv_K(xBC) + b)`` causal and
  depthwise; ``x`` (``mamba_num_heads`` heads of ``mamba_head_dim``), ``B`` and ``C``
  (``n_groups`` groups of ``ssm_state_size``; head ``h`` reads group ``h // (heads /
  groups)``) split from it; ``dt = softplus(dt + dt_bias)``; ``A = -exp(A_log)``; per
  head, from ``S_0 = 0`` at the start of every sequence, ``S_t = exp(dt_t A) S_{t-1} +
  dt_t x_t B_t^T``, ``y_t = S_t C_t + D x_t``; ``y <- RMSNorm_group(y * SiLU(z)) w`` over
  ``n_groups`` groups of the inner width; ``out = y W_out``.  ``ssd_per_token`` is the
  recurrence a token at a time; ``ssd_by_chunks`` evaluates the SAME recurrence a chunk
  of 64 tokens at a time, the pairs inside a chunk as one masked matrix of decays
  ``exp(sum of dt A over (s, t])`` and the state carried from chunk to chunk
  (``tests/`` tie the two); it is what the chip runs at 8,192 tokens.
* ``*``, attention without positions: ``num_attention_heads`` query heads, k and v at
  ``num_key_value_heads`` heads repeated to the query heads, causal softmax of
  ``q.k / sqrt(head_dim)`` a block of 256 query rows at a time, ``W_o``.
* ``E``, experts: ``s = sigmoid(h W_r)`` over all ``n_router_outputs``; the top
  ``num_experts_per_tok`` of ``s + b``; weights ``routed_scaling_factor s_e / sum_chosen
  s``; ``Shared(h) + sum over chosen AND held experts of w_e Expert_e(h)`` with
  ``Expert(h) = relu(h W_up)^2 W_down``, a dense loop over the held experts with a mask.
  What the absent experts would add is left out (the chip's share of a deployment).

Planted faults (``fault``), read beside the controls: ``no_D`` (the skip ``D x`` left out),
``no_gate`` (``y`` normalised without ``SiLU(z)``), ``relu`` (``relu`` where the model has
``relu^2``, in the experts and the shared expert).
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import reference
from benchmark.reference import _einsum
from benchmark.reference_kimi_linear import causal_softmax_attention, rms_norm

SSD_CHUNK = 64  # tokens a chunk of ``ssd_by_chunks``
MODEL_FAULTS = ("no_D", "no_gate", "relu")
_HI = jax.lax.Precision.HIGHEST


# -- weights -----------------------------------------------------------------

def widths(model: dict) -> dict:
    H, P = model["mamba_num_heads"], model["mamba_head_dim"]
    G, N = model["n_groups"], model["ssm_state_size"]
    return {"heads": H, "head_dim": P, "groups": G, "state": N, "inner": H * P,
            "conv": H * P + 2 * G * N}


def weight_shapes(model: dict) -> dict:
    d, V = model["hidden_size"], model["vocab_size"]
    w = widths(model)
    K = model["conv_kernel"]
    Hq, Hkv, D = model["num_attention_heads"], model["num_key_value_heads"], model["head_dim"]
    lo, hi = model["experts_held"]
    f, fs, R = (model["moe_intermediate_size"], model["moe_shared_expert_intermediate_size"],
                model["n_router_outputs"])
    layers = []
    for kind in model["hybrid_override_pattern"]:
        if kind == "M":
            mixer = {"in_proj": (d, 2 * w["inner"] + 2 * w["groups"] * w["state"] + w["heads"]),
                     "conv_w": (K, w["conv"]), "conv_b": (w["conv"],), "dt_bias": (w["heads"],),
                     "A_log": (w["heads"],), "D": (w["heads"],), "norm": (w["inner"],),
                     "out_proj": (w["inner"], d)}
        elif kind == "*":
            mixer = {"wq": (d, Hq, D), "wk": (d, Hkv, D), "wv": (d, Hkv, D), "wo": (Hq, D, d)}
        elif kind == "E":
            mixer = {"router": (d, R), "router_bias": (R,), "e_up": (hi - lo, d, f),
                     "e_down": (hi - lo, f, d), "shared": {"w_up": (d, fs), "w_down": (fs, d)}}
        else:
            raise ValueError(f"layer kind {kind!r}: the reference writes M, E and *")
        layers.append({"norm": (d,), "mixer": mixer})
    return {"embed": (V, d), "final_norm": (d,), "head": (d, V), "layers": layers}


def _fan_in(name: str, shape: tuple) -> int:
    if name == "wo":
        return shape[0] * shape[1]
    if name in ("embed", "e_up", "e_down"):
        return shape[1]
    return shape[0]


@functools.partial(jax.jit, static_argnames=("shapes_key", "time_step"))
def _make(key, *, shapes_key, time_step):
    low, high, floor = time_step
    out = []
    for i, (name, shape) in enumerate(shapes_key):
        k = jax.random.fold_in(key, i)
        if name.endswith("norm") or name == "D":
            w = jnp.ones(shape, jnp.float32)
        elif name == "A_log":  # exp(A_log) in 1..16
            w = jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
        elif name == "dt_bias":  # softplus(dt_bias) log-uniform in [low, high], floored
            dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32, np.log(low), np.log(high)))
            dt = jnp.maximum(dt, floor)
            w = dt + jnp.log(-jnp.expm1(-dt))
        elif name == "router_bias":  # the score-correction bias: seeded, never trained
            w = 0.02 * jax.random.normal(k, shape, jnp.float32)
        else:
            w = jax.random.normal(k, shape, jnp.float32) / np.sqrt(_fan_in(name, shape))
        out.append(w)
    return out


def make_weights(model: dict, seed: int) -> dict:
    """Float32 weights on the device, one jitted call from the seed: normal with
    variance 1/fan_in, norm scales and ``D`` 1, ``A_log``, ``dt_bias`` and the router's
    correction bias as ``_make`` says."""
    from benchmark.traffic import _key

    flat, treedef = jax.tree_util.tree_flatten_with_path(
        weight_shapes(model), is_leaf=lambda x: isinstance(x, tuple))
    names = tuple((path[-1].key, shape) for path, shape in flat)
    time_step = (model["time_step_min"], model["time_step_max"], model["time_step_floor"])
    return jax.tree_util.tree_unflatten(
        treedef, _make(_key(seed, 0), shapes_key=names, time_step=time_step))


# -- the scan ------------------------------------------------------------------

def _per_head(z, heads):  # [b, L, G, N] -> [b, L, H, N]: head h reads group h // (H / G)
    return jnp.repeat(z, heads // z.shape[2], axis=2)


def ssd_per_token(x, dt, A, B, C):
    """x: [b, L, H, P]; dt: [b, L, H]; A: [H]; B, C: [b, L, G, N].  y [b, L, H, P]
    without the skip, one token a step, in blocks of 64 steps recomputed on the way
    back."""
    b, L, H, P = x.shape
    B, C = _per_head(B, H), _per_head(C, H)

    def step(S, inputs):  # S: [b, H, P, N]
        x_t, dt_t, B_t, C_t = inputs
        S = jnp.exp(dt_t * A)[..., None, None] * S + (dt_t[..., None] * x_t)[..., None] * B_t[
            ..., None, :]
        return S, jnp.einsum("bhpn,bhn->bhp", S, C_t, precision=_HI)

    @jax.checkpoint
    def block(S, xs):
        return jax.lax.scan(step, S, xs)

    span = 64
    pad = (-L) % span
    xs = [jnp.pad(jnp.moveaxis(z, 1, 0), ((0, pad),) + ((0, 0),) * (z.ndim - 1))
          for z in (x, dt, B, C)]  # dt 0 leaves the state alone
    xs = [z.reshape((-1, span) + z.shape[1:]) for z in xs]
    _, y = jax.lax.scan(block, jnp.zeros((b, H, P, B.shape[-1]), x.dtype), xs)
    return jnp.moveaxis(y.reshape((-1,) + y.shape[2:])[:L], 0, 1)


def ssd_by_chunks(x, dt, A, B, C, chunk: int = SSD_CHUNK):
    """The same recurrence a chunk at a time.  Inside a chunk that starts from the state
    ``S_0``, with ``c_t`` the sum of ``dt A`` over the chunk's tokens up to t,

        y_t = sum_{s <= t} exp(c_t - c_s) (C_t . B_s) dt_s x_s + exp(c_t) S_0 C_t,
        S_C = exp(c_C) S_0 + sum_s exp(c_C - c_s) dt_s x_s B_s^T,

    the decays written out for every pair of the chunk."""
    b, L, H, P = x.shape
    B, C = _per_head(B, H), _per_head(C, H)
    pad = (-L) % chunk
    xs = [jnp.pad(jnp.moveaxis(z, 1, 0), ((0, pad),) + ((0, 0),) * (z.ndim - 1))
          for z in (x, dt, B, C)]
    xs = [z.reshape((-1, chunk) + z.shape[1:]) for z in xs]  # [n, Q, b, H, ...]
    t = jnp.arange(chunk)

    @jax.checkpoint
    def one(S, inputs):
        x_c, dt_c, B_c, C_c = inputs  # [Q, b, H, P], [Q, b, H], [Q, b, H, N]
        c = jnp.cumsum(dt_c * A, axis=0)  # [Q, b, H]
        diff = c[:, None] - c[None, :]  # [t, s, b, H]
        decay = jnp.exp(jnp.where((t[:, None] >= t[None, :])[..., None, None], diff, -jnp.inf))
        scores = jnp.einsum("tbhn,sbhn->tsbh", C_c, B_c, precision=_HI) * decay
        u = dt_c[..., None] * x_c
        y = (jnp.einsum("tsbh,sbhp->tbhp", scores, u, precision=_HI)
             + jnp.exp(c)[..., None] * jnp.einsum("tbhn,bhpn->tbhp", C_c, S, precision=_HI))
        S = (jnp.exp(c[-1])[..., None, None] * S
             + jnp.einsum("sbhp,sbhn->bhpn", u * jnp.exp(c[-1] - c)[..., None], B_c, precision=_HI))
        return S, y

    _, y = jax.lax.scan(one, jnp.zeros((b, H, P, B.shape[-1]), x.dtype), xs)
    return jnp.moveaxis(y.reshape((-1,) + y.shape[2:])[:L], 0, 1)


SSD_FORMS = {"per_token": ssd_per_token, "by_chunks": ssd_by_chunks}


# -- the sublayers ---------------------------------------------------------------

def causal_conv(x, w, bias):
    """x: [b, L, c]; w: [K, c]: y_t = sum_i w[i] x_{t-(K-1)+i} + bias."""
    K, L = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return sum(padded[:, i:i + L] * w[i] for i in range(K)) + bias


def mamba_mixer(h, w, model, precision, fault=None, ssd_form="by_chunks"):
    s = widths(model)
    H, P, G, N, inner = s["heads"], s["head_dim"], s["groups"], s["state"], s["inner"]
    b, L, _ = h.shape
    zxbcdt = _einsum("bld,de->ble", h, w["in_proj"], precision)
    z, xBC, dt = zxbcdt[..., :inner], zxbcdt[..., inner:inner + s["conv"]], zxbcdt[
        ..., inner + s["conv"]:]
    xBC = jax.nn.silu(causal_conv(xBC, w["conv_w"], w["conv_b"]))
    x = xBC[..., :inner].reshape(b, L, H, P)
    B = xBC[..., inner:inner + G * N].reshape(b, L, G, N)
    C = xBC[..., inner + G * N:].reshape(b, L, G, N)
    dt = jax.nn.softplus(dt + w["dt_bias"])
    y = SSD_FORMS[ssd_form](x, dt, -jnp.exp(w["A_log"]), B, C)
    if fault != "no_D":
        y = y + w["D"][:, None] * x
    y = y.reshape(b, L, inner)
    if fault != "no_gate":
        y = y * jax.nn.silu(z)
    parts = y.reshape(b, L, G, inner // G)
    parts = parts * jax.lax.rsqrt(jnp.mean(jnp.square(parts), -1, keepdims=True)
                                  + model["layer_norm_epsilon"])
    return _einsum("ble,ed->bld", parts.reshape(b, L, inner) * w["norm"], w["out_proj"],
                   precision)


def attention(h, w, model, precision):
    group = model["num_attention_heads"] // model["num_key_value_heads"]
    q = _einsum("bld,dhk->blhk", h, w["wq"], precision)
    k, v = (jnp.repeat(_einsum("bld,dhk->blhk", h, w[n], precision), group, axis=2)
            for n in ("wk", "wv"))
    return _einsum("blhk,hkd->bld", causal_softmax_attention(q, k, v, precision), w["wo"],
                   precision)


def _mlp(h, w_up, w_down, precision, act):
    return _einsum("blf,fd->bld", act(_einsum("bld,df->blf", h, w_up, precision)), w_down,
                   precision)


def expert_layer(h, w, model, precision, fault=None, held=None):
    """``held``: the range of experts whose part is added (default: the configuration's
    ``experts_held``).  Returns the layer's output."""
    lo, hi = model["experts_held"] if held is None else held
    first = model["experts_held"][0]  # w["e_*"][i] is expert first + i
    act = jax.nn.relu if fault == "relu" else (lambda a: jnp.square(jax.nn.relu(a)))
    scores = jax.nn.sigmoid(jnp.einsum("bld,de->ble", h, w["router"], precision=_HI))
    _, chosen = jax.lax.top_k(scores + w["router_bias"], model["num_experts_per_tok"])
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    if model["norm_topk_prob"]:
        picked = picked / (jnp.sum(picked, -1, keepdims=True) + 1e-20)
    picked = picked * model["routed_scaling_factor"]
    out = _mlp(h, w["shared"]["w_up"], w["shared"]["w_down"], precision, act)

    @jax.checkpoint
    def add_expert(out, x):  # every token through expert e, weighted 0 where e was not chosen
        e, w_up, w_down = x
        weight = jnp.sum(jnp.where(chosen == e, picked, 0.0), -1)
        return out + weight[..., None] * _mlp(h, w_up, w_down, precision, act), None

    out, _ = jax.lax.scan(add_expert, out, (jnp.arange(lo, hi), *(
        w[name][lo - first:hi - first] for name in ("e_up", "e_down"))))
    return out


def block(x, w, kind, model, precision, fault=None, ssd_form="by_chunks"):
    h = rms_norm(x, w["norm"], model["norm_eps"])
    if kind == "M":
        return x + mamba_mixer(h, w["mixer"], model, precision, fault, ssd_form)
    if kind == "*":
        return x + attention(h, w["mixer"], model, precision)
    return x + expert_layer(h, w["mixer"], model, precision, fault)


def loss_fn(weights, tokens, targets, row_mask, model, precision, fault=None,
            ssd_form="by_chunks"):
    """Mean next-token cross-entropy over the tokens of the rows in ``row_mask``."""
    x = weights["embed"][tokens]
    for kind, w in zip(model["hybrid_override_pattern"], weights["layers"]):
        x = jax.checkpoint(functools.partial(block, kind=kind, model=model, precision=precision,
                                             fault=fault, ssd_form=ssd_form))(x, w)
    x = rms_norm(x, weights["final_norm"], model["norm_eps"])
    logits = _einsum("bld,dv->blv", x, weights["head"], precision)
    logp = jax.nn.log_softmax(logits, axis=-1)
    per = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    mask = row_mask[:, None] * jnp.ones_like(per)
    return jnp.sum(per * mask) / jnp.maximum(jnp.sum(mask), 1.0)


# -- SGD and the round (as benchmark/reference.py does them) -------------------

MODEL_KEYS = ("hidden_size", "num_hidden_layers", "vocab_size", "norm_eps", "layer_norm_epsilon",
              "hybrid_override_pattern", "mamba_num_heads", "mamba_head_dim", "n_groups",
              "ssm_state_size", "conv_kernel", "num_attention_heads", "num_key_value_heads",
              "head_dim", "moe_intermediate_size", "moe_shared_expert_intermediate_size",
              "n_router_outputs", "experts_held", "num_experts_per_tok", "norm_topk_prob",
              "routed_scaling_factor")


def model_key(model: dict) -> str:
    """The shape- and equation-deciding entries of a configuration file, hashable for jit
    (as JSON text)."""
    return json.dumps({k: model[k] for k in MODEL_KEYS}, sort_keys=True)


@functools.partial(jax.jit, static_argnames=("model_key", "precision", "fault"),
                   donate_argnums=(0,))
def _sgd_step(weights, tokens, targets, row_mask, lr, *, model_key, precision, fault):
    model = json.loads(model_key)
    if fault == "half_batch":  # half of the batch left out, the mean over the rest
        row_mask = row_mask * (jnp.arange(row_mask.shape[0]) < row_mask.shape[0] // 2)
    planted = fault if fault in MODEL_FAULTS else None
    loss, grads = jax.value_and_grad(loss_fn)(weights, tokens, targets, row_mask, model,
                                              precision, planted)
    if fault == "state_unchanged":
        return weights, loss
    return jax.tree_util.tree_map(lambda p, g: p - lr * g, weights, grads), loss


def local_sgd(weights, x, y, order, batch, lr, model, precision="highest", fault=None):
    """Plain SGD over the rows of ``x``/``y`` in ``order`` (-1 is padding, left out of
    the mean), ``batch`` rows a step.  Returns the new weights and the mean loss over
    the rows fed.  ``weights`` is consumed."""
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
    """One FedAvg round, as ``reference.fedavg_round``: every client in ``clients`` trains
    from ``global_w``; the new global is the mean weighted by rows."""
    clients = list(range(len(shards))) if clients is None else list(clients)
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
