"""What the five config-driven decoders share (``kimi_linear.py``,
``smallthinker.py``, ``glm4_moe_lite.py``, ``sdar_moe.py``, ``nemotron_h.py``): the
grouped-query mixer, the expert layer — routing, the grouped products over the
experts held here, gated or not, the round's counters — RMSNorm, the dense MLP,
the causal depthwise convolution of the linear mixers (KDA's and Mamba-2's), the
initialiser and the LM shell (embedding -> blocks under per-layer ``remat`` ->
final RMSNorm -> untied head, and after the blocks the multi-token-prediction
module of a model that has one: :class:`PredictionModule`).

A model's config dataclass gives the shared parts these fields:
``hidden_size``, ``num_hidden_layers``, ``vocab_size``, ``rms_norm_eps``,
``moe_intermediate_size``, ``n_routed_experts``, ``experts_held`` ([lo, hi): the
experts this process holds), ``num_experts_per_token``, ``num_shared_experts``,
``dtype``, ``remat``; a model whose expert layer routes on its own input
(``kimi_linear``, ``nemotron_h``: sigmoid scores + correction bias) also gives
``routed_scaling_factor`` and ``moe_renormalize``; a model whose experts are not
gated (``nemotron_h``: ``activation(x W_up) W_down``) gives ``moe_gated`` False, and
one whose shared expert has a width of its own ``shared_expert_intermediate_size``;
a model with a
multi-token-prediction module gives ``num_nextn_predict_layers`` (1) and
``mtp_loss_weight``.

Expert layer (:class:`ExpertShare`): the router scores all
``n_routed_experts`` in float32; this process adds the part of the experts it
holds only (beside a shared expert where the model has one).  No assignment is
dropped: the assignments that land here are sorted by expert and worked off in
blocks through grouped products (``jax.lax.ragged_dot``); the number of blocks
steps up with theirs (:func:`grouped_experts`, :func:`expert_blocks`).  When
applied with the ``counters`` collection mutable and ``train=True``, every
expert layer sows the round's counters (``COUNTERS``) there; the packed round
sums them.
"""

from __future__ import annotations

import json
import os
from typing import Any, Callable, ClassVar, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax

# what an expert layer sows a step; the packed round returns their sums
COUNTERS = ("moe.assignments_local", "moe.assignments_total", "moe.expert_load_max",
            "moe.expert_load_mean", "moe.assignments_dropped")

# what a prediction module sows a step beside them, and what the engine's loss
# fills in for a module that names it (``ml/engine/train.py:build_loss_fn``)
MTP_COUNTERS = ("lm.loss_main", "mtp.loss", "mtp.positions")

# what a block's recomputation keeps from its first forward: the results of the
# token mixers' Pallas kernels, by the names their ``fwd`` rules place
# (``ops/kept.py``).  A kernel's output is cheap to keep and dear to rebuild; its
# inputs (norm, projections, rotation, convolutions, gates) are rebuilt
KEPT = ("flash_fwd.out", "flash_fwd.lse", "kda_fwd.o", "kda_fwd.states", "bd_flash_fwd.out",
        "bd_flash_fwd.lse", "ssd_fwd.y", "ssd_fwd.states")


def load_config(model_config) -> dict:
    """``model_config`` as ``arguments.py`` validates it: a dict, or the path
    of a JSON file that holds one."""
    if isinstance(model_config, (str, os.PathLike)):
        with open(model_config) as f:
            return json.load(f)
    return dict(model_config)


def compute_dtype(cfg: dict):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        cfg.get("compute_dtype", "float32")]


def held_range(cfg: dict, total: int) -> Tuple[int, int]:
    """``experts_held`` of a configuration, checked: a range [lo, hi) inside the
    router's ``total`` outputs (default: all of them)."""
    held = tuple(int(e) for e in cfg.get("experts_held", (0, total)))
    if not (len(held) == 2 and 0 <= held[0] < held[1] <= total):
        raise ValueError(f"experts_held must be a range [lo, hi) inside 0..{total}: {held}")
    return held


def _normal(fan_in: int):
    return nn.initializers.normal(stddev=fan_in ** -0.5)


def rms_norm(x, scale, eps):
    """Float32 statistics, the input's dtype out."""
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps) * scale).astype(x.dtype)


def swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def relu2(x):
    """Squared ReLU (``mlp_hidden_act: relu2``)."""
    return jnp.square(jax.nn.relu(x))


def causal_conv(x, w, bias=None):
    """Depthwise causal convolution over time.  x: [B, L, ...]; w: [K, ...]:
    ``y_t = sum_i w[i] x_{t-(K-1)+i}`` (zeros before the sequence), plus ``bias``
    [...] where given."""
    K, L = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (K - 1, 0)) + ((0, 0),) * (x.ndim - 2))
    y = sum(padded[:, i:i + L] * w[i].astype(x.dtype) for i in range(K))
    return y if bias is None else y + bias.astype(x.dtype)


class DenseMLP(nn.Module):
    """``activation(h W_gate) * (h W_up)) W_down``, or where not ``gated``
    ``activation(h W_up) W_down``."""
    cfg: Any
    width: int
    activation: Callable = jax.nn.silu
    gated: bool = True

    @nn.compact
    def __call__(self, h):
        d, f, dt = self.cfg.hidden_size, self.width, self.cfg.dtype
        shapes = (("w_gate", (d, f), d),) if self.gated else ()
        w = {n: self.param(n, _normal(fi), s, jnp.float32).astype(dt) for n, s, fi in (
            shapes + (("w_up", (d, f), d), ("w_down", (f, d), f)))}
        if not self.gated:
            return self.activation(h @ w["w_up"]) @ w["w_down"]
        return (self.activation(h @ w["w_gate"]) * (h @ w["w_up"])) @ w["w_down"]



class GQAMixer(nn.Module):
    """Grouped-query attention on ``a`` [B, L, d] (``smallthinker``, ``sdar_moe``, ``nemotron_h``):
    ``cfg.num_attention_heads`` query heads over ``cfg.num_key_value_heads`` key/value
    heads of ``cfg.head_dim`` (``cfg`` any config with those, ``hidden_size``,
    ``rope_theta``, ``rms_norm_eps`` and ``dtype``); ``window`` keys a query sees
    (None: every earlier one), ``rotate``: rotary positions on q and k.  ``qk_norm``:
    q and k pass a per-head RMSNorm (a float32 scale of ``head_dim``,
    ``rms_norm_eps``) before the rotation.  ``block_diffusion``: ``a`` is
    ``[a_noised ; a_clean]`` (2L positions, each half at positions 0..L-1) under the
    block-diffusion mask of that block length (``ops.flash_attention.attention``);
    called with ``noised_only``, the queries are the noised half's alone (k and v
    still over both halves) and the output is [B, L, d]: a last layer's, whose clean
    half nothing reads but its keys and values."""
    cfg: Any
    window: Optional[int]
    rotate: bool
    block_diffusion: Optional[int] = None
    qk_norm: bool = False

    @nn.compact
    def __call__(self, a, noised_only: bool = False):
        from ..ops.flash_attention import attention
        from .transformer import rope

        cfg = self.cfg
        d, D, dt = cfg.hidden_size, cfg.head_dim, cfg.dtype
        Hq, Hkv = cfg.num_attention_heads, cfg.num_key_value_heads

        def param(name, shape, fan_in):
            return self.param(name, _normal(fan_in), shape, jnp.float32).astype(dt)

        q = jnp.einsum("bld,dhk->blhk", a[:, :a.shape[1] // 2] if noised_only else a,
                       param("wq", (d, Hq, D), d))
        k = jnp.einsum("bld,dhk->blhk", a, param("wk", (d, Hkv, D), d))
        v = jnp.einsum("bld,dhk->blhk", a, param("wv", (d, Hkv, D), d))
        if self.qk_norm:
            q, k = (rms_norm(x, self.param(name, nn.initializers.ones, (D,), jnp.float32),
                             cfg.rms_norm_eps) for x, name in ((q, "q_norm"), (k, "k_norm")))
        if self.rotate:
            if self.block_diffusion is None:
                positions = jnp.broadcast_to(jnp.arange(a.shape[1]), a.shape[:2])
            else:  # both halves at 0..L-1
                half = jnp.arange(a.shape[1] // 2)
                positions = jnp.broadcast_to(jnp.concatenate([half, half]), a.shape[:2])
            q, k = (rope(x.astype(jnp.float32), positions[:, :x.shape[1]] if noised_only
                         else positions, cfg.rope_theta).astype(dt) for x in (q, k))
        if self.block_diffusion is None:
            o = attention(q, k, v, causal=True, window=self.window)
        else:
            o = attention(q, k, v, block_diffusion=self.block_diffusion)
        return jnp.einsum("blhk,hkd->bld", o, param("wo", (Hq, D, d), Hq * D))

def route(scores, bias, top_k: int, scaling: float = 1.0, renormalize: bool = False,
          softmax_chosen: bool = False):
    """scores: [T, E] float32.  The top ``top_k`` experts of score + bias per
    token (``bias`` None: of the score), and their weights.  Two forms:
    sigmoid scores in, the chosen scores (without the bias) out, renormalised
    over ALL chosen where asked, times ``scaling``; or, ``softmax_chosen``, the
    router's logits in and the softmax over the chosen logits out (they sum
    to 1)."""
    _, chosen = jax.lax.top_k(scores if bias is None else scores + bias, top_k)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    if softmax_chosen:
        return chosen, jax.nn.softmax(picked, axis=-1)
    if renormalize:
        picked = picked / (jnp.sum(picked, -1, keepdims=True) + 1e-20)
    return chosen, picked * scaling


EXPERT_BLOCKS = 8  # a block of the expert layer holds at least an eighth of a step's assignments


def expert_blocks(total: int, held: int, routed: int) -> Tuple[int, Tuple[int, ...], bool]:
    """(rows a block, blocks each tier runs, whether a tier's work is padded to
    its blocks) of an expert layer that holds ``held`` of ``routed`` experts and
    sees ``total`` assignments a step.  The even share of the assignments that
    land here is ``held / routed`` of them.

    A block is the larger of an eighth of the assignments and three times the
    even share; as many blocks run as hold every assignment that landed here,
    in tiers of 1, 2 and all of them, so the even share lies at a third of the
    first tier or below and the last tier holds whatever the router does.  8 of
    256: blocks of T k / 8 (four times the even share) in tiers of 1 / 2 / 8; 16
    of 64: blocks of 3 T k / 4 in tiers of 1 / 2.  Why three times: under
    random routers a layer's share is far from even (16 of 64, 16 seeds on the
    v5e, PR 31: a layer's mean share 10-47 %, a sequence's up to 59 %, over 50 %
    in 2 of the 16 seeds, never over 75 %), and a layer-step that leaves the
    first tier runs a second block: with blocks of twice the even share those
    two seeds' rounds took 1.0 and 2.8 % longer than the others'.

    Where the share sets the block, a tier's grouped products are padded to its
    rows (``padded``): a grouped product's time goes by the rows that belong to a
    group, not by its operand's rows, so without the padding a layer's time
    follows its router — at 16 of 64 the seeds' shares of 20-30 % were rounds of
    13.0-13.5 s, a spread of 1.6 % (v5e, PR 31) — and with it every layer-step
    inside a tier is the same work.  Where the floor of an eighth sets it, a
    block is four or more times the even share: padding would multiply the
    layer's work to even out a difference that is small beside it."""
    floor = -(-total // EXPERT_BLOCKS)
    base = min(total, max(floor, -(-3 * total * held // routed)))
    n_blocks = -(-total // base)
    return base, tuple(sorted({1, min(2, n_blocks), n_blocks})), base > floor


# An expert layer whose block is at least this share of a step's assignments moves its
# rows by gathers alone (:func:`grouped_experts`, "Two forms").  Readings on the v5e
# (PR 34; the parent's traced rounds, then the layer alone in both forms).  A row
# scattered: 0.108 us (``smallthinker``: 73,728 rows of 2,560 bfloat16 a call, 8.0 ms),
# 0.092 us (``glm47-flash``: 12,288 rows of 2,048, 1.13 ms), 0.164 us (``kimi-linear``:
# 8,192 of 2,304).  A row gathered by ``h[token]``, whose source XLA keeps in VMEM:
# 0.0079 us (0.58 ms) and 0.0056-0.011 us.  The gather form moves T k rows whatever
# the block, and reads them from the block's result: 0.0063 us a row where that fits
# in VMEM (``glm47-flash``'s 50 MB), 0.043 us from HBM (``smallthinker``'s 377 MB), and
# 0.008 us a row for the sum.  Scatter form -> gather form, the expert layers of a
# traced round a layer-step: block / assignments 0.75 (16 of 64) 68.6 -> 61.0 ms, 0.375
# (8 of 64) 14.7 -> 13.0 ms; the layer alone at 0.125 (8 of 256) 4.90 -> 5.22 ms: the
# forms cross between an eighth and three eighths
GATHERED_SHARE = 0.25


def _over_rows(mask, like):
    return mask.reshape(mask.shape + (1,) * (like.ndim - 1))


@jax.custom_vjp
def spread_rows(h, token, live, idx, valid):
    """[T, ...] -> [R, ...]: row s is ``h[token[s]]`` where ``live[s]``, zeros
    elsewhere.  ``idx``, ``valid`` ([k, T]) say the same thing from the tokens'
    side — ``valid[j, t]``: the j-th of token t's rows is live row ``idx[j, t]`` —
    and serve the way back, which is :func:`unpermute_sum` of the cotangent: a
    gather where XLA's transpose of ``h[token]`` is a scatter-add."""
    return jnp.where(_over_rows(live, h), h[token], 0)


@jax.custom_vjp
def unpermute_sum(rows, token, live, idx, valid):
    """[R, ...] -> [T, ...]: ``out[t]`` is the sum over j of ``rows[idx[j, t]]``
    where ``valid[j, t]``, formed in float32.  The transpose of
    :func:`spread_rows` under the same four index arrays, and the other way
    round.  k slabs of T rows and not T groups of k: a sum over the leading
    axis adds whole tiles."""
    picked = jnp.where(_over_rows(valid, rows), rows[idx], 0)
    return jnp.sum(picked, axis=0, dtype=jnp.float32).astype(rows.dtype)


def _transposes(one, other):
    one.defvjp(lambda x, *index: (one(x, *index), index),
               lambda index, g: (other(g, *index),) + (None,) * len(index))


_transposes(spread_rows, unpermute_sum)
_transposes(unpermute_sum, spread_rows)


def _rows_of(pos, start, rows: int, n_local):
    """(``idx``, ``valid``) of :func:`spread_rows` for the sorted rows
    [start, start + rows) of which those before ``n_local`` are live; ``pos``:
    [k, T], each assignment's place among the sorted rows."""
    valid = (pos >= start) & (pos < jnp.minimum(start + rows, n_local))
    return jnp.clip(pos - start, 0, rows - 1), valid


def grouped_experts(h, chosen, weights, held: Tuple[int, int], w_gate, w_up, w_down,
                    n_routed: int, activation: Callable = jax.nn.silu):
    """Sum over the chosen experts that are held here of weight x gated expert
    (``activation(x W_gate) * (x W_up)) W_down``), or, with ``w_gate`` None, of
    weight x expert ``activation(x W_up) W_down`` (two grouped products, not three).
    h: [T, d]; chosen, weights: [T, k]; w_*: [E_held, ...]; ``n_routed``: the
    router's width.  Returns ([T, d], counters).

    The T*k assignments are sorted by expert, those of absent experts last.
    The local ones are then worked off in blocks (gather the tokens, three
    grouped products over the block's rows of each expert, weight, add up each
    token's rows), each block recomputed on the way back; as many blocks run as hold
    every local assignment, in the tiers :func:`expert_blocks` gives for the
    share held (``lax.switch``: a loop with a traced trip count has no reverse
    mode, and a ``lax.cond`` a block inside one ``lax.scan`` kept 2.5 GiB more
    live).  So nothing is dropped whatever the routing, work steps up with what
    lands here, and memory is a block's.  Why a block is no smaller: with
    blocks of a sixteenth at 8 of 256 experts, seeds whose router sent one
    layer more than 6.25 % of its assignments here ran two blocks there and
    their rounds took 1.6 % longer than the others' (v5e, PR 27); and a block
    costs about 6 ms on the way back whatever its rows (the scatter-add that
    transposes its gather; v5e, PR 31).

    Two forms, chosen from (T k, held, routed) alone.  A row costs ten times as
    much to scatter-add as to gather (``GATHERED_SHARE``), the scatter form moves
    a block's rows and the gather form T k.  Where a block is ``GATHERED_SHARE`` of
    the assignments or more (16 of 64, 8 of 64), the inverse of the sort says
    where each token's k rows sit among the sorted ones, the combine is a gather
    of them and a float32 sum over k (:func:`unpermute_sum`), and the way back
    of the tokens' gather and of the weights' lookup is the same gather of the
    cotangent (:func:`spread_rows`); the experts' rows are counted by a compare
    and a sum: no scatter-add is left in the layer.  Below it (8 of 256: one
    block is an eighth) the combine is ``zeros.at[token].add``, XLA transposes
    the gathers and ``bincount`` counts, as before."""
    from ..core import obs

    T, k = chosen.shape
    d = h.shape[-1]
    lo, hi = held
    n_held, total = hi - lo, T * k
    base, tiers, padded = expert_blocks(total, n_held, n_routed)
    n_blocks = tiers[-1]
    gathered = base >= GATHERED_SHARE * total
    obs.gauge_set("moe.combine_gathered", int(gathered))
    flat = chosen.reshape(-1)
    with jax.named_scope("lm.moe.dispatch"):
        local = (flat >= lo) & (flat < hi)
        key = jnp.where(local, flat - lo, n_held)
        by_expert = jnp.argsort(key, stable=True)
        order = jnp.pad(by_expert, (0, n_blocks * base - total))
        if gathered:
            # the inverse of the sort: where assignment (t, j) sits among the sorted rows
            place = jnp.argsort(by_expert)
            pos = place.reshape(T, k).T
            # what ``bincount`` counts, without its scatter-add of T k integers
            sizes = jnp.sum(key[:, None] == jnp.arange(n_held), axis=0, dtype=jnp.int32)
        else:
            sizes = jnp.bincount(key, length=n_held + 1)[:n_held].astype(jnp.int32)
        ends = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(sizes)])
        n_local = ends[-1]
        tier = jnp.sum(n_local > base * jnp.asarray(tiers[:-1], jnp.int32))
        if padded:
            # the tier's other rows join the last expert's group: zeros in, zeros
            # out and no gradient, and the products' work is the tier's rows
            ends = ends.at[-1].set(base * jnp.asarray(tiers, jnp.int32)[tier])
    flat_weights = weights.reshape(-1)

    @jax.checkpoint
    def one_block(i):
        with jax.named_scope("lm.moe.dispatch"):
            start = i * base
            rows = jax.lax.dynamic_slice(order, (start,), (base,))
            token = rows // k
            live = start + jnp.arange(base) < n_local
            inside = jnp.clip(ends, start, start + base)
            block_sizes = inside[1:] - inside[:-1]  # this block's rows of each expert
            # rows past the local assignments belong to no expert: what a grouped
            # product leaves in rows outside its groups is undefined, forward and
            # backward, so they are cut off on the way in as on the way out
            if gathered:
                x = spread_rows(h, token, live, *_rows_of(pos, start, base, n_local))
            else:
                x = jnp.where(live[:, None], h[token], 0)
        with jax.named_scope("lm.moe.experts"):
            if w_gate is None:
                hidden = activation(jax.lax.ragged_dot(x, w_up, block_sizes))
            else:
                gate = jax.lax.ragged_dot(x, w_gate, block_sizes)
                up = jax.lax.ragged_dot(x, w_up, block_sizes)
                hidden = activation(gate) * up
            y = jax.lax.ragged_dot(hidden, w_down, block_sizes)
        with jax.named_scope("lm.moe.combine"):
            if gathered:
                w = spread_rows(flat_weights, rows, live,
                                *_rows_of(place[None], start, base, n_local)).astype(y.dtype)
            else:
                w = jnp.where(live, flat_weights[rows], 0.0).astype(y.dtype)
            return token, jnp.where(live[:, None], y * w[:, None], 0.0)

    def run(blocks):
        def branch():
            token, y = jax.lax.map(one_block, jnp.arange(blocks))
            with jax.named_scope("lm.moe.combine"):
                if gathered:
                    rows = blocks * base
                    return unpermute_sum(y.reshape(-1, d), token.reshape(-1),
                                         jnp.arange(rows) < n_local, *_rows_of(pos, 0, rows, n_local))
                return jnp.zeros_like(h).at[token.reshape(-1)].add(y.reshape(-1, d))
        return branch

    out = jax.lax.switch(tier, [run(b) for b in tiers])
    processed = jnp.minimum(n_local, base * jnp.asarray(tiers, jnp.int32)[tier])
    counters = {
        "moe.assignments_local": n_local, "moe.assignments_total": total,
        "moe.expert_load_max": jnp.max(sizes), "moe.expert_load_mean": n_local / n_held,
        "moe.assignments_dropped": n_local - processed}
    return out, {n: jnp.asarray(v, jnp.float32) for n, v in counters.items()}


class ExpertShare(nn.Module):
    """The part of an expert layer that the experts held here give (plus the
    shared expert, which every process computes alike).  ``routing``: the
    (chosen, weights) a block decided elsewhere (``smallthinker``: before the
    attention, on the attention's input); left ``None`` the layer routes on
    the tensor it transforms, by sigmoid scores and a correction bias.  The
    experts and the shared expert are gated unless ``cfg.moe_gated`` is False
    (``activation(x W_up) W_down``; gauge ``moe.gated``, 1 / 0)."""
    cfg: Any
    activation: Callable = jax.nn.silu

    @nn.compact
    def __call__(self, h, train: bool = False, routing=None):
        from ..core import obs

        cfg = self.cfg
        d, f, dt = cfg.hidden_size, cfg.moe_intermediate_size, cfg.dtype
        lo, hi = cfg.experts_held
        obs.gauge_set("moe.experts_held", hi - lo)
        obs.gauge_set("moe.experts_total", cfg.n_routed_experts)
        flat = h.reshape(-1, d)
        if routing is None:
            with jax.named_scope("lm.moe.route"):
                w_r = self.param("router", _normal(d), (d, cfg.n_routed_experts), jnp.float32)
                bias = self.param("router_bias", nn.initializers.zeros,
                                  (cfg.n_routed_experts,), jnp.float32)
                scores = jax.nn.sigmoid(jnp.matmul(
                    flat.astype(jnp.float32), w_r, precision=jax.lax.Precision.HIGHEST))
                routing = route(scores, bias, cfg.num_experts_per_token,
                                cfg.routed_scaling_factor, cfg.moe_renormalize)
        chosen, weights = routing
        gated = getattr(cfg, "moe_gated", True)
        obs.gauge_set("moe.gated", int(gated))
        shapes = (("e_gate", (d, f), d),) if gated else ()
        experts = {n: self.param(n, _normal(fi), (hi - lo,) + s, jnp.float32).astype(dt)
                   for n, s, fi in shapes + (("e_up", (d, f), d), ("e_down", (f, d), f))}
        out, counters = grouped_experts(
            flat, chosen, weights, (lo, hi), experts.get("e_gate"), experts["e_up"],
            experts["e_down"], cfg.n_routed_experts, self.activation)
        if train:
            for name, value in counters.items():
                self.sow("counters", name, value, reduce_fn=jnp.add,
                         init_fn=lambda: jnp.zeros((), jnp.float32))
        if cfg.num_shared_experts:
            width = getattr(cfg, "shared_expert_intermediate_size", 0) or f * cfg.num_shared_experts
            with jax.named_scope("lm.moe.shared"):
                out = out + DenseMLP(cfg, width, self.activation, gated, name="shared")(flat)
        return out.reshape(h.shape)


class PredictionModule(nn.Module):
    """One multi-token-prediction module in the form of the DeepSeek-V3 report
    (arXiv:2412.19437, section 2.2), trained for the token after next.  With
    ``x`` the last main block's output (NOT the final norm's), ``Emb`` the main
    embedding and ``W_head`` the main head, both handed in and so shared:

        u_i = [RMSNorm_h(x_i) ; RMSNorm_e(Emb(t_{i+1}))] W_eh      (``lm.mtp.merge``)
        y   = one whole block of the model's own kind on u          (its own weights)
        logits'_i = RMSNorm_m(y_i) W_head  predicts  t_{i+2}        (``lm.mtp.head``)

    ``t_{i+1}`` is the row's own next token, the last position's its label.  The
    module runs over all L positions (causal, so the last reaches no other) and
    the last one, which has no ``t_{i+2}``, is masked out of the loss:
    ``L_mtp`` = mean cross-entropy over the L - 1 positions of the rows in the
    batch's mask.  The head and its loss are one ``jax.checkpoint``: the module's
    float32 logits live inside its forward and again inside its backward, never
    beside the main model's.

    Sows ``mtp_loss_weight`` x ``L_mtp`` into the collection ``losses`` (the
    engine's loss adds what a module sows there) and ``mtp.loss`` (unweighted),
    ``mtp.positions`` into ``counters``."""
    cfg: Any
    block_cls: Any  # as ``DecoderLM`` wraps it: under the per-layer remat where ``cfg.remat``

    @nn.compact
    def __call__(self, x, tokens, embed, head, targets=None, train: bool = False):
        from ..core import obs

        cfg = self.cfg
        d, dt, eps = cfg.hidden_size, cfg.dtype, cfg.rms_norm_eps
        obs.gauge_set("mtp.modules", cfg.num_nextn_predict_layers)
        obs.gauge_set("mtp.loss_weight", cfg.mtp_loss_weight)

        def scale(name):
            return self.param(name, nn.initializers.ones, (d,), jnp.float32)

        labels, row_mask = targets if targets is not None else (tokens, None)
        with jax.named_scope("lm.mtp.merge"):
            nxt = jnp.concatenate([tokens[:, 1:], labels[:, -1:]], axis=1)
            w_eh = self.param("w_eh", _normal(2 * d), (2 * d, d), jnp.float32).astype(dt)
            u = jnp.concatenate([rms_norm(x, scale("h_norm"), eps),
                                 rms_norm(embed.astype(dt)[nxt], scale("e_norm"), eps)], -1) @ w_eh
        y = self.block_cls(cfg, cfg.num_hidden_layers, name="block")(u, train)
        out_scale = scale("norm")
        if targets is None:  # ``init``: the parameters exist, nothing is trained
            return
        # position i is trained on t_{i+2} = labels[i + 1]; the last has none
        after_next = jnp.concatenate([labels[:, 1:], labels[:, -1:]], axis=1)
        has_target = (jnp.arange(labels.shape[1]) < labels.shape[1] - 1).astype(jnp.float32)
        mask = row_mask.astype(jnp.float32)[:, None] * has_target

        @jax.checkpoint
        def head_loss(y, out_scale, head, after_next, mask):
            logits = rms_norm(y, out_scale, eps) @ head.astype(dt)
            per = optax.softmax_cross_entropy_with_integer_labels(
                logits.astype(jnp.float32), after_next)
            return jnp.sum(per * mask), jnp.sum(mask)

        with jax.named_scope("lm.mtp.head"):
            total, positions = head_loss(y, out_scale, head, after_next, mask)
            loss = total / jnp.maximum(positions, 1.0)
        zero = lambda: jnp.zeros((), jnp.float32)  # noqa: E731
        self.sow("losses", "mtp", cfg.mtp_loss_weight * loss, reduce_fn=jnp.add, init_fn=zero)
        self.sow("counters", "mtp.loss", loss, reduce_fn=jnp.add, init_fn=zero)
        self.sow("counters", "mtp.positions", positions, reduce_fn=jnp.add, init_fn=zero)


class DecoderLM(nn.Module):
    """The LM shell: token embedding, ``cfg.num_hidden_layers`` blocks
    (``block_cls(cfg, index, name="layer<i>")(x, train)``, each recomputed in
    the backward pass where ``cfg.remat``, all but what ``KEPT`` names), final
    RMSNorm, untied head.  A model subclasses it and names its block.

    Where ``cfg.num_nextn_predict_layers`` is 1 a :class:`PredictionModule`
    (``mtp``: one more block of the same kind under the same remat and policy)
    reads the last block's output when ``train``; the model still returns the
    main logits alone, and the module's weighted loss reaches the step through
    the ``losses`` collection.  Such a model ``takes_targets``: a training step
    hands it ``targets=(labels, row mask)``, which the engine's loss does
    (``ml/engine/train.py:build_loss_fn``).  With ``train=False`` the module
    is not run."""
    cfg: Any
    # the packed round asks for these sums beside the loss (ml/engine/packed.py)
    round_counters: Tuple[str, ...] = COUNTERS

    block_cls: ClassVar[Any] = None
    # no parameter's shape depends on the length: an eager ``init`` at a round's
    # 8,192 or 16,384 tokens would run (and compile, op by op) the whole forward
    # pass for shapes alone, so ``init`` looks at this many tokens
    init_length: ClassVar[int] = 64

    @property
    def takes_targets(self) -> bool:
        return bool(getattr(self.cfg, "num_nextn_predict_layers", 0))

    @nn.compact
    def __call__(self, tokens, train: bool = False, targets=None):
        cfg = self.cfg
        if self.is_initializing():
            tokens = tokens[:, :self.init_length]
        embed = self.param("embed", _normal(cfg.hidden_size),
                           (cfg.vocab_size, cfg.hidden_size), jnp.float32)
        with jax.named_scope("lm.embed"):
            x = embed.astype(cfg.dtype)[tokens]
        block_cls = (nn.remat(self.block_cls, static_argnums=(2,),
                              policy=jax.checkpoint_policies.save_only_these_names(*KEPT))
                     if cfg.remat else self.block_cls)
        for i in range(cfg.num_hidden_layers):
            x = block_cls(cfg, i, name=f"layer{i}")(x, train)
        scale = self.param("final_norm", nn.initializers.ones, (cfg.hidden_size,), jnp.float32)
        head = self.param("head", _normal(cfg.hidden_size),
                          (cfg.hidden_size, cfg.vocab_size), jnp.float32)
        if self.takes_targets and (train or self.is_initializing()):
            if train and targets is None:
                raise ValueError(
                    f"{type(self).__name__} trains a multi-token-prediction module: a training "
                    "step hands it targets=(labels, row mask), as ml/engine/train.py's "
                    "build_loss_fn does")
            with jax.named_scope("lm.mtp"):
                PredictionModule(cfg, block_cls, name="mtp")(x, tokens, embed, head, targets, train)
        with jax.named_scope("lm.head"):
            return rms_norm(x, scale, cfg.rms_norm_eps) @ head.astype(cfg.dtype)
