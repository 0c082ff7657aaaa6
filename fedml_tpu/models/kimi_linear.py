"""Config-driven hybrid decoder: a token mixer (KDA linear attention or NoPE
latent attention) x a channel mixer (dense SwiGLU or a share of sigmoid-routed
experts) x a layer pattern, all from a dict whose keys are those of the
published ``config.json`` of ``model_type: kimi_linear``
(moonshotai/Kimi-Linear-48B-A3B-Instruct), plus ``experts_held``.

Pre-norm residual block: ``x <- x + Mixer(RMSNorm(x))``, ``x <- x +
FFN(RMSNorm(x))``; final RMSNorm; untied output head.

* KDA mixer (:class:`KDAMixer`): q, k, v through a causal depthwise convolution
  of ``short_conv_kernel_size`` and SiLU, q and k L2-normalised per head, a
  per-channel log-gate ``g = -exp(A_log) softplus(W_f_up W_f_down x + dt_bias)``,
  ``beta = sigmoid(W_beta x)``, the gated delta rule (``ops/kda.py``, chunkwise),
  then ``W_o(RMSNorm_head(o) * sigmoid(W_g_up W_g_down x))``.
* MLA mixer (:class:`MLAMixer`), ``mla_use_nope``: keys and values up-projected
  from a normalised latent of ``kv_lora_rank``, a shared ``qk_rope_head_dim``
  key part that is NOT rotated, full-rank queries; q and k of width
  ``qk_nope_head_dim + qk_rope_head_dim`` and v of ``v_head_dim`` go through
  ``ops.flash_attention.attention``.
* Expert layer (:class:`ExpertShare`): sigmoid scores over all
  ``n_routed_experts`` in float32, the top ``num_experts_per_token`` of score +
  correction bias, weights renormalised over all chosen and scaled by
  ``routed_scaling_factor``; this process holds the experts ``experts_held =
  [lo, hi)`` and adds their part only, beside the shared expert.  No
  assignment is dropped: the assignments that land here are sorted by expert
  and worked off in blocks through grouped products (``jax.lax.ragged_dot``);
  the number of blocks steps up with theirs (:func:`grouped_experts`).

Activations and matrix products run in ``compute_dtype``; parameters, router
scores, gates and the KDA state are float32.  When applied with the
``counters`` collection mutable and ``train=True``, every expert layer sows
the round's counters (``COUNTERS``) there; the packed round sums them.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops import kda as kda_ops

# what an expert layer sows a step; the packed round returns their sums
COUNTERS = ("moe.assignments_local", "moe.assignments_total", "moe.expert_load_max",
            "moe.expert_load_mean", "moe.assignments_dropped")


@dataclasses.dataclass(frozen=True)
class KimiLinearConfig:
    hidden_size: int
    num_hidden_layers: int
    vocab_size: int
    rms_norm_eps: float
    kda_layers: Tuple[int, ...]        # 1-based, as published
    full_attn_layers: Tuple[int, ...]  # 1-based
    kda_num_heads: int
    kda_head_dim: int
    short_conv_kernel_size: int
    kda_gate_rank: int
    num_attention_heads: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    intermediate_size: int
    first_k_dense_replace: int
    moe_intermediate_size: int
    n_routed_experts: int
    experts_held: Tuple[int, int]
    num_experts_per_token: int
    num_shared_experts: int
    routed_scaling_factor: float
    moe_renormalize: bool
    dtype: Any = jnp.float32
    remat: bool = False

    @classmethod
    def from_dict(cls, cfg: dict) -> "KimiLinearConfig":
        """From the published keys.  ``num_experts`` counts the experts HELD
        where ``experts_held`` is given (the file then states the router's
        width as ``n_routed_experts``); a whole model gives neither."""
        unsupported = {
            "model_type": cfg.get("model_type", "kimi_linear") != "kimi_linear",
            "q_lora_rank": cfg.get("q_lora_rank") is not None,
            "mla_use_nope": not cfg.get("mla_use_nope", True),
            "moe_router_activation_func":
                cfg.get("moe_router_activation_func", "sigmoid") != "sigmoid",
            "num_expert_group": int(cfg.get("num_expert_group", 1)) != 1,
            "moe_layer_freq": int(cfg.get("moe_layer_freq", 1)) != 1,
            "hidden_act": cfg.get("hidden_act", "silu") != "silu",
            "tie_word_embeddings": bool(cfg.get("tie_word_embeddings", False)),
            "num_nextn_predict_layers": int(cfg.get("num_nextn_predict_layers", 0)) != 0,
        }
        bad = sorted(k for k, v in unsupported.items() if v)
        if bad:
            raise NotImplementedError(f"kimi_linear: no code for the given {bad}")
        lin = cfg["linear_attn_config"]
        total = int(cfg.get("n_routed_experts", cfg["num_experts"]))
        held = tuple(int(e) for e in cfg.get("experts_held", (0, total)))
        if not (len(held) == 2 and 0 <= held[0] < held[1] <= total):
            raise ValueError(f"experts_held must be a range [lo, hi) inside 0..{total}: {held}")
        dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
            cfg.get("compute_dtype", "float32")]
        return cls(
            hidden_size=int(cfg["hidden_size"]),
            num_hidden_layers=int(cfg["num_hidden_layers"]),
            vocab_size=int(cfg["vocab_size"]), rms_norm_eps=float(cfg["rms_norm_eps"]),
            kda_layers=tuple(lin["kda_layers"]), full_attn_layers=tuple(lin["full_attn_layers"]),
            kda_num_heads=int(lin["num_heads"]), kda_head_dim=int(lin["head_dim"]),
            short_conv_kernel_size=int(lin["short_conv_kernel_size"]),
            kda_gate_rank=int(cfg.get("kda_gate_rank", lin["head_dim"])),
            num_attention_heads=int(cfg["num_attention_heads"]),
            kv_lora_rank=int(cfg["kv_lora_rank"]),
            qk_nope_head_dim=int(cfg["qk_nope_head_dim"]),
            qk_rope_head_dim=int(cfg["qk_rope_head_dim"]), v_head_dim=int(cfg["v_head_dim"]),
            intermediate_size=int(cfg["intermediate_size"]),
            first_k_dense_replace=int(cfg["first_k_dense_replace"]),
            moe_intermediate_size=int(cfg["moe_intermediate_size"]),
            n_routed_experts=total, experts_held=held,
            num_experts_per_token=int(cfg["num_experts_per_token"]),
            num_shared_experts=int(cfg["num_shared_experts"]),
            routed_scaling_factor=float(cfg["routed_scaling_factor"]),
            moe_renormalize=bool(cfg["moe_renormalize"]),
            dtype=dtype, remat=bool(cfg.get("remat", False)))


def load_config(model_config) -> dict:
    """``model_config`` as ``arguments.py`` validates it: a dict, or the path
    of a JSON file that holds one."""
    if isinstance(model_config, (str, os.PathLike)):
        with open(model_config) as f:
            return json.load(f)
    return dict(model_config)


def _normal(fan_in: int):
    return nn.initializers.normal(stddev=fan_in ** -0.5)


def rms_norm(x, scale, eps):
    """Float32 statistics, the input's dtype out."""
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps) * scale).astype(x.dtype)


def causal_conv(x, w):
    """Depthwise causal convolution over time.  x: [B, L, ...]; w: [K, ...]:
    ``y_t = sum_i w[i] x_{t-(K-1)+i}`` (zeros before the sequence)."""
    K, L = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (K - 1, 0)) + ((0, 0),) * (x.ndim - 2))
    return sum(padded[:, i:i + L] * w[i].astype(x.dtype) for i in range(K))


class KDAMixer(nn.Module):
    cfg: KimiLinearConfig

    @nn.compact
    def __call__(self, h):
        cfg = self.cfg
        d, H, D, r = cfg.hidden_size, cfg.kda_num_heads, cfg.kda_head_dim, cfg.kda_gate_rank
        K, dt = cfg.short_conv_kernel_size, cfg.dtype

        def proj(name, shape, fan_in, x, spec):
            w = self.param(name, _normal(fan_in), shape, jnp.float32)
            return jnp.einsum(spec, x, w.astype(dt))

        def conv_proj(name):
            x = proj("w" + name, (d, H, D), d, h, "bld,dhk->blhk")
            w = self.param("conv_" + name, _normal(K), (K, H, D), jnp.float32)
            return jax.nn.silu(causal_conv(x, w))

        def l2(x):
            x32 = x.astype(jnp.float32)
            return x32 * jax.lax.rsqrt(jnp.sum(jnp.square(x32), -1, keepdims=True) + 1e-6)

        q, k, v = conv_proj("q"), conv_proj("k"), conv_proj("v")
        f = proj("f_up", (r, H, D), r, proj("f_down", (d, r), d, h, "bld,dr->blr"),
                 "blr,rhk->blhk")
        a_log = self.param("A_log", lambda key, s: jnp.log(
            jax.random.uniform(key, s, jnp.float32, 1.0, 16.0)), (H,))
        dt_bias = self.param("dt_bias", lambda key, s: jnp.log(jnp.expm1(
            jnp.exp(jax.random.uniform(key, s, jnp.float32, jnp.log(1e-3), jnp.log(1e-1))))),
            (H, D))
        g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(f.astype(jnp.float32) + dt_bias)
        beta = jax.nn.sigmoid(proj("w_beta", (d, H), d, h, "bld,dh->blh").astype(jnp.float32))
        o = kda_ops.kda(l2(q).astype(dt), l2(k).astype(dt), v, g, beta)
        gate = proj("g_up", (r, H, D), r, proj("g_down", (d, r), d, h, "bld,dr->blr"),
                    "blr,rhk->blhk")
        o_norm = self.param("o_norm", nn.initializers.ones, (D,), jnp.float32)
        o = rms_norm(o, o_norm, cfg.rms_norm_eps) * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(dt)
        return proj("wo", (H, D, d), H * D, o, "blhk,hkd->bld")


class MLAMixer(nn.Module):
    cfg: KimiLinearConfig

    @nn.compact
    def __call__(self, h):
        from ..ops.flash_attention import attention

        cfg = self.cfg
        d, H, dt = cfg.hidden_size, cfg.num_attention_heads, cfg.dtype
        nope, pe, dv, rank = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim,
                              cfg.kv_lora_rank)

        def param(name, shape, fan_in):
            return self.param(name, _normal(fan_in), shape, jnp.float32).astype(dt)

        q = jnp.einsum("bld,dhk->blhk", h, param("wq", (d, H, nope + pe), d))
        kv = jnp.einsum("bld,dr->blr", h, param("w_kv_down", (d, rank + pe), d))
        kv_norm = self.param("kv_norm", nn.initializers.ones, (rank,), jnp.float32)
        c = rms_norm(kv[..., :rank], kv_norm, cfg.rms_norm_eps)
        up = jnp.einsum("blr,rhk->blhk", c, param("w_kv_up", (rank, H, nope + dv), rank))
        # the shared key part is broadcast over the heads and, NoPE, not rotated
        k_pe = jnp.broadcast_to(kv[..., None, rank:], kv.shape[:2] + (H, pe))
        k = jnp.concatenate([up[..., :nope], k_pe], -1)
        o = attention(q, k, up[..., nope:], causal=True)
        return jnp.einsum("blhk,hkd->bld", o, param("wo", (H, dv, d), H * dv))


def swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


class DenseMLP(nn.Module):
    cfg: KimiLinearConfig
    width: int

    @nn.compact
    def __call__(self, h):
        d, f, dt = self.cfg.hidden_size, self.width, self.cfg.dtype
        w = {n: self.param(n, _normal(fi), s, jnp.float32).astype(dt) for n, s, fi in (
            ("w_gate", (d, f), d), ("w_up", (d, f), d), ("w_down", (f, d), f))}
        return swiglu(h, w["w_gate"], w["w_up"], w["w_down"])


def route(scores, bias, top_k: int, scaling: float, renormalize: bool):
    """scores: [T, E] sigmoid scores (float32).  The top ``top_k`` experts of
    score + bias per token, and their weights: the chosen scores (without the
    bias), renormalised over ALL chosen, times ``scaling``."""
    _, chosen = jax.lax.top_k(scores + bias, top_k)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    if renormalize:
        picked = picked / (jnp.sum(picked, -1, keepdims=True) + 1e-20)
    return chosen, picked * scaling


def grouped_experts(h, chosen, weights, held: Tuple[int, int], w_gate, w_up, w_down):
    """Sum over the chosen experts that are held here of weight x SwiGLU
    expert.  h: [T, d]; chosen, weights: [T, k]; w_*: [E_held, ...].  Returns
    ([T, d], counters).

    The T*k assignments are sorted by expert, those of absent experts last.
    The local ones are then worked off in blocks of an eighth of T*k rows
    (gather the tokens, three grouped products over the block's rows of each
    expert, weight, scatter back), each block recomputed on the way back; as
    many blocks run as hold every local assignment, in steps of 1, 2 and 8
    (``lax.switch``: a loop with a traced trip count has no reverse mode, and
    a ``lax.cond`` a block inside one ``lax.scan`` kept 2.5 GiB more live).
    So nothing is dropped whatever the routing, work steps up with what lands
    here, and memory is a block's.  One block holds four times the even share
    of 8 of 256 experts: with blocks of a sixteenth, seeds whose router sent
    one layer more than 6.25 % of its assignments here ran two blocks there
    and their rounds took 1.6 % longer than the others' (v5e, PR 27)."""
    T, k = chosen.shape
    d = h.shape[-1]
    lo, hi = held
    n_held, total = hi - lo, T * k
    base = -(-total // 8)
    n_blocks = -(-total // base)
    tiers = sorted({1, min(2, n_blocks), n_blocks})
    flat = chosen.reshape(-1)
    with jax.named_scope("lm.moe.dispatch"):
        local = (flat >= lo) & (flat < hi)
        key = jnp.where(local, flat - lo, n_held)
        order = jnp.pad(jnp.argsort(key, stable=True), (0, n_blocks * base - total))
        sizes = jnp.bincount(key, length=n_held + 1)[:n_held].astype(jnp.int32)
        ends = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(sizes)])
        n_local = ends[-1]
        tier = jnp.sum(n_local > base * jnp.asarray(tiers[:-1], jnp.int32))
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
            # rows past the local assignments belong to no group: what a grouped
            # product leaves in them is undefined, forward and backward, so they
            # are cut off on the way in as on the way out
            x = jnp.where(live[:, None], h[token], 0)
        with jax.named_scope("lm.moe.experts"):
            gate = jax.lax.ragged_dot(x, w_gate, block_sizes)
            up = jax.lax.ragged_dot(x, w_up, block_sizes)
            y = jax.lax.ragged_dot(jax.nn.silu(gate) * up, w_down, block_sizes)
        with jax.named_scope("lm.moe.combine"):
            w = jnp.where(live, flat_weights[rows], 0.0).astype(y.dtype)
            return token, jnp.where(live[:, None], y * w[:, None], 0.0)

    def run(blocks):
        def branch():
            token, y = jax.lax.map(one_block, jnp.arange(blocks))
            with jax.named_scope("lm.moe.combine"):
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
    cfg: KimiLinearConfig

    @nn.compact
    def __call__(self, h, train: bool = False):
        from ..core import obs

        cfg = self.cfg
        d, f, dt = cfg.hidden_size, cfg.moe_intermediate_size, cfg.dtype
        lo, hi = cfg.experts_held
        obs.gauge_set("moe.experts_held", hi - lo)
        obs.gauge_set("moe.experts_total", cfg.n_routed_experts)
        flat = h.reshape(-1, d)
        with jax.named_scope("lm.moe.route"):
            w_r = self.param("router", _normal(d), (d, cfg.n_routed_experts), jnp.float32)
            bias = self.param("router_bias", nn.initializers.zeros,
                              (cfg.n_routed_experts,), jnp.float32)
            scores = jax.nn.sigmoid(jnp.matmul(
                flat.astype(jnp.float32), w_r, precision=jax.lax.Precision.HIGHEST))
            chosen, weights = route(scores, bias, cfg.num_experts_per_token,
                                    cfg.routed_scaling_factor, cfg.moe_renormalize)
        experts = {n: self.param(n, _normal(fi), (hi - lo,) + s, jnp.float32).astype(dt)
                   for n, s, fi in (("e_gate", (d, f), d), ("e_up", (d, f), d),
                                    ("e_down", (f, d), f))}
        out, counters = grouped_experts(flat, chosen, weights, (lo, hi), experts["e_gate"],
                                        experts["e_up"], experts["e_down"])
        if train:
            for name, value in counters.items():
                self.sow("counters", name, value, reduce_fn=jnp.add,
                         init_fn=lambda: jnp.zeros((), jnp.float32))
        with jax.named_scope("lm.moe.shared"):
            if cfg.num_shared_experts:
                out = out + DenseMLP(cfg, f * cfg.num_shared_experts, name="shared")(flat)
        return out.reshape(h.shape)


class Block(nn.Module):
    cfg: KimiLinearConfig
    index: int  # 0-based

    @nn.compact
    def __call__(self, x, train: bool = False):
        cfg = self.cfg

        def norm(name):
            scale = self.param(name, nn.initializers.ones, (cfg.hidden_size,), jnp.float32)
            return rms_norm(x, scale, cfg.rms_norm_eps)

        if self.index + 1 in cfg.kda_layers:
            with jax.named_scope("lm.kda"):
                x = x + KDAMixer(cfg, name="kda")(norm("mixer_norm"))
        elif self.index + 1 in cfg.full_attn_layers:
            with jax.named_scope("lm.mla"):
                x = x + MLAMixer(cfg, name="mla")(norm("mixer_norm"))
        else:
            raise ValueError(f"layer {self.index + 1} is in neither kda_layers nor full_attn_layers")
        if self.index < cfg.first_k_dense_replace:
            return x + DenseMLP(cfg, cfg.intermediate_size, name="mlp")(norm("ffn_norm"))
        return x + ExpertShare(cfg, name="moe")(norm("ffn_norm"), train)


class KimiLinearLM(nn.Module):
    cfg: KimiLinearConfig
    # the packed round asks for these sums beside the loss (ml/engine/packed.py)
    round_counters: Tuple[str, ...] = COUNTERS

    @nn.compact
    def __call__(self, tokens, train: bool = False):
        cfg = self.cfg
        if self.is_initializing():
            # no parameter's shape depends on the length: an eager ``init`` at a
            # round's 8,192 tokens would run (and compile, op by op) the whole
            # forward pass for shapes alone
            tokens = tokens[:, :kda_ops.CHUNK]
        embed = self.param("embed", _normal(cfg.hidden_size),
                           (cfg.vocab_size, cfg.hidden_size), jnp.float32)
        x = embed.astype(cfg.dtype)[tokens]
        block_cls = nn.remat(Block, static_argnums=(2,)) if cfg.remat else Block
        for i in range(cfg.num_hidden_layers):
            x = block_cls(cfg, i, name=f"layer{i}")(x, train)
        scale = self.param("final_norm", nn.initializers.ones, (cfg.hidden_size,), jnp.float32)
        head = self.param("head", _normal(cfg.hidden_size),
                          (cfg.hidden_size, cfg.vocab_size), jnp.float32)
        return rms_norm(x, scale, cfg.rms_norm_eps) @ head.astype(cfg.dtype)
