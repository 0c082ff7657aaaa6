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
* MLA mixer (``latent_attention.MLAMixer``, shared with ``glm4_moe_lite.py``,
  here in its ``mla_use_nope`` form): keys and values up-projected from a
  normalised latent of ``kv_lora_rank``, a shared ``qk_rope_head_dim`` key part
  that is NOT rotated, full-rank queries; q and k of width ``qk_nope_head_dim +
  qk_rope_head_dim`` and v of ``v_head_dim`` go through
  ``ops.flash_attention.attention``.
* Expert layer (``expert_lm.ExpertShare``): sigmoid scores over all
  ``n_routed_experts`` in float32, the top ``num_experts_per_token`` of score +
  correction bias, weights renormalised over all chosen and scaled by
  ``routed_scaling_factor``; this process holds the experts ``experts_held =
  [lo, hi)`` and adds their part only, beside the shared expert.  No
  assignment is dropped: the assignments that land here are sorted by expert
  and worked off in blocks through grouped products (``jax.lax.ragged_dot``);
  the number of blocks steps up with theirs (``expert_lm.grouped_experts``).

Activations and matrix products run in ``compute_dtype``; parameters, router
scores, gates and the KDA state are float32.  When applied with the
``counters`` collection mutable and ``train=True``, every expert layer sows
the round's counters (``expert_lm.COUNTERS``) there; the packed round sums them.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops import kda as kda_ops
# the expert layer, the norm, the dense MLP, the short convolution and the LM shell
# are shared with the other config-driven decoders
from .expert_lm import (DecoderLM, DenseMLP, ExpertShare, _normal, causal_conv, compute_dtype,
                        held_range, rms_norm)
from .latent_attention import MLAMixer

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
        held = held_range(cfg, total)
        dtype = compute_dtype(cfg)
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
            with jax.named_scope("lm.mlp"):
                return x + DenseMLP(cfg, cfg.intermediate_size, name="mlp")(norm("ffn_norm"))
        with jax.named_scope("lm.norm"):
            h = norm("ffn_norm")
        return x + ExpertShare(cfg, name="moe")(h, train)


class KimiLinearLM(DecoderLM):
    cfg: KimiLinearConfig
    block_cls = Block
    init_length = kda_ops.CHUNK
