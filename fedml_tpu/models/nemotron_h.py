"""Config-driven hybrid decoder of ``model_type: nemotron_h``
(nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16): Mamba-2 state-space layers, a share of
sigmoid-routed squared-ReLU experts beside a shared one, and grouped-query attention
without positions — all from a dict whose keys are those of the published
``config.json``, plus ``n_router_outputs`` / ``experts_held`` where a process holds a
share of the experts (``n_routed_experts`` then counts the experts held).

A layer is ONE sublayer, ``x <- x + Sublayer(RMSNorm(x))`` (RMSNorm with a float32
scale, ``norm_eps``), chosen by layer ``i``'s character of ``hybrid_override_pattern``:

* ``M``, the Mamba-2 mixer (:class:`Mamba2Mixer`, scope ``lm.ssm``), with ``d_inner =
  mamba_num_heads x mamba_head_dim`` (4,096: the modeling code's width, NOT ``expand x
  hidden_size``), ``G = n_groups``, ``N = ssm_state_size``:

      [z | xBC | dt] = h W_in                       widths d_inner | d_inner + 2 G N | heads
      xBC <- SiLU(causal depthwise conv_K(xBC) + b)  K = conv_kernel
      x, B, C = xBC                                  x: heads x mamba_head_dim; B, C: G x N
      dt <- softplus(dt + dt_bias);  A = -exp(A_log)   (one value a head, float32)
      S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T;  y_t = S_t C_t + D x_t   (head h reads group h // (heads / G))
      y <- GroupRMSNorm(y * SiLU(z)) w               over G groups of d_inner / G, eps layer_norm_epsilon
      out = y W_out

  The scan is ``ops/ssd.ssd``: the Pallas kernels ``ssd_fwd`` / ``ssd_bwd`` on ``tpu``,
  the chunked XLA form elsewhere.  ``time_step_limit`` (0, inf): nothing is clamped.
* ``E``, the expert layer (``expert_lm.ExpertShare``, ``moe_gated`` False): sigmoid
  scores over all ``n_router_outputs`` in float32, the top ``num_experts_per_tok`` of
  score + correction bias, weights renormalised over the chosen (``norm_topk_prob``)
  and scaled by ``routed_scaling_factor``; experts and the shared expert are
  ``relu(x W_up)^2 W_down`` at ``moe_intermediate_size`` and
  ``moe_shared_expert_intermediate_size``.
* ``*``, attention (``expert_lm.GQAMixer``, window None, no rotation: the modeling code
  rotates nothing; scope ``lm.attn.global``): ``num_attention_heads`` query heads over
  ``num_key_value_heads`` key/value heads of ``head_dim``, causal.

Final RMSNorm, untied head.  Activations and matrix products run in
``compute_dtype``; parameters, router scores, the gates, the scan's state and the
gated norm are float32.  Counters: the expert layers' (``expert_lm.COUNTERS``), and
``ssm.positions``, the tokens each Mamba-2 layer's scan ran over, summed.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops import ssd as ssd_ops
from .expert_lm import (COUNTERS, DecoderLM, ExpertShare, GQAMixer, _normal, causal_conv,
                        compute_dtype, held_range, relu2, rms_norm)

SSM_COUNTERS = ("ssm.positions",)

# the published config.json's keys (the catalog's copy), what a share adds, and what a
# configuration file says about itself; another key names a mechanism this module does
# not write
_PUBLISHED = {
    "attention_bias", "chunk_size", "conv_kernel", "expand", "head_dim", "hidden_size",
    "hybrid_override_pattern", "intermediate_size", "layer_norm_epsilon", "mamba_head_dim",
    "mamba_hidden_act", "mamba_num_heads", "mamba_proj_bias", "max_position_embeddings",
    "mlp_bias", "mlp_hidden_act", "model_type", "moe_intermediate_size",
    "moe_shared_expert_intermediate_size", "n_group", "n_groups", "n_routed_experts",
    "n_shared_experts", "norm_eps", "norm_topk_prob", "num_attention_heads", "num_experts_per_tok",
    "num_hidden_layers", "num_key_value_heads", "num_logits_to_keep", "partial_rotary_factor",
    "rescale_prenorm_residual", "residual_in_fp32", "rope_theta", "routed_scaling_factor",
    "sliding_window", "ssm_state_size", "tie_word_embeddings", "time_step_floor", "time_step_limit",
    "time_step_max", "time_step_min", "topk_group", "use_bias", "use_conv_bias",
    "use_mamba_kernels", "vocab_size"}
_OWN = {"n_router_outputs", "experts_held", "compute_dtype", "param_dtype", "remat"}
_ABOUT = {"name", "source", "reduced", "published", "assumed", "deployment", "parameters",
          "bytes_reckoned"}
_KINDS = "ME*"  # a Mamba-2 mixer, an expert layer, attention


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    hidden_size: int
    num_hidden_layers: int
    vocab_size: int
    rms_norm_eps: float  # the blocks' and the final norm's (``norm_eps``)
    layer_kinds: str  # ``hybrid_override_pattern``: a character a layer
    mamba_num_heads: int
    mamba_head_dim: int
    n_groups: int
    ssm_state_size: int
    conv_kernel: int
    use_conv_bias: bool
    time_step_range: Tuple[float, float]  # (time_step_min, time_step_max)
    time_step_floor: float
    mamba_norm_eps: float  # the gated norm's (``layer_norm_epsilon``)
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    moe_intermediate_size: int
    shared_expert_intermediate_size: int
    n_routed_experts: int  # the router's outputs, as ``expert_lm`` names them
    experts_held: Tuple[int, int]
    num_experts_per_token: int
    num_shared_experts: int
    routed_scaling_factor: float
    moe_renormalize: bool
    moe_gated: bool = False  # relu(x W_up)^2 W_down: two products, not three
    dtype: Any = jnp.float32
    remat: bool = False

    @classmethod
    def from_dict(cls, cfg: dict) -> "NemotronHConfig":
        """From the published keys.  ``n_routed_experts`` counts the experts HELD where
        ``experts_held`` is given (the file then states the router's width as
        ``n_router_outputs``); a whole model gives neither.  ``d_inner`` is
        ``mamba_num_heads x mamba_head_dim``; ``expand`` is read by nothing."""
        unknown = sorted(set(cfg) - _PUBLISHED - _OWN - _ABOUT)
        if unknown:
            raise ValueError(f"nemotron_h: unknown keys {unknown}")
        pattern = str(cfg["hybrid_override_pattern"])
        limit = cfg.get("time_step_limit", (0.0, float("inf")))
        unsupported = {
            "model_type": cfg.get("model_type", "nemotron_h") != "nemotron_h",
            "hybrid_override_pattern": "-" in pattern,  # a dense MLP layer
            "n_group": int(cfg.get("n_group", 1)) != 1,
            "topk_group": int(cfg.get("topk_group", 1)) != 1,
            "mamba_proj_bias": bool(cfg.get("mamba_proj_bias", False)),
            "time_step_limit": float(limit[0]) != 0.0 or not math.isinf(float(limit[1])),
            "attention_bias": bool(cfg.get("attention_bias", False)),
            "mlp_bias": bool(cfg.get("mlp_bias", False)),
            "use_bias": bool(cfg.get("use_bias", False)),
            "tie_word_embeddings": bool(cfg.get("tie_word_embeddings", False)),
            "residual_in_fp32": bool(cfg.get("residual_in_fp32", False)),
            "sliding_window": cfg.get("sliding_window") is not None,
            "mlp_hidden_act": cfg.get("mlp_hidden_act", "relu2") != "relu2",
            "mamba_hidden_act": cfg.get("mamba_hidden_act", "silu") != "silu",
        }
        bad = sorted(k for k, v in unsupported.items() if v)
        if bad:
            raise NotImplementedError(f"nemotron_h: no code for the given {bad}")
        layers = int(cfg["num_hidden_layers"])
        if len(pattern) != layers or set(pattern) - set(_KINDS):
            raise ValueError(f"hybrid_override_pattern {pattern!r} must give one of "
                             f"{sorted(_KINDS)} to each of {layers} layers")
        if int(cfg.get("chunk_size", ssd_ops.CHUNK)) != ssd_ops.CHUNK:
            raise ValueError(f"chunk_size {cfg['chunk_size']}: the scan's chunk is {ssd_ops.CHUNK}")
        heads, kv_heads = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
        if heads % kv_heads:
            raise ValueError(f"{heads} query heads are no multiple of {kv_heads} key/value heads")
        m_heads, groups = int(cfg["mamba_num_heads"]), int(cfg["n_groups"])
        if m_heads % groups:
            raise ValueError(f"{m_heads} Mamba heads are no multiple of {groups} groups")
        total = int(cfg.get("n_router_outputs", cfg["n_routed_experts"]))
        held = held_range(cfg, total)
        if "experts_held" in cfg and held[1] - held[0] != int(cfg["n_routed_experts"]):
            raise ValueError(f"n_routed_experts counts the experts held: "
                             f"{cfg['n_routed_experts']} against {held} of {total}")
        top_k = int(cfg["num_experts_per_tok"])
        if not 0 < top_k <= total:
            raise ValueError(f"{top_k} experts a token of {total}")
        return cls(
            hidden_size=int(cfg["hidden_size"]), num_hidden_layers=layers,
            vocab_size=int(cfg["vocab_size"]), rms_norm_eps=float(cfg["norm_eps"]),
            layer_kinds=pattern, mamba_num_heads=m_heads,
            mamba_head_dim=int(cfg["mamba_head_dim"]), n_groups=groups,
            ssm_state_size=int(cfg["ssm_state_size"]), conv_kernel=int(cfg["conv_kernel"]),
            use_conv_bias=bool(cfg.get("use_conv_bias", True)),
            time_step_range=(float(cfg["time_step_min"]), float(cfg["time_step_max"])),
            time_step_floor=float(cfg["time_step_floor"]),
            mamba_norm_eps=float(cfg["layer_norm_epsilon"]), num_attention_heads=heads,
            num_key_value_heads=kv_heads, head_dim=int(cfg["head_dim"]),
            moe_intermediate_size=int(cfg["moe_intermediate_size"]),
            shared_expert_intermediate_size=int(cfg["moe_shared_expert_intermediate_size"]),
            n_routed_experts=total, experts_held=held, num_experts_per_token=top_k,
            num_shared_experts=int(cfg["n_shared_experts"]),
            routed_scaling_factor=float(cfg["routed_scaling_factor"]),
            moe_renormalize=bool(cfg["norm_topk_prob"]),
            dtype=compute_dtype(cfg), remat=bool(cfg.get("remat", False)))


def _a_log_init(key, shape):  # exp(A_log) uniform over 1..16, as Mamba-2 starts
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))


def _dt_bias_init(low, high, floor):
    """softplus(dt_bias) log-uniform over [low, high], floored at ``floor``."""
    def init(key, shape):
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, math.log(low), math.log(high)))
        dt = jnp.maximum(dt, floor)
        return dt + jnp.log(-jnp.expm1(-dt))
    return init


def gated_group_norm(y, z, scale, groups: int, eps: float):
    """``RMSNorm(y * SiLU(z))`` over ``groups`` groups of the last axis, times ``scale``,
    in float32."""
    g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    parts = g.reshape(g.shape[:-1] + (groups, g.shape[-1] // groups))
    parts = parts * jax.lax.rsqrt(jnp.mean(jnp.square(parts), -1, keepdims=True) + eps)
    return parts.reshape(g.shape) * scale


class Mamba2Mixer(nn.Module):
    """The module docstring's ``M``, from ``W_in`` to ``W_out``."""
    cfg: NemotronHConfig

    @nn.compact
    def __call__(self, h, train: bool = False):
        cfg = self.cfg
        d, H, P = cfg.hidden_size, cfg.mamba_num_heads, cfg.mamba_head_dim
        G, N, K, dt = cfg.n_groups, cfg.ssm_state_size, cfg.conv_kernel, cfg.dtype
        inner, conv_dim = H * P, H * P + 2 * G * N
        b, L = h.shape[:2]
        w_in = self.param("in_proj", _normal(d), (d, inner + conv_dim + H), jnp.float32)
        zxbcdt = h @ w_in.astype(dt)
        z, xBC, delta = jnp.split(zxbcdt, [inner, inner + conv_dim], axis=-1)
        conv_w = self.param("conv_w", _normal(K), (K, conv_dim), jnp.float32)
        conv_b = (self.param("conv_b", nn.initializers.zeros, (conv_dim,), jnp.float32)
                  if cfg.use_conv_bias else None)
        xBC = jax.nn.silu(causal_conv(xBC, conv_w, conv_b))
        x, B, C = jnp.split(xBC, [inner, inner + G * N], axis=-1)
        x, B, C = x.reshape(b, L, H, P), B.reshape(b, L, G, N), C.reshape(b, L, G, N)
        dt_bias = self.param("dt_bias", _dt_bias_init(*cfg.time_step_range, cfg.time_step_floor),
                             (H,))
        a_log = self.param("A_log", _a_log_init, (H,))
        skip = self.param("D", nn.initializers.ones, (H,), jnp.float32)
        delta = jax.nn.softplus(delta.astype(jnp.float32) + dt_bias)
        y = ssd_ops.ssd(x, delta, -jnp.exp(a_log), B, C)
        y = y.astype(jnp.float32) + x.astype(jnp.float32) * skip[:, None]
        norm = self.param("norm", nn.initializers.ones, (inner,), jnp.float32)
        y = gated_group_norm(y.reshape(b, L, inner), z, norm, G, cfg.mamba_norm_eps).astype(dt)
        if train:
            self.sow("counters", "ssm.positions", jnp.asarray(b * L, jnp.float32),
                     reduce_fn=jnp.add, init_fn=lambda: jnp.zeros((), jnp.float32))
        w_out = self.param("out_proj", _normal(inner), (inner, d), jnp.float32)
        return y @ w_out.astype(dt)


class Block(nn.Module):
    """Layer ``index``: ``x + Sublayer(RMSNorm(x))``, the sublayer by its character."""
    cfg: NemotronHConfig
    index: int  # 0-based

    @nn.compact
    def __call__(self, x, train: bool = False):
        cfg = self.cfg
        kind = cfg.layer_kinds[self.index]
        with jax.named_scope("lm.norm"):
            scale = self.param("norm", nn.initializers.ones, (cfg.hidden_size,), jnp.float32)
            h = rms_norm(x, scale, cfg.rms_norm_eps)
        if kind == "M":
            with jax.named_scope("lm.ssm"):
                return x + Mamba2Mixer(cfg, name="mixer")(h, train)
        if kind == "*":
            with jax.named_scope("lm.attn.global"):
                return x + GQAMixer(cfg, None, False, name="mixer")(h)
        return x + ExpertShare(cfg, relu2, name="mixer")(h, train)


class NemotronHLM(DecoderLM):
    cfg: NemotronHConfig
    round_counters: Tuple[str, ...] = COUNTERS + SSM_COUNTERS
    block_cls = Block
    init_length = ssd_ops.CHUNK
