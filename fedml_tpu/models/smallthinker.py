"""Config-driven sparse decoder of ``PowerInfer/SmallThinker-21BA3B-Instruct``:
grouped-query attention that is windowed with rotary positions in some layers
and global without positions (NoPE) in the others, a router that reads the
attention's input, and a share of softmax-routed ReGLU experts — all from a
dict whose keys are those of the published ``config.json``, plus
``n_routed_experts`` / ``experts_held`` where a process holds a share.

Layer ``i`` (pre-norm residual, RMSNorm with a float32 scale, no bias anywhere):

1. ``a = RMSNorm_in(x)``.
2. Router, before the attention, on ``a``: ``logits = a W_r`` over all
   ``n_routed_experts`` in float32; the ``moe_num_active_primary_experts``
   largest logits of a token are chosen and weighted by the softmax over those
   chosen logits (``moe_primary_router_apply_softmax``).
3. Attention on ``a`` (:class:`GQAMixer`): ``num_attention_heads`` query heads
   over ``num_key_value_heads`` key/value heads of ``head_dim``; where
   ``sliding_window_layout[i]`` is 1 a query sees the ``sliding_window_size``
   keys that end with its own, where ``rope_layout[i]`` is 1 q and k are rotated
   at ``rope_theta`` (halves paired, as ``models/transformer.py:rope``);
   ``ops.flash_attention.attention`` takes the window and the unequal head
   counts.  ``x <- x + W_o o``.
4. ``m = RMSNorm_post(x)``; ``x <- x + sum over the chosen experts held here of
   w_e (relu(m W_gate,e) * (m W_up,e)) W_down,e`` (``expert_lm.ExpertShare``,
   which is handed step 2's decision).

Final RMSNorm, untied head.  Activations and matrix products run in
``compute_dtype``; parameters and the router's logits are float32, the rotation
is applied in float32.  Counters as ``expert_lm`` sows them.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from .expert_lm import (DecoderLM, ExpertShare, GQAMixer, _normal, compute_dtype, held_range,
                        rms_norm, route)

# every ``moe_*`` key of the published config.json; another one (the family's
# secondary experts, say) names a mechanism this module does not write
_MOE_KEYS = {"moe_ffn_hidden_size", "moe_num_active_primary_experts", "moe_num_primary_experts",
             "moe_primary_router_apply_softmax"}


@dataclasses.dataclass(frozen=True)
class SmallThinkerConfig:
    hidden_size: int
    num_hidden_layers: int
    vocab_size: int
    rms_norm_eps: float
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    rope_theta: float
    sliding_window_size: int
    sliding_window_layout: Tuple[int, ...]  # per layer: 1 windowed, 0 global
    rope_layout: Tuple[int, ...]            # per layer: 1 rotated, 0 NoPE
    moe_intermediate_size: int
    n_routed_experts: int
    experts_held: Tuple[int, int]
    num_experts_per_token: int
    num_shared_experts: int = 0
    dtype: Any = jnp.float32
    remat: bool = False

    @classmethod
    def from_dict(cls, cfg: dict) -> "SmallThinkerConfig":
        """From the published keys.  ``moe_num_primary_experts`` counts the
        experts HELD where ``experts_held`` is given (the file then states the
        router's width as ``n_routed_experts``); a whole model gives neither.
        The two layouts may be the published 52 entries: a model of fewer
        layers reads its first ``num_hidden_layers``."""
        unsupported = {
            "rope_scaling": cfg.get("rope_scaling") is not None,
            "tie_word_embeddings": bool(cfg.get("tie_word_embeddings", False)),
            "moe_primary_router_apply_softmax":
                not cfg.get("moe_primary_router_apply_softmax", True),
            **{k: True for k in cfg if k.startswith("moe_") and k not in _MOE_KEYS},
        }
        bad = sorted(k for k, v in unsupported.items() if v)
        if bad:
            raise NotImplementedError(f"smallthinker: no code for the given {bad}")
        layers = int(cfg["num_hidden_layers"])
        heads, kv_heads = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
        if heads % kv_heads:
            raise ValueError(f"{heads} query heads are no multiple of {kv_heads} key/value heads")
        layouts = {}
        for key in ("sliding_window_layout", "rope_layout"):
            layout = tuple(int(v) for v in cfg[key])
            if len(layout) < layers or set(layout) - {0, 1}:
                raise ValueError(f"{key} must give 0 or 1 for each of the {layers} layers: {layout}")
            layouts[key] = layout[:layers]
        total = int(cfg.get("n_routed_experts", cfg["moe_num_primary_experts"]))
        held = held_range(cfg, total)
        if "experts_held" in cfg and held[1] - held[0] != int(cfg["moe_num_primary_experts"]):
            raise ValueError(f"moe_num_primary_experts counts the experts held: "
                             f"{cfg['moe_num_primary_experts']} against {held}")
        top_k = int(cfg["moe_num_active_primary_experts"])
        if not 0 < top_k <= total:
            raise ValueError(f"{top_k} experts a token of {total}")
        return cls(
            hidden_size=int(cfg["hidden_size"]), num_hidden_layers=layers,
            vocab_size=int(cfg["vocab_size"]), rms_norm_eps=float(cfg["rms_norm_eps"]),
            num_attention_heads=heads, num_key_value_heads=kv_heads,
            head_dim=int(cfg["head_dim"]), rope_theta=float(cfg["rope_theta"]),
            sliding_window_size=int(cfg["sliding_window_size"]), **layouts,
            moe_intermediate_size=int(cfg["moe_ffn_hidden_size"]),
            n_routed_experts=total, experts_held=held, num_experts_per_token=top_k,
            dtype=compute_dtype(cfg), remat=bool(cfg.get("remat", False)))


class Block(nn.Module):
    cfg: SmallThinkerConfig
    index: int  # 0-based

    @nn.compact
    def __call__(self, x, train: bool = False):
        cfg = self.cfg
        d = cfg.hidden_size

        def norm(name):
            scale = self.param(name, nn.initializers.ones, (d,), jnp.float32)
            return rms_norm(x, scale, cfg.rms_norm_eps)

        with jax.named_scope("lm.norm"):  # router and attention share it
            a = norm("attn_norm")
        with jax.named_scope("lm.moe.route"):
            w_r = self.param("router", _normal(d), (d, cfg.n_routed_experts), jnp.float32)
            logits = jnp.matmul(a.reshape(-1, d).astype(jnp.float32), w_r,
                                precision=jax.lax.Precision.HIGHEST)
            routing = route(logits, None, cfg.num_experts_per_token, softmax_chosen=True)
        windowed = bool(cfg.sliding_window_layout[self.index])
        with jax.named_scope("lm.attn.window" if windowed else "lm.attn.global"):
            x = x + GQAMixer(cfg, cfg.sliding_window_size if windowed else None,
                             bool(cfg.rope_layout[self.index]), name="attn")(a)
        with jax.named_scope("lm.norm"):
            h = norm("ffn_norm")
        return x + ExpertShare(cfg, jax.nn.relu, name="moe")(h, train, routing)


class SmallThinkerLM(DecoderLM):
    cfg: SmallThinkerConfig
    block_cls = Block
