"""Config-driven sparse decoder of ``model_type: glm4_moe_lite``
(zai-org/GLM-4.7-Flash): rotated latent attention with low-rank queries in every
layer, one leading dense layer, then a share of sigmoid-routed experts beside a
shared one, and one multi-token-prediction module trained through the engine's
loss — all from a dict whose keys are those of the published ``config.json``,
plus ``n_router_outputs`` / ``experts_held`` where a process holds a share and
``mtp_loss_weight``.

Layer ``i`` (pre-norm residual, RMSNorm with a float32 scale, no bias anywhere):

1. ``x <- x + MLA(RMSNorm_in(x))`` (``latent_attention.MLAMixer``, the mixer of
   ``kimi_linear.py``, here with ``q = RMSNorm_q(a W_dq) W_uq`` at ``q_lora_rank``
   and ``q_pe`` / the shared ``k_pe`` rotated at ``rope_theta`` over all
   ``qk_rope_head_dim``; q and k ``qk_nope_head_dim + qk_rope_head_dim`` wide, v
   ``v_head_dim``), scope ``lm.mla``.
2. ``i < first_k_dense_replace``: ``x <- x + SwiGLU(RMSNorm_post(x))`` at
   ``intermediate_size``; else the expert layer (``expert_lm.ExpertShare``):
   sigmoid scores over all the router's outputs in float32, the top
   ``num_experts_per_tok`` of score + ``e_score_correction_bias`` (one group),
   weights renormalised over the chosen (``norm_topk_prob``) and scaled by
   ``routed_scaling_factor``; the experts ``experts_held = [lo, hi)`` add their
   part beside the shared expert.

Final RMSNorm, untied head, and after the last block the prediction module
(``expert_lm.PredictionModule`` under ``lm.mtp``: one more block of this kind fed
``[RMSNorm(x) ; RMSNorm(Emb(next token))] W_eh``, the main embedding and head
shared).  The step trains ``L_main + mtp_loss_weight x L_mtp``; ``train=False``
runs no module and returns the main logits, as ``train=True`` does.

Activations and matrix products run in ``compute_dtype``; parameters, router
scores and the rotation are float32.  Counters as ``expert_lm`` sows them, with
``lm.loss_main``, ``mtp.loss`` and ``mtp.positions`` beside the five ``moe.*``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from .expert_lm import (COUNTERS, MTP_COUNTERS, DecoderLM, DenseMLP, ExpertShare, compute_dtype,
                        held_range, rms_norm)
from .latent_attention import MLAMixer

# the published config.json's keys (the catalog's copy), what a share adds, and
# what a configuration file says about itself; another key names a mechanism
# this module does not write
_PUBLISHED = {
    "attention_bias", "hidden_act", "hidden_size", "intermediate_size", "max_position_embeddings",
    "model_type", "moe_intermediate_size", "topk_method", "norm_topk_prob", "num_attention_heads",
    "n_group", "topk_group", "n_routed_experts", "n_shared_experts", "routed_scaling_factor",
    "num_experts_per_tok", "first_k_dense_replace", "num_hidden_layers", "num_key_value_heads",
    "num_nextn_predict_layers", "partial_rotary_factor", "rms_norm_eps", "rope_scaling",
    "rope_theta", "tie_word_embeddings", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
    "qk_rope_head_dim", "v_head_dim", "vocab_size"}
_OWN = {"n_router_outputs", "experts_held", "mtp_loss_weight", "compute_dtype", "param_dtype",
        "remat"}
_ABOUT = {"name", "source", "reduced", "published", "assumed", "deployment", "parameters",
          "bytes_reckoned"}


@dataclasses.dataclass(frozen=True)
class Glm4MoeLiteConfig:
    hidden_size: int
    num_hidden_layers: int
    vocab_size: int
    rms_norm_eps: float
    num_attention_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_theta: float
    intermediate_size: int
    first_k_dense_replace: int
    moe_intermediate_size: int
    n_routed_experts: int  # the router's outputs, as ``expert_lm`` names them
    experts_held: Tuple[int, int]
    num_experts_per_token: int
    num_shared_experts: int
    routed_scaling_factor: float
    moe_renormalize: bool
    num_nextn_predict_layers: int
    mtp_loss_weight: float
    dtype: Any = jnp.float32
    remat: bool = False

    @classmethod
    def from_dict(cls, cfg: dict) -> "Glm4MoeLiteConfig":
        """From the published keys.  ``n_routed_experts`` counts the experts HELD
        where ``experts_held`` is given (the file then states the router's width
        as ``n_router_outputs``); a whole model gives neither."""
        unknown = sorted(set(cfg) - _PUBLISHED - _OWN - _ABOUT)
        if unknown:
            raise ValueError(f"glm4_moe_lite: unknown keys {unknown}")
        unsupported = {
            "model_type": cfg.get("model_type", "glm4_moe_lite") != "glm4_moe_lite",
            "attention_bias": bool(cfg.get("attention_bias", False)),
            "hidden_act": cfg.get("hidden_act", "silu") != "silu",
            "topk_method": cfg.get("topk_method", "noaux_tc") != "noaux_tc",
            "n_group": int(cfg.get("n_group", 1)) != 1,
            "topk_group": int(cfg.get("topk_group", 1)) != 1,
            "partial_rotary_factor": float(cfg.get("partial_rotary_factor", 1)) != 1.0,
            "rope_scaling": cfg.get("rope_scaling") is not None,
            "tie_word_embeddings": bool(cfg.get("tie_word_embeddings", False)),
            "q_lora_rank": cfg.get("q_lora_rank") is None,
            "num_nextn_predict_layers": int(cfg.get("num_nextn_predict_layers", 0)) not in (0, 1),
        }
        bad = sorted(k for k, v in unsupported.items() if v)
        if bad:
            raise NotImplementedError(f"glm4_moe_lite: no code for the given {bad}")
        heads = int(cfg["num_attention_heads"])
        if int(cfg.get("num_key_value_heads", heads)) != heads:
            raise ValueError(f"latent attention has one key/value head a query head: "
                             f"{cfg['num_key_value_heads']} against {heads}")
        total = int(cfg.get("n_router_outputs", cfg["n_routed_experts"]))
        held = held_range(cfg, total)
        if "experts_held" in cfg and held[1] - held[0] != int(cfg["n_routed_experts"]):
            raise ValueError(f"n_routed_experts counts the experts held: "
                             f"{cfg['n_routed_experts']} against {held} of {total}")
        top_k = int(cfg["num_experts_per_tok"])
        if not 0 < top_k <= total:
            raise ValueError(f"{top_k} experts a token of {total}")
        layers, dense = int(cfg["num_hidden_layers"]), int(cfg["first_k_dense_replace"])
        if not 0 <= dense <= layers:
            raise ValueError(f"{dense} leading dense layers of {layers}")
        modules = int(cfg.get("num_nextn_predict_layers", 0))
        weight = float(cfg.get("mtp_loss_weight", 0.0))
        if weight < 0.0 or (weight and not modules):
            raise ValueError(f"mtp_loss_weight {weight} with {modules} prediction modules")
        return cls(
            hidden_size=int(cfg["hidden_size"]), num_hidden_layers=layers,
            vocab_size=int(cfg["vocab_size"]), rms_norm_eps=float(cfg["rms_norm_eps"]),
            num_attention_heads=heads, q_lora_rank=int(cfg["q_lora_rank"]),
            kv_lora_rank=int(cfg["kv_lora_rank"]),
            qk_nope_head_dim=int(cfg["qk_nope_head_dim"]),
            qk_rope_head_dim=int(cfg["qk_rope_head_dim"]), v_head_dim=int(cfg["v_head_dim"]),
            rope_theta=float(cfg["rope_theta"]), intermediate_size=int(cfg["intermediate_size"]),
            first_k_dense_replace=dense,
            moe_intermediate_size=int(cfg["moe_intermediate_size"]),
            n_routed_experts=total, experts_held=held, num_experts_per_token=top_k,
            num_shared_experts=int(cfg["n_shared_experts"]),
            routed_scaling_factor=float(cfg["routed_scaling_factor"]),
            moe_renormalize=bool(cfg["norm_topk_prob"]),
            num_nextn_predict_layers=modules, mtp_loss_weight=weight,
            dtype=compute_dtype(cfg), remat=bool(cfg.get("remat", False)))


class Block(nn.Module):
    cfg: Glm4MoeLiteConfig
    index: int  # 0-based; the prediction module's block is ``num_hidden_layers``

    @nn.compact
    def __call__(self, x, train: bool = False):
        cfg = self.cfg

        def norm(name):
            scale = self.param(name, nn.initializers.ones, (cfg.hidden_size,), jnp.float32)
            return rms_norm(x, scale, cfg.rms_norm_eps)

        with jax.named_scope("lm.mla"):
            x = x + MLAMixer(cfg, cfg.q_lora_rank, cfg.rope_theta, name="mla")(norm("mixer_norm"))
        if self.index < cfg.first_k_dense_replace:
            with jax.named_scope("lm.mlp"):
                return x + DenseMLP(cfg, cfg.intermediate_size, name="mlp")(norm("ffn_norm"))
        with jax.named_scope("lm.norm"):
            h = norm("ffn_norm")
        return x + ExpertShare(cfg, name="moe")(h, train)


class Glm4MoeLiteLM(DecoderLM):
    cfg: Glm4MoeLiteConfig
    round_counters: Tuple[str, ...] = COUNTERS + MTP_COUNTERS
    block_cls = Block
