"""Latent attention (MLA), the one mixer of ``kimi_linear.py`` and
``glm4_moe_lite.py``: keys and values up-projected from a normalised latent of
``kv_lora_rank``, a ``qk_rope_head_dim``-wide key part that all heads share, and
queries that are full-rank or low-rank.

On ``a = RMSNorm_in(x)`` [B, L, d], with H heads:

* queries, ``q_lora_rank`` None: ``q = a W_q`` (leaf ``wq``); given:
  ``q = RMSNorm_q(a W_dq) W_uq`` (leaves ``w_q_down``, ``q_norm``, ``w_q_up``);
  per head ``[q_nope (qk_nope_head_dim) | q_pe (qk_rope_head_dim)]``;
* ``[c_kv (kv_lora_rank) | k_pe] = a W_dkv`` (``w_kv_down``); ``[k_nope | v]`` per
  head ``= RMSNorm_kv(c_kv) W_ukv`` (``kv_norm``, ``w_kv_up``);
* ``rope_theta`` None (NoPE): ``q_pe`` and ``k_pe`` stay as they are; given: both
  are rotated over all their ``qk_rope_head_dim`` (halves paired, as
  ``models/transformer.py:rope``, in float32) under the scope ``lm.mla.rope``;
* ``k = [k_nope | k_pe]`` with the ONE ``k_pe`` broadcast over the heads; causal
  softmax of ``q k^T (qk_nope_head_dim + qk_rope_head_dim)^-1/2`` through
  ``ops.flash_attention.attention`` (v of ``v_head_dim``); ``W_o`` (``wo``).

``cfg`` gives ``hidden_size``, ``num_attention_heads``, ``kv_lora_rank``,
``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim``, ``rms_norm_eps``,
``dtype``.  Gauges ``mla.q_lora_rank`` (0: full-rank queries) and ``mla.rope_dim``
(0: NoPE) say which form was traced.
"""

from __future__ import annotations

from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from .expert_lm import _normal, rms_norm
from .transformer import rope


class MLAMixer(nn.Module):
    cfg: Any
    q_lora_rank: Optional[int] = None
    rope_theta: Optional[float] = None

    @nn.compact
    def __call__(self, h):
        from ..core import obs
        from ..ops.flash_attention import attention

        cfg = self.cfg
        d, H, dt = cfg.hidden_size, cfg.num_attention_heads, cfg.dtype
        nope, pe, dv, rank = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim,
                              cfg.kv_lora_rank)
        obs.gauge_set("mla.q_lora_rank", self.q_lora_rank or 0)
        obs.gauge_set("mla.rope_dim", pe if self.rope_theta is not None else 0)

        def param(name, shape, fan_in):
            return self.param(name, _normal(fan_in), shape, jnp.float32).astype(dt)

        if self.q_lora_rank is None:
            q = jnp.einsum("bld,dhk->blhk", h, param("wq", (d, H, nope + pe), d))
        else:
            r = self.q_lora_rank
            c_q = jnp.einsum("bld,dr->blr", h, param("w_q_down", (d, r), d))
            q_norm = self.param("q_norm", nn.initializers.ones, (r,), jnp.float32)
            q = jnp.einsum("blr,rhk->blhk", rms_norm(c_q, q_norm, cfg.rms_norm_eps),
                           param("w_q_up", (r, H, nope + pe), r))
        kv = jnp.einsum("bld,dr->blr", h, param("w_kv_down", (d, rank + pe), d))
        kv_norm = self.param("kv_norm", nn.initializers.ones, (rank,), jnp.float32)
        c = rms_norm(kv[..., :rank], kv_norm, cfg.rms_norm_eps)
        up = jnp.einsum("blr,rhk->blhk", c, param("w_kv_up", (rank, H, nope + dv), rank))
        k_pe = kv[..., None, rank:]  # [B, L, 1, pe]: one key part for all heads
        if self.rope_theta is not None:
            with jax.named_scope("lm.mla.rope"):
                positions = jnp.broadcast_to(jnp.arange(h.shape[1]), h.shape[:2])
                q_pe, k_pe = (rope(x.astype(jnp.float32), positions, self.rope_theta).astype(dt)
                              for x in (q[..., nope:], k_pe))
                q = jnp.concatenate([q[..., :nope], q_pe], -1)
        k_pe = jnp.broadcast_to(k_pe, kv.shape[:2] + (H, pe))
        k = jnp.concatenate([up[..., :nope], k_pe], -1)
        o = attention(q, k, up[..., nope:], causal=True)
        return jnp.einsum("blhk,hkd->bld", o, param("wo", (H, dv, d), H * dv))
