"""Decoder-only Transformer LM (flax.linen), the flagship model for the
distributed/long-context path.

The reference has no transformer of its own (its NLP apps use stock
HuggingFace models, ``python/app/fednlp/``); this module provides the
equivalent capability TPU-first:

* RoPE positions (stateless — compatible with sequence-sharded ring
  attention, see fedml_tpu/parallel/ring_attention.py);
* an injectable ``attention_fn`` so the same module runs with plain fused
  attention on one chip or ring attention over an ``sp`` mesh axis;
* parameter shapes chosen to shard cleanly over a ``tp`` axis (head dim and
  mlp dim are the partitioned axes — see parallel/sharding.py rules).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    d_model: int = 256
    n_heads: int = 8
    n_layers: int = 4
    d_ff: int = 1024
    max_seq_len: int = 2048
    dropout: float = 0.0
    dtype: Any = jnp.float32  # set bfloat16 for TPU runs
    remat: bool = False  # jax.checkpoint each block (HBM <-> FLOPs trade)


def rope(x: jnp.ndarray, positions: jnp.ndarray, base: float = 10000.0) -> jnp.ndarray:
    """Rotary embedding. x: [B, L, H, D], positions: [B, L] absolute indices
    (absolute so sequence-sharded blocks stay correct)."""
    d = x.shape[-1]
    half = d // 2
    freqs = 1.0 / (base ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B, L, half]
    cos = jnp.cos(angles)[:, :, None, :].astype(x.dtype)
    sin = jnp.sin(angles)[:, :, None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def causal_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray) -> jnp.ndarray:
    """Default attention: [B, L, H, D] -> [B, L, H, D], causal — the pallas
    flash kernel on TPU (fwd + bwd, ops/flash_attention.py), the fused XLA
    reference elsewhere.
    Single definition lives in ops (also the pallas kernel's oracle)."""
    from ..ops.flash_attention import attention

    return attention(q, k, v, causal=True)


AttentionFn = Callable[[jnp.ndarray, jnp.ndarray, jnp.ndarray], jnp.ndarray]


class Block(nn.Module):
    cfg: TransformerConfig
    attention_fn: AttentionFn = causal_attention

    @nn.compact
    def __call__(self, x, positions, train: bool = False):
        cfg = self.cfg
        # the scopes are names on the device trace's time (core/obs/scopes.py):
        # each holds its norm, its matmuls and its residual add
        with jax.named_scope("lm.attn"):
            h = nn.RMSNorm(dtype=cfg.dtype, name="attn_norm")(x)
            d_head = cfg.d_model // cfg.n_heads
            qkv = nn.DenseGeneral((3, cfg.n_heads, d_head), axis=-1, use_bias=False,
                                  dtype=cfg.dtype, name="qkv")(h)
            q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
            q = rope(q, positions)
            k = rope(k, positions)
            attn = self.attention_fn(q, k, v)
            attn = nn.DenseGeneral(cfg.d_model, axis=(-2, -1), use_bias=False,
                                   dtype=cfg.dtype, name="out_proj")(attn)
            x = x + attn
        with jax.named_scope("lm.mlp"):
            h = nn.RMSNorm(dtype=cfg.dtype, name="mlp_norm")(x)
            gate = nn.Dense(cfg.d_ff, use_bias=False, dtype=cfg.dtype, name="wi_gate")(h)
            up = nn.Dense(cfg.d_ff, use_bias=False, dtype=cfg.dtype, name="wi_up")(h)
            h = nn.Dense(cfg.d_model, use_bias=False, dtype=cfg.dtype, name="wo")(
                nn.silu(gate) * up
            )
            return x + h


class TransformerLM(nn.Module):
    cfg: TransformerConfig
    attention_fn: AttentionFn = causal_attention

    @nn.compact
    def __call__(self, tokens, positions: Optional[jnp.ndarray] = None, train: bool = False):
        cfg = self.cfg
        if positions is None:
            positions = jnp.broadcast_to(
                jnp.arange(tokens.shape[1]), tokens.shape
            )
        with jax.named_scope("lm.embed"):
            x = nn.Embed(cfg.vocab_size, cfg.d_model, dtype=cfg.dtype, name="embed")(tokens)
        block_cls = Block
        if cfg.remat:
            block_cls = nn.remat(Block, static_argnums=(3,))
        for i in range(cfg.n_layers):
            x = block_cls(cfg, self.attention_fn, name=f"layer{i}")(x, positions, train)
        with jax.named_scope("lm.head"):
            x = nn.RMSNorm(dtype=cfg.dtype, name="final_norm")(x)
            return nn.Dense(cfg.vocab_size, use_bias=False, dtype=cfg.dtype, name="lm_head")(x)
