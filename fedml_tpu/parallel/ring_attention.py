"""Ring attention: exact attention over a sequence-sharded mesh axis.

Long-context capability the reference lacks entirely (SURVEY.md §5
"long-context/sequence parallelism: absent") but this framework treats as
first-class: each device in the ``sp`` ring holds one sequence shard of
Q/K/V; K/V blocks rotate around the ring via ``jax.lax.ppermute`` (ICI
neighbor traffic, no all-gather), and softmax is accumulated online
(flash-attention style running max / denominator), so the full [L, L] score
matrix never materializes and memory per chip stays O(L/sp · L/sp).

Two entry points:

* :func:`ring_attention_inner` — use inside an existing ``shard_map`` (this
  is what the sequence-parallel transformer binds as its ``attention_fn``);
* :func:`ring_attention` — standalone: shard_maps itself over ``axis_name``.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


# one canonical definition of the per-shard online-softmax math, shared
# with the pallas kernel's backward (ops/flash_attention.py)
from ..ops.flash_attention import shard_update_reference


def _block_attend(q, k, v, q_pos, k_pos, causal, m, l, o):
    """One K/V block's contribution under online softmax (the fused-XLA
    default block_fn; see :func:`shard_update_reference`)."""
    return shard_update_reference(q, k, v, q_pos, k_pos, causal, m, l, o)


def pallas_block_attend(q, k, v, q_pos, k_pos, causal, m, l, o,
                        block_q: int = 128, block_k: int = 128,
                        interpret: bool = False):
    """Drop-in for :func:`_block_attend` that folds the K/V shard through
    the pallas block-update kernel (ops/flash_attention.flash_shard_update):
    the ring moves shards over ICI via ppermute, the kernel does the
    per-chip block math in VMEM — the composed ring+flash design."""
    from ..ops.flash_attention import flash_shard_update

    return flash_shard_update(q, k, v, q_pos, k_pos, m, l, o, causal=causal,
                              block_q=block_q, block_k=block_k,
                              interpret=interpret)


def ring_attention_inner(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    axis_name: str = "sp",
    causal: bool = True,
    block_fn=None,
) -> jnp.ndarray:
    """Exact attention where q/k/v are the LOCAL sequence shards [B, Ls, H, D]
    of a ring over ``axis_name``.  Must run inside shard_map.  ``block_fn``
    selects the per-shard update: the fused-XLA :func:`_block_attend`
    (default) or :func:`pallas_block_attend` (the flash kernel per chip)."""
    if block_fn is None:
        block_fn = _block_attend
    n = jax.lax.psum(1, axis_name)
    my = jax.lax.axis_index(axis_name)
    B, Ls, H, D = q.shape
    m = jnp.full((B, H, Ls), -jnp.inf, jnp.float32)
    l = jnp.zeros((B, H, Ls), jnp.float32)
    o = jnp.zeros((B, Ls, H, D), jnp.float32)
    q_pos = my * Ls + jnp.arange(Ls)

    perm = [(i, (i + 1) % n) for i in range(n)]
    cur_k, cur_v = k, v
    for r in range(n):
        src = (my - r) % n  # ring shift r: the block originated on device my-r
        k_pos = src * Ls + jnp.arange(cur_k.shape[1])
        m, l, o = block_fn(q, cur_k, cur_v, q_pos, k_pos, causal, m, l, o)
        if r < n - 1:
            # one collective for both operands (pytree ppermute)
            cur_k, cur_v = jax.lax.ppermute((cur_k, cur_v), axis_name, perm)
    denom = jnp.maximum(l, 1e-20).transpose(0, 2, 1)[..., None]  # [B, Lq, H, 1]
    return (o / denom).astype(q.dtype)


def ring_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh: Mesh,
    axis_name: str = "sp",
    causal: bool = True,
    block_fn=None,
) -> jnp.ndarray:
    """Standalone ring attention: q/k/v are FULL [B, L, H, D] arrays; the
    sequence axis is sharded over ``axis_name`` and the result gathered."""
    spec = P(None, axis_name, None, None)
    fn = shard_map(
        partial(ring_attention_inner, axis_name=axis_name, causal=causal,
                block_fn=block_fn),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        # only the pallas block_fn needs the relaxation (pallas_call outputs
        # can't declare vma); the default XLA path keeps strict checking
        check_vma=block_fn is None,
    )
    return fn(q, k, v)
