"""Pallas flash attention (TPU kernel) and its fused-XLA reference.

The single-chip hot path of the transformer stack: blockwise attention with
online softmax.  Grid is (batch·heads, q blocks, k blocks) — TPU executes
the innermost grid dimension sequentially per core, so the running
(max, denom, out) accumulators live in VMEM scratch across k-steps and only
[block_q, D] / [block_k, D] tiles are VMEM-resident (never the full K/V, so
long contexts aren't VMEM-capped).  Composes with ring attention
(parallel/ring_attention.py): the ring moves K/V shards across chips via
ppermute and :func:`flash_shard_update` folds each shard into the running
online-softmax state per chip (wired as
``ring_attention(..., block_fn=pallas_block_attend)``).

Differentiation: a ``jax.custom_vjp`` over dedicated pallas backward
kernels — the forward additionally emits the per-row log-sum-exp, and the
backward re-materializes P blockwise from (q, k, lse) in two passes (a dQ
pass with k innermost, a dK/dV pass with q innermost), so backward memory
is O(block²) per core like the forward, never the O(L²) probs matrix.  The
forward rule names its output and the log-sum-exp (``flash_fwd.out``,
``flash_fwd.lse``: ``ops/kept.py``), so a caller that recomputes its layers
can keep those two and find the kernel's second call dead.

How blocks are chosen.  A grid step costs 0.1-0.35 µs on a v5e whatever it
computes (the more where it waits for a copy), a 128 x 128 tile's matmuls a
tenth of that, so the tiling, not the MXU, sets the kernels' time.  ``block_q`` / ``block_k`` left ``None``
are chosen per kernel by :func:`_choose_blocks`, a pure function of L, D and
the input dtype: the largest multiples of 128 that divide the lane-rounded
length, up to the pair the on-chip sweep read best for that kernel
(``_BLOCK_TARGET``) and inside a VMEM budget written beside it
(``_VMEM_BUDGET``, ``_vmem_bytes``).  Explicit blocks are taken as given
(interpret-mode tests pass small ones); there is no option, environment
variable or run-time autotune.  What was chosen is left as the gauges
``flash.block_q``, ``flash.block_k``, ``flash.grid_steps`` and
``flash.live_step_share`` per kernel name (docs/OBSERVABILITY.md).

The grid holds its live tiles and no rectangle of them.  Under the causal mask
a tile whose keys all lie after its queries (and any tile of padding) is dead,
and under a ``window`` (query ``t`` sees the keys ``s`` with ``0 <= t - s <
window``) so is one whose keys all lie before the window of all its queries.
The live tiles of a row (of a column in the keys-major pass) are one
contiguous run, short at one end of the causal triangle and long at the other
(under a window: no longer than the band), so one step of the outer block
axis walks row ``i`` and then row ``n - 1 - i`` and the sequential inner axis
is as long as the longest such pair: at the cells' shapes every step of a
global call is live, and 7 of 8 of a windowed one's (the band's first rows
are short).  Which row and tile a step is comes from its two program ids by a
compare and a subtract (``_paired_walk``; no scalar-prefetched schedule, no
operand added); the accumulators are zeroed at each row's first tile and the
output written at its last, so an output block is visited in one contiguous
run and its tiles are folded in ascending order, as in a rectangle.  A step
past both walks names the block already in VMEM and computes nothing.  A call
that would save no step (no causal mask, one block, every run equally long)
keeps the rectangular grid ``(heads, L/block_q, L/block_k)``, whose dead
steps run, copy nothing (their BlockSpec index is clamped into the row's live
run) and compute nothing.  A live tile that the diagonal, the window's far
edge and the padded tail do not cross is all live and takes an unmasked path
(no iotas, compare or select); only the others build a mask.

Grouped kv heads.  k and v may have fewer heads than q (``Hq % Hkv == 0``;
query head ``h`` reads kv head ``h // (Hq // Hkv)``).  They are never repeated
in HBM: the grids run over ``B*Hq`` query heads and the k/v BlockSpecs' index
maps send a query head's index to its kv head's (``_kv_head``); the dK/dV
pass runs over ``B*Hkv`` kv heads and its sequential inner axis walks a
column's q blocks for each of the group's query heads in turn, so the group's
sum forms in the VMEM accumulators and dK / dV leave at ``Hkv`` heads.

Layouts are the ones Mosaic accepts: per-row softmax statistics are
``[block_q, 1]`` columns inside a kernel (they broadcast along lanes against
the ``[block_q, block_k]`` scores) and lane-dense ``[B*H, 1, L]`` rows in HBM,
blocked ``(1, 1, block_q)``; the dK/dV pass works keys-major (``[block_k,
block_q]``) so every matmul is a plain or rhs-transposed one.

``interpret=True`` runs the same kernels on CPU (how tests exercise them);
``tests/test_tpu_lowering.py`` cross-lowers them for the TPU without a chip.
:func:`attention` picks the kernel when the default backend is ``tpu`` and
the fused-XLA reference elsewhere; ragged lengths pad to their lane rounding
(to a common multiple of explicit blocks where those are given).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import kept

_LANES = 128  # TPU vreg lane width: the alignment of every block's last dim
_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_NN = (((1,), (0,)), ((), ()))  # a @ b


def reference_attention(q, k, v, causal: bool = True, window: int | None = None,
                        block_diffusion: int | None = None):
    """Fused-XLA attention, [B, L, H, D] layout (fallback, test oracle, and
    the single fused-attention definition — models/transformer.py delegates
    here).  v may have another width than q and k.  k and v may have fewer
    heads than q (grouped-query attention: query head ``h`` reads kv head
    ``h // (Hq // Hkv)``; the repeat is written out here).  ``window``: query
    ``t`` sees the keys ``s`` with ``0 <= t - s < window`` (causal only).
    ``block_diffusion``: the mask of :func:`block_diffusion_mask` over the
    2L positions of k and v, written out; its first L rows where q holds the
    noised half's queries alone."""
    d = q.shape[-1]
    group = _kv_group(q, k, causal, window)
    if group > 1:
        k, v = (jnp.repeat(x, group, axis=2) for x in (k, v))
    scores = jnp.einsum("blhd,bmhd->bhlm", q, k).astype(jnp.float32) / jnp.sqrt(
        jnp.float32(d)
    )
    if block_diffusion is not None:
        mask = block_diffusion_mask(k.shape[1] // 2, block_diffusion)
        if q.shape[1] != k.shape[1]:
            mask = mask[:q.shape[1]]
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
    elif causal:
        L, M = q.shape[1], k.shape[1]
        mask = jnp.tril(jnp.ones((L, M), dtype=bool))
        if window is not None:
            mask = mask & ~jnp.tril(jnp.ones((L, M), dtype=bool), -window)
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhlm,bmhd->blhd", probs, v)


def block_diffusion_mask(L: int, block_len: int):
    """[2L, 2L] bool, queries by keys, of block-diffusion training over
    ``[x_noised ; x_clean]`` (BD3-LM, arXiv:2503.09573): position ``t`` of
    either half is in block ``t // block_len``; a clean query sees the clean
    keys of its own and earlier blocks, a noised query the noised keys of its
    own block and the clean keys of the blocks strictly before it.  The
    oracle's form; the kernels never build it."""
    blk = jnp.arange(L) // block_len
    q_blk, k_blk = blk[:, None], blk[None, :]
    noised_rows = jnp.concatenate([q_blk == k_blk, q_blk > k_blk], axis=1)
    clean_rows = jnp.concatenate([jnp.zeros((L, L), bool), q_blk >= k_blk], axis=1)
    return jnp.concatenate([noised_rows, clean_rows], axis=0)


def _kv_group(q, k, causal, window):
    """Query heads a kv head serves (1: multi-head attention), from the
    operands' head counts; checks what every entry point asks of them."""
    Hq, Hkv = q.shape[2], k.shape[2]
    if Hq % Hkv:
        raise ValueError(f"{Hq} query heads are no multiple of {Hkv} key/value heads")
    if window is not None and (not causal or window < 1):
        raise ValueError(f"a window is a causal one of at least 1 key: {window}, causal={causal}")
    return Hq // Hkv


def _col_to_row(x):
    """``[n, 1]`` column -> ``[1, n]`` row (sublanes -> lanes) as an aligned
    2-D transpose of the lane-replicated column, the relayout Mosaic has."""
    return jnp.broadcast_to(x, (x.shape[0], _LANES)).T[:1]


def _fold_block(s, v, m_ref, l_ref, acc_ref, rows=...):
    """Fold one score block ``s`` [bq, bk] (dead entries ``-inf``) and its
    values ``v`` [bk, D] into the running online-softmax state held in VMEM
    scratch: ``m``/``l`` [bq, 1] f32 columns, ``acc`` [bq, D] f32
    (unnormalized); ``rows``: the slice of that state ``s`` stands for (all of
    it by default).  Shared by the forward and the ring shard-update kernel
    so the two cannot drift."""
    m = m_ref[rows]
    new_m = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    # rows with every position masked keep a -inf max; shift by a finite one,
    # so that a dead entry (and a first block's -inf history) is exp(-inf) = 0
    # with no select over the block
    safe_m = jnp.where(new_m > -jnp.inf, new_m, 0.0)
    p = jnp.exp(s - safe_m)
    corr = jnp.exp(m - safe_m)
    m_ref[rows] = new_m
    l_ref[rows] = l_ref[rows] * corr + jnp.sum(p, axis=-1, keepdims=True)
    # matmuls stay in the input dtype (bf16 rides the MXU at full rate)
    # with f32 accumulation; softmax state is f32 throughout
    acc_ref[rows] = acc_ref[rows] * corr + jax.lax.dot_general(
        p.astype(v.dtype), v, _NN, preferred_element_type=jnp.float32)


# -- tiling ------------------------------------------------------------------
# Every kernel walks the (q block, k block) tiles of one head.  Three
# predicates of the tile's indices, written once for the kernels, their
# BlockSpec index maps and the trace-time gauges (program ids, or numpy
# index grids): whether a tile holds any live pair, whether all of it is
# live, and the mask of one that is neither.

def _tile_live(qi, kj, *, block_q, block_k, causal, valid_len, window=None):
    """Tile (qi, kj) holds at least one live (query, key) pair: neither block
    is all padding, under the causal mask the tile's first key is not after
    its last query and, under a window, its last key is not before the window
    of its first query.  A dead tile's step runs no FLOPs."""
    live = (kj * block_k < valid_len) & (qi * block_q < valid_len)
    if causal:
        live = live & (kj * block_k <= (qi + 1) * block_q - 1)
    if window is not None:
        live = live & ((kj + 1) * block_k - 1 > qi * block_q - window)
    return live


def _tile_interior(qi, kj, *, block_q, block_k, causal, valid_len, window=None):
    """Every pair of tile (qi, kj) is live: its keys end inside ``valid_len``,
    under the causal mask its last key is not after its first query and, under
    a window, its first key is inside the window of its last query.  Such a
    tile needs no mask.  (Padded QUERY rows need none either: their outputs
    are cut off and their dO is zero.)"""
    inside = (kj + 1) * block_k <= valid_len
    if causal:
        inside = inside & ((kj + 1) * block_k - 1 <= qi * block_q)
    if window is not None:
        inside = inside & (kj * block_k > (qi + 1) * block_q - 1 - window)
    return inside


def _tile_mask(qi, kj, shape, q_dim, *, block_q, block_k, causal, valid_len,
               window=None):
    """Live entries of a tile the diagonal, the window's far edge or the
    padded tail crosses; ``shape`` has queries along ``q_dim`` and keys along
    the other."""
    k_idx = jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_dim)
    live = k_idx < valid_len - kj * block_k  # padded tail keys never contribute
    if causal:
        q_idx = jax.lax.broadcasted_iota(jnp.int32, shape, q_dim)
        live = live & (q_idx - k_idx >= kj * block_k - qi * block_q)
        if window is not None:
            live = live & (q_idx - k_idx < window + kj * block_k - qi * block_q)
    return live


def _div(a, b):
    """``a // b`` of non-negative integers: one ``div`` on a program id (no
    sign fix), numpy's on a trace-time index grid."""
    return jax.lax.div(a, b) if isinstance(a, jax.Array) else a // b


def _xp(i):
    """The tile arithmetic below runs on program ids (kernels, index maps) and,
    at trace time, on numpy index grids (``_tiling``'s grid and gauges, tests)."""
    return jnp if isinstance(i, jax.Array) else np


def _live_k_run(i, *, block_q, block_k, causal, valid_len, window=None):
    """(first, last) K/V block of row ``i`` of a queries-major grid: its live
    tiles are that one contiguous run, from the tile that holds the first key
    of the first query's window to the one the diagonal or the tail ends in.
    (A row of padding alone has no live tile and still a run of one or more.)"""
    xp = _xp(i)
    last = (valid_len - 1) // block_k
    if causal:
        last = xp.minimum(last, _div((i + 1) * block_q - 1, block_k))
    if window is None:
        return 0, last
    first = _div(xp.maximum(i * block_q - window + 1, 0), block_k)
    return xp.minimum(first, last), last


def _live_q_run(j, *, block_q, block_k, causal, valid_len, window=None):
    """(first, last) q-side block (q, dO, lse, delta) of column ``j`` of the
    keys-major grid: its live tiles start under the causal mask at the
    diagonal and end under a window with the last query that sees the
    column's last key."""
    xp = _xp(j)
    last = (valid_len - 1) // block_q
    if window is not None:
        last = xp.minimum(last, _div((j + 1) * block_k + window - 2, block_q))
    first = xp.minimum(_div(j * block_k, block_q), last) if causal else 0
    return first, last


def _live_k_block(i, j, **tile):
    """Index map of a K/V block in the rectangular queries-major grid: ``j``
    clamped into the live run of row ``i``, so a dead step names a block that
    is resident, or the row's first live one, which it prefetches, and the
    pipeline copies nothing else."""
    first, last = _live_k_run(i, **tile)
    xp = _xp(j)
    return xp.minimum(j, last) if tile.get("window") is None else xp.clip(j, first, last)


def _live_q_block(i, j, **tile):
    """Index map of a q-side block in the rectangular keys-major grid: ``i``
    clamped into the live run of column ``j`` (the dead steps before the
    diagonal prefetch it)."""
    return _xp(i).clip(i, *_live_q_run(j, **tile))


# VMEM a step may plan for: the 16 MiB that Mosaic gives a kernel by default on
# a v5e (its scoped limit; no call raises it, so the same rule serves chips
# with less VMEM behind that default).
_VMEM_BUDGET = 16 * 2**20
# What a step of each kernel holds: q-sized and k-sized blocks the pipeline
# double-buffers in the input dtype, and q-sized and k-sized f32 accumulators.
# Each entry counts (blocks of the q/k width, blocks of the v width): the two
# widths differ under latent attention (q, k of 192, v of 128).
_FOOTPRINT = {
    "flash_fwd": ((1, 1), (1, 1), (0, 1), (0, 0)),      # q, out | k, v | acc
    "flash_bwd_dq": ((2, 1), (1, 1), (1, 0), (0, 0)),   # q, dq, dO | k, v | acc
    "flash_bwd_dkv": ((1, 1), (2, 2), (0, 0), (1, 1)),  # q, dO | k, dk, v, dv | dk, dv
    # block diffusion: the noised keys and values of a q block (queries-major) or
    # of the column (keys-major, with their gradients and accumulators) beside
    "bd_flash_fwd": ((2, 2), (1, 1), (0, 1), (0, 0)),
    "bd_flash_bwd_dq": ((3, 2), (1, 1), (1, 0), (0, 0)),
    "bd_flash_bwd_dkv": ((1, 1), (4, 4), (0, 0), (2, 2)),
}
# f32 [block_q, block_k] intermediates a step is reckoned to hold at once
# (scores -> probs in place, one more, a bf16 copy for the MXU).  Calibrated
# against Mosaic at 64 x 2,048 x 128 bf16: with it every pair that compiled is
# inside the budget (1,024 x 1,024 and 2,048 x 512 in all three kernels, 512 x
# 2,048 forward and dQ) and the one that ran out of VMEM (dK/dV at 512 x 2,048)
# is not.
_SCORE_TILES = 2.5
# The largest (block_q, block_k) each kernel is given: where the sweep on the
# v5e at the cells' shape (64 x 2,048 x 128 bf16; PERF.md section 6, PR 26)
# read its best time.  The forward and dQ (queries-major: their per-row
# softmax columns cost a step the same whatever its keys) want the widest key
# block that still skips half the causal square; dK/dV is flat from 512 up.
_BLOCK_TARGET = {
    "flash_fwd": (1024, 1024),
    "flash_bwd_dq": (1024, 1024),
    "flash_bwd_dkv": (512, 512),
}


def _lane_round(n):
    return -(-n // _LANES) * _LANES


def _vmem_bytes(kernel, block_q, block_k, D, itemsize, Dv=None):
    """Upper estimate of the VMEM one grid step of ``kernel`` holds; ``D`` is
    the width of q and k, ``Dv`` that of v and the output (default: ``D``)."""
    # the last dim pads to whole lanes
    widths = (_lane_round(D), _lane_round(D if Dv is None else Dv))
    q_pipe, k_pipe, q_acc, k_acc = (
        sum(n * w for n, w in zip(counts, widths)) for counts in _FOOTPRINT[kernel])
    stats = 2 * 4 * _LANES * block_q  # m, l columns (lane-padded); lse, delta rows
    return int(2 * itemsize * (q_pipe * block_q + k_pipe * block_k)
               + 4 * (q_acc * block_q + k_acc * block_k)
               + 4 * _SCORE_TILES * block_q * block_k + stats)


def _choose_blocks(kernel, L, D, dtype, Dv=None):
    """(block_q, block_k) of ``kernel`` for a sequence of ``L``, from what the
    call can see.  Each block is the largest multiple of 128 that divides the
    lane-rounded length and is at most the kernel's ``_BLOCK_TARGET``, so no
    length pads beyond its lane rounding (1,023 -> 1,024, 8 -> 128) and every
    kernel of one call shares one padded length; a length whose lane count
    is prime (1,664 = 13 x 128) falls to 128 and pays the grid-step floor
    rather than padding.  The larger block then steps down to the next divisor
    until the step's ``_vmem_bytes`` is inside ``_VMEM_BUDGET`` (wide heads,
    f32 inputs)."""
    lanes = -(-L // _LANES)
    fits = [n * _LANES for n in range(1, lanes + 1) if lanes % n == 0]
    itemsize = jnp.dtype(dtype).itemsize
    block_q, block_k = (max(b for b in fits if b <= target)
                        for target in _BLOCK_TARGET[kernel.removeprefix("bd_")])
    while (_vmem_bytes(kernel, block_q, block_k, D, itemsize, Dv) > _VMEM_BUDGET
           and max(block_q, block_k) > _LANES):
        if block_q >= block_k:
            block_q = max(b for b in fits if b < block_q)
        else:
            block_k = max(b for b in fits if b < block_k)
    return block_q, block_k


def _fit_block(block, L):
    """Clamp a block to the lane-rounded length: a sequence shorter than the
    block (``model.init`` traces L=8) pads up to one full-width block, not
    down to a sub-tile shape Mosaic would have to relayout."""
    return min(block, _lane_round(L))


def _geometry(L, D, dtype, block_q, block_k, Dv=None):
    """``{kernel: (block_q, block_k)}`` and the padded length all three
    kernels of a call share.  An explicit block is every kernel's; one left
    ``None`` is each kernel's own choice (:func:`_choose_blocks`).  The
    length pads to a common multiple of ALL blocks: the grids are
    (Lp//block_q, Lp//block_k), so a padded length one block does not divide
    would silently truncate that axis (keys never folded in / rows never
    written).  Chosen blocks all divide the lane-rounded length."""
    blocks = {}
    for kernel in _BLOCK_TARGET:
        own_q, own_k = _choose_blocks(kernel, L, D, dtype, Dv)
        blocks[kernel] = (_fit_block(block_q, L) if block_q else own_q,
                          _fit_block(block_k, L) if block_k else own_k)
    m = math.lcm(*(b for pair in blocks.values() for b in pair))
    return blocks, -(-L // m) * m


def _rect_walk(n, inner, reps, clamp):
    """The rectangular grid ``(n, reps * inner)``: step ``(o, s)`` is member
    (row, or column in the keys-major pass) ``o`` at tile ``s``, for each of
    ``reps`` query heads in turn (``s = g * inner + tile``).  A dead tile's
    step runs and names the block ``clamp`` gives."""
    def tile(s):
        return s if reps == 1 else jax.lax.rem(s, inner)

    def step(o, s):
        return o, tile(s), s == 0, s == reps * inner - 1, None

    def block(o, s):
        return o, clamp(o, tile(s)), 0 if reps == 1 else jax.lax.div(s, inner)

    return (n, reps * inner), step, block


def _paired_walk(n, steps, reps, run):
    """The grid ``(ceil(n / 2), steps)`` that holds live tiles alone: outer
    step ``o`` walks member ``o``'s live run (``run(o)``: first, last), once
    for each of ``reps`` query heads, and then member ``n - 1 - o``'s, a short
    one of the causal triangle with a long one, so that every pair takes
    (nearly) the same ``steps``.  Which member and tile a step is comes from
    its two program ids by a compare and a subtract; a step past both walks
    (a window's first rows are short) stays on the pair's last block and is
    not ``inside``; an odd ``n``'s middle member walks alone."""
    def locate(o, s):
        xp = _xp(s)
        a, b = o, n - 1 - o
        (first_a, last_a), (first_b, last_b) = run(a), run(b)
        len_a, len_b = last_a - first_a + 1, last_b - first_b + 1
        in_a = s < len_a * reps
        run_len = xp.where(in_a, len_a, len_b)
        pos = xp.where(in_a, s, s - len_a * reps)
        inside = pos < run_len * reps
        if n % 2:
            inside = inside & (in_a | (a != b))
        at = xp.minimum(pos, run_len * reps - 1)
        rep = 0 if reps == 1 else _div(at, run_len)
        tile = xp.where(in_a, first_a, first_b) + at - rep * run_len
        return xp.where(in_a, a, b), tile, rep, pos, run_len * reps, inside

    def step(o, s):
        member, tile, _, pos, length, inside = locate(o, s)
        return member, tile, inside & (pos == 0), inside & (pos == length - 1), inside

    def block(o, s):
        return locate(o, s)[:3]

    return (-(-n // 2), steps), step, block


def _tiling(kernel, Lp, blocks, causal, valid_len, window=None, group=1, queries=None):
    """One call's tile parameters (the keywords of the ``_tile_*`` predicates),
    its grid's two block axes and the two functions of their program ids that
    say where a step is: ``step(o, s)`` -> (member, tile, first, last, inside)
    for the kernel (the member's accumulators start at ``first`` and leave at
    ``last``; ``inside`` None: every step is) and ``block(o, s)`` -> (member,
    tile, query head of the kv head's group) for the index maps.  A member is
    a q block and a tile a K/V block, or the other way round in the keys-major
    ``flash_bwd_dkv``, whose walk repeats for each of ``group`` query heads.

    A causal call's grid is the paired walk of its live runs wherever that
    takes fewer steps than the rectangle; any other call keeps the rectangle.

    Leaves what was chosen as gauges per kernel name (trace time: Python, from
    shapes): the blocks, the steps the grid runs for one head of its first
    axis, live steps over those, the window (0: none) and the query heads a kv
    head serves.  A windowed call's gauges carry the label ``window`` beside
    ``kernel``, so that a model with both kinds of layer keeps both readings;
    a block-diffusion call of one query set (``queries``: ``"noised"``) carries
    ``queries`` likewise."""
    from ..core import obs

    block_q, block_k = blocks
    tile = dict(block_q=block_q, block_k=block_k, causal=causal, valid_len=valid_len)
    labels = {"kernel": kernel}
    if window is not None:
        tile["window"] = labels["window"] = int(window)
    if queries is not None:
        labels["queries"] = queries
    n_qb, n_kb = Lp // block_q, Lp // block_k
    live = _tile_live(np.arange(n_qb)[:, None], np.arange(n_kb)[None, :], **tile)
    if kernel.endswith("flash_bwd_dkv"):
        n, inner, reps = n_kb, n_qb, group
        run = functools.partial(_live_q_run, **tile)
        clamp = lambda j, i: _live_q_block(i, j, **tile)
    else:
        n, inner, reps = n_qb, n_kb, 1
        run = functools.partial(_live_k_run, **tile)
        clamp = functools.partial(_live_k_block, **tile)
    first, last = run(np.arange(n))
    length = np.broadcast_to(last - first + 1, (n,))
    members = np.arange(n)  # an odd n's middle member is its own partner and walks once
    pair = length + np.where(members == members[::-1], 0, length[::-1])
    if causal and n > 1 and -(-n // 2) * pair.max() < n * inner:
        grid, step, block = _paired_walk(n, int(pair.max()) * reps, reps, run)
    else:
        grid, step, block = _rect_walk(n, inner, reps, clamp)
    grid_steps = grid[0] * grid[1]
    obs.gauge_set("flash.block_q", block_q, labels)
    obs.gauge_set("flash.block_k", block_k, labels)
    obs.gauge_set("flash.grid_steps", grid_steps, labels)
    obs.gauge_set("flash.live_step_share", float(np.sum(live)) * reps / grid_steps, labels)
    obs.gauge_set("flash.window", window or 0, labels)
    obs.gauge_set("flash.kv_group", group, labels)
    return tile, grid, step, block


def _kv_head(group):
    """Grid index of a query head -> that of its kv head, both batch-major
    ([B*Hq] and [B*Hkv]): ``b // group``; the identity for equal head counts."""
    return (lambda b: b) if group == 1 else (lambda b: jax.lax.div(b, group))


# batch·heads and the outer block axis are independent; the inner one carries
# the accumulators (free on a v5e's single core, required to split a megacore)
_GRID_SEMANTICS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


def _to_bh(x, B, L, H, D, Lp, Dp=None):  # [B, L, H, D] -> [B*H, Lp, Dp or D]
    x = x.transpose(0, 2, 1, 3).reshape(B * H, L, D)
    Dp = D if Dp is None else Dp
    if Lp != L or Dp != D:
        x = jnp.pad(x, ((0, 0), (0, Lp - L), (0, Dp - D)))
    return x


def _from_bh(x, B, L, H, D):  # [B*H, Lp, >=D] -> [B, L, H, D]
    return x[:, :L, :D].reshape(B, H, L, D).transpose(0, 2, 1, 3)


def _head_widths(q, v):
    """(D, Dv, Dp): the width of q and k, that of v and the output, and the
    width q and k are zero-padded to where theirs is no lane multiple and
    differs from v's (latent attention: 192 -> 256; zero columns leave q.k
    as it is).  Equal widths are left alone, as they always were."""
    D, Dv = q.shape[-1], v.shape[-1]
    return D, Dv, (_lane_round(D) if D != Dv else D)


def _scratch(block_q, D):
    return [
        pltpu.VMEM((block_q, 1), jnp.float32),
        pltpu.VMEM((block_q, 1), jnp.float32),
        pltpu.VMEM((block_q, D), jnp.float32),
    ]


def _when_live(qi, kj, tile, step, inside=None):
    """Run ``step(masked)`` for a live tile: unmasked where the whole tile is
    live, with the mask only where the diagonal or the padded tail crosses.
    ``inside``: the grid step is one of its walk's (None: every step is)."""
    live = _tile_live(qi, kj, **tile)
    if inside is not None:
        live = live & inside
    interior = _tile_interior(qi, kj, **tile)
    pl.when(live & interior)(functools.partial(step, False))
    pl.when(live & jnp.logical_not(interior))(functools.partial(step, True))


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref, *,
                  walk, scale, tile):
    """Grid cell (bh, o, s), which ``walk`` places at q block qi and K/V block
    kj: fold the K/V block into the q block's online softmax state (scratch
    persists across the sequential inner dimension, which walks the K/V blocks
    of one q block and then, in the paired grid, those of a second)."""
    qi, kj, first, last, inside = walk(pl.program_id(1), pl.program_id(2))

    @pl.when(first)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _attend(masked):
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0], _NT, preferred_element_type=jnp.float32
        ) * scale  # [bq, bk]
        if masked:
            s = jnp.where(_tile_mask(qi, kj, s.shape, 0, **tile), s, -jnp.inf)
        _fold_block(s, v_ref[0], m_ref, l_ref, acc_ref)

    _when_live(qi, kj, tile, _attend, inside)

    @pl.when(last)
    def _finish():
        l = l_ref[...]
        m = m_ref[...]
        o_ref[0] = (acc_ref[...] * (1.0 / jnp.maximum(l, 1e-20))).astype(o_ref.dtype)
        # per-row log-sum-exp of the SCALED scores — the softmax statistic
        # the backward kernels re-materialize P from (-inf for dead rows)
        lse = jnp.where(
            l > 0, jnp.where(m > -jnp.inf, m, 0.0) + jnp.log(jnp.maximum(l, 1e-38)),
            -jnp.inf,
        )
        lse_ref[0] = _col_to_row(lse)


def _specs(D, Dv, block_q, block_k, q_index, k_index):
    """BlockSpecs of a q-sized and a k-sized block at the q/k width ``D`` and
    at the v width ``Dv`` (one object each where the widths are equal)."""
    def spec(block, width, index):
        return pl.BlockSpec((1, block, width), lambda *ids: (*index(*ids), 0))

    q_spec, k_spec = spec(block_q, D, q_index), spec(block_k, D, k_index)
    if Dv == D:
        return q_spec, k_spec, q_spec, k_spec
    return q_spec, k_spec, spec(block_q, Dv, q_index), spec(block_k, Dv, k_index)


def _fwd_call(qb, kb, vb, blocks, causal, valid_len, interpret, scale=None,
              window=None):
    """``flash_fwd`` over ``[B*Hq, Lp, D]`` q, ``[B*Hkv, Lp, D]`` k and
    ``[B*Hkv, Lp, Dv]`` v: (out [B*Hq, Lp, Dv], lse [B*Hq, 1, Lp]).  ``scale``
    defaults to that of the operands' own width (a caller that zero-padded q
    and k gives the real one)."""
    BH, Lp, D = qb.shape
    Dv = vb.shape[-1]
    block_q, block_k = blocks
    group = BH // kb.shape[0]
    tile, grid, walk, block = _tiling("flash_fwd", Lp, blocks, causal, valid_len, window, group)
    kv_head = _kv_head(group)
    q_spec, k_spec, o_spec, v_spec = _specs(
        D, Dv, block_q, block_k, lambda b, o, s: (b, block(o, s)[0]),
        lambda b, o, s: (kv_head(b), block(o, s)[1]))
    return pl.pallas_call(
        functools.partial(_flash_kernel, walk=walk,
                          scale=float(scale or 1.0 / (D**0.5)), tile=tile),
        grid=(BH, *grid),
        in_specs=[q_spec, k_spec, v_spec],
        out_specs=[
            o_spec,
            pl.BlockSpec((1, 1, block_q), lambda b, o, s: (b, 0, block(o, s)[0])),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Lp, Dv), qb.dtype),
            jax.ShapeDtypeStruct((BH, 1, Lp), jnp.float32),
        ],
        scratch_shapes=_scratch(block_q, Dv),
        compiler_params=_GRID_SEMANTICS,
        interpret=interpret,
        name="flash_fwd",
    )(qb, kb, vb)


def _flash_forward(q, k, v, causal, block_q, block_k, interpret, window=None,
                   with_lse: bool = False):
    B, L, H, _ = q.shape
    Hkv = H // _kv_group(q, k, causal, window)
    D, Dv, Dp = _head_widths(q, v)
    blocks, Lp = _geometry(L, Dp, q.dtype, block_q, block_k, Dv)
    qb, kb = _to_bh(q, B, L, H, D, Lp, Dp), _to_bh(k, B, L, Hkv, D, Lp, Dp)
    vb = _to_bh(v, B, L, Hkv, Dv, Lp)
    out, lse = _fwd_call(qb, kb, vb, blocks["flash_fwd"], causal, L, interpret,
                         scale=1.0 / (D**0.5), window=window)
    out = _from_bh(out, B, L, H, Dv)
    return (out, lse) if with_lse else out


def _block_grads(q, k, v, do, lse, delta, mask, *, scale, keys_major):
    """Shared backward block math: re-materialize this tile's probs P from
    (q, k, lse) and form dS WITHOUT its factor ``scale`` (each kernel applies
    it once, to the accumulated gradient) — used identically by the dQ and
    dK/dV kernels so the two gradients cannot desynchronize.

    ``keys_major=False`` (dQ pass) works on ``[bq, bk]`` blocks with
    ``lse``/``delta`` as ``[bq, 1]`` columns; ``keys_major=True`` (dK/dV
    pass) on the transposed ``[bk, bq]`` blocks with ``[1, bq]`` rows, so
    that pass's accumulating matmuls need no transposed left operand.
    ``mask`` is the tile's live entries, or None for a tile that is all live.

    ``lse`` is finite for any q row that attends >=1 live key — which
    includes padded q-tail rows (the live mask constrains keys, not
    queries).  Padded-tail GRADIENT correctness therefore rests on dO (and
    hence delta) being zero-padded by _to_bh, not on lse masking; the
    finiteness guard only covers rows with no live keys at all (e.g. the
    first rows of a fully-masked causal block), which no all-live tile has."""
    if keys_major:
        s = jax.lax.dot_general(k, q, _NT, preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(v, do, _NT, preferred_element_type=jnp.float32)
    else:
        s = jax.lax.dot_general(q, k, _NT, preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, _NT, preferred_element_type=jnp.float32)
    if mask is None:
        p = jnp.exp(s * scale - lse)
    else:
        row_live = lse > -jnp.inf
        p = jnp.where(mask & row_live,
                      jnp.exp(s * scale - jnp.where(row_live, lse, 0.0)), 0.0)
    return p, p * (dp - delta)


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, acc_ref, *, walk, scale, tile):
    """Grid cell (bh, o, s), placed by ``walk`` like the forward's: accumulate
    q block qi's gradient over its K/V blocks (acc persists in VMEM scratch)."""
    qi, kj, first, last, inside = walk(pl.program_id(1), pl.program_id(2))

    @pl.when(first)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _accum(masked):
        k = k_ref[0]
        mask = (_tile_mask(qi, kj, (tile["block_q"], tile["block_k"]), 0, **tile)
                if masked else None)
        _, ds = _block_grads(
            q_ref[0], k, v_ref[0], do_ref[0],
            lse_ref[0, 0][:, None], delta_ref[0, 0][:, None], mask,
            scale=scale, keys_major=False,
        )
        acc_ref[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, _NN, preferred_element_type=jnp.float32)

    _when_live(qi, kj, tile, _accum, inside)

    @pl.when(last)
    def _finish():
        dq_ref[0] = (acc_ref[...] * scale).astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, dk_acc, dv_acc, *, walk, scale, tile):
    """Grid cell (kv head, o, s), which ``walk`` places at K/V block kj and q
    block qi: accumulate the K/V block's gradients over its q blocks
    (sequential innermost axis) of every query head the kv head serves, one
    head's blocks after the other's, so a group's sum is formed in the VMEM
    accumulators and dK / dV leave at the kv heads' count.  p is zero wherever
    q_pos < k_pos, so the q blocks entirely above kj are dead tiles."""
    kj, qi, first, last, inside = walk(pl.program_id(1), pl.program_id(2))

    @pl.when(first)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def _accum(masked):
        q = q_ref[0]
        do = do_ref[0]
        mask = (_tile_mask(qi, kj, (tile["block_k"], tile["block_q"]), 1, **tile)
                if masked else None)
        p_t, ds_t = _block_grads(
            q, k_ref[0], v_ref[0], do, lse_ref[0], delta_ref[0], mask,
            scale=scale, keys_major=True,
        )  # both [bk, bq]
        dv_acc[...] += jax.lax.dot_general(
            p_t.astype(do.dtype), do, _NN, preferred_element_type=jnp.float32)
        dk_acc[...] += jax.lax.dot_general(
            ds_t.astype(q.dtype), q, _NN, preferred_element_type=jnp.float32)

    _when_live(qi, kj, tile, _accum, inside)

    @pl.when(last)
    def _finish():
        dk_ref[0] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _dq_call(qb, kb, vb, dob, lse, delta, blocks, causal, valid_len, interpret,
             scale=None, window=None):
    """``flash_bwd_dq`` over ``[B*Hq, Lp, D]`` q, ``[B*Hkv, Lp, D]`` k,
    ``[B*Hkv, Lp, Dv]`` v, ``[B*Hq, Lp, Dv]`` dO and ``[B*Hq, 1, Lp]`` row
    statistics: dQ, queries-major like the forward."""
    BH, Lp, D = qb.shape
    Dv = vb.shape[-1]
    block_q, block_k = blocks
    group = BH // kb.shape[0]
    tile, grid, walk, block = _tiling("flash_bwd_dq", Lp, blocks, causal, valid_len, window,
                                      group)
    kv_head = _kv_head(group)
    q_spec, k_spec, do_spec, v_spec = _specs(
        D, Dv, block_q, block_k, lambda b, o, s: (b, block(o, s)[0]),
        lambda b, o, s: (kv_head(b), block(o, s)[1]))
    row_spec = pl.BlockSpec((1, 1, block_q), lambda b, o, s: (b, 0, block(o, s)[0]))
    return pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, walk=walk,
                          scale=float(scale or 1.0 / (D**0.5)), tile=tile),
        grid=(BH, *grid),
        in_specs=[q_spec, k_spec, v_spec, do_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((BH, Lp, D), qb.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        compiler_params=_GRID_SEMANTICS,
        interpret=interpret,
        name="flash_bwd_dq",
    )(qb, kb, vb, dob, lse, delta)


def _dkv_call(qb, kb, vb, dob, lse, delta, blocks, causal, valid_len, interpret,
              scale=None, window=None):
    """``flash_bwd_dkv`` over the same operands: (dK, dV) at the kv heads'
    count, keys-major — the grid's first axis is the kv heads and the q-side
    blocks follow the inner axis, which walks the q blocks of each query head
    of the kv head's group in turn (``_flash_bwd_dkv_kernel``)."""
    BH, Lp, D = kb.shape
    Dv = vb.shape[-1]
    block_q, block_k = blocks
    group = qb.shape[0] // BH
    tile, grid, walk, block = _tiling("flash_bwd_dkv", Lp, blocks, causal, valid_len, window,
                                      group)

    def q_at(b, o, s):
        _, qi, g = block(o, s)
        return (b if group == 1 else b * group + g), qi

    q_spec, k_spec, do_spec, v_spec = _specs(
        D, Dv, block_q, block_k, q_at, lambda b, o, s: (b, block(o, s)[0]))

    def row_at(b, o, s):
        head, qi = q_at(b, o, s)
        return head, 0, qi

    row_spec = pl.BlockSpec((1, 1, block_q), row_at)
    return pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, walk=walk,
                          scale=float(scale or 1.0 / (D**0.5)), tile=tile),
        grid=(BH, *grid),
        in_specs=[q_spec, k_spec, v_spec, do_spec, row_spec, row_spec],
        out_specs=[k_spec, v_spec],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Lp, D), qb.dtype),
            jax.ShapeDtypeStruct((BH, Lp, Dv), qb.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((block_k, D), jnp.float32),
                        pltpu.VMEM((block_k, Dv), jnp.float32)],
        compiler_params=_GRID_SEMANTICS,
        interpret=interpret,
        name="flash_bwd_dkv",
    )(qb, kb, vb, dob, lse, delta)


def _flash_backward(q, k, v, out, lse, g, causal, block_q, block_k, interpret,
                    window=None):
    """Pallas flash backward: same blockwise structure as the forward — P is
    re-materialized per block from (q, k, lse), so backward memory is
    O(block² ) per core instead of the O(L²) probs matrix."""
    B, L, H, _ = q.shape
    Hkv = k.shape[2]
    D, Dv, Dp = _head_widths(q, v)
    blocks, Lp = _geometry(L, Dp, q.dtype, block_q, block_k, Dv)
    qb, kb = _to_bh(q, B, L, H, D, Lp, Dp), _to_bh(k, B, L, Hkv, D, Lp, Dp)
    vb = _to_bh(v, B, L, Hkv, Dv, Lp)
    dob, ob = (_to_bh(x, B, L, H, Dv, Lp) for x in (g.astype(q.dtype), out))
    # delta_i = rowsum(dO * O): tiny elementwise pass, fused by XLA
    delta = jnp.sum(dob.astype(jnp.float32) * ob.astype(jnp.float32),
                    axis=-1)[:, None, :]  # [B*H, 1, Lp], like lse
    operands = (qb, kb, vb, dob, lse, delta)
    scale = 1.0 / (D**0.5)
    dq = _dq_call(*operands, blocks["flash_bwd_dq"], causal, L, interpret, scale, window)
    dk, dv = _dkv_call(*operands, blocks["flash_bwd_dkv"], causal, L, interpret, scale,
                       window)
    return (_from_bh(dq, B, L, H, D), _from_bh(dk, B, L, Hkv, D),
            _from_bh(dv, B, L, Hkv, Dv))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = True,
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool = False,
    window: int | None = None,
) -> jnp.ndarray:
    """Pallas blockwise attention. q: [B, L, Hq, D], k: [B, L, Hkv, D], v:
    [B, L, Hkv, Dv] -> [B, L, Hq, Dv] (``Dv`` may differ from ``D``: latent
    attention's 192 / 128; ``Hkv`` may divide ``Hq``: query head ``h`` reads kv
    head ``h // (Hq // Hkv)`` through the k/v BlockSpecs' index maps, k and v
    are never repeated in HBM, and dK / dV come out at ``Hkv`` heads).
    ``window``: query ``t`` sees the keys ``s`` with ``0 <= t - s < window``.
    ``block_q`` / ``block_k`` left ``None`` are chosen per kernel from the
    shape (:func:`_choose_blocks`); ragged L is padded internally, to its
    lane rounding then and to a common multiple of explicit blocks else."""
    return _flash_forward(q, k, v, causal, block_q, block_k, interpret, window)


def _flash_fwd(q, k, v, causal, block_q, block_k, interpret, window):
    out, lse = _flash_forward(q, k, v, causal, block_q, block_k, interpret, window,
                              with_lse=True)
    # a recomputing caller may keep these two (ops/kept.py); q, k and v it rebuilds
    out, lse = kept.tag("flash_fwd", out=out, lse=lse)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, block_q, block_k, interpret, window, res, g):
    q, k, v, out, lse = res
    return _flash_backward(q, k, v, out, lse, g, causal, block_q, block_k,
                           interpret, window)


flash_attention.defvjp(_flash_fwd, _flash_bwd)


def shard_update_reference(q, k, v, q_pos, k_pos, causal, m, l, o):
    """Fused-XLA online-softmax shard update — the SINGLE canonical
    definition of ring attention's per-shard math (parallel/ring_attention
    aliases this as ``_block_attend``), and the recompute path for
    :func:`flash_shard_update`'s backward.

    q: [B, Lq, H, D]; k, v: [B, Lk, H, D]; q_pos/k_pos: [Lq]/[Lk] global
    positions; (m, l, o): running (max [B,H,Lq], denom [B,H,Lq],
    UNNORMALIZED out [B,Lq,H,D]) accumulators, all float32."""
    d = q.shape[-1]
    scores = jnp.einsum("blhd,bmhd->bhlm", q, k).astype(jnp.float32)
    scores = scores / jnp.sqrt(jnp.float32(d))
    live = (k_pos >= 0)[None, :]  # k_pos < 0 marks padding
    if causal:
        live = live & (q_pos[:, None] >= k_pos[None, :])
    scores = jnp.where(live[None, None], scores, -jnp.inf)
    block_max = jnp.max(scores, axis=-1)  # [B, H, Lq]
    new_m = jnp.maximum(m, block_max)
    # guard: rows with every position masked keep -inf max; exp(-inf - -inf)
    # would be nan, so shift by a finite max in that case
    safe_m = jnp.where(jnp.isfinite(new_m), new_m, 0.0)
    p = jnp.exp(scores - safe_m[..., None])  # [B, H, Lq, Lk]
    p = jnp.where(jnp.isfinite(scores), p, 0.0)
    correction = jnp.exp(jnp.where(jnp.isfinite(m), m - safe_m, -jnp.inf))
    correction = jnp.where(jnp.isfinite(m), correction, 0.0)  # first block: no history
    new_l = l * correction + jnp.sum(p, axis=-1)
    pv = jnp.einsum("bhlm,bmhd->blhd", p, v.astype(jnp.float32))
    new_o = o * correction.transpose(0, 2, 1)[..., None] + pv
    return new_m, new_l, new_o


_NO_LIVE_KEY = 2**30  # first-live-key sentinel of an all-padding key block


def _flash_update_kernel(k_first_ref, q_last_ref, q_ref, k_ref, v_ref, qp_ref,
                         kp_ref, mi_ref, li_ref, oi_ref, mo_ref, lo_ref, oo_ref,
                         m_s, l_s, acc_s, *, n_kb, causal, scale):
    """Grid cell (bh, qi, kj): fold K/V block kj into the RUNNING online-
    softmax state (m, l, unnormalized o) carried in from outside — the
    per-chip block update of ring attention.  Positions come from the
    q_pos/k_pos arrays (global ring offsets), not program ids; k_pos < 0
    marks padding and is always dead."""
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _seed():
        m_s[...] = mi_ref[0, 0][:, None]
        l_s[...] = li_ref[0, 0][:, None]
        acc_s[...] = oi_ref[0].astype(jnp.float32)

    # dead-block skip (mirrors _flash_kernel's block_live): an all-padded
    # key block, or a causal block whose earliest live key lies after this
    # q block's last row, contributes nothing — skip both matmuls.  The
    # per-block position summaries are scalar-prefetched (SMEM): positions
    # are data here, and a vector->scalar reduction has no place in a
    # pl.when predicate.
    block_live = k_first_ref[kj] < _NO_LIVE_KEY
    if causal:
        block_live = jnp.logical_and(block_live, q_last_ref[qi] >= k_first_ref[kj])

    @pl.when(block_live)
    def _attend():
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0], _NT, preferred_element_type=jnp.float32
        ) * scale
        k_pos = kp_ref[...]  # [1, bk] i32
        live = k_pos >= 0
        if causal:
            live = live & (qp_ref[0][:, None] >= k_pos)
        _fold_block(jnp.where(live, s, -jnp.inf), v_ref[0], m_s, l_s, acc_s)

    @pl.when(kj == n_kb - 1)
    def _finish():
        mo_ref[0] = _col_to_row(m_s[...])
        lo_ref[0] = _col_to_row(l_s[...])
        oo_ref[0] = acc_s[...].astype(oo_ref.dtype)


def _flash_shard_update_impl(q, k, v, q_pos, k_pos, m, l, o, causal,
                            block_q, block_k, interpret):
    """Pallas block update for ring attention: fold ONE K/V shard into the
    running (m, l, unnormalized o) online-softmax state.

    q: [B, Lq, H, D]; k, v: [B, Lk, H, D]; q_pos/k_pos: [Lq]/[Lk] global
    positions (i32); m, l: [B, H, Lq] f32; o: [B, Lq, H, D] f32
    (UNNORMALIZED accumulator).  Returns updated (m, l, o) — the exact
    math of :func:`fedml_tpu.parallel.ring_attention._block_attend`, block
    by block in VMEM.  Pallas-kernel side of the ring+flash composition:
    the ring moves K/V shards over ICI, this folds each shard locally."""
    B, Lq, H, D = q.shape
    Lk = k.shape[1]
    block_q = _fit_block(block_q, Lq)
    block_k = _fit_block(block_k, Lk)
    # q and k pad independently here: the grid axes are separate, so no
    # common-multiple constraint (unlike _pad_geometry's shared L)
    Lqp = -(-Lq // block_q) * block_q
    Lkp = -(-Lk // block_k) * block_k
    n_qb, n_kb = Lqp // block_q, Lkp // block_k

    qb = _to_bh(q, B, Lq, H, D, Lqp)
    kb = _to_bh(k, B, Lk, H, D, Lkp)
    vb = _to_bh(v, B, Lk, H, D, Lkp)
    qp = jnp.pad(q_pos.astype(jnp.int32), (0, Lqp - Lq))[None, :]
    kp = jnp.pad(k_pos.astype(jnp.int32), (0, Lkp - Lk),
                 constant_values=-1)[None, :]  # padded keys: always dead
    k_first = jnp.min(jnp.where(kp >= 0, kp, _NO_LIVE_KEY).reshape(n_kb, block_k), axis=1)
    q_last = jnp.max(qp.reshape(n_qb, block_q), axis=1)
    mb = jnp.pad(m.reshape(B * H, 1, Lq), ((0, 0), (0, 0), (0, Lqp - Lq)),
                 constant_values=-jnp.inf)
    lb = jnp.pad(l.reshape(B * H, 1, Lq), ((0, 0), (0, 0), (0, Lqp - Lq)))
    ob = _to_bh(o, B, Lq, H, D, Lqp)
    scale = float(1.0 / (D**0.5))
    kernel = functools.partial(
        _flash_update_kernel, n_kb=n_kb, causal=causal, scale=scale,
    )
    stat_spec = pl.BlockSpec((1, 1, block_q), lambda b, i, j, *_: (b, 0, i))
    mo, lo, oo = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,  # k_first, q_last
            grid=(B * H, n_qb, n_kb),
            in_specs=[
                pl.BlockSpec((1, block_q, D), lambda b, i, j, *_: (b, i, 0)),  # q
                pl.BlockSpec((1, block_k, D), lambda b, i, j, *_: (b, j, 0)),  # k
                pl.BlockSpec((1, block_k, D), lambda b, i, j, *_: (b, j, 0)),  # v
                pl.BlockSpec((1, block_q), lambda b, i, j, *_: (0, i)),        # q_pos
                pl.BlockSpec((1, block_k), lambda b, i, j, *_: (0, j)),        # k_pos
                stat_spec,                                                     # m in
                stat_spec,                                                     # l in
                pl.BlockSpec((1, block_q, D), lambda b, i, j, *_: (b, i, 0)),  # o in
            ],
            out_specs=[
                stat_spec,
                stat_spec,
                pl.BlockSpec((1, block_q, D), lambda b, i, j, *_: (b, i, 0)),
            ],
            scratch_shapes=_scratch(block_q, D),
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B * H, 1, Lqp), jnp.float32),
            jax.ShapeDtypeStruct((B * H, 1, Lqp), jnp.float32),
            jax.ShapeDtypeStruct((B * H, Lqp, D), jnp.float32),
        ],
        interpret=interpret,
        name="flash_shard_update",
    )(k_first, q_last, qb, kb, vb, qp, kp, mb, lb, ob)
    m_out = mo[:, 0, :Lq].reshape(B, H, Lq)
    l_out = lo[:, 0, :Lq].reshape(B, H, Lq)
    o_out = _from_bh(oo, B, Lq, H, D)
    return m_out, l_out, o_out


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9, 10, 11))
def _flash_shard_update_vjp(q, k, v, q_pos, k_pos, m, l, o, causal, block_q,
                            block_k, interpret):
    return _flash_shard_update_impl(q, k, v, q_pos, k_pos, m, l, o, causal,
                                    block_q, block_k, interpret)


def _shard_update_fwd(q, k, v, q_pos, k_pos, m, l, o, causal, block_q,
                      block_k, interpret):
    out = _flash_shard_update_impl(q, k, v, q_pos, k_pos, m, l, o, causal,
                                   block_q, block_k, interpret)
    return out, (q, k, v, q_pos, k_pos, m, l, o)


def _shard_update_bwd(causal, block_q, block_k, interpret, res, g):
    # exact gradients by recomputing through the canonical XLA update (the
    # same trade the main kernel made before its dedicated backward): the
    # composed ring+pallas path stays trainable
    import numpy as np

    q, k, v, q_pos, k_pos, m, l, o = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_, m_, l_, o_: shard_update_reference(
            q_, k_, v_, q_pos, k_pos, causal, m_, l_, o_
        ),
        q, k, v, m, l, o,
    )
    dq, dk, dv, dm, dl, do = vjp(g)
    zq = np.zeros(q_pos.shape, dtype=jax.dtypes.float0)  # int positions
    zk = np.zeros(k_pos.shape, dtype=jax.dtypes.float0)
    return dq, dk, dv, zq, zk, dm, dl, do


_flash_shard_update_vjp.defvjp(_shard_update_fwd, _shard_update_bwd)


def flash_shard_update(q, k, v, q_pos, k_pos, m, l, o, causal: bool = True,
                       block_q: int = 128, block_k: int = 128,
                       interpret: bool = False):
    """Differentiable pallas shard update (see _flash_shard_update_impl for
    the kernel): forward in VMEM blocks, backward by exact recompute through
    :func:`shard_update_reference`."""
    return _flash_shard_update_vjp(q, k, v, q_pos, k_pos, m, l, o, causal,
                                   block_q, block_k, interpret)


# -- block diffusion ------------------------------------------------------------
# Training a block-diffusion LM (BD3-LM, arXiv:2503.09573) runs the model over
# ``[x_noised ; x_clean]``: 2L positions, the halves' positions both 0..L-1, cut
# into blocks of ``block_len``.  A clean query sees the clean keys of its own
# and earlier blocks (block-causal); a noised query the clean keys of the
# blocks STRICTLY before its own and the noised keys of its own block
# (:func:`block_diffusion_mask`).  One flat call over the 2L concatenation would
# give a noised row two runs of live tiles; instead the noised queries are
# further query heads of each kv head's group (``2 * group`` heads a kv head,
# the noised ones first), every head's clean keys are the run of the causal
# walk (block-causal, or strictly earlier blocks, is a mask on the diagonal
# tile alone, since ``block_len`` divides the tiles), and the ``block_len``
# in-block keys of a noised query come from a second (k, v) pair, the noised
# half's, read on the diagonal tile only and folded into the same online
# softmax in sub-blocks of 128 rows: a 128 x 128 product each, whose mask is
# the same block diagonal in every sub-block.  The grids, the paired walk,
# ``_kv_head`` and the dead-tile skipping are the causal calls'; the blocks are
# square (q block i and K/V block i hold the same positions).  k and v are
# never repeated or concatenated in HBM; dK / dV of the clean keys sum the
# clean and the noised query heads of a group in VMEM, and those of the noised
# keys come out of the same keys-major pass, from the diagonal tile of its
# noised heads.  The kernels are named ``bd_flash_fwd`` / ``bd_flash_bwd_dq`` /
# ``bd_flash_bwd_dkv`` and leave the causal calls' gauges under those names
# (``flash.kv_group`` counts the ``2 * group`` heads).
#
# The query set is one half or both, read off the shapes: q over L rows against
# k and v over 2L is the noised queries alone (a model's last layer, whose clean
# half nothing reads but its keys and values).  A kv head then has ``group``
# query heads, all noised; the kernels, their names and arity are the same,
# and dK / dV of the clean keys sum over the noised heads alone.  Such a call's
# gauges carry the label ``queries: noised``.

_BD_KERNELS = tuple("bd_" + k for k in _BLOCK_TARGET)


def _bd_check(q, k, v, block_len):
    """(L, group) of a block-diffusion call; checks what the mode asks: k and v
    over 2L, q over both halves (2L) or the noised one (L)."""
    L2, (Lq, Hq, D) = k.shape[1], q.shape[1:]
    if L2 % 2 or Lq not in (L2, L2 // 2) or v.shape[-1] != D:
        raise ValueError(f"block diffusion takes [x_noised ; x_clean] of equal halves, q over "
                         f"both or the first, and equal q/v widths: q {q.shape}, k {k.shape}, "
                         f"v {v.shape}")
    L = L2 // 2
    if block_len < 1 or _LANES % block_len or L % block_len:
        raise ValueError(f"block length {block_len} must divide {_LANES} and the length {L}")
    return L, _kv_group(q, k, True, None)


def _bd_geometry(L, D, dtype, block, block_len):
    """``{kernel: (b, b)}`` and the padded length of a block-diffusion call:
    square blocks, the smaller of each kernel's chosen pair (or ``block`` where
    given), a multiple of ``block_len`` and of its 128-row sub-blocks."""
    blocks = {}
    for kernel in _BD_KERNELS:
        b = _fit_block(block, L) if block else min(_choose_blocks(kernel, L, D, dtype))
        if b % min(_LANES, b) or min(_LANES, b) % block_len:
            raise ValueError(f"block {b} does not hold whole sub-blocks of blocks of {block_len}")
        blocks[kernel] = (b, b)
    m = math.lcm(*(b for b, _ in blocks.values()))
    return blocks, -(-L // m) * m


def _bd_q_to_bh(x, B, L, Hkv, group, Lp):
    """[B, h*L, Hkv*group, D] -> [B*Hkv*h*group, Lp, D] for a query set of h
    halves (2, or 1 for the noised alone): a kv head's noised query heads, then
    its clean ones."""
    halves, D = x.shape[1] // L, x.shape[-1]
    x = x.reshape(B, halves, L, Hkv, group, D).transpose(0, 3, 1, 4, 2, 5)
    x = x.reshape(B * Hkv * halves * group, L, D)
    return jnp.pad(x, ((0, 0), (0, Lp - L), (0, 0))) if Lp != L else x


def _bd_q_from_bh(x, B, L, Hkv, group):
    halves, D = x.shape[0] // (B * Hkv * group), x.shape[-1]
    x = x[:, :L].reshape(B, Hkv, halves, group, L, D).transpose(0, 2, 4, 1, 3, 5)
    return x.reshape(B, halves * L, Hkv * group, D)


def _bd_halves(x, B, L, Lp):
    """[B, 2L, Hkv, D] -> (noised, clean), each [B*Hkv, Lp, D]."""
    H, D = x.shape[2:]
    return _to_bh(x[:, :L], B, L, H, D, Lp), _to_bh(x[:, L:], B, L, H, D, Lp)


def _bd_tile_mask(qi, kj, shape, q_dim, noised, *, block_len, block_q, block_k, causal,
                  valid_len, window=None):
    """Live clean keys of a tile the diagonal or the padded tail crosses: key
    block <= query block for a clean head, < for a noised one (``noised``: 0
    or 1); ``shape`` has queries along ``q_dim``."""
    k_idx = jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_dim)
    q_idx = jax.lax.broadcasted_iota(jnp.int32, shape, q_dim)
    live = k_idx < valid_len - kj * block_k
    shift = block_len.bit_length() - 1
    if shift:
        k_idx, q_idx = k_idx >> shift, q_idx >> shift
    ahead = qi * (block_q // block_len) - kj * (block_k // block_len)
    return live & (k_idx - q_idx <= ahead - noised)


def _in_block_mask(n, block_len):
    """[n, n] of a 128-row sub-block of the diagonal: query and key in one block."""
    shift = block_len.bit_length() - 1
    r = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0) >> shift
    c = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1) >> shift
    return r == c


def _sub_blocks(block):
    """The 128-row slices (``pl.ds``) of a block the in-block keys are read in."""
    sub = min(_LANES, block)
    return [pl.ds(r * sub, sub) for r in range(block // sub)]


def _bd_diagonal(qi, kj, noised, inside):
    """The step at which a noised head folds its in-block keys: its diagonal tile."""
    at = (noised == 1) & (qi == kj)
    return at if inside is None else at & inside


def _bd_flash_kernel(q_ref, k_ref, v_ref, kn_ref, vn_ref, o_ref, lse_ref, m_ref, l_ref,
                     acc_ref, *, walk, scale, tile, block_len, noised_of):
    """``_flash_kernel`` over the clean keys with the block-diffusion mask, and
    for a noised head, on its diagonal tile, the in-block noised keys folded
    into the same state before the row is finished."""
    qi, kj, first, last, inside = walk(pl.program_id(1), pl.program_id(2))
    noised = noised_of(pl.program_id(0))

    @pl.when(first)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _attend(masked):
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0], _NT, preferred_element_type=jnp.float32) * scale
        if masked:
            s = jnp.where(_bd_tile_mask(qi, kj, s.shape, 0, noised, block_len=block_len, **tile),
                          s, -jnp.inf)
        _fold_block(s, v_ref[0], m_ref, l_ref, acc_ref)

    _when_live(qi, kj, tile, _attend, inside)

    @pl.when(_bd_diagonal(qi, kj, noised, inside))
    def _in_block():
        for rows in _sub_blocks(tile["block_q"]):
            s = jax.lax.dot_general(q_ref[0, rows, :], kn_ref[0, rows, :], _NT,
                                    preferred_element_type=jnp.float32) * scale
            s = jnp.where(_in_block_mask(s.shape[0], block_len), s, -jnp.inf)
            _fold_block(s, vn_ref[0, rows, :], m_ref, l_ref, acc_ref, rows)

    @pl.when(last)
    def _finish():
        l = l_ref[...]
        m = m_ref[...]
        o_ref[0] = (acc_ref[...] * (1.0 / jnp.maximum(l, 1e-20))).astype(o_ref.dtype)
        lse = jnp.where(
            l > 0, jnp.where(m > -jnp.inf, m, 0.0) + jnp.log(jnp.maximum(l, 1e-38)),
            -jnp.inf,
        )
        lse_ref[0] = _col_to_row(lse)


def _bd_flash_bwd_dq_kernel(q_ref, k_ref, v_ref, kn_ref, vn_ref, do_ref, lse_ref, delta_ref,
                            dq_ref, acc_ref, *, walk, scale, tile, block_len, noised_of):
    """``_flash_bwd_dq_kernel`` under the block-diffusion mask, plus a noised
    head's in-block keys on its diagonal tile."""
    qi, kj, first, last, inside = walk(pl.program_id(1), pl.program_id(2))
    noised = noised_of(pl.program_id(0))

    @pl.when(first)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _accum(masked):
        k = k_ref[0]
        mask = (_bd_tile_mask(qi, kj, (tile["block_q"], tile["block_k"]), 0, noised,
                              block_len=block_len, **tile) if masked else None)
        _, ds = _block_grads(
            q_ref[0], k, v_ref[0], do_ref[0],
            lse_ref[0, 0][:, None], delta_ref[0, 0][:, None], mask,
            scale=scale, keys_major=False,
        )
        acc_ref[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, _NN, preferred_element_type=jnp.float32)

    _when_live(qi, kj, tile, _accum, inside)

    @pl.when(_bd_diagonal(qi, kj, noised, inside))
    def _in_block():
        for rows in _sub_blocks(tile["block_q"]):
            kn = kn_ref[0, rows, :]
            _, ds = _block_grads(
                q_ref[0, rows, :], kn, vn_ref[0, rows, :], do_ref[0, rows, :],
                lse_ref[0, 0, rows][:, None], delta_ref[0, 0, rows][:, None],
                _in_block_mask(kn.shape[0], block_len), scale=scale, keys_major=False)
            acc_ref[rows, :] += jax.lax.dot_general(
                ds.astype(kn.dtype), kn, _NN, preferred_element_type=jnp.float32)

    @pl.when(last)
    def _finish():
        dq_ref[0] = (acc_ref[...] * scale).astype(dq_ref.dtype)


def _bd_flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, kn_ref, vn_ref, do_ref, lse_ref, delta_ref,
                             dk_ref, dv_ref, dkn_ref, dvn_ref, dk_acc, dv_acc, dkn_acc, dvn_acc,
                             *, walk, head, scale, tile, block_len, group):
    """``_flash_bwd_dkv_kernel`` over the ``2 * group`` query heads of a kv head
    (the noised ones first; the ``group`` noised ones alone in a call of one
    query set) under the block-diffusion mask, and on a noised
    head's diagonal tile the gradients of the column's noised keys and values,
    summed over the group in their own VMEM accumulators."""
    kj, qi, first, last, inside = walk(pl.program_id(1), pl.program_id(2))
    noised = (head(pl.program_id(1), pl.program_id(2)) < group).astype(jnp.int32)

    @pl.when(first)
    def _init():
        for acc in (dk_acc, dv_acc, dkn_acc, dvn_acc):
            acc[...] = jnp.zeros_like(acc)

    def _accum(masked):
        q = q_ref[0]
        do = do_ref[0]
        mask = (_bd_tile_mask(qi, kj, (tile["block_k"], tile["block_q"]), 1, noised,
                              block_len=block_len, **tile) if masked else None)
        p_t, ds_t = _block_grads(
            q, k_ref[0], v_ref[0], do, lse_ref[0], delta_ref[0], mask,
            scale=scale, keys_major=True,
        )
        dv_acc[...] += jax.lax.dot_general(
            p_t.astype(do.dtype), do, _NN, preferred_element_type=jnp.float32)
        dk_acc[...] += jax.lax.dot_general(
            ds_t.astype(q.dtype), q, _NN, preferred_element_type=jnp.float32)

    _when_live(qi, kj, tile, _accum, inside)

    @pl.when(_bd_diagonal(qi, kj, noised, inside))
    def _in_block():
        for rows in _sub_blocks(tile["block_k"]):
            q, do = q_ref[0, rows, :], do_ref[0, rows, :]
            p_t, ds_t = _block_grads(
                q, kn_ref[0, rows, :], vn_ref[0, rows, :], do, lse_ref[0, :, rows],
                delta_ref[0, :, rows], _in_block_mask(q.shape[0], block_len),
                scale=scale, keys_major=True)
            dvn_acc[rows, :] += jax.lax.dot_general(
                p_t.astype(do.dtype), do, _NN, preferred_element_type=jnp.float32)
            dkn_acc[rows, :] += jax.lax.dot_general(
                ds_t.astype(q.dtype), q, _NN, preferred_element_type=jnp.float32)

    @pl.when(last)
    def _finish():
        dk_ref[0] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)
        dkn_ref[0] = (dkn_acc[...] * scale).astype(dkn_ref.dtype)
        dvn_ref[0] = dvn_acc[...].astype(dvn_ref.dtype)


def _noised_head(group, halves):
    """Grid index of a query head ([B*Hkv*halves*group]) -> 1 where it is a
    noised head (the first ``group`` of its kv head's ``2 * group``; every head
    of a call of one query set), else 0."""
    if halves == 1:
        return lambda b: 1
    return lambda b: (jax.lax.rem(jax.lax.div(b, group), 2) == 0).astype(jnp.int32)


def _bd_queries(halves):
    """The gauges' ``queries`` label of a call: ``noised`` for one query set."""
    return "noised" if halves == 1 else None


def _bd_queries_major(kernel, qb, kb, knb, blocks, valid_len, group):
    """What the forward and dQ calls share: tiling, the q-side and k-side
    BlockSpecs and the in-block keys' spec (a noised head's q block; a clean
    head, which never reads them, stays on block 0)."""
    block_q, block_k = blocks
    D = qb.shape[-1]
    halves = qb.shape[0] // (kb.shape[0] * group)
    tile, grid, walk, block = _tiling(kernel, qb.shape[1], blocks, True, valid_len, None,
                                      halves * group, _bd_queries(halves))
    kv_head, noised_of = _kv_head(halves * group), _noised_head(group, halves)
    q_spec = pl.BlockSpec((1, block_q, D), lambda b, o, s: (b, block(o, s)[0], 0))
    k_spec = pl.BlockSpec((1, block_k, D), lambda b, o, s: (kv_head(b), block(o, s)[1], 0))
    kn_spec = pl.BlockSpec((1, block_q, D), lambda b, o, s: (
        kv_head(b), block(o, s)[0] * noised_of(b), 0))
    row_spec = pl.BlockSpec((1, 1, block_q), lambda b, o, s: (b, 0, block(o, s)[0]))
    return tile, grid, walk, noised_of, (q_spec, k_spec, kn_spec, row_spec)


def _bd_fwd_call(qb, kb, vb, knb, vnb, blocks, valid_len, block_len, group, scale, interpret):
    """``bd_flash_fwd`` over ``[B*Hkv*h*group, Lp, D]`` q (h halves) and
    ``[B*Hkv, Lp, D]`` clean and noised k and v: (out, lse) at q's heads."""
    BH, Lp, D = qb.shape
    tile, grid, walk, noised_of, (q_spec, k_spec, kn_spec, row_spec) = _bd_queries_major(
        "bd_flash_fwd", qb, kb, knb, blocks, valid_len, group)
    return pl.pallas_call(
        functools.partial(_bd_flash_kernel, walk=walk, scale=scale, tile=tile,
                          block_len=block_len, noised_of=noised_of),
        grid=(BH, *grid),
        in_specs=[q_spec, k_spec, k_spec, kn_spec, kn_spec],
        out_specs=[q_spec, row_spec],
        out_shape=[jax.ShapeDtypeStruct((BH, Lp, D), qb.dtype),
                   jax.ShapeDtypeStruct((BH, 1, Lp), jnp.float32)],
        scratch_shapes=_scratch(blocks[0], D),
        compiler_params=_GRID_SEMANTICS,
        interpret=interpret,
        name="bd_flash_fwd",
    )(qb, kb, vb, knb, vnb)


def _bd_dq_call(qb, kb, vb, knb, vnb, dob, lse, delta, blocks, valid_len, block_len, group,
                scale, interpret):
    BH, Lp, D = qb.shape
    tile, grid, walk, noised_of, (q_spec, k_spec, kn_spec, row_spec) = _bd_queries_major(
        "bd_flash_bwd_dq", qb, kb, knb, blocks, valid_len, group)
    return pl.pallas_call(
        functools.partial(_bd_flash_bwd_dq_kernel, walk=walk, scale=scale, tile=tile,
                          block_len=block_len, noised_of=noised_of),
        grid=(BH, *grid),
        in_specs=[q_spec, k_spec, k_spec, kn_spec, kn_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((BH, Lp, D), qb.dtype),
        scratch_shapes=[pltpu.VMEM((blocks[0], D), jnp.float32)],
        compiler_params=_GRID_SEMANTICS,
        interpret=interpret,
        name="bd_flash_bwd_dq",
    )(qb, kb, vb, knb, vnb, dob, lse, delta)


def _bd_dkv_call(qb, kb, vb, knb, vnb, dob, lse, delta, blocks, valid_len, block_len, group,
                 scale, interpret):
    """``bd_flash_bwd_dkv``: (dK, dV, dK_noised, dV_noised) at the kv heads'
    count, keys-major; the inner axis walks each of a kv head's ``2 * group``
    query heads' q blocks in turn (``group`` in a call of one query set)."""
    BH, Lp, D = kb.shape
    block_q, block_k = blocks
    reps = qb.shape[0] // BH
    tile, grid, walk, block = _tiling("bd_flash_bwd_dkv", Lp, blocks, True, valid_len, None,
                                      reps, _bd_queries(reps // group))

    def q_at(b, o, s):
        _, qi, g = block(o, s)
        return b * reps + g, qi

    q_spec = pl.BlockSpec((1, block_q, D), lambda b, o, s: (*q_at(b, o, s), 0))
    k_spec = pl.BlockSpec((1, block_k, D), lambda b, o, s: (b, block(o, s)[0], 0))
    row_spec = pl.BlockSpec((1, 1, block_q), lambda b, o, s: (q_at(b, o, s)[0], 0,
                                                            q_at(b, o, s)[1]))
    # a walk of one query head a column names no head (0): the kernel compares a scalar
    head = (lambda o, s: block(o, s)[2]) if reps > 1 else (lambda o, s: jnp.int32(0))
    return pl.pallas_call(
        functools.partial(_bd_flash_bwd_dkv_kernel, walk=walk, head=head, scale=scale,
                          tile=tile, block_len=block_len, group=group),
        grid=(BH, *grid),
        in_specs=[q_spec, k_spec, k_spec, k_spec, k_spec, q_spec, row_spec, row_spec],
        out_specs=[k_spec] * 4,
        out_shape=[jax.ShapeDtypeStruct((BH, Lp, D), qb.dtype)] * 4,
        scratch_shapes=[pltpu.VMEM((block_k, D), jnp.float32)] * 4,
        compiler_params=_GRID_SEMANTICS,
        interpret=interpret,
        name="bd_flash_bwd_dkv",
    )(qb, kb, vb, knb, vnb, dob, lse, delta)


def _bd_operands(q, k, v, block_len, block, extra=()):
    """The layouts and geometry a block-diffusion call's kernels share."""
    B, _, H, D = q.shape
    L, group = _bd_check(q, k, v, block_len)
    blocks, Lp = _bd_geometry(L, D, q.dtype, block, block_len)
    Hkv = H // group
    (kn, kc), (vn, vc) = _bd_halves(k, B, L, Lp), _bd_halves(v, B, L, Lp)
    qs = tuple(_bd_q_to_bh(x, B, L, Hkv, group, Lp) for x in (q,) + tuple(extra))
    return (B, L, Hkv, group), blocks, qs, (kc, vc, kn, vn)


def _bd_forward(q, k, v, block_len, block, interpret):
    (B, L, Hkv, group), blocks, (qb,), kv = _bd_operands(q, k, v, block_len, block)
    out, lse = _bd_fwd_call(qb, *kv, blocks["bd_flash_fwd"], L, block_len, group,
                            1.0 / (q.shape[-1] ** 0.5), interpret)
    return _bd_q_from_bh(out, B, L, Hkv, group), lse


def _bd_backward(q, k, v, out, lse, g, block_len, block, interpret):
    (B, L, Hkv, group), blocks, (qb, dob, ob), kv = _bd_operands(
        q, k, v, block_len, block, (g.astype(q.dtype), out))
    delta = jnp.sum(dob.astype(jnp.float32) * ob.astype(jnp.float32), axis=-1)[:, None, :]
    operands = (qb, *kv, dob, lse, delta)
    scale = 1.0 / (q.shape[-1] ** 0.5)
    dq = _bd_dq_call(*operands, blocks["bd_flash_bwd_dq"], L, block_len, group, scale,
                     interpret)
    dk, dv, dkn, dvn = _bd_dkv_call(*operands, blocks["bd_flash_bwd_dkv"], L, block_len,
                                    group, scale, interpret)
    H, D = k.shape[2:]
    halves = lambda n, c: jnp.concatenate(  # noqa: E731
        [_from_bh(n, B, L, H, D), _from_bh(c, B, L, H, D)], axis=1)
    return _bd_q_from_bh(dq, B, L, Hkv, group), halves(dkn, dk), halves(dvn, dv)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def bd_flash_attention(q, k, v, block_len: int, block: int | None = None,
                       interpret: bool = False):
    """Block-diffusion attention (the mask of :func:`block_diffusion_mask`) by
    the Pallas kernels.  k, v: [B, 2L, Hkv, D], the noised half first; q:
    [B, 2L, Hq, D], or [B, L, Hq, D] for the noised half's queries alone (the
    mask's first L rows) -> q's shape.  ``block``: the square tile of every
    kernel (None: chosen per kernel from the shape)."""
    return _bd_forward(q, k, v, block_len, block, interpret)[0]


def _bd_fwd_rule(q, k, v, block_len, block, interpret):
    out, lse = _bd_forward(q, k, v, block_len, block, interpret)
    out, lse = kept.tag("bd_flash_fwd", out=out, lse=lse)
    return out, (q, k, v, out, lse)


def _bd_bwd_rule(block_len, block, interpret, res, g):
    return _bd_backward(*res, g, block_len, block, interpret)


bd_flash_attention.defvjp(_bd_fwd_rule, _bd_bwd_rule)


def attention(q, k, v, causal: bool = True, window: int | None = None,
              block_diffusion: int | None = None):
    """Dispatch on the default backend and nothing else: the pallas kernel on
    ``tpu`` (a kernel that does not compile raises — it never quietly becomes
    the reference), the fused-XLA reference on every other backend.
    ``block_diffusion``: the block length of a block-diffusion call over
    ``[x_noised ; x_clean]``, q over both halves or the noised one
    (:func:`bd_flash_attention`)."""
    if block_diffusion is not None:
        if jax.default_backend() == "tpu":
            return bd_flash_attention(q, k, v, block_diffusion)
        return reference_attention(q, k, v, block_diffusion=block_diffusion)
    if jax.default_backend() == "tpu":
        return flash_attention(q, k, v, causal=causal, window=window)
    return reference_attention(q, k, v, causal=causal, window=window)
