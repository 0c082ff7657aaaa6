"""Kimi Delta Attention (KDA): the per-channel gated delta rule, chunkwise.

Per head, with a state ``S`` of ``[d_k, d_v]`` that starts at zero:

    S~_t = Diag(exp(g_t)) S_{t-1}
    S_t  = S~_t + beta_t k_t (v_t - S~_t^T k_t)^T
    o_t  = S_t^T q_t * d_k^-1/2

:func:`kda_recurrent` is that recurrence token by token (``lax.scan``): the
definition, the test oracle, and nothing a training path should run at 8,192
tokens.  :func:`kda` is what the model calls: on the ``tpu`` backend, for heads
whose widths are whole lanes, the Pallas kernels ``kda_fwd`` / ``kda_bwd``
(:func:`kda_pallas`, further down, with its own notes); everywhere else
:func:`kda_chunked`, which computes the same thing chunk by chunk in XLA ops
and is the definition on other backends and, with the recurrence, the kernels'
oracle: inside a chunk of ``chunk`` tokens the updates
``u_t = beta_t (v_t - S~_t^T k_t)`` solve one unit-lower-triangular system,

    (I + Diag(beta) A) U = Diag(beta) (V - (K . exp(G)) S_0),
    A_tj = sum_d k_t[d] k_j[d] exp(G_t[d] - G_j[d])   (j < t),

with ``G`` the gates' running sum inside the chunk and ``S_0`` the state the
chunk starts from (the system's inverse is formed by matrix products,
:func:`_inverse_unit_lower`); the state-free parts (``A``, its q-side twin, the two
solves) are computed for a run of chunks at once and a ``lax.scan`` over the
chunks carries the state through three matrix products a chunk.  Its backward
is jax's own differentiation of that program: chunkwise too.  A long sequence
goes a group of 16 chunks at a time, each group recomputed on the way back, so
that the ``[16, 16, d_k]`` intermediates of the diagonal blocks and the dozens
of ``[B, H, L, d]`` float32 arrays live for one group only.

Every exponential is of a non-positive number.  ``exp(G_t - G_j)`` does not
factor into ``exp(G_t) exp(-G_j)`` safely (the second overflows after a few
strongly gated tokens), so a chunk is cut into blocks of ``block`` tokens:
pairs inside a block take the explicit per-channel form, and a pair in two
blocks factors through the gates' sum at the end of the block before the
row's, which lies between the two.

Gates, the running sums, the solves and the state are float32 whatever the
inputs' dtype; the state-free products run at ``HIGHEST`` precision (a few
per cent of the layer's operations), the three products of the scan at the
backend's default.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import kept

CHUNK = 64  # tokens a chunk: the published kernels' size
BLOCK = 16  # tokens a block of a chunk's gate factorisation
_GROUP = 16  # chunks a group of a long sequence (see kda_chunked)

_HI = jax.lax.Precision.HIGHEST


def kda_recurrent(q, k, v, g, beta):
    """The recurrence token by token.  q, k, g: [B, L, H, d_k]; v: [B, L, H,
    d_v]; beta: [B, L, H].  Returns o [B, L, H, d_v] in float32."""
    scale = q.shape[-1] ** -0.5
    q, k, v, g, beta = (jnp.moveaxis(x.astype(jnp.float32), 1, 0)
                        for x in (q, k, v, g, beta))

    def step(S, x):
        q_t, k_t, v_t, g_t, b_t = x
        S = jnp.exp(g_t)[..., None] * S
        u = b_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", S, k_t, precision=_HI))
        S = S + k_t[..., :, None] * u[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, q_t, precision=_HI) * scale

    S0 = jnp.zeros(q.shape[1:] + (v.shape[-1],), jnp.float32)
    _, o = jax.lax.scan(step, S0, (q, k, v, g, beta))
    return jnp.moveaxis(o, 0, 1)


def _intra_chunk(q, k, G, block: int = BLOCK):
    """The two state-free matrices of every chunk.  q, k, G: [..., C, d_k]
    (``G`` the inclusive running sum of the gates inside the chunk).  Returns
    ``A`` (strictly lower: k_t . k_j decayed from j to t) and ``B`` (lower
    with the diagonal: q_t . k_j decayed), both [..., C, C]."""
    *lead, C, D = k.shape
    nb = C // block
    qb, kb, Gb = (x.reshape(*lead, nb, block, D) for x in (q, k, G))
    t = jnp.arange(block)
    lower = t[:, None] >= t[None, :]
    # pairs inside one block: the per-channel decay, explicitly
    E = jnp.exp(jnp.where(lower[..., None], Gb[..., :, None, :] - Gb[..., None, :, :],
                          -jnp.inf))  # [..., nb, t, j, D]
    kE = kb[..., None, :, :] * E
    A_in = jnp.where(t[:, None] > t[None, :], jnp.sum(kb[..., :, None, :] * kE, -1), 0.0)
    B_in = jnp.sum(qb[..., :, None, :] * kE, -1)
    # pairs in two blocks factor through the gates' sum at the end of the
    # block before the row's: both exponents are non-positive
    ref = jnp.concatenate([jnp.zeros_like(Gb[..., :1, -1, :]), Gb[..., :-1, -1, :]], -2)
    row = jnp.exp(Gb - ref[..., None, :])
    col = k[..., None, :, :] * jnp.exp(
        jnp.minimum(ref[..., :, None, :] - G[..., None, :, :], 0.0))  # [..., nb, C, D]
    before = (jnp.arange(C) // block)[None, None, :] < jnp.arange(nb)[:, None, None]
    eye = jnp.eye(nb, dtype=G.dtype)[:, None, :, None]

    def whole(inside, rows):
        across = jnp.einsum("...ntd,...njd->...ntj", rows * row, col, precision=_HI)
        across = jnp.where(before, across, 0.0)
        return (across + (inside[..., :, :, None, :] * eye).reshape(*lead, nb, block, C)
                ).reshape(*lead, C, C)

    return whole(A_in, kb), whole(B_in, qb)


def _inverse_unit_lower(M, block: int):
    """Inverse of unit lower-triangular matrices [..., C, C]: the diagonal
    blocks of ``block`` rows by forward substitution (row i of the inverse is
    ``e_i - sum_{j<i} M_ij row_j``), then pairs of blocks merged,
    ``[[A, 0], [X, D]]^-1 = [[A^-1, 0], [-D^-1 X A^-1, D^-1]]``, until one is
    left.  Matrix products alone (XLA's triangular solve runs a sequential
    kernel a matrix that took a sixth of this op's time on the v5e)."""
    *lead, C, _ = M.shape
    n = C // block
    tiles = M.reshape(*lead, n, block, n, block)
    diagonal = jnp.stack([tiles[..., i, :, i, :] for i in range(n)], -3)  # [..., n, b, b]
    eye = jnp.eye(block, dtype=M.dtype)
    rows = [jnp.broadcast_to(eye[0], diagonal.shape[:-2] + (block,))]
    for i in range(1, block):
        done = jnp.stack(rows, -2)  # [..., n, i, b]
        rows.append(eye[i] - jnp.einsum("...j,...jk->...k", diagonal[..., i, :i], done,
                                        precision=_HI))
    inverse, size = jnp.stack(rows, -2), block  # [..., n, size, size]
    while size < C:
        n //= 2
        tiles = M.reshape(*lead, n, 2, size, n, 2, size)
        below = jnp.stack([tiles[..., i, 1, :, i, 0, :] for i in range(n)], -3)
        pairs = inverse.reshape(*lead, n, 2, size, size)
        top, bottom = pairs[..., 0, :, :], pairs[..., 1, :, :]
        corner = -jnp.einsum("...ij,...jk,...kl->...il", bottom, below, top, precision=_HI)
        inverse = jnp.concatenate([
            jnp.concatenate([top, jnp.zeros_like(top)], -1),
            jnp.concatenate([corner, bottom], -1)], -2)
        size *= 2
    return inverse[..., 0, :, :]


def _run_chunks(S, x, block: int):
    """A run of chunks from the state ``S``.  x = (q, k, v, g, beta), each
    [B, H, n, C, ...] float32.  Returns (state after the run, o [B, H, n, C, d_v])
    without the output's scale."""
    q, k, v, g, beta = x
    Dk, chunk = q.shape[-1], q.shape[-2]
    G = jnp.cumsum(g, axis=-2)
    A, Bq = _intra_chunk(q, k, G, block)
    b = beta[..., None]
    solved = jnp.einsum(
        "...ij,...jk->...ik", _inverse_unit_lower(jnp.eye(chunk, dtype=jnp.float32) + b * A, block),
        b * jnp.concatenate([k * jnp.exp(G), v], -1), precision=_HI)
    W, Uv = solved[..., :Dk], solved[..., Dk:]
    G_end = G[..., -1:, :]
    xs = (Uv, W, q * jnp.exp(G), Bq, k * jnp.exp(G_end - G), jnp.exp(G_end[..., 0, :]))

    def step(S, x):
        Uv_n, W_n, Q_n, B_n, K_n, decay = x
        U = Uv_n - jnp.einsum("bhck,bhkv->bhcv", W_n, S)
        o = jnp.einsum("bhck,bhkv->bhcv", Q_n, S) + jnp.einsum("bhcj,bhjv->bhcv", B_n, U)
        S = decay[..., None] * S + jnp.einsum("bhck,bhcv->bhkv", K_n, U)
        return S, o

    S, o = jax.lax.scan(step, S, jax.tree_util.tree_map(lambda x: jnp.moveaxis(x, 2, 0), xs))
    return S, jnp.moveaxis(o, 0, 2)


def kda_chunked(q, k, v, g, beta, *, chunk: int = CHUNK, block: int = BLOCK):
    """The same function as :func:`kda_recurrent`, chunk by chunk; any length
    (a short last chunk is padded with tokens that leave the state alone: zero
    keys, values and gates).  Returns o [B, L, H, d_v] in ``v``'s dtype."""
    from ..core import obs

    obs.gauge_set("kda.chunk", chunk)
    obs.gauge_set("kda.kernel", 0)
    B, L, H, Dk = q.shape
    Dv = v.shape[-1]
    N = -(-L // chunk)

    def chunks(x):  # [B, L, H, ...] -> [B, H, N, C, ...] float32
        x = jnp.moveaxis(x.astype(jnp.float32), 2, 1)
        x = jnp.pad(x, ((0, 0), (0, 0), (0, N * chunk - L)) + ((0, 0),) * (x.ndim - 3))
        return x.reshape(B, H, N, chunk, *x.shape[3:])

    x = tuple(map(chunks, (q, k, v, g, beta)))
    S0 = jnp.zeros((B, H, Dk, Dv), jnp.float32)
    if N > _GROUP and N % _GROUP == 0:
        # a long sequence goes a group of chunks at a time, each group
        # recomputed on the way back: only the states between the groups are
        # kept, and the dozens of [B, H, L, d] float32 intermediates (GiBs at
        # 8,192 tokens) live for one group at a time
        groups = jax.tree_util.tree_map(
            lambda a: jnp.moveaxis(a.reshape(B, H, N // _GROUP, _GROUP, *a.shape[3:]), 2, 0), x)
        _, o = jax.lax.scan(jax.checkpoint(lambda S, xg: _run_chunks(S, xg, block)), S0, groups)
        o = jnp.moveaxis(o, 0, 2)
    else:
        _, o = _run_chunks(S0, x, block)
    o = o.reshape(B, H, N * chunk, Dv)[:, :, :L] * Dk ** -0.5
    return jnp.moveaxis(o, 1, 2).astype(v.dtype)


# -- the chunk path as Pallas kernels ----------------------------------------
# A head's sequence goes a run of chunks a grid step; the state (kept
# transposed, [d_v, d_k], so that a chunk's decay scales its lanes) stays in a
# VMEM scratch along the sequential axis, and everything a chunk needs -- the
# gates' sums, ``A``, ``Bq``, the inverse, ``W``, ``U`` -- is made in VMEM from
# the inputs and never written to HBM.  The forward emits the state at the start
# of each run, the only residual beside the inputs; the backward walks the runs
# in reverse: it runs a run's chunks forward again from that state, keeping
# their matrices in VMEM, then back, carrying ``dS``.
#
# Inside a kernel the gate factorisation is dyadic rather than by blocks of 16:
# at level ``s`` (1, 2, ... 32) a chunk falls into groups of ``2 s`` tokens, and a
# pair (t in a group's second half, j in its first) factors through the gates'
# sum at the end of the first half, which lies between the two:
# ``exp(G_t - G_j) = exp(sum g (ref, t]) exp(sum g (j, ref])``.  Every pair j < t
# belongs to exactly one level, every exponent is a sum of gates (non-positive,
# and no difference of running sums), and a level is one ``[2C, d] x [d, C]``
# product under a constant mask.  The sums are made by six steps of sublane
# rolls, selects and adds (:func:`_gate_sums`).

_LEVELS = (1, 2, 4, 8, 16, 32)  # half-sizes of the groups; CHUNK = 64
_LANES = 128
_NN = ((1,), (0,))  # a @ b
_NT = ((1,), (1,))  # a @ b.T
_TN = ((0,), (0,))  # a.T @ b
# chunks a grid step: a step costs 0.35 us whatever it does (PERF.md section 6,
# PR 26), and a run's first state is a residual of [d_v, d_k] float32 a head
_RUN_TARGET = 16
# VMEM a step may plan for, and the scoped limit the calls ask Mosaic for (a
# v5e has 128 MiB; the difference is the chunks' own temporaries and spills)
_VMEM_BUDGET = 40 * 2**20
_VMEM_LIMIT = 64 * 2**20
# heads a grid step (see the note before _gate_sums)
_HEADS_TARGET = 4


def _dot(a, b, dims, precision=None):
    return jax.lax.dot_general(a, b, (dims, ((), ())), precision=precision,
                               preferred_element_type=jnp.float32)


def _masks(Dk):
    """The constant masks of a chunk, from iotas (made once a grid step)."""
    C = CHUNK
    r = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    t = jax.lax.broadcasted_iota(jnp.int32, (C, Dk), 0)

    def together(size):  # both in one aligned group of ``size`` tokens
        shift = size.bit_length() - 1
        return jnp.right_shift(r, shift) == jnp.right_shift(c, shift)

    def below(s):  # t in the second half of a group of 2 s, j in its first
        return ((r & s) != 0) & ((c & s) == 0) & together(2 * s)

    return dict(eye=r == c, strict=r > c, lower=r >= c, last=t == C - 1, t=t,
                block=together(BLOCK), level={s: below(s) for s in _LEVELS},
                bit={s: (t & s) != 0 for s in _LEVELS})


# A chunk is a chain of some twenty dependent small products, and a product
# that waits for the one before it costs 0.3 us on a v5e whatever its size
# (PERF.md section 6, PR 28).  So a grid step takes several heads, which are
# independent, and every function below works on a list with one item a head,
# stage by stage: neighbours in the program are independent, and the scheduler
# fills one head's waits with another's work.

def _gate_sums(gs, bit):
    """gs: gates, [C, d_k] a head.  Per level and head the exponent of a
    token's factor (second half: the sum from the first half's end up to it;
    first half: from after it to that end), then the inclusive running sum, the
    sum after each token, and the chunk's whole sum on every row."""
    pre, suf, tot, levels = list(gs), [jnp.zeros_like(g) for g in gs], list(gs), []
    for s in _LEVELS:
        levels.append([jnp.where(bit[s], p, f) for p, f in zip(pre, suf)])
        # the other half's sum: the s-segment before a second half, after a first
        other = [jnp.where(bit[s], pltpu.roll(t, s, 0), pltpu.roll(t, CHUNK - s, 0)) for t in tot]
        pre = [p + jnp.where(bit[s], x, 0.0) for p, x in zip(pre, other)]
        suf = [f + jnp.where(bit[s], 0.0, x) for f, x in zip(suf, other)]
        tot = [t + x for t, x in zip(tot, other)]
    return levels, pre, suf, tot


def _chunk_gates(qs, ks, gs, m):
    """What a chunk's gates give, float32, a head an item: per level the factor
    ``e`` of every token, the decay from the chunk's start (``decay``) and to
    its end (``to_end``), the chunk's whole decay (``gamma``, a row), and q and
    k under them."""
    levels, pre, suf, tot = _gate_sums(gs, m["bit"])
    out = []
    for h, (q, k) in enumerate(zip(qs, ks)):
        decay, to_end = jnp.exp(pre[h]), jnp.exp(suf[h])
        out.append(dict(e=[jnp.exp(level[h]) for level in levels], decay=decay, to_end=to_end,
                        gamma=jnp.exp(tot[h][:1]), kg=k * decay, qg=q * decay, kd=k * to_end))
    return out


def _inverse_in_vmem(Ls, m):
    """(I + L)^-1 of strictly lower [C, C] matrices: the diagonal blocks of BLOCK
    by the finite series (I + N)(I + N^2)(I + N^4)(I + N^8), N = -L there, then
    the two merges of :func:`_inverse_unit_lower`, all as [C, C] products."""
    C = CHUNK
    Ns = [jnp.where(m["block"], -L, 0.0) for L in Ls]
    Ds = [m["eye"].astype(jnp.float32) + N for N in Ns]
    Ps = [_dot(N, N, _NN, _HI) for N in Ns]
    for _ in range(BLOCK.bit_length() - 3):
        both = [_dot(jnp.concatenate([D, P], 0), P, _NN, _HI) for D, P in zip(Ds, Ps)]
        Ds, Ps = [D + x[:C] for D, x in zip(Ds, both)], [x[C:] for x in both]
    Ds = [D + _dot(D, P, _NN, _HI) for D, P in zip(Ds, Ps)]
    for s in (s for s in _LEVELS if s >= BLOCK):  # what lies below the diagonal blocks
        left = [_dot(D, jnp.where(m["level"][s], L, 0.0), _NN, _HI) for D, L in zip(Ds, Ls)]
        Ds = [D - _dot(x, D, _NN, _HI) for D, x in zip(Ds, left)]
    return Ds


def _beta_columns(beta_rows, m):  # [1, C] rows -> [C, 1] columns
    return [jnp.sum(jnp.where(m["eye"], row, 0.0), axis=1, keepdims=True) for row in beta_rows]


def _chunk_matrices(qs, ks, vs, betas, gates, m):
    """The state-free matrices of a chunk, a head an item: ``A`` (strictly
    lower), ``Bq`` (lower), ``T = (I + beta A)^-1`` and ``T beta [k exp(G) | v]``
    = ``[W | Uv]``; ``betas`` are columns."""
    C = CHUNK
    As = [jnp.zeros((C, C), jnp.float32) for _ in qs]
    Bqs = [jnp.where(m["eye"], jnp.sum(q * k, axis=1, keepdims=True), 0.0)
           for q, k in zip(qs, ks)]
    for i, s in enumerate(_LEVELS):
        xks = [k * x["e"][i] for k, x in zip(ks, gates)]
        Ps = [_dot(jnp.concatenate([xk, q * x["e"][i]], 0), xk, _NT, _HI)
              for xk, q, x in zip(xks, qs, gates)]
        # the levels' masks are disjoint: a select places what a sum would add
        As = [jnp.where(m["level"][s], P[:C], A) for A, P in zip(As, Ps)]
        Bqs = [jnp.where(m["level"][s], P[C:], Bq) for Bq, P in zip(Bqs, Ps)]
    Ts = _inverse_in_vmem([beta * A for beta, A in zip(betas, As)], m)
    WUvs = [_dot(T, beta * jnp.concatenate([x["kg"], v], 1), _NN, _HI)
            for T, beta, x, v in zip(Ts, betas, gates, vs)]
    return As, Bqs, Ts, WUvs


def _load_chunk(refs, b_ref, c, heads):
    """Chunk ``c`` of a block's heads: its rows, per input the heads' [C, d]
    float32, and beta's rows."""
    rows = pl.ds(pl.multiple_of(c * CHUNK, CHUNK), CHUNK)
    loaded = []
    for ref in refs:
        x, d = ref[0, rows, :].astype(jnp.float32), ref.shape[-1] // heads
        loaded.append([x[:, h * d:(h + 1) * d] for h in range(heads)])
    return rows, loaded, [b_ref[0, h, pl.ds(c, 1), :] for h in range(heads)]


def _state_step(Sts, gates, Bqs, WUvs, Dk):
    """One chunk from the states ``St`` [d_v, d_k]: (U, o without its scale, the
    state after), a head an item.  The three state products, at the backend's
    default."""
    WS = [_dot(WUv[:, :Dk], St, _NT) for WUv, St in zip(WUvs, Sts)]
    Us = [WUv[:, Dk:] - x for WUv, x in zip(WUvs, WS)]
    os = [_dot(x["qg"], St, _NT) + _dot(Bq, U, _NN)
          for x, St, Bq, U in zip(gates, Sts, Bqs, Us)]
    return Us, os, [St * x["gamma"] + _dot(U, x["kd"], _TN) for St, x, U in zip(Sts, gates, Us)]


def _kda_fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, s_ref, state, *, n, scale):
    """Grid cell (batch, heads, run): ``n`` chunks of the block's heads from the
    states in scratch; the states the run starts from go out as the backward's
    residual."""
    heads = state.shape[0]
    Dk = q_ref.shape[-1] // heads

    @pl.when(pl.program_id(2) == 0)
    def _start():
        state[...] = jnp.zeros_like(state)

    s_ref[0, :, 0] = state[...]
    m = _masks(Dk)

    def chunk(c, carry):
        rows, (qs, ks, vs, gs), beta_rows = _load_chunk((q_ref, k_ref, v_ref, g_ref), b_ref, c,
                                                        heads)
        gates = _chunk_gates(qs, ks, gs, m)
        _, Bqs, _, WUvs = _chunk_matrices(qs, ks, vs, _beta_columns(beta_rows, m), gates, m)
        _, os, Sts = _state_step([state[h] for h in range(heads)], gates, Bqs, WUvs, Dk)
        for h, St in enumerate(Sts):
            state[h] = St
        o_ref[0, rows, :] = (jnp.concatenate(os, 1) * scale).astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, n, chunk, 0)


def _kda_bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, s_ref, do_ref,
                    dq_ref, dk_ref, dv_ref, dg_ref, db_ref,
                    dstate, states, mats, wuvs, us, *, n, scale):
    """Grid cell (batch, heads, run), the runs last to first.  The run's chunks
    go forward once more from the states it started from, their matrices and
    states kept in VMEM, then backward with ``dS`` carried in scratch (across
    the runs too).  Gradients of all five inputs."""
    heads = dstate.shape[0]
    Dk = q_ref.shape[-1] // heads
    each = range(heads)

    @pl.when(pl.program_id(2) == 0)
    def _start():
        dstate[...] = jnp.zeros_like(dstate)

    m = _masks(Dk)
    refs = (q_ref, k_ref, v_ref, g_ref)

    def forward(c, Sts):
        _, (qs, ks, vs, gs), beta_rows = _load_chunk(refs, b_ref, c, heads)
        gates = _chunk_gates(qs, ks, gs, m)
        As, Bqs, Ts, WUvs = _chunk_matrices(qs, ks, vs, _beta_columns(beta_rows, m), gates, m)
        Us, _, after = _state_step(Sts, gates, Bqs, WUvs, Dk)
        for h in each:
            states[c, h], mats[c, h, 0], mats[c, h, 1], mats[c, h, 2] = Sts[h], As[h], Bqs[h], Ts[h]
            wuvs[c, h], us[c, h] = WUvs[h], Us[h]
        return tuple(after)

    jax.lax.fori_loop(0, n, forward, tuple(s_ref[0, h, 0] for h in each))

    def backward(i, carry):
        c = n - 1 - i
        rows, (qs, ks, vs, gs, dos), beta_rows = _load_chunk(refs + (do_ref,), b_ref, c, heads)
        gates = _chunk_gates(qs, ks, gs, m)
        betas = _beta_columns(beta_rows, m)
        work = [dict(q=qs[h], k=ks[h], v=vs[h], do=dos[h] * scale, beta=betas[h], x=gates[h],
                     St=states[c, h], dSt=dstate[h], A=mats[c, h, 0], Bq=mats[c, h, 1],
                     T=mats[c, h, 2], WUv=wuvs[c, h], U=us[c, h]) for h in each]
        for stage in (_backward_state, _backward_solve, _backward_intra):
            for w in work:  # a stage for every head, then the next (see above)
                stage(w, m, Dk)
        for h, w in enumerate(work):
            dstate[h] = w["dSt_out"]
        dq_ref[0, rows, :] = jnp.concatenate([w["dq"] for w in work], 1).astype(dq_ref.dtype)
        dk_ref[0, rows, :] = jnp.concatenate([w["dk"] for w in work], 1).astype(dk_ref.dtype)
        dv_ref[0, rows, :] = jnp.concatenate([w["dv"] for w in work], 1).astype(dv_ref.dtype)
        dg_ref[0, rows, :] = jnp.concatenate([w["dg"] for w in work], 1).astype(dg_ref.dtype)
        for h, w in enumerate(work):
            db_ref[0, h, pl.ds(c, 1), :] = jnp.sum(
                jnp.where(m["eye"], w["dbeta"], 0.0), axis=0, keepdims=True).astype(db_ref.dtype)
        return carry

    jax.lax.fori_loop(0, n, backward, 0)


def _backward_state(w, m, Dk):
    """Through the state products (the backend's default, as forward)."""
    x, do, St, dSt, Bq, U, WUv = w["x"], w["do"], w["St"], w["dSt"], w["Bq"], w["U"], w["WUv"]
    w["dU"] = dU = _dot(Bq, do, _TN) + _dot(x["kd"], dSt, _NT)
    w["dBq"] = jnp.where(m["lower"], _dot(do, U, _NT), 0.0)
    w["dqg"] = _dot(do, St, _NN)
    w["dkd"] = _dot(U, dSt, _NN)
    w["dgamma"] = jnp.sum(St * dSt, axis=0, keepdims=True)
    w["dSt_out"] = dSt * x["gamma"] + _dot(do, x["qg"], _TN) - _dot(dU, WUv[:, :Dk], _TN)
    w["dW"] = -_dot(dU, St, _NN)


def _backward_solve(w, m, Dk):
    """Through the solve: [W | Uv] = T beta [kg | v], T = (I + beta A)^-1."""
    dR = _dot(w["T"], jnp.concatenate([w["dW"], w["dU"]], 1), _TN, _HI)
    dL = jnp.where(m["strict"], -_dot(dR, w["WUv"], _NT, _HI), 0.0)
    w["dA"] = w["beta"] * dL
    w["dbeta"] = (jnp.sum(dL * w["A"], axis=1, keepdims=True)
                  + jnp.sum(dR * jnp.concatenate([w["x"]["kg"], w["v"]], 1), axis=1,
                            keepdims=True))
    w["dkg"] = w["beta"] * dR[:, :Dk]
    w["dv"] = w["beta"] * dR[:, Dk:]


def _backward_intra(w, m, Dk):
    """Through A and Bq, level by level, and their diagonal (q_t . k_t); then
    from the gates' sums to the gates."""
    C, x, q, k, dBq, dA = CHUNK, w["x"], w["q"], w["k"], w["dBq"], w["dA"]
    dqg, dkg, dkd = w["dqg"], w["dkg"], w["dkd"]
    diag = jnp.sum(jnp.where(m["eye"], dBq, 0.0), axis=1, keepdims=True)
    dq = dqg * x["decay"] + diag * k
    dk = dkg * x["decay"] + dkd * x["to_end"] + diag * q
    dG = dkg * x["kg"] + dqg * x["qg"] - dkd * x["kd"]
    for s, e in zip(_LEVELS, x["e"]):
        xk, xq = k * e, q * e
        both = jnp.concatenate([jnp.where(m["level"][s], dA, 0.0),
                                jnp.where(m["level"][s], dBq, 0.0)], 0)  # [2C, C]
        row = _dot(both, xk, _NN, _HI)  # the rows' side: of k, then of q
        col = _dot(both, jnp.concatenate([xk, xq], 0), _TN, _HI)  # the columns' (k)
        dq = dq + row[C:] * e
        dk = dk + (row[:C] + col) * e
        dG = dG + row[:C] * xk + row[C:] * xq - col * xk
    # the chunk's whole sum: k's decay to the end and the state's decay
    dG = dG + jnp.where(m["last"], jnp.sum(dkd * x["kd"], axis=0, keepdims=True)
                        + w["dgamma"] * x["gamma"], 0.0)
    for s in _LEVELS:  # G is the gates' running sum: dg sums dG from t on
        dG = dG + jnp.where(m["t"] < C - s, pltpu.roll(dG, C - s, 0), 0.0)
    w["dq"], w["dk"], w["dg"] = dq, dk, dG


def _vmem_bytes(heads, n, Dk, Dv, itemsize):
    """Upper estimate of the VMEM one grid step of the heavier kernel, the
    backward, holds at ``heads`` heads of ``n`` chunks: the blocks the pipeline
    double-buffers (q, k, v, dO and their gradients in the inputs' dtype, g and
    dg float32, the run's state) and the scratch (dS; a chunk's A, Bq, T,
    [W | Uv], U and state)."""
    T, state = n * CHUNK, 4 * Dk * Dv
    blocks = T * ((4 * Dk + 3 * Dv) * itemsize + 8 * Dk) + state
    return heads * (2 * blocks + state + n * (4 * CHUNK * (3 * _LANES + Dk + 2 * Dv) + state))


def _choose_step(N, H, Dk, Dv, dtype):
    """(heads, chunks) of a grid step for ``H`` heads of ``N`` chunks.  A short
    sequence is one run; else a run is 16 or 8 chunks (beta's block, ``[n, 64]``,
    wants whole sublane tiles): the one that pads ``N`` least, the larger at a
    tie.  Heads: the most that divide ``H``, up to ``_HEADS_TARGET``; then heads
    and chunks step down until the backward is inside the VMEM budget."""
    itemsize = jnp.dtype(dtype).itemsize
    runs = [N] if N <= _RUN_TARGET else sorted(
        (_RUN_TARGET, _RUN_TARGET // 2), key=lambda n: (-(-N // n) * n, -n))
    for heads in (h for h in range(_HEADS_TARGET, 0, -1) if H % h == 0):
        for run in runs:
            if _vmem_bytes(heads, run, Dk, Dv, itemsize) <= _VMEM_BUDGET:
                return heads, run
    return 1, min(runs)


def _geometry(q, v):
    """(heads, n, steps): heads and chunks a grid step and grid steps a
    sequence; leaves the gauges that say the kernels were traced."""
    from ..core import obs

    N = -(-q.shape[1] // CHUNK)
    heads, n = _choose_step(N, q.shape[2], q.shape[-1], v.shape[-1], q.dtype)
    obs.gauge_set("kda.chunk", CHUNK)
    obs.gauge_set("kda.kernel", 1)
    obs.gauge_set("kda.chunks_per_step", n)
    return heads, n, -(-N // n)


def _rows(x, Lp):  # [B, L, H, D] -> [B, Lp, H * D]: a head is D lanes of a row
    B, L, H, D = x.shape
    return jnp.pad(x, ((0, 0), (0, Lp - L), (0, 0), (0, 0))).reshape(B, Lp, H * D)


def _beta_rows(beta, Lp):  # [B, L, H] -> [B, H, Lp / C, C]
    B, L, H = beta.shape
    beta = jnp.pad(beta.astype(jnp.float32), ((0, 0), (0, Lp - L), (0, 0)))
    return jnp.moveaxis(beta, 2, 1).reshape(B, H, Lp // CHUNK, CHUNK)


def _specs(heads, n, Dk, Dv, step):
    """BlockSpecs of a run of a block of heads: token rows at the k and at the v
    width, beta's rows, the run's states; ``step`` maps the grid's run index."""
    T = n * CHUNK

    def rows(D):
        return pl.BlockSpec((1, T, heads * D), lambda b, h, i: (b, step(i), h))

    return (rows(Dk), rows(Dv),
            pl.BlockSpec((1, heads, n, CHUNK), lambda b, h, i: (b, h, step(i), 0)),
            pl.BlockSpec((1, heads, 1, Dv, Dk), lambda b, h, i: (b, h, step(i), 0, 0)))


_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT)


def _forward(heads, n, interpret, q, k, v, g, beta):
    """``kda_fwd`` over rows (see :func:`_rows`): (o [B, Lp, H d_v], the states
    the runs start from [B, H, runs, d_v, d_k])."""
    B, H, N, _ = beta.shape
    Dk, Dv, steps = q.shape[-1] // H, v.shape[-1] // H, N // n
    k_rows, v_rows, b_rows, state = _specs(heads, n, Dk, Dv, lambda i: i)
    return pl.pallas_call(
        functools.partial(_kda_fwd_kernel, n=n, scale=Dk ** -0.5),
        grid=(B, H // heads, steps),
        in_specs=[k_rows, k_rows, v_rows, k_rows, b_rows],
        out_specs=[v_rows, state],
        out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct((B, H, steps, Dv, Dk), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((heads, Dv, Dk), jnp.float32)],
        compiler_params=_COMPILER_PARAMS, interpret=interpret, name="kda_fwd",
    )(q, k, v, g, beta)


def _backward(heads, n, interpret, q, k, v, g, beta, states, do):
    """``kda_bwd`` over the same rows, the forward's states and dO: the five
    gradients, laid out as their inputs."""
    B, H, N, _ = beta.shape
    Dk, Dv, steps = q.shape[-1] // H, v.shape[-1] // H, N // n
    k_rows, v_rows, b_rows, state = _specs(heads, n, Dk, Dv, lambda i: steps - 1 - i)
    return pl.pallas_call(
        functools.partial(_kda_bwd_kernel, n=n, scale=Dk ** -0.5),
        grid=(B, H // heads, steps),
        in_specs=[k_rows, k_rows, v_rows, k_rows, b_rows, state, v_rows],
        out_specs=[k_rows, k_rows, v_rows, k_rows, b_rows],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for x in (q, k, v, g, beta)],
        scratch_shapes=[pltpu.VMEM((heads, Dv, Dk), jnp.float32),
                        pltpu.VMEM((n, heads, Dv, Dk), jnp.float32),
                        pltpu.VMEM((n, heads, 3, CHUNK, CHUNK), jnp.float32),
                        pltpu.VMEM((n, heads, CHUNK, Dk + Dv), jnp.float32),
                        pltpu.VMEM((n, heads, CHUNK, Dv), jnp.float32)],
        compiler_params=_COMPILER_PARAMS, interpret=interpret, name="kda_bwd",
    )(q, k, v, g, beta, states, do)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _kda_rows(heads, n, interpret, q, k, v, g, beta):
    return _forward(heads, n, interpret, q, k, v, g, beta)[0]


def _kda_rows_fwd(heads, n, interpret, *rows):
    o, states = _forward(heads, n, interpret, *rows)
    # a recomputing caller may keep these two (ops/kept.py); the rows it rebuilds
    o, states = kept.tag("kda_fwd", o=o, states=states)
    return o, (*rows, states)  # the inputs as the kernels read them, and 16 MiB a layer


def _kda_rows_bwd(heads, n, interpret, residuals, do):
    return tuple(_backward(heads, n, interpret, *residuals, do))


_kda_rows.defvjp(_kda_rows_fwd, _kda_rows_bwd)


def kda_pallas(q, k, v, g, beta, interpret=False):
    """The same function as :func:`kda_chunked`, by the kernels ``kda_fwd`` and
    ``kda_bwd``: d_k and d_v multiples of 128, chunk 64, any length (padded with
    tokens that leave the state alone); a grid step's heads and chunks come from
    the shape (:func:`_choose_step`).  ``interpret=True`` runs the kernels on the
    CPU.  The re-layout to rows and back is plain jax, differentiated by jax; the
    kernels' ``jax.custom_vjp`` is over the rows, so the backward reads the very
    arrays the forward did."""
    B, L, H, _ = q.shape
    heads, n, steps = _geometry(q, v)
    Lp = steps * n * CHUNK
    o = _kda_rows(heads, n, interpret, _rows(q, Lp), _rows(k, Lp), _rows(v, Lp),
                  _rows(g.astype(jnp.float32), Lp), _beta_rows(beta, Lp))
    return o[:, :L].reshape(B, L, H, v.shape[-1])


def _kernels_take(q, v):
    """The kernels' shapes: head widths that are whole lanes."""
    return q.shape[-1] % _LANES == 0 and v.shape[-1] % _LANES == 0


def kda(q, k, v, g, beta):
    """What the model calls.  Dispatch on the default backend and the shapes and
    nothing else: the kernels on ``tpu`` for heads they take (a kernel that does
    not compile raises), :func:`kda_chunked` everywhere else."""
    if jax.default_backend() == "tpu" and _kernels_take(q, v):
        return kda_pallas(q, k, v, g, beta)
    return kda_chunked(q, k, v, g, beta)
