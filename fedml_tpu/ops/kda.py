"""Kimi Delta Attention (KDA): the per-channel gated delta rule, chunkwise.

Per head, with a state ``S`` of ``[d_k, d_v]`` that starts at zero:

    S~_t = Diag(exp(g_t)) S_{t-1}
    S_t  = S~_t + beta_t k_t (v_t - S~_t^T k_t)^T
    o_t  = S_t^T q_t * d_k^-1/2

:func:`kda_recurrent` is that recurrence token by token (``lax.scan``): the
definition, the test oracle, and nothing a training path should run at 8,192
tokens.  :func:`kda_chunked` computes the same thing chunk by chunk in XLA ops
(no Pallas kernel): inside a chunk of ``chunk`` tokens the updates
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

import jax
import jax.numpy as jnp

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
