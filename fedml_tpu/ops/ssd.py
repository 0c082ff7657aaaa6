"""Mamba-2's state-space scan (SSD, arXiv:2405.21060), chunkwise.

Per head ``h``, with a state ``S`` of ``[P, N]`` that starts at zero and a scalar
decay a head:

    S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t B_t^T
    y_t = S_t C_t

``x``: [B, L, H, P]; ``dt``: [B, L, H], positive (the softplus is the caller's);
``A``: [H], negative; ``B``, ``C``: [B, L, G, N], head ``h`` reading group
``h // (H / G)``.  The skip ``D x`` is the caller's.

:func:`ssd_recurrent` is that recurrence token by token (``lax.scan``): the
definition and the oracle.  :func:`ssd` is what the model calls: on the ``tpu``
backend, for shapes whose groups are whole lanes, the Pallas kernels ``ssd_fwd`` /
``ssd_bwd`` (:func:`ssd_pallas`, further down, with its own notes); everywhere else
:func:`ssd_chunked`, the same function chunk by chunk in XLA ops, which is the
kernels' oracle.  Inside a chunk of ``Q`` tokens that starts from the state
``S_0``, with ``a = dt A`` and ``c`` its running sum inside the chunk (inclusive),

    y_t   = sum_{s <= t} exp(c_t - c_s) (C_t . B_s) dt_s x_s  +  exp(c_t) S_0 C_t
    S_Q   = exp(c_Q) S_0 + sum_s exp(c_Q - c_s) dt_s x_s B_s^T

the first term a masked ``[Q, Q]`` matrix (``C B^T`` once a group, a decay a
head) times ``dt x``, the rest products with the state.  The decay is a scalar a
head, so ``exp(c_t - c_s)`` with ``s <= t`` is the exponential of a sum of
non-positive numbers: nothing overflows and nothing needs blocking inside a
chunk.  Gates, their sums and the state are float32 whatever the inputs' dtype.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import kept

CHUNK = 128  # tokens a chunk: the published ``chunk_size``
_HI = jax.lax.Precision.HIGHEST


def ssd_recurrent(x, dt, A, B, C):
    """The recurrence token by token.  Returns y [b, L, H, P] float32."""
    H, G = x.shape[2], B.shape[2]
    Bh, Ch = (jnp.repeat(z.astype(jnp.float32), H // G, axis=2) for z in (B, C))
    x, dt = x.astype(jnp.float32), dt.astype(jnp.float32)
    A = A.astype(jnp.float32)

    def step(S, inputs):  # S: [b, H, P, N]
        x_t, dt_t, B_t, C_t = inputs
        S = (jnp.exp(dt_t * A)[..., None, None] * S
             + (dt_t[..., None] * x_t)[..., :, None] * B_t[..., None, :])
        return S, jnp.einsum("bhpn,bhn->bhp", S, C_t, precision=_HI)

    S0 = jnp.zeros(x.shape[:1] + x.shape[2:] + (B.shape[-1],), jnp.float32)
    _, y = jax.lax.scan(step, S0, tuple(jnp.moveaxis(z, 1, 0) for z in (x, dt, Bh, Ch)))
    return jnp.moveaxis(y, 0, 1)


def _pad_time(z, Lp):
    return jnp.pad(z, ((0, 0), (0, Lp - z.shape[1])) + ((0, 0),) * (z.ndim - 2))


def ssd_chunked(x, dt, A, B, C, *, chunk: int = CHUNK):
    """The same function as :func:`ssd_recurrent`, chunk by chunk; any length (a
    short last chunk is padded with tokens that leave the state alone: ``dt`` 0).
    Returns y [b, L, H, P] in ``x``'s dtype."""
    from ..core import obs

    obs.gauge_set("ssd.chunk", chunk)
    obs.gauge_set("ssd.kernel", 0)
    b, L, H, P = x.shape
    G, N = B.shape[2:]
    n = -(-L // chunk)
    Lp = n * chunk

    def chunks(z):  # [b, L, ...] -> [b, n, Q, ...] float32
        z = _pad_time(z.astype(jnp.float32), Lp)
        return z.reshape((b, n, chunk) + z.shape[2:])

    xc, dtc, Bc, Cc = (chunks(z) for z in (x, dt, B, C))
    Bh, Ch = (jnp.repeat(z, H // G, axis=3) for z in (Bc, Cc))  # [b, n, Q, H, N]
    cs = jnp.cumsum(dtc * A.astype(jnp.float32), axis=2)  # [b, n, Q, H]
    u = xc * dtc[..., None]
    t = jnp.arange(chunk)
    seg = jnp.moveaxis(cs, 2, 3)  # [b, n, H, Q]
    decay = jnp.exp(jnp.where(t[:, None] >= t[None, :], seg[..., :, None] - seg[..., None, :],
                              -jnp.inf))  # [b, n, H, Q(t), Q(s)]
    scores = jnp.einsum("bnthk,bnshk->bnhts", Ch, Bh) * decay
    y = jnp.einsum("bnhts,bnshp->bnthp", scores, u)
    # each chunk's contribution to the state from zero, then the states carried
    to_end = jnp.exp(cs[:, :, -1:, :] - cs)  # [b, n, Q, H]
    added = jnp.einsum("bnshp,bnshk->bnhpk", u * to_end[..., None], Bh)
    whole = jnp.exp(cs[:, :, -1, :])  # [b, n, H]

    def carry(S, inputs):
        gamma, add = inputs
        return gamma[..., None, None] * S + add, S

    _, before = jax.lax.scan(carry, jnp.zeros((b, H, P, N), jnp.float32),
                             (jnp.moveaxis(whole, 1, 0), jnp.moveaxis(added, 1, 0)))
    before = jnp.moveaxis(before, 0, 1)  # [b, n, H, P, N]: the state each chunk starts from
    y = y + jnp.exp(cs)[..., None] * jnp.einsum("bnthk,bnhpk->bnthp", Ch, before)
    return y.reshape(b, Lp, H, P)[:, :L].astype(x.dtype)


# -- the chunk path as Pallas kernels ----------------------------------------
# A grid step is one B/C group of one sequence over a run of chunks: the group's
# heads' ``x`` are ``heads * P`` lanes of a row (512 at 8 heads of 64), ``B`` and
# ``C`` its ``N`` lanes, so ``C B^T`` is formed once a group and a chunk and each
# head scales it by its own decay.  The heads' states, transposed (``[N, P]`` a
# head, ``[N, heads * P]`` the group), stay in a VMEM scratch along the sequential
# axis; the forward emits the state each run starts from, the only residual
# beside the inputs, and the backward runs a run's chunks forward again from it,
# keeping each chunk's starting state in VMEM, then back, carrying ``dS``.
#
# Everything is done a 128-lane tile at a time: a tile holds ``128 / P`` heads
# (two of 64), a head's own products take the tile with the other heads' lanes
# masked to zero, and per-head scales are selects of columns over the tile.  No
# operand is sliced at less than a whole tile.  The chunk's running sums ``c``
# come in from XLA (a ``cumsum`` of ``dt A`` inside each chunk, differentiated by
# jax), as rows ``[1, Q]`` a head and chunk, beside ``dt``'s rows.

_LANES = 128
_NN = ((1,), (0,))  # a @ b
_NT = ((1,), (1,))  # a @ b.T
_TN = ((0,), (0,))  # a.T @ b
_RUN_TARGET = 16  # chunks a grid step (the state a run starts from is its residual)
_VMEM_LIMIT = 64 * 2**20  # the scoped limit the calls ask Mosaic for (a v5e has 128 MiB)


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())), preferred_element_type=jnp.float32)


def _column(row, eye):  # [1, Q] -> [Q, 1]
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _row(column, eye):  # [Q, 1] -> [1, Q]
    return jnp.sum(jnp.where(eye, column, 0.0), axis=0, keepdims=True)


class _Chunk:
    """What a chunk's gates give a head, float32: the decay matrix ``L`` (``exp(c_t -
    c_s)``, s <= t), and as columns ``dt``, ``e = exp(c)``, ``w = exp(c_Q - c)``,
    and ``gamma = exp(c_Q)`` ([1, 1])."""

    def __init__(self, dt_row, cs_row, m):
        self.dt = _column(dt_row, m["eye"])
        c = _column(cs_row, m["eye"])
        last = c[-1:, :]
        self.L = jnp.exp(jnp.where(m["lower"], c - cs_row, -jnp.inf))
        self.e, self.w, self.gamma = jnp.exp(c), jnp.exp(last - c), jnp.exp(last)


def _masks(P):
    Q = CHUNK
    r = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    owner = jax.lax.broadcasted_iota(jnp.int32, (Q, _LANES), 1) // P
    owner_row = jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1) // P
    return dict(eye=r == c, lower=r >= c, last=c[:1] == Q - 1,
                head=[owner == k for k in range(_LANES // P)],
                head_row=[owner_row == k for k in range(_LANES // P)])


def _over_tile(values, masks):
    """Per-head values (columns or scalars) of one tile's heads -> one array over the
    tile's lanes: head ``k``'s value on its lanes."""
    out = values[0] * jnp.ones_like(masks[0], jnp.float32)
    for v, mask in zip(values[1:], masks[1:]):
        out = jnp.where(mask, v, out)
    return out


def _load(refs, c, heads):
    """Chunk ``c``: its token rows, the rows as float32, and each head's ``dt`` and
    ``c`` rows [1, Q]."""
    x_ref, dt_ref, cs_ref, b_ref, c_ref = refs
    rows = pl.ds(pl.multiple_of(c * CHUNK, CHUNK), CHUNK)
    x = x_ref[0, rows, :].astype(jnp.float32)
    Bc, Cc = (r[0, rows, :].astype(jnp.float32) for r in (b_ref, c_ref))
    gates = [(dt_ref[0, h, pl.ds(c, 1), :], cs_ref[0, h, pl.ds(c, 1), :]) for h in range(heads)]
    return rows, x, Bc, Cc, gates


def _tiles(heads, P):
    """(lane slice, the tile's heads) of a group's lanes, a tile at a time."""
    per = _LANES // P
    return [(slice(j * _LANES, (j + 1) * _LANES), list(range(j * per, (j + 1) * per)))
            for j in range(heads * P // _LANES)]


def _ssd_fwd_kernel(x_ref, dt_ref, cs_ref, b_ref, c_ref, y_ref, s_ref, state, *, n, heads, P):
    """Grid cell (sequence, group, run): ``n`` chunks of the group's heads from the
    state in scratch; the state the run starts from goes out as the backward's
    residual."""

    @pl.when(pl.program_id(2) == 0)
    def _start():
        state[...] = jnp.zeros_like(state)

    s_ref[0, 0, 0] = state[...]
    m = _masks(P)
    refs = (x_ref, dt_ref, cs_ref, b_ref, c_ref)

    def chunk(c, carry):
        rows, x, Bc, Cc, gates = _load(refs, c, heads)
        G = _dot(Cc, Bc, _NT)
        ch = [_Chunk(dt, cs, m) for dt, cs in gates]
        for lanes, tile_heads in _tiles(heads, P):
            k = [ch[h] for h in tile_heads]
            S = state[:, lanes]
            u = x[:, lanes] * _over_tile([g.dt for g in k], m["head"])
            y = _over_tile([g.e for g in k], m["head"]) * _dot(Cc, S, _NN)
            for g, mask in zip(k, m["head"]):
                y = y + _dot(G * g.L, jnp.where(mask, u, 0.0), _NN)
            y_ref[0, rows, lanes] = y.astype(y_ref.dtype)
            state[:, lanes] = (_over_tile([g.gamma for g in k], m["head_row"]) * S
                               + _dot(Bc, _over_tile([g.w for g in k], m["head"]) * u, _TN))
        return carry

    jax.lax.fori_loop(0, n, chunk, 0)


def _ssd_bwd_kernel(x_ref, dt_ref, cs_ref, b_ref, c_ref, s_ref, dy_ref,
                    dx_ref, ddt_ref, dcs_ref, db_ref, dc_ref, dstate, starts, *, n, heads, P):
    """Grid cell (sequence, group, run), the runs last to first.  The run's chunks
    go forward once more from the state it started from, each chunk's starting
    state kept in VMEM, then backward with ``dS`` carried in scratch (across the
    runs too).  Gradients of ``x``, ``dt``, ``c``, ``B`` and ``C``."""

    @pl.when(pl.program_id(2) == 0)
    def _start():
        dstate[...] = jnp.zeros_like(dstate)

    m = _masks(P)
    refs = (x_ref, dt_ref, cs_ref, b_ref, c_ref)
    tiles = _tiles(heads, P)

    def forward(c, S):
        starts[c] = S
        _, x, Bc, _, gates = _load(refs, c, heads)
        ch = [_Chunk(dt, cs, m) for dt, cs in gates]
        out = []
        for lanes, tile_heads in tiles:
            k = [ch[h] for h in tile_heads]
            u = x[:, lanes] * _over_tile([g.dt for g in k], m["head"])
            out.append(_over_tile([g.gamma for g in k], m["head_row"]) * S[:, lanes]
                       + _dot(Bc, _over_tile([g.w for g in k], m["head"]) * u, _TN))
        return jnp.concatenate(out, axis=1) if len(out) > 1 else out[0]

    jax.lax.fori_loop(0, n, forward, s_ref[0, 0, 0])

    def backward(i, carry):
        c = n - 1 - i
        rows, x, Bc, Cc, gates = _load(refs, c, heads)
        dy = dy_ref[0, rows, :].astype(jnp.float32)
        S0, dS = starts[c], dstate[...]
        G = _dot(Cc, Bc, _NT)
        ch = [_Chunk(dt, cs, m) for dt, cs in gates]
        dG = jnp.zeros_like(G)
        dB = jnp.zeros_like(Bc)
        dC = jnp.zeros_like(Cc)
        d_c, d_cs_row, d_dt, d_last = {}, {}, {}, {}
        for lanes, tile_heads in tiles:
            k = [ch[h] for h in tile_heads]
            Dt = _over_tile([g.dt for g in k], m["head"])
            E = _over_tile([g.e for g in k], m["head"])
            W = _over_tile([g.w for g in k], m["head"])
            x_t, dy_t, S, dS_t = x[:, lanes], dy[:, lanes], S0[:, lanes], dS[:, lanes]
            u = x_t * Dt
            CS, BdS = _dot(Cc, S, _NN), _dot(Bc, dS_t, _NN)
            du = W * BdS
            for h, g, mask in zip(tile_heads, k, m["head"]):
                dy_h = jnp.where(mask, dy_t, 0.0)
                dM = _dot(dy_h, u, _NT)
                M = G * g.L
                du = du + _dot(M, dy_h, _TN)
                dG = dG + dM * g.L
                R = dM * M  # dL * L: the decay's own gradient
                d_c[h] = jnp.sum(R, axis=1, keepdims=True)
                d_cs_row[h] = -jnp.sum(R, axis=0, keepdims=True)
            uBdS, dyCS, SdS, dux = u * BdS, dy_t * CS, S * dS_t, du * x_t
            for h, g, mask, mask_row in zip(tile_heads, k, m["head"], m["head_row"]):
                dw = jnp.sum(jnp.where(mask, uBdS, 0.0), axis=1, keepdims=True)
                de = jnp.sum(jnp.where(mask, dyCS, 0.0), axis=1, keepdims=True)
                dgamma = jnp.sum(jnp.where(mask_row, SdS, 0.0), keepdims=True)
                d_c[h] = d_c[h] + de * g.e - dw * g.w
                d_last[h] = jnp.sum(dw * g.w, keepdims=True) + dgamma * g.gamma
                d_dt[h] = jnp.sum(jnp.where(mask, dux, 0.0), axis=1, keepdims=True)
            dx_ref[0, rows, lanes] = (du * Dt).astype(dx_ref.dtype)
            dB = dB + _dot(W * u, dS_t, _NT)
            dC = dC + _dot(E * dy_t, S, _NT)
            dstate[:, lanes] = (_over_tile([g.gamma for g in k], m["head_row"]) * dS_t
                                + _dot(Cc, E * dy_t, _TN))
        dC = dC + _dot(dG, Bc, _NN)
        dB = dB + _dot(dG, Cc, _TN)
        db_ref[0, rows, :] = dB.astype(db_ref.dtype)
        dc_ref[0, rows, :] = dC.astype(dc_ref.dtype)
        for h in range(heads):
            ddt_ref[0, h, pl.ds(c, 1), :] = _row(d_dt[h], m["eye"])
            dcs_ref[0, h, pl.ds(c, 1), :] = (d_cs_row[h] + _row(d_c[h], m["eye"])
                                            + jnp.where(m["last"], d_last[h], 0.0))
        return carry

    jax.lax.fori_loop(0, n, backward, 0)


def _choose_run(N):
    """Chunks a grid step for a sequence of ``N`` chunks: a short sequence is one run;
    else 16 or 8 (the gates' rows, ``[n, Q]``, want whole sublane tiles), the one
    that pads ``N`` least, the larger at a tie."""
    if N <= _RUN_TARGET:
        return N
    return min((_RUN_TARGET, _RUN_TARGET // 2), key=lambda r: (-(-N // r) * r, -r))


def _specs(heads, P, N_state, n, step):
    """BlockSpecs of a run of one group: the heads' token rows, ``B`` / ``C``'s, the
    heads' gate rows, the run's state; ``step`` maps the grid's run index."""
    T = n * CHUNK
    return (pl.BlockSpec((1, T, heads * P), lambda b, g, i: (b, step(i), g)),
            pl.BlockSpec((1, T, N_state), lambda b, g, i: (b, step(i), g)),
            pl.BlockSpec((1, heads, n, CHUNK), lambda b, g, i: (b, g, step(i), 0)),
            pl.BlockSpec((1, 1, 1, N_state, heads * P), lambda b, g, i: (b, g, step(i), 0, 0)))


_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT)


def _forward(n, groups, interpret, x, dt, cs, B, C):
    """``ssd_fwd`` over rows: (y [b, Lp, H P], the states the runs start from
    [b, G, runs, N, heads P])."""
    bsz, H, NC, _ = dt.shape
    N, heads = B.shape[-1] // groups, H // groups
    P, steps = x.shape[-1] // H, NC // n
    x_rows, bc_rows, g_rows, state = _specs(heads, P, N, n, lambda i: i)
    return pl.pallas_call(
        functools.partial(_ssd_fwd_kernel, n=n, heads=heads, P=P),
        grid=(bsz, groups, steps),
        in_specs=[x_rows, g_rows, g_rows, bc_rows, bc_rows],
        out_specs=[x_rows, state],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((bsz, groups, steps, N, heads * P), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((N, heads * P), jnp.float32)],
        compiler_params=_COMPILER_PARAMS, interpret=interpret, name="ssd_fwd",
    )(x, dt, cs, B, C)


def _backward(n, groups, interpret, x, dt, cs, B, C, states, dy):
    """``ssd_bwd`` over the same rows, the forward's states and dy: the gradients of
    x, dt, c, B and C, laid out as their inputs."""
    bsz, H, NC, _ = dt.shape
    N, heads = B.shape[-1] // groups, H // groups
    P, steps = x.shape[-1] // H, NC // n
    x_rows, bc_rows, g_rows, state = _specs(heads, P, N, n, lambda i: steps - 1 - i)
    return pl.pallas_call(
        functools.partial(_ssd_bwd_kernel, n=n, heads=heads, P=P),
        grid=(bsz, groups, steps),
        in_specs=[x_rows, g_rows, g_rows, bc_rows, bc_rows, state, x_rows],
        out_specs=[x_rows, g_rows, g_rows, bc_rows, bc_rows],
        out_shape=[jax.ShapeDtypeStruct(a.shape, a.dtype) for a in (x, dt, cs, B, C)],
        scratch_shapes=[pltpu.VMEM((N, heads * P), jnp.float32),
                        pltpu.VMEM((n, N, heads * P), jnp.float32)],
        compiler_params=_COMPILER_PARAMS, interpret=interpret, name="ssd_bwd",
    )(x, dt, cs, B, C, states, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _ssd_rows(n, groups, interpret, x, dt, cs, B, C):
    return _forward(n, groups, interpret, x, dt, cs, B, C)[0]


def _ssd_rows_fwd(n, groups, interpret, *rows):
    y, states = _forward(n, groups, interpret, *rows)
    # a recomputing caller may keep these two (ops/kept.py); the rows it rebuilds
    y, states = kept.tag("ssd_fwd", y=y, states=states)
    return y, (*rows, states)


def _ssd_rows_bwd(n, groups, interpret, residuals, dy):
    return tuple(_backward(n, groups, interpret, *residuals, dy))


_ssd_rows.defvjp(_ssd_rows_fwd, _ssd_rows_bwd)


def ssd_pallas(x, dt, A, B, C, interpret=False):
    """The same function as :func:`ssd_chunked`, by the kernels ``ssd_fwd`` and
    ``ssd_bwd``: chunk 128, a group's heads a whole number of 128-lane tiles (``P``
    dividing 128), ``N`` a multiple of 128, any length (padded with tokens that
    leave the state alone); a grid step's chunks come from the length
    (:func:`_choose_run`).  ``interpret=True`` runs the kernels on the CPU.  The
    re-layout to rows, the running sums of ``dt A`` and the way back through them
    are plain jax; the kernels' ``jax.custom_vjp`` is over the rows."""
    from ..core import obs

    bsz, L, H, P = x.shape
    G, N = B.shape[2:]
    NC = -(-L // CHUNK)
    n = _choose_run(NC)
    steps = -(-NC // n)
    Lp = steps * n * CHUNK
    obs.gauge_set("ssd.chunk", CHUNK)
    obs.gauge_set("ssd.kernel", 1)
    obs.gauge_set("ssd.heads_per_step", H // G)
    obs.gauge_set("ssd.chunks_per_step", n)
    dt = _pad_time(dt.astype(jnp.float32), Lp)  # [b, Lp, H]
    cs = jnp.cumsum((dt * A.astype(jnp.float32)).reshape(bsz, Lp // CHUNK, CHUNK, H), axis=2)

    def gate_rows(z):  # [b, Lp / Q, Q, H] -> [b, H, Lp / Q, Q]
        return jnp.moveaxis(z, 3, 1)

    y = _ssd_rows(n, G, interpret,
                  _pad_time(x, Lp).reshape(bsz, Lp, H * P),
                  gate_rows(dt.reshape(bsz, Lp // CHUNK, CHUNK, H)), gate_rows(cs),
                  _pad_time(B, Lp).reshape(bsz, Lp, G * N), _pad_time(C, Lp).reshape(bsz, Lp, G * N))
    return y[:, :L].reshape(bsz, L, H, P)


def _kernels_take(x, B):
    """The kernels' shapes: a group's heads whole 128-lane tiles, a state of whole lanes."""
    H, P = x.shape[2:]
    G, N = B.shape[2:]
    return _LANES % P == 0 and (H // G * P) % _LANES == 0 and N % _LANES == 0


def ssd(x, dt, A, B, C):
    """What the model calls.  Dispatch on the default backend and the shapes and
    nothing else: the kernels on ``tpu`` for shapes they take (a kernel that does
    not compile raises), :func:`ssd_chunked` everywhere else."""
    if jax.default_backend() == "tpu" and _kernels_take(x, B):
        return ssd_pallas(x, dt, A, B, C)
    return ssd_chunked(x, dt, A, B, C)
