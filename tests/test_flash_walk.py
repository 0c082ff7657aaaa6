"""The flash kernels' grids hold their live tiles (PR 36): the sequential axis walks one
member's live run and then its partner's from the other end of the causal triangle.

Pure numpy over the walk's own arithmetic (``_tiling``'s ``step`` / ``block`` take index
grids as they take program ids), then the kernels in interpret mode against
``reference_attention`` on the same geometries cut to small blocks."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.core import obs

fa = importlib.import_module("fedml_tpu.ops.flash_attention")

# (L, block_q, block_k, window, causal, group)
WALKS = [
    # the cells' shapes: smallthinker (forward and dQ tiles, dK/dV tiles; windowed, global),
    # glm47-flash and kimi-linear (forward, dQ at 256 / 256, dK/dV), dsllm7b-sim
    (16384, 1024, 1024, 4096, True, 7), (16384, 512, 512, 4096, True, 7),
    (16384, 1024, 1024, None, True, 7), (16384, 512, 512, None, True, 7),
    (8192, 1024, 1024, None, True, 1), (8192, 512, 1024, None, True, 1),
    (8192, 512, 512, None, True, 1),
    (2048, 1024, 1024, None, True, 1), (2048, 512, 512, None, True, 1),
    # an odd count of blocks (the middle one walks alone), with and without a window
    (1280, 256, 256, None, True, 2), (1280, 256, 256, 300, True, 1), (384, 128, 128, 1, True, 3),
    # one block; unequal blocks both ways; a ragged length; a padded tail of whole blocks
    (100, 128, 128, None, True, 1), (1024, 128, 256, 200, True, 2),
    (1024, 256, 128, None, True, 1), (1000, 128, 128, 300, True, 7),
    (300, 128, 256, None, True, 2), (300, 256, 128, 150, True, 1),
    # no causal mask: the rectangle stays
    (1024, 128, 128, None, False, 1), (300, 128, 256, None, False, 2),
]
_walk_id = lambda c: "-".join(str(x) for x in c)


def _padded(L, bq, bk):
    m = int(np.lcm(bq, bk))
    return -(-L // m) * m


@pytest.mark.parametrize("kernel", ["flash_fwd", "flash_bwd_dkv"])
@pytest.mark.parametrize("case", WALKS, ids=_walk_id)
def test_the_walk_visits_every_live_tile_once_in_order(case, kernel):
    L, bq, bk, window, causal, group = case
    Lp = _padded(L, bq, bk)
    tile, grid, step, block = fa._tiling(kernel, Lp, (bq, bk), causal, L, window, group)
    keys_major = kernel == "flash_bwd_dkv"
    # [q block, k block] holds a live pair: the differences t - s of a block of queries
    # and a block of keys (both cut to the L real positions) are every integer between
    # the smallest and the largest, and a pair is live where 0 <= t - s < window
    q0, k0 = np.arange(Lp // bq)[:, None] * bq, np.arange(Lp // bk)[None, :] * bk
    q1, k1 = np.minimum(q0 + bq, L) - 1, np.minimum(k0 + bk, L) - 1
    live = (q0 <= q1) & (k0 <= k1)
    if causal:
        live &= (q1 - k0 >= 0) & (q0 - k1 < (window or Lp))
    if keys_major:
        live = live.T
    n, inner = live.shape
    reps = group if keys_major else 1

    # a member's run is its live tiles and nothing else; a member of padding alone keeps
    # a run (its accumulators are still zeroed and written once)
    run = fa._live_q_run if keys_major else fa._live_k_run
    first, last = (np.broadcast_to(x, (n,)) for x in run(np.arange(n), **tile))
    for m in range(n):
        assert 0 <= first[m] <= last[m] < inner
        if live[m].any():
            assert list(np.flatnonzero(live[m])) == list(range(first[m], last[m] + 1))
    length = last - first + 1
    longest = max(length[o] + (length[n - 1 - o] if n - 1 - o != o else 0)
                  for o in range(-(-n // 2)))
    paired = causal and n > 1 and -(-n // 2) * longest < n * inner
    assert grid == ((-(-n // 2), reps * longest) if paired else (n, reps * inner))

    o, s = np.meshgrid(np.arange(grid[0]), np.arange(grid[1]), indexing="ij")
    member, at, starts, ends, inside = (None if x is None else np.asarray(x)
                                        for x in step(o, s))
    named = [np.broadcast_to(np.asarray(x), o.shape) for x in block(o, s)]
    runs = live[member, at] if inside is None else live[member, at] & inside
    visits = {}  # member -> [(rep, tile)] in the grid's order
    for oo in range(grid[0]):
        seen = []  # the members of this outer step, in order: each one contiguous run
        for ss in range(grid[1]):
            m = int(member[oo, ss])
            assert 0 <= named[0][oo, ss] < n and 0 <= named[1][oo, ss] < inner
            assert 0 <= named[2][oo, ss] < reps
            if not seen or seen[-1] != m:
                assert m not in seen, "a member's steps are one contiguous run"
                seen.append(m)
            assert int(named[0][oo, ss]) == m  # the output block follows the member
            if runs[oo, ss]:
                rep = int(named[2][oo, ss])
                assert int(named[1][oo, ss]) == int(at[oo, ss])  # the step's own tile
                visits.setdefault(m, []).append((rep, int(at[oo, ss])))
        # the accumulators: zeroed once before a member's first tile, written once after
        # its last, every tile in between
        for m in seen:
            mine = np.flatnonzero(member[oo] == m)
            began, ended = (np.flatnonzero(x[oo] & (member[oo] == m)) for x in (starts, ends))
            assert len(began) == 1 and len(ended) == 1 and began[0] <= ended[0]
            ran = mine[runs[oo, mine]]
            assert all(began[0] <= x <= ended[0] for x in ran)
    assert sorted(int(m) for m in np.unique(member[starts])) == list(range(n))
    assert int(starts.sum()) == n and int(ends.sum()) == n
    for m in range(n):
        want = [(g, j) for g in range(reps) for j in np.flatnonzero(live[m])]
        assert visits.get(m, []) == want  # each live tile once, ascending, head after head

    gauges = {r["metric"]: r["value"] for r in obs.registry().export()
              if r["labels"].get("kernel") == kernel
              and r["labels"].get("window") == (None if window is None else str(window))}
    assert gauges["flash.grid_steps"] == grid[0] * grid[1]
    assert gauges["flash.live_step_share"] == live.sum() * reps / (grid[0] * grid[1])
    if not causal:
        assert gauges["flash.grid_steps"] == n * inner * reps  # no step to save: the rectangle


# (kernel, L, blocks, window, group) at the cells' shapes -> (steps a head's grid runs, live)
@pytest.mark.parametrize("kernel,L,blocks,window,group,steps,live", [
    ("flash_fwd", 16384, (1024, 1024), 4096, 7, 80, 70),
    ("flash_bwd_dq", 16384, (1024, 1024), 4096, 7, 80, 70),
    ("flash_bwd_dkv", 16384, (512, 512), 4096, 7, 2016, 1764),
    ("flash_fwd", 16384, (1024, 1024), None, 7, 136, 136),
    ("flash_bwd_dkv", 16384, (512, 512), None, 7, 3696, 3696),
    ("flash_fwd", 8192, (1024, 1024), None, 1, 36, 36),
    ("flash_bwd_dq", 8192, (512, 1024), None, 1, 72, 72),
    ("flash_bwd_dkv", 8192, (512, 512), None, 1, 136, 136),
    ("flash_fwd", 2048, (1024, 1024), None, 1, 3, 3),
    ("flash_bwd_dkv", 2048, (512, 512), None, 1, 10, 10),
])
def test_the_cells_grids_are_their_live_tiles(kernel, L, blocks, window, group, steps, live):
    _, grid, _, _ = fa._tiling(kernel, L, blocks, True, L, window, group)
    assert grid[0] * grid[1] == steps
    share = next(r["value"] for r in obs.registry().export()
                 if r["metric"] == "flash.live_step_share" and r["labels"].get("kernel") == kernel
                 and r["labels"].get("window") == (None if window is None else str(window)))
    assert share == live / steps >= 0.85


# the same geometries cut to blocks of 8-32 rows (the cells' 16 and 32 blocks a sequence,
# their windows of 4 and 8 blocks, 7 query heads a kv head), for the kernels themselves
KERNELS = [
    (256, 16, 16, 64, True, 7), (256, 8, 8, 64, True, 7), (256, 16, 16, None, True, 7),
    (128, 16, 16, None, True, 1), (128, 8, 16, None, True, 1), (32, 8, 8, None, True, 1),
    (80, 16, 16, None, True, 2), (80, 16, 16, 20, True, 1), (48, 16, 16, 2, True, 3),
    (12, 16, 16, None, True, 1), (128, 16, 32, 25, True, 2), (128, 32, 16, None, True, 1),
    (100, 16, 16, 30, True, 7), (40, 16, 32, None, True, 2), (40, 32, 16, 18, True, 1),
    (64, 16, 16, None, False, 1),
]


@pytest.mark.parametrize("case", KERNELS, ids=_walk_id)
def test_the_kernels_on_the_walk_match_the_reference(case):
    L, bq, bk, window, causal, group = case
    ks = jax.random.split(jax.random.PRNGKey(L + bq), 3)
    q = jax.random.normal(ks[0], (1, L, 2 * group, 16))
    k, v = (jax.random.normal(key, (1, L, 2, 16)) for key in ks[1:])

    def value_and_grads(fn):
        return jax.jit(jax.value_and_grad(lambda *a: jnp.sum(jnp.sin(fn(*a))), (0, 1, 2)))(q, k, v)

    want = value_and_grads(lambda *a: fa.reference_attention(*a, causal, window))
    got = value_and_grads(lambda *a: fa.flash_attention(*a, causal, bq, bk, True, window))
    for x, y in zip(jax.tree_util.tree_leaves(want), jax.tree_util.tree_leaves(got)):
        assert x.shape == y.shape
        assert float(jnp.max(jnp.abs(x - y))) <= 2e-5 * (float(jnp.max(jnp.abs(x))) + 1e-12)
