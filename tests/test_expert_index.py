"""The expert layer's two ways of moving its rows (``expert_lm.grouped_experts``): where a
tier's block is a large share of the step's assignments both scatter-adds — the
combine and the transpose of the dispatch's gather — are gathers through the inverse of
the layer's sort (``spread_rows`` / ``unpermute_sum``, each the other's transpose);
below ``GATHERED_SHARE`` the layer is the scatter form it was.  On the CPU at tiny
widths: one form against the other and against a plain loop over the experts, the two
helpers against XLA's own transposes, and which form a shape takes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.models import expert_lm

HELD, ROUTED, T, K, D, F = 4, 16, 80, 4, 16, 8  # 4 of 16: a block of 240 of the 320 assignments


def _layer(dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    h = jax.random.normal(ks[0], (T, D), dtype)
    weights = jax.nn.softmax(jax.random.normal(ks[1], (T, K)), -1)
    w_gate, w_up = (jax.random.normal(k, (HELD, D, F), dtype) * D ** -0.5 for k in ks[2:4])
    w_down = jax.random.normal(ks[4], (HELD, F, D), dtype) * F ** -0.5
    return h, weights, w_gate, w_up, w_down


def _chosen(share, seed=1):
    """The first ``share`` of the tokens choose among the held experts, the others among
    the absent ones: ``share`` of the assignments land here."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    here = (jnp.arange(T) < round(share * T))[:, None]
    return jnp.where(here, jax.random.randint(ks[0], (T, K), 0, HELD),
                     HELD + jax.random.randint(ks[1], (T, K), 0, ROUTED - HELD))


def _value_and_grads(chosen, operands, held=(0, HELD), routed=ROUTED):
    def loss(h, weights, w_gate, w_up, w_down):
        out, counters = expert_lm.grouped_experts(h, chosen, weights, held, w_gate, w_up,
                                                  w_down, routed)
        wave = jnp.cos(jnp.arange(out.size, dtype=jnp.float32)).reshape(out.shape)
        return jnp.sum(out.astype(jnp.float32) * wave), (out, counters)

    (_, (out, counters)), grads = jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1, 2, 3, 4), has_aux=True))(*operands)
    return (out,) + grads, counters


def _in_form(monkeypatch, gathered, *args, **kwargs):
    monkeypatch.setattr(expert_lm, "GATHERED_SHARE", 0.0 if gathered else 2.0)
    return _value_and_grads(*args, **kwargs)


def _gauge():
    from fedml_tpu.core import obs

    return [r["value"] for r in obs.registry().export()
            if r["kind"] == "gauge" and r["metric"] == "moe.combine_gathered"]


# 0.8 and 1: over the block's 75 %, a tier of two blocks
@pytest.mark.parametrize("share", [0.0, 0.1, 0.5, 0.8, 1.0])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-6), (jnp.bfloat16, 2 ** -7)],
                         ids=["float32", "bfloat16"])
def test_the_gather_form_is_the_scatter_form(monkeypatch, share, dtype, tol):
    """Output and the gradients with respect to ``h``, the weights and the three expert
    matrices.  bfloat16: the scatter form adds a token's k terms in bfloat16 one after
    another, the gather form rounds their float32 sum once."""
    chosen, operands = _chosen(share), _layer(dtype)
    got, counters = _in_form(monkeypatch, True, chosen, operands)
    want, _ = _in_form(monkeypatch, False, chosen, operands)
    assert float(counters["moe.assignments_local"]) == round(share * T) * K
    assert float(counters["moe.assignments_dropped"]) == 0.0
    assert (float(counters["moe.assignments_local"]) > 240) == (share > 0.75)  # the second block
    for g, w in zip(got, want):
        g, w = g.astype(jnp.float32), w.astype(jnp.float32)
        assert g.shape == w.shape and bool(jnp.all(jnp.isfinite(g)))
        assert float(jnp.max(jnp.abs(g - w))) <= tol * (float(jnp.max(jnp.abs(w))) + 1e-12)
    if dtype == jnp.bfloat16:  # no further from the float32 layer than the scatter form is
        exact = _value_and_grads(chosen, [o.astype(jnp.float32) for o in operands])[0][0]
        err = [float(jnp.max(jnp.abs(x[0].astype(jnp.float32) - exact))) for x in (got, want)]
        assert err[0] <= err[1] + 1e-6


def test_a_token_with_every_assignment_here_and_one_with_none(monkeypatch):
    """Token 0: all k assignments on held experts (two on the same one); token 1: none;
    the others mixed.  Against a plain loop over the experts."""
    monkeypatch.setattr(expert_lm, "GATHERED_SHARE", 0.0)
    h, weights, w_gate, w_up, w_down = operands = _layer(jnp.float32, seed=3)
    chosen = jax.random.randint(jax.random.PRNGKey(4), (T, K), 0, ROUTED)
    chosen = chosen.at[0].set(jnp.array([5, 4, 5, 7])).at[1].set(jnp.array([0, 3, 8, 15]))
    held = (4, 8)

    def plain(h, weights, w_gate, w_up, w_down):
        out = jnp.zeros_like(h)
        for e in range(held[1] - held[0]):
            y = expert_lm.swiglu(h, w_gate[e], w_up[e], w_down[e])
            out = out + jnp.sum(jnp.where(chosen == held[0] + e, weights, 0.0), -1)[:, None] * y
        return out

    got, counters = _value_and_grads(chosen, operands, held=held)
    wave = jnp.cos(jnp.arange(T * D, dtype=jnp.float32)).reshape(T, D)
    want = jax.grad(lambda *a: jnp.sum(plain(*a) * wave), argnums=(0, 1, 2, 3, 4))(*operands)
    assert float(counters["moe.assignments_dropped"]) == 0.0
    for g, w in zip(got, (plain(*operands),) + want):
        np.testing.assert_allclose(g, w, atol=2e-5)
    assert float(jnp.max(jnp.abs(got[0][0]))) > 0.0
    np.testing.assert_array_equal(got[0][1], 0.0)  # nothing of token 1 was computed here
    np.testing.assert_array_equal(got[1][1], 0.0)  # and nothing reaches it on the way back
    np.testing.assert_array_equal(got[2][1], 0.0)  # its weights' neither


def _index(rows, start, n_local, seed=0):
    """A sort's two sides for ``T * K`` assignments of which ``n_local`` are live, seen
    from the sorted rows [start, start + rows)."""
    order = jax.random.permutation(jax.random.PRNGKey(seed), T * K)
    pos = jnp.argsort(order).reshape(T, K).T
    token = jnp.pad(order, (0, 2 * T * K))[start:start + rows] // K
    live = start + jnp.arange(rows) < n_local
    return (token, live) + expert_lm._rows_of(pos, start, rows, n_local)


@pytest.mark.parametrize("rows,start,n_local", [(240, 0, 100), (240, 0, 320), (240, 240, 300),
                                                (480, 0, 320), (240, 0, 0)])
def test_each_helper_is_the_others_transpose(rows, start, n_local):
    """``spread_rows``' backward against XLA's transpose of its forward (the scatter-add
    it replaces), ``unpermute_sum``'s likewise, and the two as one pair of adjoints."""
    index = _index(rows, start, n_local)
    h = jax.random.normal(jax.random.PRNGKey(1), (T, D))
    g = jax.random.normal(jax.random.PRNGKey(2), (rows, D))

    def plain_spread(h):
        return jnp.where(index[1][:, None], h[index[0]], 0)

    want, = jax.linear_transpose(plain_spread, h)(g)
    got, = jax.vjp(lambda h: expert_lm.spread_rows(h, *index), h)[1](g)
    np.testing.assert_allclose(got, want, atol=1e-6)
    np.testing.assert_allclose(expert_lm.unpermute_sum(g, *index), want, atol=1e-6)
    np.testing.assert_array_equal(expert_lm.spread_rows(h, *index), plain_spread(h))
    back, = jax.vjp(lambda g: expert_lm.unpermute_sum(g, *index), g)[1](h)
    np.testing.assert_array_equal(back, plain_spread(h))
    # <spread(h), g> = <h, unpermute_sum(g)>
    np.testing.assert_allclose(jnp.vdot(plain_spread(h), g), jnp.vdot(h, got), rtol=1e-5)


def _scatter_adds(jaxpr, found=None):
    """The shape of every ``scatter-add`` equation's result, sub-jaxprs included."""
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scatter-add":
            found.append(eqn.outvars[0].aval.shape)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _scatter_adds(sub, found)
    return found


@pytest.mark.parametrize("tokens,k,held,routed,d,gathered", [
    (8192, 8, 8, 256, 32, False),   # kimi-linear's cell: a block of an eighth
    (16384, 6, 16, 64, 32, True),   # smallthinker's: three quarters
    (T, K, HELD, ROUTED, D, True)])  # the tiny presets
def test_which_form_a_shape_takes(tokens, k, held, routed, d, gathered):
    """From (assignments, held, routed) alone: the jaxpr of forward + backward holds no
    scatter-add in the gather form and all of them below the constant, and the gauge
    says which."""
    from fedml_tpu.core import obs

    obs.gauge_set("moe.combine_gathered", -1)

    def loss(h, weights, w_gate, w_up, w_down, chosen):
        out, _ = expert_lm.grouped_experts(h, chosen, weights, (0, held), w_gate, w_up,
                                           w_down, routed)
        return out.astype(jnp.float32).sum()

    s = jax.ShapeDtypeStruct
    shapes = (s((tokens, d), jnp.bfloat16), s((tokens, k), jnp.float32),
              s((held, d, 8), jnp.bfloat16), s((held, d, 8), jnp.bfloat16),
              s((held, 8, d), jnp.bfloat16), s((tokens, k), jnp.int32))
    jaxpr = jax.make_jaxpr(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4)))(*shapes)
    found = _scatter_adds(jaxpr.jaxpr)
    assert _gauge() == [int(gathered)]
    if gathered:
        assert found == []
    else:  # the combine and the gather's transpose; the weights' cotangent; ``bincount``
        assert {(tokens, d), (tokens * k,), (held + 1,)} == set(found)
    base, _, _ = expert_lm.expert_blocks(tokens * k, held, routed)
    assert gathered == (base >= expert_lm.GATHERED_SHARE * tokens * k)


def test_the_backward_gathers_sit_under_the_layers_scopes():
    """``benchmark/scope_times.py`` files device time by ``op_name``: the custom
    backward's gathers carry the scope their forward was called in.  A gather into
    [k, T, d] under ``lm.moe.dispatch`` can only be the dispatch's way back, one into a
    block's [240, d] under ``lm.moe.combine`` only the combine's."""
    import re

    chosen, operands = _chosen(0.5), _layer(jnp.float32)

    def loss(h, weights, *experts):
        return expert_lm.grouped_experts(h, chosen, weights, (0, HELD), *experts, ROUTED)[0].sum()

    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(*operands).as_text(
        debug_info=True)
    names = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    gathers = {(shape, names[loc].split("/")[-2]) for shape, loc in re.findall(
        r'"stablehlo\.gather".*-> tensor<([^>]+)> loc\((#loc\d+)\)', text)}
    assert {scope for _, scope in gathers} == {"lm.moe.dispatch", "lm.moe.combine"}
    assert {(f"{K}x{T}x{D}xf32", "lm.moe.dispatch"), (f"240x{D}xf32", "lm.moe.combine"),
            (f"{K}x{T}x{D}xf32", "lm.moe.combine"), (f"240x{D}xf32", "lm.moe.dispatch")} <= gathers


# (d, f, tokens a step, experts a token, held, routed) of each configuration's expert layer at
# its cell (``benchmark/configs``; ``sdar``'s layers see both halves of a row) and of the tiny
# presets, and whether the grouped products take a tiling of their own: only
# nemotron-3-nano's widths fall to the compiler's 128-wide tiles (2,688 = 21 x 128 and 1,856,
# no multiple of 128)
TILING_CASES = {
    "nemotron-3-nano-30b-a3b-sim": (2688, 1856, 8192, 6, 8, 128, True),
    "kimi-linear-48b-a3b-sim": (2304, 1024, 8192, 8, 8, 256, False),
    "smallthinker-21b-a3b-sim": (2560, 768, 16384, 6, 16, 64, False),
    "glm-4.7-flash-sim": (2048, 1536, 8192, 4, 8, 64, False),
    "sdar-30b-a3b-sim": (2048, 768, 16384, 8, 16, 128, False),
    "tiny-nemotron-h": (32, 8, 64, 6, 2, 8, False),
    "tiny-kimi-linear": (36, 16, 64, 4, 4, 16, False),
    "tiny-smallthinker": (40, 12, 64, 4, 4, 16, False),
    "tiny-glm47-flash": (32, 24, 64, 4, 8, 8, False),
    "tiny-sdar": (32, 12, 64, 2, 4, 4, False)}


@pytest.mark.parametrize("preset", list(TILING_CASES))
def test_which_grouped_products_take_a_tiling(preset):
    """``product_tiling`` reads a product's shape alone: a tiling for nemotron-3-nano's two
    products (k and n tiles of 256 or more), none for the other configurations' and the
    tiny presets'.  The gauges say what a traced layer asked for."""
    from fedml_tpu.core import obs

    d, f, tokens, k, held, routed, tiled = TILING_CASES[preset]
    base = expert_lm.expert_blocks(tokens * k, held, routed)[0]
    asked = [expert_lm.product_tiling(base, d, f), expert_lm.product_tiling(base, f, d)]
    if not tiled:
        assert asked == [None, None]
    else:
        for tiling in asked:
            rows, tile_k, tile_n = map(int, tiling.split(","))
            assert min(tile_k, tile_n) >= 256 and rows <= base

    gated = preset != "tiny-nemotron-h" and "nemotron" not in preset
    s = jax.ShapeDtypeStruct
    shapes = (s((tokens, d), jnp.bfloat16), s((tokens, k), jnp.int32), s((tokens, k), jnp.float32),
              s((held, d, f), jnp.bfloat16), s((held, f, d), jnp.bfloat16),
              s((held, d, f), jnp.bfloat16) if gated else None)

    def layer(h, chosen, weights, w_up, w_down, w_gate):
        return expert_lm.grouped_experts(h, chosen, weights, (0, held), w_gate, w_up, w_down,
                                         routed, jax.nn.silu if gated else expert_lm.relu2)[0]

    jax.make_jaxpr(layer)(*shapes)
    gauges = {r["metric"]: r["value"] for r in obs.registry().export() if r["kind"] == "gauge"
              and r["metric"] in ("moe.tiled_products", "moe.tile_k", "moe.tile_n")}
    up = asked[0].split(",")[1:] if tiled else [expert_lm._compiler_tile(w) for w in (d, f)]
    assert gauges == {"moe.tiled_products": 6 if tiled else 0,
                      "moe.tile_k": int(up[0]), "moe.tile_n": int(up[1])}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
def test_a_tiled_product_is_the_plain_one(dtype):
    """Widths that fall to the compiler's 128-wide tiles (640 and 576: 5 and 4.5 x 128): the
    product under its tilings — its own custom backward, the cotangent's product tiled from
    its own widths — gives ``jax.lax.ragged_dot``'s value and gradients, bit for bit."""
    rows, groups, k, n = 512, 4, 640, 576
    assert expert_lm.product_tiling(rows, k, n) == "512,640,640"
    assert expert_lm.product_tiling(rows, n, k) == "512,640,640"
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    x = jax.random.normal(ks[0], (rows, k), dtype)
    w = (jax.random.normal(ks[1], (groups, k, n)) * k ** -0.5).astype(dtype)
    wave = jax.random.normal(ks[2], (rows, n))
    sizes = jnp.array([100, 0, 250, 162], jnp.int32)

    def loss(product):
        return lambda x, w: jnp.sum(product(x, w, sizes).astype(jnp.float32) * wave)

    got = jax.jit(jax.value_and_grad(loss(expert_lm._grouped_product), (0, 1)))(x, w)
    want = jax.jit(jax.value_and_grad(loss(jax.lax.ragged_dot), (0, 1)))(x, w)
    jaxpr = str(jax.make_jaxpr(jax.grad(loss(expert_lm._grouped_product)))(x, w))
    assert "custom_vjp_call" in jaxpr
    for g, r in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(g, np.float32), np.asarray(r, np.float32))
