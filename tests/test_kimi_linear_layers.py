"""The ``kimi_linear`` decoder's layers against their plain reference, on the CPU
in float32 at tiny widths with the published ratios (``benchmark/configs/
tiny-kimi-linear.json``): KDA chunkwise and its kernels against the per-token
recurrence, the flash kernels at q/k width != v width, the MLA mixer, the expert
layer (uniform and planted routing, nothing dropped, the shares of a deployment
adding up to the uncut layer).  ``tests/test_kimi_linear.py`` holds the whole
model and its round."""

import importlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_kimi_linear as ref
from fedml_tpu.models import kimi_linear as kl
from fedml_tpu.ops import kda

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = os.path.join(ROOT, "benchmark", "configs", "tiny-kimi-linear.json")
fa = importlib.import_module("fedml_tpu.ops.flash_attention")


@pytest.fixture(scope="module")
def model():
    with open(TINY) as f:
        return json.load(f)


def _kda_inputs(L, B=2, H=3, D=8, gate=1.0, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q, k = (jax.random.normal(ks[i], (B, L, H, D)) for i in (0, 1))
    q, k = (x / jnp.linalg.norm(x, axis=-1, keepdims=True) for x in (q, k))
    v = jax.random.normal(ks[2], (B, L, H, D))
    g = -gate * jax.nn.softplus(jax.random.normal(ks[3], (B, L, H, D)))
    return q, k, v, g, jax.nn.sigmoid(jax.random.normal(ks[4], (B, L, H)))


def _value_and_grads(fn, args):
    return jax.jit(jax.value_and_grad(lambda *a: jnp.sum(jnp.sin(fn(*a))),
                                      tuple(range(len(args)))))(*args)


def _assert_close(a, b, tol):
    for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        scale = float(jnp.max(jnp.abs(x))) + 1e-12
        assert float(jnp.max(jnp.abs(x - y))) <= tol * scale


# L not a multiple of the chunk, L under one chunk, strong gates (exp(-8 * 64)
# underflows: every exponent stays non-positive), and with small chunks the
# long-sequence path (groups of 16 chunks, recomputed on the way back)
@pytest.mark.parametrize("L,gate,chunks", [(100, 1.0, {}), (40, 1.0, {}), (130, 8.0, {}),
                                           (128, 1.0, {"chunk": 4, "block": 2}),
                                           (67, 0.05, {"chunk": 8, "block": 4})])
def test_kda_chunkwise_is_the_per_token_recurrence(L, gate, chunks):
    args = _kda_inputs(L, gate=gate)
    want = _value_and_grads(kda.kda_recurrent, args)
    got = _value_and_grads(lambda *a: kda.kda_chunked(*a, **chunks), args)
    np.testing.assert_allclose(jax.jit(lambda *a: kda.kda_chunked(*a, **chunks))(*args),
                               jax.jit(kda.kda_recurrent)(*args), atol=5e-6)
    _assert_close(want, got, 5e-5)
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in got[1])


# the kernels in interpret mode at d 128, over the cases above that the XLA path
# answers for: L not a multiple of the chunk (and a block of three heads), L under
# one chunk, strong gates, and three runs of 8 chunks, so that the carried state
# and the reverse dS cross a run's boundary (L 1,100: the last run ends in padding)
@pytest.mark.parametrize("L,gate,B,H,run", [(100, 1.0, 1, 3, 2), (40, 1.0, 2, 1, 1),
                                            (130, 8.0, 1, 2, 3), (1100, 1.0, 1, 1, 8)])
def test_kda_kernels_are_the_per_token_recurrence(L, gate, B, H, run):
    from fedml_tpu.core import obs

    args = _kda_inputs(L, B=B, H=H, D=128, gate=gate)
    kernels = lambda *a: kda.kda_pallas(*a, interpret=True)
    want = _value_and_grads(kda.kda_recurrent, args)
    got = _value_and_grads(kernels, args)
    np.testing.assert_allclose(jax.jit(kernels)(*args), jax.jit(kda.kda_recurrent)(*args),
                               atol=5e-6)
    _assert_close(want, got, 5e-5)
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in got[1])
    gauges = {r["metric"]: r["value"] for r in obs.registry().export() if r["kind"] == "gauge"}
    assert gauges["kda.kernel"] == 1 and gauges["kda.chunk"] == 64
    assert gauges["kda.chunks_per_step"] == run  # the shape's own: 18 chunks pad least by 8


def test_kda_kernels_take_bfloat16():
    """q, k, v as the model hands them over; o and their gradients leave in
    bfloat16, so they are held to its rounding (2^-8 of the largest), the
    float32 gradients of g and beta to what that rounding of o moves them."""
    q, k, v, g, beta = _kda_inputs(100, B=1, H=2, D=128)
    args = (q.astype(jnp.bfloat16), k.astype(jnp.bfloat16), v.astype(jnp.bfloat16), g, beta)
    loss = lambda fn: lambda *a: fn(*a).astype(jnp.float32)
    want = _value_and_grads(loss(kda.kda_recurrent), args)
    got = _value_and_grads(loss(lambda *a: kda.kda_pallas(*a, interpret=True)), args)
    assert [x.dtype for x in got[1]] == [x.dtype for x in args]
    as_float = lambda t: jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), t)
    _assert_close(as_float(want), as_float(got), 2.0 ** -7)
    _assert_close(want[1][3:], got[1][3:], 1e-3)
    assert all(bool(jnp.all(jnp.isfinite(x))) for x in as_float(got[1]))


def test_kda_entry_dispatches_on_backend_and_shape():
    """Off the TPU every shape is the XLA path's; on it the kernels take heads
    whose widths are lane multiples (the tiny presets' d = 8 are not)."""
    from fedml_tpu.core import obs

    tiny, wide = _kda_inputs(70, B=1, H=1), jax.eval_shape(lambda: _kda_inputs(70, D=128))
    obs.gauge_set("kda.kernel", 1)
    assert jax.jit(kda.kda)(*tiny).shape == tiny[2].shape
    gauges = {r["metric"]: r["value"] for r in obs.registry().export() if r["kind"] == "gauge"}
    assert jax.default_backend() != "tpu" and gauges["kda.kernel"] == 0
    assert kda._kernels_take(wide[0], wide[2]) and not kda._kernels_take(tiny[0], tiny[2])
    # a step's heads and chunks, from the shape: the cell's, a float32 twin, a prime
    # count of chunks (8 pads it least), one run, heads that 4 does not divide
    assert kda._choose_step(128, 32, 128, 128, jnp.bfloat16) == (4, 16)
    assert kda._choose_step(128, 32, 128, 128, jnp.float32) == (4, 8)
    assert kda._choose_step(17, 32, 128, 128, jnp.bfloat16) == (4, 8)
    assert kda._choose_step(5, 6, 128, 128, jnp.bfloat16) == (3, 5)
    for heads, run in ((4, 16), (4, 8), (3, 5)):
        assert kda._vmem_bytes(heads, run, 128, 128, 2) <= kda._VMEM_BUDGET


@pytest.mark.parametrize("L", [75, 32])
def test_reference_kda_by_chunks_is_its_per_token_form(L):
    args = _kda_inputs(L, gate=2.0, seed=1)
    _assert_close(_value_and_grads(ref.kda_per_token, args),
                  _value_and_grads(ref.kda_by_chunks, args), 2e-5)
    # and the program's oracle is the same recurrence
    np.testing.assert_allclose(ref.kda_per_token(*args), kda.kda_recurrent(*args), atol=1e-6)


@pytest.mark.parametrize("B,L,H,D,Dv", [(2, 200, 3, 24, 16), (1, 130, 2, 192, 128)])
def test_flash_kernels_at_unequal_head_widths(B, L, H, D, Dv):
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q, k = (jax.random.normal(ks[i], (B, L, H, D)) for i in (0, 1))
    v = jax.random.normal(ks[2], (B, L, H, Dv))
    want = _value_and_grads(lambda *a: fa.reference_attention(*a, True), (q, k, v))
    got = _value_and_grads(lambda *a: fa.flash_attention(*a, True, 128, 128, True), (q, k, v))
    assert fa.attention(q, k, v).shape == (B, L, H, Dv)
    _assert_close(want, got, 2e-5)


def test_equal_head_widths_keep_their_geometry():
    """What the cells of dsllm7b-sim run did not move: no padding of the head,
    the blocks and the VMEM reckoning as they were."""
    q = jnp.zeros((1, 8, 2, 128))
    assert fa._head_widths(q, q) == (128, 128, 128)
    assert fa._head_widths(jnp.zeros((1, 8, 2, 192)), q) == (192, 128, 256)
    assert fa._vmem_bytes("flash_fwd", 1024, 1024, 128, 2) == 14155776
    assert fa._choose_blocks("flash_bwd_dkv", 2048, 128, jnp.bfloat16) == (512, 512)
    for kernel in fa._BLOCK_TARGET:
        bq, bk = fa._choose_blocks(kernel, 8192, 256, jnp.bfloat16, 128)
        assert fa._vmem_bytes(kernel, bq, bk, 256, 2, 128) <= fa._VMEM_BUDGET


def test_mla_mixer_is_the_reference(model):
    cfg = kl.KimiLinearConfig.from_dict(model)
    w = ref.make_weights(model, 3)["layers"][3]["mla"]
    h = jax.random.normal(jax.random.PRNGKey(4), (2, 50, model["hidden_size"]))
    got = _value_and_grads(lambda p, x: kl.MLAMixer(cfg).apply({"params": p}, x), (w, h))
    want = _value_and_grads(lambda p, x: ref.mla_mixer(x, p, model, "highest"), (w, h))
    _assert_close(want, got, 2e-5)


def _expert_weights(model, seed, routed_to=None):
    """An expert layer's weights; ``routed_to``: plant the router's correction
    bias so that every token's top k are these experts."""
    w = ref.make_weights(model, seed)["layers"][1]["moe"]
    if routed_to is not None:
        bias = jnp.zeros_like(w["router_bias"]).at[jnp.asarray(routed_to)].set(10.0)
        w = dict(w, router_bias=bias)
    return w


@pytest.mark.parametrize("routed_to", [None, (0, 1, 2, 3)], ids=["uniform", "all_held"])
def test_expert_layer_is_the_reference_and_drops_nothing(model, routed_to):
    cfg = kl.KimiLinearConfig.from_dict(model)
    w = _expert_weights(model, 5, routed_to)
    h = jax.random.normal(jax.random.PRNGKey(6), (2, 40, model["hidden_size"]))

    def program(p, x):
        out, sown = kl.ExpertShare(cfg).apply({"params": p}, x, True, mutable=["counters"])
        return out, sown["counters"]

    got = _value_and_grads(lambda p, x: program(p, x)[0], (w, h))
    want = _value_and_grads(lambda p, x: ref.expert_layer(x, p, model, "highest")[0], (w, h))
    _assert_close(want, got, 2e-5)
    counters = jax.jit(program)(w, h)[1]
    total = 2 * 40 * model["num_experts_per_token"]
    assert float(counters["moe.assignments_total"]) == total
    assert float(counters["moe.assignments_dropped"]) == 0.0
    if routed_to is not None:  # the worst case: every assignment lands here
        assert float(counters["moe.assignments_local"]) == total
    else:
        assert 0 < float(counters["moe.assignments_local"]) < total
    assert float(got[1][0]["router_bias"].max()) == 0.0  # chosen by it, never trained by it


def test_shares_of_a_deployment_add_up_to_the_uncut_layer(model):
    """4 shares of 2 of 8 held experts (of 16 routed), the shared expert
    counted once, against the reference's layer with all 8."""
    whole = dict(model, experts_held=[0, 8], num_experts=8)
    w = ref.make_weights(whole, 7)["layers"][1]["moe"]
    h = jax.random.normal(jax.random.PRNGKey(8), (2, 40, model["hidden_size"]))
    want = ref.expert_layer(h, w, whole, "highest")[0]
    shared = ref.swiglu(h, w["shared"]["w_gate"], w["shared"]["w_up"], w["shared"]["w_down"],
                        "highest")
    total = shared
    for lo in range(0, 8, 2):
        cfg = kl.KimiLinearConfig.from_dict(dict(model, experts_held=[lo, lo + 2]))
        part = dict(w, **{n: w[n][lo:lo + 2] for n in ("e_gate", "e_up", "e_down")})
        total = total + kl.ExpertShare(cfg).apply({"params": part}, h) - shared
    np.testing.assert_allclose(total, want, atol=2e-5)
    # and the reference's own shares add up alike
    parts = sum(ref.expert_layer(h, w, whole, "highest", held=(lo, lo + 2))[0] - shared
                for lo in range(0, 8, 2))
    np.testing.assert_allclose(parts + shared, want, atol=2e-5)
