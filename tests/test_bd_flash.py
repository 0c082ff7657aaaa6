"""The block-diffusion mode of the flash kernels (``bd_flash_*``, interpret mode on the
CPU) that the ``sdar_moe`` decoder trains by: forward and gradients against the
``[2L, 2L]`` mask written out, block length one against the causal call, the grids'
live steps and gauges at the cell's shape, and the block lengths refused.
``tests/test_sdar_moe.py`` holds the model."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.test_sdar_moe import _assert_close

fa = importlib.import_module("fedml_tpu.ops.flash_attention")


def _bd_operands(L, Hq, Hkv, D=8, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed + L + Hq), 4)
    q = jax.random.normal(ks[0], (1, 2 * L, Hq, D))
    k, v = (jax.random.normal(key, (1, 2 * L, Hkv, D)) for key in ks[1:3])
    return q, k, v, jax.random.normal(ks[3], (1, 2 * L, Hq, D))


@pytest.mark.parametrize("group", [1, 8])
@pytest.mark.parametrize("block_len", [1, 4, 16])
@pytest.mark.parametrize("L,block,queries", [(256, 128, "both"), (640, 256, "both"),
                                             (256, 128, "noised"), (640, 256, "noised")],
                         ids=["L256", "L640_padded_tail", "L256_noised", "L640_padded_tail_noised"])
def test_bd_kernels_are_the_mask_written_out(L, block, queries, block_len, group):
    """Forward, dQ, dK and dV of the three ``bd_flash_*`` kernels against
    ``reference_attention`` with the [2L, 2L] mask written out (640 under blocks of 256
    pads its tail to 768).  ``noised``: q over the noised half alone (L rows against 2L
    keys: the mask's first L rows), and the same call against the full call's noised half
    under a cotangent that is zero on the clean half (dK / dV over both halves).
    Tolerance 2e-6 of a leaf's largest entry: float32 in interpret mode, the online
    softmax's summation order against the reference's one softmax."""
    q_full, k, v, w_full = _bd_operands(L, group, 1)
    rows = L if queries == "noised" else 2 * L
    q, w = q_full[:, :rows], w_full[:, :rows]

    def out_and_grads(fn, q, w):
        out, vjp = jax.vjp(fn, q, k, v)
        return out, vjp(w)

    def kernels(*a):
        return fa.bd_flash_attention(*a, block_len, block, True)

    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda: out_and_grads(
            lambda *a: fa.reference_attention(*a, block_diffusion=block_len), q, w))()
    got = jax.jit(lambda: out_and_grads(kernels, q, w))()
    _assert_close(want, got, 2e-6)
    if queries == "noised":
        out, (dq, dk, dv) = jax.jit(lambda: out_and_grads(
            kernels, q_full, w_full.at[:, L:].set(0.0)))()
        _assert_close((out[:, :L], (dq[:, :L], dk, dv)), got, 2e-6)


def test_block_length_one_is_the_causal_call_on_the_clean_half():
    """At B = 1 a clean query sees the clean keys up to its own: the clean half's output
    and log-sum-exp are the causal call's, to float32 rounding."""
    L, Hq, Hkv, D = 256, 4, 2, 8
    q, k, v, _ = _bd_operands(L, Hq, Hkv, D)
    out, lse = jax.jit(lambda *a: fa._bd_forward(*a, 1, 128, True))(q, k, v)
    causal, causal_lse = jax.jit(lambda *a: fa._flash_forward(
        *a, True, 128, 128, True, with_lse=True))(q[:, L:], k[:, L:], v[:, L:])
    np.testing.assert_allclose(out[:, L:], causal, atol=2e-6)
    # lse rows are [B * Hkv * 2 * group, 1, Lp], a kv head's noised heads first
    clean = lse.reshape(Hkv, 2, Hq // Hkv, L)[:, 1].reshape(Hq, L)
    np.testing.assert_allclose(clean, causal_lse[:, 0], atol=2e-6)


def test_bd_grids_walk_live_steps_and_leave_their_gauges():
    """The cell's shape: every row's run is causal-shaped, so the paired walk holds live
    steps only (held here to a share >= 0.95); the gauges carry the bd names, the causal calls'
    theirs, and the last layer's call of the noised queries alone a second label
    ``queries: noised``, so the full calls' readings stay."""
    from fedml_tpu.core import obs

    S = jax.ShapeDtypeStruct
    kv = S((1, 2 * 8192, 4, 128), jnp.bfloat16)
    for rows in (2 * 8192, 8192):
        jax.make_jaxpr(jax.grad(
            lambda *a: fa.bd_flash_attention(*a, 4).astype(jnp.float32).sum(), (0, 1, 2)))(
                S((1, rows, 32, 128), jnp.bfloat16), kv, kv)
    gauges = {(r["metric"], r["labels"].get("kernel"), r["labels"].get("queries")): r["value"]
              for r in obs.registry().export()
              if r["kind"] == "gauge" and r["labels"].get("kernel", "").startswith("bd_")}
    for kernel in fa._BD_KERNELS:
        for queries, group in ((None, 16), ("noised", 8)):  # the noised copy's heads beside the 8
            assert gauges[("flash.live_step_share", kernel, queries)] >= 0.95, kernel
            assert gauges[("flash.kv_group", kernel, queries)] == group
            assert (gauges[("flash.block_q", kernel, queries)]
                    == gauges[("flash.block_k", kernel, queries)])


@pytest.mark.parametrize("block_len,L,error", [(3, 96, "divide"), (4, 30, "divide"),
                                               (256, 512, "divide")])
def test_bd_mode_refuses_blocks_that_do_not_divide_the_tiles(block_len, L, error):
    q, k, v, _ = _bd_operands(L, 2, 1)
    with pytest.raises(ValueError, match=error):
        fa.bd_flash_attention(q, k, v, block_len, None, True)
