"""Long-context stack: ring attention over the virtual 8-device mesh,
sequence-parallel transformer, pallas flash-attention kernel (interpret mode).
The capability SURVEY.md §5 lists as absent in the reference and the brief
requires first-class."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.models.transformer import TransformerConfig, TransformerLM, causal_attention
from fedml_tpu.ops.flash_attention import flash_attention, reference_attention
from fedml_tpu.parallel.mesh import create_mesh
from fedml_tpu.parallel.ring_attention import ring_attention

pytestmark = pytest.mark.heavy  # long XLA compiles; see pytest.ini


def _qkv(B=2, L=64, H=4, D=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (B, L, H, D)
    return tuple(jax.random.normal(k, shape, jnp.float32) * 0.5 for k in ks)


@pytest.fixture(scope="module")
def sp_mesh():
    return create_mesh((8,), ("sp",))


class TestRingAttention:
    def test_matches_full_attention_causal(self, sp_mesh):
        q, k, v = _qkv()
        full = reference_attention(q, k, v, causal=True)
        ring = ring_attention(q, k, v, sp_mesh, axis_name="sp", causal=True)
        np.testing.assert_allclose(np.asarray(ring), np.asarray(full), atol=2e-5)

    def test_matches_full_attention_noncausal(self, sp_mesh):
        q, k, v = _qkv(seed=3)
        full = reference_attention(q, k, v, causal=False)
        ring = ring_attention(q, k, v, sp_mesh, axis_name="sp", causal=False)
        np.testing.assert_allclose(np.asarray(ring), np.asarray(full), atol=2e-5)

    def test_grad_flows(self, sp_mesh):
        q, k, v = _qkv(L=32, seed=5)

        def loss_ring(q):
            return jnp.sum(ring_attention(q, k, v, sp_mesh) ** 2)

        def loss_full(q):
            return jnp.sum(reference_attention(q, k, v) ** 2)

        g_ring = jax.grad(loss_ring)(q)
        g_full = jax.grad(loss_full)(q)
        np.testing.assert_allclose(np.asarray(g_ring), np.asarray(g_full), atol=5e-4)


class TestSequenceParallelTransformer:
    def test_forward_matches_single_device(self, sp_mesh):
        from fedml_tpu.parallel.seq_parallel import sp_apply, sp_init

        cfg = TransformerConfig(vocab_size=128, d_model=64, n_heads=4, n_layers=2,
                                d_ff=128, max_seq_len=64)
        params = sp_init(cfg, seed=0)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, 128)

        single = TransformerLM(cfg).apply(params, tokens)
        sp = sp_apply(cfg, params, tokens, sp_mesh)
        np.testing.assert_allclose(np.asarray(sp), np.asarray(single), atol=3e-4)

    def test_sp_training_step_decreases_loss(self, sp_mesh):
        import optax

        from fedml_tpu.parallel.seq_parallel import sp_init, sp_loss_fn

        cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=2, n_layers=1, d_ff=64)
        params = sp_init(cfg, seed=0)
        loss_fn = sp_loss_fn(cfg, sp_mesh)
        tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 32), 0, 64)
        targets = jnp.roll(tokens, -1, axis=1)
        tx = optax.adam(1e-2)
        opt = tx.init(params)
        grad_fn = jax.jit(jax.value_and_grad(lambda p: loss_fn(p, tokens, targets)))
        l0, grads = grad_fn(params)
        for _ in range(5):
            l, grads = grad_fn(params)
            updates, opt = tx.update(grads, opt, params)
            params = optax.apply_updates(params, updates)
        l_end, _ = grad_fn(params)
        assert float(l_end) < float(l0)


class TestFlashAttentionKernel:
    @pytest.mark.parametrize("causal", [True, False])
    def test_kernel_matches_reference(self, causal):
        q, k, v = _qkv(B=1, L=64, H=2, D=16, seed=7)
        ref = reference_attention(q, k, v, causal=causal)
        out = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16,
                              interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    def test_kernel_single_block(self):
        q, k, v = _qkv(B=1, L=16, H=1, D=8, seed=9)
        ref = reference_attention(q, k, v, causal=True)
        out = flash_attention(q, k, v, causal=True, block_q=16, block_k=16,
                              interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    def test_kernel_grad_matches_reference(self):
        """custom_vjp: jax.grad through the kernel == grad through reference."""
        q, k, v = _qkv(B=1, L=32, H=2, D=8, seed=11)

        def loss_flash(q, k, v):
            return jnp.sum(
                flash_attention(q, k, v, True, 16, 16, True) ** 2
            )

        def loss_ref(q, k, v):
            return jnp.sum(reference_attention(q, k, v, causal=True) ** 2)

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)

    @pytest.mark.parametrize("causal", [True, False])
    def test_kernel_grad_ragged_and_noncausal(self, causal):
        """The pallas backward kernels must keep exact gradients through the
        internal pad-to-block path (dead lse rows, padded key tails) and for
        both mask modes."""
        q, k, v = _qkv(B=1, L=24, H=2, D=8, seed=17)

        def loss_flash(q, k, v):
            return jnp.sum(flash_attention(q, k, v, causal, 16, 16, True) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(reference_attention(q, k, v, causal=causal) ** 2)

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)

    def test_ragged_length_padded(self):
        """L not divisible by block size is padded internally."""
        q, k, v = _qkv(B=1, L=24, H=2, D=8, seed=13)
        ref = reference_attention(q, k, v, causal=True)
        out = flash_attention(q, k, v, causal=True, block_q=16, block_k=16,
                              interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
        # non-causal must also exclude padded keys
        refn = reference_attention(q, k, v, causal=False)
        outn = flash_attention(q, k, v, causal=False, block_q=16, block_k=16,
                               interpret=True)
        np.testing.assert_allclose(np.asarray(outn), np.asarray(refn), atol=2e-5)

    def test_mismatched_block_sizes(self):
        """block_q != block_k where the smaller does not divide the padded
        length: geometry must pad to a common multiple, not silently truncate
        one grid axis (keys never folded in / rows never written)."""
        q, k, v = _qkv(B=1, L=32, H=1, D=8, seed=19)
        for bq, bk in ((32, 24), (24, 32)):
            ref = reference_attention(q, k, v, causal=False)
            out = flash_attention(q, k, v, causal=False, block_q=bq,
                                  block_k=bk, interpret=True)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       atol=2e-5, err_msg=f"bq={bq} bk={bk}")

    @pytest.mark.parametrize("L", [88, 96])
    @pytest.mark.parametrize("blocks", [(32, 16), (16, 32), (64, 16), (16, 64)],
                             ids=lambda b: f"{b[0]}x{b[1]}")
    def test_unequal_blocks_forward_and_grad(self, blocks, L):
        """block_q != block_k both ways, a ragged and an even length: every
        grid holds dead tiles (above the diagonal; wholly padded at 64x16,
        L 88), interior tiles (the unmasked path), diagonal tiles and — at L
        88 — a tile the padded tail crosses, and the K/V (dQ pass: K/V; dK/dV
        pass: q, dO, lse, delta) index maps are clamped on the dead steps."""
        bq, bk = blocks
        q, k, v = _qkv(B=1, L=L, H=2, D=8, seed=37 + L)
        w = jax.random.normal(jax.random.PRNGKey(L), q.shape, jnp.float32)

        def loss(attn):
            return lambda q, k, v: jnp.sum(attn(q, k, v) * w)

        flash = lambda q, k, v: flash_attention(q, k, v, True, bq, bk, True)
        ref = lambda q, k, v: reference_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(flash(q, k, v)),
                                   np.asarray(ref(q, k, v)), atol=2e-5)
        gf = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss(ref), argnums=(0, 1, 2))(q, k, v)
        for name, a, b in zip("qkv", gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4,
                                       err_msg=f"d{name} at {bq}x{bk}, L={L}")

    def test_default_blocks_match_reference(self):
        """No explicit block: each kernel picks its own pair from the shape
        (one 128-row tile here, the tail crossing it), and records it."""
        from fedml_tpu.core import obs

        q, k, v = _qkv(B=1, L=40, H=2, D=8, seed=41)
        ref = reference_attention(q, k, v, causal=True)
        out = flash_attention(q, k, v, causal=True, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
        gauges = {(r["metric"], r["labels"].get("kernel")): r["value"]
                  for r in obs.registry().export()
                  if r["metric"].startswith("flash.") and "window" not in r["labels"]}
        assert gauges[("flash.block_q", "flash_fwd")] == 128
        assert gauges[("flash.block_k", "flash_fwd")] == 128
        assert gauges[("flash.live_step_share", "flash_fwd")] == 1.0

    def test_transformer_with_flash_attention(self):
        """The kernel slots in as the transformer's attention_fn."""
        from functools import partial

        cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=2, n_layers=1, d_ff=64)
        attn = lambda q, k, v: flash_attention(q, k, v, causal=True, block_q=16,
                                               block_k=16, interpret=True)
        tokens = jax.random.randint(jax.random.PRNGKey(3), (1, 32), 0, 64)
        params = TransformerLM(cfg).init(jax.random.PRNGKey(0), tokens)
        base = TransformerLM(cfg).apply(params, tokens)
        flash = TransformerLM(cfg, attention_fn=attn).apply(params, tokens)
        np.testing.assert_allclose(np.asarray(flash), np.asarray(base), atol=3e-4)


class TestRingPlusPallas:
    """The composed design: ppermute moves K/V shards around the ring, the
    pallas block-update kernel (flash_shard_update) folds each shard into
    the running online-softmax state per chip."""

    @pytest.mark.parametrize("causal", [True, False])
    def test_ring_with_pallas_blocks_matches_reference(self, sp_mesh, causal):
        from functools import partial

        from fedml_tpu.parallel.ring_attention import (
            pallas_block_attend,
            ring_attention,
        )

        q, k, v = _qkv(B=1, L=64, H=2, D=16, seed=23)
        full = reference_attention(q, k, v, causal=causal)
        ring = ring_attention(
            q, k, v, sp_mesh, axis_name="sp", causal=causal,
            block_fn=partial(pallas_block_attend, block_q=8, block_k=8,
                             interpret=True),
        )
        np.testing.assert_allclose(np.asarray(ring), np.asarray(full), atol=2e-5)

    def test_shard_update_matches_block_attend(self):
        """One shard fold: the kernel must reproduce _block_attend exactly,
        including carried state from a previous fold."""
        from fedml_tpu.ops.flash_attention import flash_shard_update
        from fedml_tpu.parallel.ring_attention import _block_attend

        q, k, v = _qkv(B=2, L=32, H=2, D=8, seed=29)
        k2, v2 = k + 0.1, v - 0.1
        q_pos = jnp.arange(32)
        k_pos = jnp.arange(32) + 32  # a later shard (partially masked causal)
        B, L, H, D = q.shape
        m0 = jnp.full((B, H, L), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((B, H, L), jnp.float32)
        o0 = jnp.zeros((B, L, H, D), jnp.float32)
        # first fold: the local shard
        m1, l1, o1 = _block_attend(q, k, v, q_pos, q_pos, True, m0, l0, o0)
        # second fold via BOTH paths, carrying the first fold's state
        ref = _block_attend(q, k2, v2, q_pos, k_pos, True, m1, l1, o1)
        got = flash_shard_update(q, k2, v2, q_pos, k_pos, m1, l1, o1,
                                 causal=True, block_q=8, block_k=8,
                                 interpret=True)
        for a, b in zip(got, ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)

    def test_ring_with_pallas_blocks_is_trainable(self, sp_mesh):
        """jax.grad flows through the composed path (custom_vjp recompute
        through the canonical shard update) and matches the full-attention
        gradient."""
        from functools import partial

        from fedml_tpu.parallel.ring_attention import (
            pallas_block_attend,
            ring_attention,
        )

        q, k, v = _qkv(B=1, L=32, H=2, D=8, seed=31)
        bf = partial(pallas_block_attend, block_q=8, block_k=8, interpret=True)

        def loss_ring(q):
            return jnp.sum(ring_attention(q, k, v, sp_mesh, block_fn=bf) ** 2)

        def loss_full(q):
            return jnp.sum(reference_attention(q, k, v) ** 2)

        g_ring = jax.grad(loss_ring)(q)
        g_full = jax.grad(loss_full)(q)
        np.testing.assert_allclose(np.asarray(g_ring), np.asarray(g_full),
                                   atol=5e-4)


@pytest.fixture(scope="module")
def fa():
    """The kernel's module (the package re-exports the name as the function)."""
    import importlib

    return importlib.import_module("fedml_tpu.ops.flash_attention")


class TestFlashBlockRule:
    """The pure function that tiles the three kernels from the shape."""

    @pytest.mark.parametrize("kernel", ["flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"])
    @pytest.mark.parametrize("L", [8, 1023, 1152, 1664, 2048, 32768])
    @pytest.mark.parametrize("head", [(128, jnp.bfloat16), (64, jnp.bfloat16),
                                      (32, jnp.float32), (256, jnp.float32)],
                             ids=lambda h: f"D{h[0]}-{jnp.dtype(h[1]).name}")
    def test_blocks_divide_the_lane_rounded_length_inside_the_budget(
            self, fa, kernel, L, head):
        D, dtype = head
        rounded = -(-L // 128) * 128
        bq, bk = fa._choose_blocks(kernel, L, D, dtype)
        for b in (bq, bk):
            assert b % 128 == 0 and rounded % b == 0, (bq, bk)
        assert fa._vmem_bytes(kernel, bq, bk, D, jnp.dtype(dtype).itemsize) \
            <= fa._VMEM_BUDGET
        target = fa._BLOCK_TARGET[kernel]
        assert bq <= target[0] and bk <= target[1]
        # nothing pads beyond its lane rounding: L 1,023 -> 1,024, L 8 -> 128
        blocks, padded = fa._geometry(L, D, dtype, None, None)
        assert padded == rounded and blocks[kernel] == (bq, bk)

    def test_rule_at_the_cells_shape_leaves_the_step_floor(self, fa):
        """At L 2,048, D 128, bf16 no kernel is left at 128 x 128 (16,384
        grid steps a call): at most 1,024 steps of 64 head-batches."""
        for kernel in fa._BLOCK_TARGET:
            bq, bk = fa._choose_blocks(kernel, 2048, 128, jnp.bfloat16)
            assert (2048 // bq) * (2048 // bk) <= 16, (kernel, bq, bk)

    def test_explicit_blocks_override_and_share_one_padded_length(self, fa):
        blocks, padded = fa._geometry(32, 8, jnp.float32, 32, 24)
        assert set(blocks.values()) == {(32, 24)} and padded == 96
        # one explicit block: the other is each kernel's own, one length for all
        blocks, padded = fa._geometry(1023, 64, jnp.bfloat16, 256, None)
        assert all(b[0] == 256 for b in blocks.values())
        assert all(padded % b == 0 for pair in blocks.values() for b in pair)

    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.parametrize("blocks", [(32, 16), (16, 32), (64, 16)],
                             ids=lambda b: f"{b[0]}x{b[1]}")
    def test_clamped_index_maps_stay_on_live_tiles(self, fa, blocks, causal):
        """On every step of either grid the clamped index names a live tile of
        its row (column), and on a live step it is the step's own tile."""
        bq, bk = blocks
        L, padded = 88, 128
        tile = dict(block_q=bq, block_k=bk, causal=causal, valid_len=L)
        for i in range(padded // bq):
            for j in range(padded // bk):
                live = bool(fa._tile_live(i, j, **tile))
                kj = int(fa._live_k_block(i, j, **tile))
                qi = int(fa._live_q_block(i, j, **tile))
                if live:
                    assert (kj, qi) == (j, i)
                if i * bq < L:  # a row with live tiles names one of them
                    assert fa._tile_live(i, kj, **tile)
                if j * bk < L:
                    assert fa._tile_live(qi, j, **tile)
                # an interior tile is live and its mask is all true
                if fa._tile_interior(i, j, **tile) and i * bq < L:
                    assert live
                    assert bool(jnp.all(fa._tile_mask(i, j, (bq, bk), 0, **tile)))
