"""Cross-lower every Pallas entry point for the TPU from the CPU sandbox, so
a BlockSpec the TPU lowering refuses fails CI without a chip (every kernel
in the tree was refused this way until PR 21, and ``interpret=True`` tests
could not see it).  Where libtpu can describe a v5e without a chip attached
the kernels are also compiled, which runs Mosaic itself."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.ops.flash_attention import flash_attention, flash_shard_update

# (B, L, H, D, dtype): bench transformer attention (ragged L, D=64), a
# 128-wide head, the TransformerConfig default head dim (256/8 = 32),
# model.init's L=8 trace, and the benchmark cells' own shape (dsllm7b-sim)
SHAPES = [(8, 1023, 16, 64, jnp.bfloat16), (2, 1024, 8, 128, jnp.bfloat16),
          (2, 256, 8, 32, jnp.float32), (2, 8, 8, 32, jnp.float32),
          (2, 2048, 32, 128, jnp.bfloat16)]


def _forward(q, k, v):
    return flash_attention(q, k, v, causal=True)


def _grad(q, k, v):
    return jax.grad(lambda *a: _forward(*a).astype(jnp.float32).sum(),
                    argnums=(0, 1, 2))(q, k, v)


def _shard_update(q, k, v, q_pos, k_pos, m, l, o):
    return flash_shard_update(q, k, v, q_pos, k_pos, m, l, o, causal=True)


def _entry_points(B, L, H, D, dtype):
    qkv = (jax.ShapeDtypeStruct((B, L, H, D), dtype),) * 3
    pos = jax.ShapeDtypeStruct((L,), jnp.int32)
    stat = jax.ShapeDtypeStruct((B, H, L), jnp.float32)
    acc = jax.ShapeDtypeStruct((B, L, H, D), jnp.float32)
    return [("forward", _forward, qkv), ("grad", _grad, qkv),
            ("shard_update", _shard_update, qkv + (pos, pos, stat, stat, acc))]


@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: "x".join(map(str, s[:4])))
def test_pallas_entry_points_lower_for_tpu(shape):
    for name, fn, args in _entry_points(*shape):
        text = jax.jit(fn).trace(*args).lower(
            lowering_platforms=("tpu",)).as_text()
        assert "tpu_custom_call" in text, name


@pytest.fixture(scope="module")
def v5e():
    """One compile-only v5e device, or skip: needs a libtpu that can
    describe the topology with no chip attached."""
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2",
            chips_per_host_bounds=(2, 2, 1), num_slices=1)
    except Exception as e:  # no libtpu, or one that wants real hardware
        pytest.skip(f"no compile-only TPU topology here: {e}")
    return topo.devices[0]


@pytest.mark.parametrize("shape", SHAPES[:1] + SHAPES[2:],
                         ids=lambda s: "x".join(map(str, s[:4])))
def test_pallas_entry_points_compile_under_mosaic(v5e, shape):
    sharding = jax.sharding.SingleDeviceSharding(v5e)
    for name, fn, args in _entry_points(*shape):
        args = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)
                for a in args]
        assert jax.jit(fn).lower(*args).compile() is not None, name


# latent attention: q and k of 192 (128 + 64, no lane multiple), v of 128; the
# second is the benchmark's own shape (kimi-linear-48b-a3b-sim, one sequence).  And
# rotated latent attention with v as wide as q and k, 256 / 256 at 20 heads: the
# benchmark's own shape (glm-4.7-flash-sim, one sequence of 8,192: the forward's step is
# reckoned at the 16 MiB VMEM budget to the byte, dQ's q block steps down to 512) and a
# ragged length in float32 (the tiles step down further)
MLA_SHAPES = [(2, 1000, 4, 192, 128, jnp.bfloat16), (1, 8192, 32, 192, 128, jnp.bfloat16),
              (1, 8192, 20, 256, 256, jnp.bfloat16), (2, 1000, 5, 256, 256, jnp.float32)]


def _mla_args(B, L, H, D, Dv, dtype, sharding=None):
    kw = {} if sharding is None else {"sharding": sharding}
    qk = jax.ShapeDtypeStruct((B, L, H, D), dtype, **kw)
    return (qk, qk, jax.ShapeDtypeStruct((B, L, H, Dv), dtype, **kw))


@pytest.mark.parametrize("shape", MLA_SHAPES, ids=lambda s: "x".join(map(str, s[:5])))
def test_unequal_head_widths_lower_for_tpu(shape):
    for name, fn in (("forward", _forward), ("grad", _grad)):
        text = jax.jit(fn).trace(*_mla_args(*shape)).lower(
            lowering_platforms=("tpu",)).as_text()
        assert "tpu_custom_call" in text, name


@pytest.mark.parametrize("shape", MLA_SHAPES, ids=lambda s: "x".join(map(str, s[:5])))
def test_unequal_head_widths_compile_under_mosaic(v5e, shape):
    args = _mla_args(*shape, sharding=jax.sharding.SingleDeviceSharding(v5e))
    for name, fn in (("forward", _forward), ("grad", _grad)):
        assert jax.jit(fn).lower(*args).compile() is not None, name


# grouped kv heads and a window: the benchmark's own shape (smallthinker-21b-a3b-sim:
# 28 / 4 heads of 128, one sequence of 16,384, window 4,096 or none) and a ragged
# length whose window is no multiple of a block
GQA_SHAPES = [(1, 16384, 28, 4, 128, 4096, jnp.bfloat16), (1, 16384, 28, 4, 128, None, jnp.bfloat16),
              (2, 1000, 14, 2, 128, 300, jnp.float32)]


def _gqa_case(B, L, Hq, Hkv, D, window, dtype, sharding=None):
    kw = {} if sharding is None else {"sharding": sharding}
    q = jax.ShapeDtypeStruct((B, L, Hq, D), dtype, **kw)
    kv = jax.ShapeDtypeStruct((B, L, Hkv, D), dtype, **kw)
    forward = lambda q, k, v: flash_attention(q, k, v, causal=True, window=window)
    grad = lambda q, k, v: jax.grad(lambda *a: forward(*a).astype(jnp.float32).sum(),
                                    argnums=(0, 1, 2))(q, k, v)
    return (("forward", forward), ("grad", grad)), (q, kv, kv)


_gqa_id = lambda s: "x".join(map(str, s[:6]))


@pytest.mark.parametrize("shape", GQA_SHAPES, ids=_gqa_id)
def test_window_and_grouped_kv_heads_lower_for_tpu(shape):
    fns, args = _gqa_case(*shape)
    for name, fn in fns:
        text = jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",)).as_text()
        assert text.count("tpu_custom_call") == (1 if name == "forward" else 3), name


@pytest.mark.parametrize("shape", GQA_SHAPES, ids=_gqa_id)
def test_window_and_grouped_kv_heads_compile_under_mosaic(v5e, shape):
    fns, args = _gqa_case(*shape, sharding=jax.sharding.SingleDeviceSharding(v5e))
    for name, fn in fns:
        compiled = jax.jit(fn).lower(*args).compile()
        if name == "grad":  # dK and dV leave at the kv heads' count: k and v were never repeated
            assert [o.shape for o in compiled.out_info] == [a.shape for a in args]


# the four configurations' own attention calls, one sequence (two for dsllm7b-sim):
# (B, L, Hq, Hkv, D, Dv, window)
CELL_SHAPES = [(2, 2048, 32, 32, 128, 128, None), (1, 8192, 32, 32, 192, 128, None),
               (1, 16384, 28, 4, 128, 128, 4096), (1, 16384, 28, 4, 128, 128, None),
               (1, 8192, 20, 20, 256, 256, None)]


@pytest.mark.parametrize("shape", CELL_SHAPES, ids=lambda s: "x".join(map(str, s)))
def test_the_cells_calls_keep_their_names_and_arity(shape):
    """What the benchmark's readers find the kernels by: one custom call of each name a
    layer, 3 operands -> 2 results, 6 -> 1 and 6 -> 2 (no scalar-prefetch operand), and a
    first result of the operands' own shape [B * heads, L, width]."""
    B, L, Hq, Hkv, D, Dv, window = shape
    q, k, v = (jax.ShapeDtypeStruct((B, L, H, W), jnp.bfloat16)
               for H, W in ((Hq, D), (Hkv, D), (Hkv, Dv)))
    grad = jax.grad(lambda *a: flash_attention(*a, causal=True, window=window)
                    .astype(jnp.float32).sum(), argnums=(0, 1, 2))
    text = jax.jit(grad).trace(q, k, v).lower(lowering_platforms=("tpu",)).as_text()
    calls = {}
    for line in text.splitlines():
        if "@tpu_custom_call" in line:
            name = re.search(r'kernel_name = "(\w+)"', line).group(1)
            operands, results = line.rsplit(" : (", 1)[1].split(") -> ")
            assert name not in calls, f"two calls named {name}"
            calls[name] = (operands.count("tensor<"), results.count("tensor<"),
                           re.search(r"tensor<(\w+)>", results).group(1))
    Dp = 256 if D == 192 else D  # q and k of 192 are zero-padded to whole lanes
    assert calls == {"flash_fwd": (3, 2, f"{B * Hq}x{L}x{Dv}xbf16"),
                     "flash_bwd_dq": (6, 1, f"{B * Hq}x{L}x{Dp}xbf16"),
                     "flash_bwd_dkv": (6, 2, f"{B * Hkv}x{L}x{Dp}xbf16")}


# the KDA kernels at the benchmark cell's shape (kimi-linear-48b-a3b-sim, one
# sequence) and at a ragged length under float32 inputs
KDA_SHAPES = [(1, 8192, 32, 128, jnp.bfloat16), (2, 1000, 3, 128, jnp.float32)]


def _kda_entry_points(B, L, H, D, dtype, sharding=None):
    from fedml_tpu.ops import kda

    kw = {} if sharding is None else {"sharding": sharding}
    qkv = jax.ShapeDtypeStruct((B, L, H, D), dtype, **kw)
    args = (qkv, qkv, qkv, jax.ShapeDtypeStruct((B, L, H, D), jnp.float32, **kw),
            jax.ShapeDtypeStruct((B, L, H), jnp.float32, **kw))
    grad = jax.grad(lambda *a: kda.kda_pallas(*a).astype(jnp.float32).sum(),
                    argnums=(0, 1, 2, 3, 4))
    return [("forward", lambda *a: kda.kda_pallas(*a), args), ("grad", grad, args)]


@pytest.mark.parametrize("shape", KDA_SHAPES, ids=lambda s: "x".join(map(str, s[:4])))
def test_kda_kernels_lower_for_tpu(shape):
    for name, fn, args in _kda_entry_points(*shape):
        text = jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",)).as_text()
        assert "tpu_custom_call" in text and "kda_fwd" in text, name
        assert ("kda_bwd" in text) == (name == "grad"), name


@pytest.mark.parametrize("shape", KDA_SHAPES, ids=lambda s: "x".join(map(str, s[:4])))
def test_kda_kernels_compile_under_mosaic(v5e, shape):
    """The off-chip guard of the kernels' VMEM and layouts."""
    for name, fn, args in _kda_entry_points(*shape, jax.sharding.SingleDeviceSharding(v5e)):
        assert "kda_" in jax.jit(fn).lower(*args).compile().as_text(), name


def test_kimi_linear_ops_compile_for_v5e(v5e):
    """The XLA-op paths the ``kimi_linear`` decoder adds, forward and backward
    at the benchmark cell's shapes: KDA chunkwise (scans, the triangular
    solves) and the expert layer's grouped products (``ragged_dot``, which the
    TPU compiler turns into its own Mosaic kernels)."""
    from fedml_tpu.models import expert_lm
    from fedml_tpu.ops import kda

    sharding = jax.sharding.SingleDeviceSharding(v5e)

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    qkv = s((1, 8192, 32, 128), jnp.bfloat16)
    kda_args = (qkv, qkv, qkv, s((1, 8192, 32, 128), jnp.float32), s((1, 8192, 32), jnp.float32))
    kda_grad = jax.grad(lambda *a: kda.kda_chunked(*a).astype(jnp.float32).sum(),
                        argnums=(0, 1, 2, 3, 4))
    assert jax.jit(kda_grad).lower(*kda_args).compile() is not None

    def experts(h, chosen, weights, w_gate, w_up, w_down):
        out, counters = expert_lm.grouped_experts(h, chosen, weights, (0, 8),
                                                  w_gate, w_up, w_down, 256)
        return out.astype(jnp.float32).sum() + counters["moe.assignments_dropped"]

    moe_args = (s((8192, 2304), jnp.bfloat16), s((8192, 8), jnp.int32),
                s((8192, 8), jnp.float32), s((8, 2304, 1024), jnp.bfloat16),
                s((8, 2304, 1024), jnp.bfloat16), s((8, 1024, 2304), jnp.bfloat16))
    compiled = jax.jit(jax.grad(experts, argnums=(0, 3, 4, 5))).lower(*moe_args).compile()
    assert "ragged" in compiled.as_text()


def test_a_large_tiers_expert_layer_moves_its_rows_by_gathers(v5e):
    """``sim.fedavg.smallthinker.1chip``'s expert layer, forward and backward at the
    cell's shape (16 of 64 experts, 6 a token: a block of three quarters of the
    assignments), compiled for the described v5e: the combine and the dispatch's way
    back are gathers and the experts' rows are counted without ``bincount``, so the
    layer holds no scatter.  ``kimi-linear``'s shape, a block of an eighth, keeps its
    row-sized scatter-adds."""
    import re

    from fedml_tpu.models import expert_lm

    sharding = jax.sharding.SingleDeviceSharding(v5e)

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    def scatters(T, k, held, routed, d, f):
        def experts(h, chosen, weights, w_gate, w_up, w_down):
            out, counters = expert_lm.grouped_experts(h, chosen, weights, (0, held), w_gate,
                                                      w_up, w_down, routed, jax.nn.relu)
            return out.astype(jnp.float32).sum() + counters["moe.assignments_dropped"]

        args = (s((T, d), jnp.bfloat16), s((T, k), jnp.int32), s((T, k), jnp.float32),
                s((held, d, f), jnp.bfloat16), s((held, d, f), jnp.bfloat16),
                s((held, f, d), jnp.bfloat16))
        text = jax.jit(jax.value_and_grad(experts, argnums=(0, 2, 3, 4, 5))).lower(
            *args).compile().as_text()
        assert "ragged" in text
        return re.findall(r"= (\w+)\[([\d,]*)\]\S* scatter\(", text)

    assert scatters(16384, 6, 16, 64, 2560, 768) == []
    kept = scatters(8192, 8, 8, 256, 2304, 1024)
    assert any(dtype in ("bf16", "f32") and shape.startswith("8192,") for dtype, shape in kept)


# block diffusion over [x_noised ; x_clean]: the benchmark's own call (sdar-30b-a3b-sim: 32 / 4
# heads of 128, one sequence of 8,192 data tokens, blocks of 4) and a ragged length in
# float32, with q over both halves (2) and over the noised half alone (1: a last layer's
# call) (B, L, Hq, Hkv, D, block_length, dtype, query halves)
BD_SHAPES = [(1, 8192, 32, 4, 128, 4, jnp.bfloat16, 2), (2, 1000, 8, 2, 128, 8, jnp.float32, 2),
             (1, 8192, 32, 4, 128, 4, jnp.bfloat16, 1), (2, 1000, 8, 2, 128, 8, jnp.float32, 1)]


def _bd_id(shape):
    return "x".join(map(str, shape[:6])) + ("_noised" if shape[7] == 1 else "")


def _bd_case(B, L, Hq, Hkv, D, block_len, dtype, halves, sharding=None):
    from fedml_tpu.ops.flash_attention import bd_flash_attention

    kw = {} if sharding is None else {"sharding": sharding}
    q = jax.ShapeDtypeStruct((B, halves * L, Hq, D), dtype, **kw)
    kv = jax.ShapeDtypeStruct((B, 2 * L, Hkv, D), dtype, **kw)
    grad = jax.grad(lambda *a: bd_flash_attention(*a, block_len).astype(jnp.float32).sum(),
                    argnums=(0, 1, 2))
    return grad, (q, kv, kv)


@pytest.mark.parametrize("shape", BD_SHAPES, ids=_bd_id)
def test_block_diffusion_calls_are_named_apart(shape):
    """The mode's three calls, one of each name a layer: 5 operands -> 2 results (q, the
    clean and the noised k and v), 8 -> 1 and 8 -> 4 (the noised keys' dK and dV beside the
    clean ones'), q at 2 x the query heads (1 x for the noised queries alone): the accepted
    readers, which find the causal calls by name and by (3 in, 2 out), cannot take them for
    those."""
    B, L, Hq, Hkv, D, _, dtype, halves = shape
    grad, args = _bd_case(*shape)
    text = jax.jit(grad).trace(*args).lower(lowering_platforms=("tpu",)).as_text()
    calls = {}
    for line in text.splitlines():
        if "@tpu_custom_call" in line:
            name = re.search(r'kernel_name = "(\w+)"', line).group(1)
            operands, results = line.rsplit(" : (", 1)[1].split(") -> ")
            calls[name] = (operands.count("tensor<"), results.count("tensor<"),
                           re.search(r"tensor<(\w+)x\w+>", results).group(1))
    Lp = -(-L // 128) * 128
    assert calls == {"bd_flash_fwd": (5, 2, f"{halves * B * Hq}x{Lp}x{D}"),
                     "bd_flash_bwd_dq": (8, 1, f"{halves * B * Hq}x{Lp}x{D}"),
                     "bd_flash_bwd_dkv": (8, 4, f"{B * Hkv}x{Lp}x{D}")}


@pytest.mark.parametrize("shape", BD_SHAPES, ids=_bd_id)
def test_block_diffusion_kernels_compile_under_mosaic(v5e, shape):
    grad, args = _bd_case(*shape, sharding=jax.sharding.SingleDeviceSharding(v5e))
    compiled = jax.jit(grad).lower(*args).compile()
    assert [o.shape for o in compiled.out_info] == [a.shape for a in args]


# the SSD kernels at the benchmark cell's shape (nemotron-3-nano-30b-a3b-sim, one
# sequence: 64 heads of 64 over 8 groups of 128, four runs of 16 chunks) and at a
# ragged length under float32 inputs (two groups of two heads, one run of 8 chunks)
# (B, L, heads, head_dim, groups, state, dtype)
SSD_SHAPES = [(1, 8192, 64, 64, 8, 128, jnp.bfloat16), (2, 1000, 4, 64, 2, 128, jnp.float32)]


def _ssd_case(B, L, H, P, G, N, dtype, sharding=None):
    from fedml_tpu.ops import ssd

    kw = {} if sharding is None else {"sharding": sharding}
    args = (jax.ShapeDtypeStruct((B, L, H, P), dtype, **kw),
            jax.ShapeDtypeStruct((B, L, H), jnp.float32, **kw),
            jax.ShapeDtypeStruct((H,), jnp.float32, **kw),
            jax.ShapeDtypeStruct((B, L, G, N), dtype, **kw),
            jax.ShapeDtypeStruct((B, L, G, N), dtype, **kw))
    grad = jax.grad(lambda *a: ssd.ssd_pallas(*a).astype(jnp.float32).sum(),
                    argnums=(0, 1, 2, 3, 4))
    return grad, args


@pytest.mark.parametrize("shape", SSD_SHAPES, ids=lambda s: "x".join(map(str, s[:6])))
def test_ssd_kernels_lower_for_tpu_and_keep_their_names_and_arity(shape):
    """What ``ssd_fwd_roofline`` / ``ssd_bwd_roofline`` find the kernels by: one call of
    each name, x, dt, the running sums, B and C in -> y and the runs' states out (5 -> 2),
    those five, the states and dy in -> the five gradients out (7 -> 5), y and dx at the
    rows' shape [B, Lp, heads x head_dim]."""
    B, L, H, P, G, N, dtype = shape
    grad, args = _ssd_case(*shape)
    text = jax.jit(grad).trace(*args).lower(lowering_platforms=("tpu",)).as_text()
    calls = {}
    for line in text.splitlines():
        if "@tpu_custom_call" in line:
            name = re.search(r'kernel_name = "(\w+)"', line).group(1)
            operands, results = line.rsplit(" : (", 1)[1].split(") -> ")
            assert name not in calls, f"two calls named {name}"
            calls[name] = (operands.count("tensor<"), results.count("tensor<"),
                           re.search(r"tensor<(\w+)x\w+>", results).group(1))
    chunks = -(-L // 128)
    run = chunks if chunks <= 16 else 8 if (-(-chunks // 8) * 8) < (-(-chunks // 16) * 16) else 16
    Lp = -(-chunks // run) * run * 128
    rows = f"{B}x{Lp}x{H * P}"
    assert calls == {"ssd_fwd": (5, 2, rows), "ssd_bwd": (7, 5, rows)}


@pytest.mark.parametrize("shape", SSD_SHAPES, ids=lambda s: "x".join(map(str, s[:6])))
def test_ssd_kernels_compile_under_mosaic(v5e, shape):
    """The off-chip guard of the kernels' VMEM and layouts."""
    grad, args = _ssd_case(*shape, sharding=jax.sharding.SingleDeviceSharding(v5e))
    compiled = jax.jit(grad).lower(*args).compile()
    assert [o.shape for o in compiled.out_info] == [a.shape for a in args]
    text = compiled.as_text()
    assert "ssd_fwd" in text and "ssd_bwd" in text


def test_a_non_gated_expert_layer_compiles_for_v5e(v5e):
    """``sim.fedavg.nemotron-nano.1chip``'s expert layer, forward and backward at the cell's
    shape (8 of 128 experts, 6 a token, squared ReLU): two grouped products a block, not
    three."""
    from fedml_tpu.models import expert_lm

    sharding = jax.sharding.SingleDeviceSharding(v5e)

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    def experts(h, chosen, weights, w_up, w_down):
        out, counters = expert_lm.grouped_experts(h, chosen, weights, (0, 8), None, w_up,
                                                  w_down, 128, expert_lm.relu2)
        return out.astype(jnp.float32).sum() + counters["moe.assignments_dropped"]

    args = (s((8192, 2688), jnp.bfloat16), s((8192, 6), jnp.int32), s((8192, 6), jnp.float32),
            s((8, 2688, 1856), jnp.bfloat16), s((8, 1856, 2688), jnp.bfloat16))
    lowered = jax.jit(jax.grad(experts, argnums=(0, 3, 4))).lower(*args)
    assert lowered.as_text().count("ragged_dot") > 0
    assert "ragged" in lowered.compile().as_text()
