"""The reducer on a small recorded trace kept beside it: three steps of a
two-layer TransformerLM (d_model 256, 2 heads of 128, L 256, batch 2, bf16,
flash kernels) on one TPU v5 lite chip, each under a ``round``
TraceAnnotation with a 10 ms sleep after it (my chip run, PR 24)."""

from __future__ import annotations

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import reduce_trace  # noqa: E402


@pytest.fixture(scope="module")
def summary():
    return reduce_trace.reduce(os.path.join(HERE, "data", "tiny_trace_1dev.xplane.pb"))


def test_busy_is_the_union_of_op_intervals(summary):
    assert summary.n_devices == 1 and len(summary.modules[0]) == 3
    # three runs of about 117 us each, about 13 ms apart
    assert summary.busy_s == pytest.approx(3 * 117e-6, rel=0.05)
    assert summary.window_s == pytest.approx(0.0258, rel=0.02)
    # self times add up to what the device was busy: nothing counted twice
    assert sum(summary.op_self_s[0].values()) <= summary.busy_s * 1.001
    assert sum(summary.op_self_s[0].values()) == pytest.approx(summary.busy_s, rel=0.1)


def test_kernels_are_told_by_signature_and_shape(summary):
    shape = (2 * 2, 256, 128)  # batch 2 x 2 heads, length 256, head size 128
    fwd = summary.kernel_seconds(((3, 2),), shape)
    bwd = summary.kernel_seconds(((6, 1), (6, 2)), shape)
    assert fwd == summary.kernel_seconds(((3, 2),))
    # the same arity at another batch, length or head size is another kernel
    for other in ((8, 256, 128), (4, 512, 128), (4, 256, 64)):
        assert summary.kernel_seconds(((3, 2),), other) == 0.0
    assert summary.pallas_calls() == {
        "3 operands 2 results [(4, 256, 128), (4, 1, 256)]": 6,
        "6 operands 1 results [(4, 256, 128)]": 6,
        "6 operands 2 results [(4, 256, 128), (4, 256, 128)]": 6}
    # two layers x three steps: 8 us a forward call, 4-5 us a backward one
    assert fwd == pytest.approx(6 * 8e-6, rel=0.1)
    assert bwd == pytest.approx(6 * (4.3e-6 + 4.75e-6), rel=0.1)
    assert summary.kernel_seconds(((9, 9),)) == 0.0
    hlo = ('%layer0.3 = (bf16[4,256,128]{2,1,0}, f32[4,1,256]{2,1,0}) custom-call(bf16[4,256,128]{2,1,0} '
           '%a, bf16[4,256,128]{2,1,0} %b, bf16[4,256,128]{2,1,0} %c), custom_call_target="tpu_custom_call"')
    assert reduce_trace.custom_call_signature(hlo) == (3, 2)
    assert reduce_trace.result_shapes(hlo) == [(4, 256, 128), (4, 1, 256)]
    assert reduce_trace.shape_matches((2, 2, 256, 128), (4, 256, 128))
    assert not reduce_trace.shape_matches((256, 128), (4, 256, 128))
    assert reduce_trace.custom_call_signature("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %x)") is None


def test_collectives_and_labels():
    ar = "%all-reduce-start.1 = f32[8]{0:T(8)} all-reduce-start(f32[8]{0} %x), replica_groups={}"
    assert reduce_trace.opcode(ar) == "all-reduce-start" and reduce_trace._is_collective(ar)
    fusion = "%fusion.2 = (f32[8]{0:T(8)S(1)}, f32[8]{0}) fusion(f32[8]{0} %all-reduce.3), kind=kLoop"
    assert reduce_trace.opcode(fusion) == "fusion" and not reduce_trace._is_collective(fusion)


def test_idle_gaps_are_named_by_the_host(summary):
    gaps = summary.idle_gaps()
    assert gaps[0][0] == "$time sleep" and gaps[0][1] == pytest.approx(0.0255, rel=0.05)
    labels = [name for name, _ in summary.top_ops(4)]
    assert labels[0].endswith("pallas custom-call 3 operands 2 results")


def test_self_time_of_nested_events():
    e = reduce_trace.Event
    events = [e("while", 0, 100), e("a", 10, 40), e("b", 50, 90), e("after", 100, 120)]
    reduce_trace._fill_self_time(events)
    assert {x.name: x.self_ns for x in events} == {"while": 30, "a": 30, "b": 40, "after": 20}


def test_a_default_kernel_that_is_not_in_the_trace_fails_the_reader(summary):
    import importlib.util
    import types

    from benchmark import flops, peaks

    spec = importlib.util.spec_from_file_location(
        "flash_fwd_roofline", os.path.join(ROOT, "benchmark", "layer_metrics", "flash_fwd_roofline.py"))
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)

    def ctx(length, attention):
        return types.SimpleNamespace(
            trace=summary, chips=1, sequences=6, flops=flops, peaks=peaks.peaks_for("TPU v5 lite"),
            model={"num_attention_heads": 2, "hidden_size": 256, "num_hidden_layers": 2,
                   "compute_dtype": "bfloat16"},
            traffic={"sequence_length": length, "batch_sequences": 2},
            driver=types.SimpleNamespace(default_attention=lambda: attention))

    assert 0.0 < reader.read(ctx(256, "flash")) < 100.0
    assert reader.read(ctx(512, "other")) is None
    with pytest.raises(RuntimeError, match="no Pallas call of arity"):
        reader.read(ctx(512, "flash"))
