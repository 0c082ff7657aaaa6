"""The readers of the in-mesh round's spans and structure
(``benchmark/round_phases.py`` and the ``idle.*`` / ``round.*_device_ms``
metrics) on a small trace recorded on the chip and kept beside the first:
``data/tiny_round_1dev.xplane.pb`` — three rounds of a tiny packed round
through ``XLASimulator`` on one TPU v5 lite chip (one-layer TransformerLM,
d_model 256, 2 heads of 128, L 256, bf16, flash kernels; 2 clients with
shards of 2 and 4 sequences, batch 2: 3 local steps and 2 flushes a round),
python tracer off (my chip run, PR 25) — and on hand-made summaries."""

from __future__ import annotations

import importlib.util
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import reduce_trace, round_phases  # noqa: E402

ROUNDS = 3
E = reduce_trace.Event


def _reader(name):
    path = os.path.join(ROOT, "benchmark", "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("reader_" + name.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def _ctx(trace, rounds=ROUNDS, packed=True, **sim):
    driver = types.SimpleNamespace(sim=types.SimpleNamespace(packed=packed, **sim))
    return types.SimpleNamespace(trace=trace, units=[{}] * rounds, driver=driver,
                                 window_s=trace.window_s if trace else 0.0)


@pytest.fixture(scope="module")
def recorded():
    return reduce_trace.reduce(os.path.join(HERE, "data", "tiny_round_1dev.xplane.pb"))


@pytest.fixture(scope="module")
def spanless():
    """PR 24's trace: a ``round`` annotation and none of the phase spans."""
    return reduce_trace.reduce(os.path.join(HERE, "data", "tiny_trace_1dev.xplane.pb"))


def test_recorded_trace_has_the_program_s_names(recorded):
    assert recorded.n_devices == 1
    modules = [m.name for m in recorded.modules[0]]
    assert sum(name.startswith("jit_fedml_round_packed(") for name in modules) == ROUNDS
    spans = [e.name for e in recorded.host]
    for name in ("sim.train", "round", "round.select", "round.pack", "round.dispatch",
                 "round.wait", "round.close"):
        assert spans.count(name) == ROUNDS, name


def test_idle_parts_add_up_to_the_window_s_idle(recorded):
    split = round_phases.idle_split(recorded)
    lo, hi = round_phases.window_ns(recorded)
    busy = reduce_trace._union([(e.start, e.end) for e in recorded.ops[0]])
    idle_s = ((hi - lo) - sum(b - a for a, b in busy)) / 1e9
    assert sum(split[p] for p in round_phases.PARTS) == pytest.approx(idle_s, rel=1e-9)
    assert split["window_s"] == pytest.approx((hi - lo) / 1e9)
    # a tiny round is all host: the device sits idle under every phase
    assert all(split[p] > 0.0 for p in ("prep", "dispatch", "between_rounds"))
    parts = [_reader(f"idle.{p}_ms_per_round")(_ctx(recorded)) for p in round_phases.PARTS]
    assert sum(parts) == pytest.approx(1000.0 * idle_s / ROUNDS, rel=1e-9)


def test_idle_under_wait_is_small_and_named_unattributed(recorded):
    """The device works through ``round.wait``: what idles there is the
    program's own stall, and it is all that is left unattributed."""
    split = round_phases.idle_split(recorded)
    waits = round_phases._named(recorded.host, round_phases.WAIT)
    busy = reduce_trace._union([(e.start, e.end) for e in recorded.ops[0]])
    under_wait = sum(b - a for a, b in waits) - sum(
        round_phases._overlap(a, b, waits) for a, b in busy)
    # ... but for the microseconds between one phase span's end and the next one's
    # start, where the spans themselves are written (under 0.15 ms a round here)
    assert 0.0 <= split["unattributed"] - under_wait / 1e9 < 0.15e-3 * ROUNDS
    assert under_wait / 1e9 > 0.5 * split["unattributed"]


def test_flush_and_server_step_are_found_by_structure(recorded):
    split = round_phases.round_split(recorded)
    assert split["rounds"] == ROUNDS
    assert split["flush_s"] > 0.0 and split["server_step_s"] > 0.0
    conds = [e for e in recorded.ops[0] if reduce_trace.opcode(e.name) == "conditional"]
    assert len(conds) >= ROUNDS * 3  # one a local step; two of the three flush
    flush = _reader("round.flush_device_ms")(_ctx(recorded))
    server = _reader("round.server_step_device_ms")(_ctx(recorded))
    assert flush == pytest.approx(1000.0 * split["flush_s"] / ROUNDS)
    assert server == pytest.approx(1000.0 * split["server_step_s"] / ROUNDS)
    # both lie inside the round modules' device time
    module_s = sum(m.end - m.start for m in recorded.modules[0]
                   if m.name.startswith("jit_fedml_round_packed")) / 1e9
    assert split["flush_s"] + split["server_step_s"] < module_s


def test_a_trace_without_the_spans_is_all_unattributed(spanless):
    split = round_phases.idle_split(spanless)
    assert split["prep"] == split["dispatch"] == split["between_rounds"] == 0.0
    # (its window reaches to the ends of the ``round`` annotations, past the device's events)
    assert split["window_s"] >= spanless.window_s
    assert split["unattributed"] == pytest.approx(split["window_s"] - spanless.busy_s, rel=1e-6)
    for part in round_phases.PARTS[:3]:
        assert _reader(f"idle.{part}_ms_per_round")(_ctx(spanless)) == 0.0
    # its steps are no packed round: no while with a branch inside, so the
    # device readers are silent for a simulator that is not packed ...
    assert round_phases.round_split(spanless) is None
    assert _reader("round.flush_device_ms")(_ctx(spanless, packed=False)) is None
    # ... and fail the run for one that is
    with pytest.raises(RuntimeError, match="no flush"):
        _reader("round.flush_device_ms")(_ctx(spanless, packed=True))
    with pytest.raises(RuntimeError, match="no server step"):
        _reader("round.server_step_device_ms")(_ctx(spanless, packed=True))


def test_readers_are_silent_without_a_trace():
    ctx = _ctx(None)
    for name in ("idle.prep_ms_per_round", "idle.dispatch_ms_per_round",
                 "idle.between_rounds_ms_per_round", "idle.unattributed_ms_per_round",
                 "round.flush_device_ms", "round.server_step_device_ms"):
        assert _reader(name)(ctx) is None


def _summary(ops, modules, host):
    ops = {d: list(evs) for d, evs in ops.items()}
    for evs in ops.values():
        reduce_trace._fill_self_time(evs)
    return reduce_trace.TraceSummary(
        window_s=0.0, busy_s=0.0, busy_s_by_device={}, op_self_s={}, ops=ops,
        modules=modules, host=host, n_devices=len(ops))


def test_a_gap_is_shared_by_overlap_and_devices_are_averaged():
    host = [E("sim.train", 0, 1000), E("round.select", 0, 100), E("round.pack", 100, 300),
            E("round.dispatch", 300, 400), E("round.wait", 400, 900), E("round.close", 900, 1000)]
    # device 0 idles from 0 to 350 (300 of it prep, 50 dispatch) and from 800 on
    # (100 under wait, 100 after it); device 1 is busy throughout
    trace = _summary({0: [E("%a = f32[] add()", 350, 800)], 1: [E("%a = f32[] add()", 0, 1000)]},
                     {}, host)
    split = round_phases.idle_split(trace)
    assert split == pytest.approx({"prep": 150e-9, "dispatch": 25e-9, "between_rounds": 50e-9,
                                   "unattributed": 50e-9, "window_s": 1000e-9})


def test_structure_flush_is_the_outermost_conditional_server_step_skips_collectives():
    w = "%while.1 = (f32[]) while((f32[]) %t), condition=%c, body=%b"
    cond = "%conditional.2 = (f32[]) conditional(pred[] %p, (f32[]) %x, (f32[]) %x)"
    ops = [E("%copy.0 = f32[] copy(f32[] %p0)", 0, 10),
           E(w, 10, 500),
           E("%fusion.1 = f32[] fusion(f32[] %x), kind=kLoop", 20, 200),
           E(cond, 200, 300), E(cond.replace("conditional.2", "conditional.3"), 210, 250),
           E("%fusion.4 = f32[] fusion(f32[] %x), kind=kLoop", 250, 290),
           E(cond.replace("conditional.2", "conditional.5"), 400, 420),
           E("%all-reduce.6 = f32[] all-reduce(f32[] %x), replica_groups={}", 500, 600),
           E("%fusion.7 = f32[] fusion(f32[] %all-reduce.6), kind=kLoop", 600, 650),
           # another module: a while without a branch is no round
           E(w, 1000, 1100), E("%fusion.8 = f32[] fusion(f32[] %x), kind=kLoop", 1100, 1150)]
    modules = {0: [E("jit_fedml_round_packed(1)", 0, 700), E("jit_other(2)", 1000, 1200)]}
    split = round_phases.round_split(_summary({0: ops}, modules, []))
    assert split == pytest.approx({"rounds": 1, "flush_s": 120e-9, "server_step_s": 50e-9})


@pytest.mark.parametrize("name,sim,want", [
    ("round.host_ms", {"round_log": [
        {"select_s": 9.0, "pack_s": 9.0, "dispatch_s": 9.0, "close_s": 9.0, "wait_s": 9.0},
        {"select_s": 1e-3, "pack_s": 2e-3, "dispatch_s": 3e-3, "close_s": 4e-3, "wait_s": 5.0},
        {"select_s": 1e-3, "pack_s": 2e-3, "dispatch_s": 3e-3, "close_s": 6e-3, "wait_s": 5.0},
        {"select_s": 1e-3, "pack_s": 2e-3, "dispatch_s": 3e-3, "close_s": 8e-3, "wait_s": 5.0}]},
     12.0),  # the median of the window's three rounds; set-up's round is left out
    ("round.host_ms", {}, None),
    ("startup.sim_build_s", {"startup_log": {"build_s": 13.5, "init_variables_s": 9.0}}, 13.5),
    ("startup.sim_build_s", {}, None),
])
def test_record_readers(name, sim, want):
    got = _reader(name)(_ctx(None, **sim))
    assert got == (pytest.approx(want) if want is not None else None)


def test_counter_readers_read_the_program_s_obs_and_are_silent_without_it(monkeypatch):
    trace_s, compiled = _reader("startup.trace_s"), _reader("startup.programs_compiled")
    fake = types.SimpleNamespace(enabled=lambda: True, trace_seconds_total=lambda: 2.5,
                                 compiles_total=lambda: 41)
    monkeypatch.setitem(sys.modules, "fedml_tpu.core.obs", fake)
    assert trace_s(_ctx(None)) == 2.5 and compiled(_ctx(None)) == 41.0
    # the parent of PR 25: an obs layer with neither counter
    monkeypatch.setitem(sys.modules, "fedml_tpu.core.obs",
                        types.SimpleNamespace(enabled=lambda: True))
    assert trace_s(_ctx(None)) is None and compiled(_ctx(None)) is None
    fake.enabled = lambda: False  # obs not configured: nothing was counted
    monkeypatch.setitem(sys.modules, "fedml_tpu.core.obs", fake)
    assert trace_s(_ctx(None)) is None and compiled(_ctx(None)) is None
