"""Every device millisecond of a round under a scope the program names (ISSUE 35):
the scopes are names only (the lowered round without debug info is the parent's, byte
for byte), each model's round holds the vocabulary, ``XLASimulator.round_scopes()``
hands out the instruction-to-scope table of its own compiled round, and
``core/obs/scopes.py`` joins it to a trace's op line by SELF time, first match wins.
CPU, tiny presets."""

import hashlib
import json
import os
import re
import subprocess
import sys
import types

import jax
import pytest

import fedml_tpu
from benchmark import program_scopes, reduce_trace, run, scope_times, traffic as traffic_mod
from benchmark import reference_glm47_flash, reference_kimi_linear, reference_smallthinker
from benchmark.drivers import flax_lm, sim, sim_glm47_flash, sim_kimi_linear, sim_smallthinker
from fedml_tpu.core import obs
from fedml_tpu.core.obs import scopes

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = os.path.join(ROOT, "benchmark", "configs")

# preset -> (traffic, driver module, the reference's weights, their map to the program's)
PRESETS = {
    "tiny": ("tiny.fedavg", sim, traffic_mod.make_weights, flax_lm.to_program),
    "tiny-kimi-linear": ("tiny.fedavg.kimi-linear", sim_kimi_linear,
                         reference_kimi_linear.make_weights, sim_kimi_linear.to_program),
    "tiny-smallthinker": ("tiny.fedavg.smallthinker", sim_smallthinker,
                          reference_smallthinker.make_weights, sim_kimi_linear.to_program),
    "tiny-glm47-flash": ("tiny.fedavg.glm47-flash", sim_glm47_flash,
                         reference_glm47_flash.make_weights, sim_glm47_flash.to_program),
}
# sha256 of the preset's packed round lowered on one CPU device WITHOUT debug info, jax's
# private function numbers cut out, on the PARENT's tree (commit 78203fc, PR 34; jax 0.9.0),
# taken before this PR touched a model: a scope changes metadata and nothing else
ROUND_BEFORE = {
    "tiny": "b9af27f12467dad15e4f757ca38fe0dba8187c478ae826ac9d78619b59e7c360",
    "tiny-kimi-linear": "2429494198875ff3796e146082cad2a5bba6006c8a47bbcdbe60a64a3a2a21e8",
    "tiny-smallthinker": "628c4830314b1d324ff7b34786e9c85fdc24ab981210920fb6a8f285f01b0917",
    "tiny-glm47-flash": "55d406455e7d14b4e1ae1a6119cef55dfef275cdc27efb1e86ce3550a0db44fb",
}
EVERY_MODEL = ("lm.embed", "lm.head", "fed.loss", "fed.sgd", "fed.gather", "fed.local_step",
               "fed.flush", "fed.exchange", "fed.server_step")
# what each model's round holds besides, and what it must not
HOLDS = {
    "tiny": (("lm.attn", "lm.mlp"), ("lm.norm", "lm.moe.", "lm.mtp")),
    "tiny-kimi-linear": (("lm.kda", "lm.mla", "lm.mlp", "lm.norm", "lm.moe.route"),
                         ("lm.attn", "lm.mtp")),
    "tiny-smallthinker": (("lm.attn.window", "lm.attn.global", "lm.norm", "lm.moe.route"),
                          ("lm.mlp", "lm.mla", "lm.mtp")),
    "tiny-glm47-flash": (("lm.mla", "lm.mlp", "lm.norm", "lm.moe.route", "lm.mtp.merge",
                          "lm.mtp.head", "lm.mtp/mtp/block/lm.norm"), ("lm.attn", "lm.kda")),
}
_lowered = {}


def _model(preset):
    with open(os.path.join(CONFIGS, preset + ".json")) as f:
        return json.load(f)


def lowered_round(preset):
    """(text without debug info, text with) of the preset's packed round on one CPU device,
    lowered once a process (``benchmark/tests/test_compile_v5e_glm47_flash.py``'s recipe)."""
    if preset not in _lowered:
        from benchmark.tests.test_compile_v5e_glm47_flash import lowered_round as lower

        traffic, driver_mod, make_weights, to_program = PRESETS[preset]
        model, create = _model(preset), fedml_tpu.models.create
        if preset == "tiny":  # ``sim``'s driver builds TransformerLM at the file's sizes itself
            fedml_tpu.models.create = lambda args, vocab: flax_lm.build_module(model)
        try:
            lowered = lower(jax.devices(), model, run.load_traffic(traffic), driver_mod,
                            make_weights, to_program, "cpu")
        finally:
            fedml_tpu.models.create = create
        _lowered[preset] = (lowered.as_text(), lowered.as_text(debug_info=True))
    return _lowered[preset]


# -- (a) names only ------------------------------------------------------------------

@pytest.mark.parametrize("preset", sorted(PRESETS))
def test_the_scopes_leave_the_lowered_round_as_the_parent_had_it(preset):
    text = re.sub(r"@(\w+?)_\d+\b", r"@\1", lowered_round(preset)[0])
    assert hashlib.sha256(text.encode()).hexdigest() == ROUND_BEFORE[preset]


# -- (b) the vocabulary, model by model ------------------------------------------------

@pytest.mark.parametrize("preset", sorted(PRESETS))
def test_each_models_round_holds_its_scopes(preset):
    named = lowered_round(preset)[1]
    held, absent = HOLDS[preset]
    for scope in EVERY_MODEL + held:
        assert scope in named, scope
    for scope in absent:
        assert scope not in named, scope
    # the prediction module's head is its own: no op is under both
    locations = re.findall(r'loc\("([^"]*)"', named)
    assert not any("lm.mtp" in where and "lm.head" in where for where in locations)
    if preset == "tiny-glm47-flash":
        assert any("lm.mtp.head" in where for where in locations)
    # every scope of the program is one the table's order knows
    used = {m for where in locations for m in re.findall(r"\b(?:lm|fed)\.[a-z_.]+", where)}
    unknown = {u for u in used if u != "fed.local_step" and not any(s in u for s in scopes.SCOPES)}
    assert not unknown, unknown


# -- (c) the round program hands out its own table ---------------------------------------

@pytest.fixture(scope="module", params=["tiny", "tiny-smallthinker"])
def driven(request):
    """A driver of the benchmark after its set-up, with what the simulator said before a
    round ran; ``sim_smallthinker``'s swaps ``sim._round_fn`` for a wrapper over the first call."""
    traffic, driver_mod, _, _ = PRESETS[request.param]
    driver = driver_mod.Driver(_model(request.param), run.load_traffic(traffic), 2147483700,
                               len(jax.devices()), "cpu")
    try:
        driver.setup()
        before = driver.sim.round_scopes()
        driver.run_unit()
        yield driver, before
    finally:
        driver.release()


def test_round_scopes_is_none_before_a_round_and_a_table_after(driven):
    driver, before = driven
    assert before is None
    table = driver.sim.round_scopes()
    assert isinstance(table, dict) and table
    for scope in ("fed.flush", "fed.server_step", "fed.sgd", "fed.loss", "lm.head", "lm.embed"):
        assert any(scope in op_name for op_name in table.values()), scope
    assert all("%" not in name for name in table)


def test_round_scopes_is_built_once_and_a_later_round_takes_no_signature(driven, monkeypatch):
    driver, _ = driven
    table, signature = driver.sim.round_scopes(), driver.sim._round_signature
    parsed = []
    monkeypatch.setattr(obs, "program_scopes", lambda text: parsed.append(1) or {})
    assert driver.sim.round_scopes() is table and not parsed
    driver.run_unit()  # a second ``run()``: the bucket is known
    assert driver.sim._round_signature is signature
    assert driver.sim.round_scopes() is table and not parsed


def test_the_drivers_wrapper_and_the_programs_table_work_side_by_side(driven):
    driver, _ = driven
    if not hasattr(driver, "round_shapes"):
        # ``benchmark/drivers/sim.py`` keeps no shapes: the benchmark's own join is silent there
        assert scope_times.round_op_names(driver) is None
    else:
        # the wrapper saw the first call, put the jit back, and the simulator took its own
        # signature of the same call (with no sharding where an input was not committed,
        # so that it lowers on a mesh of several devices as well)
        assert driver.round_shapes is not None and driver.sim._round_fn is driver.round_fn
        theirs = jax.tree_util.tree_leaves(driver.round_shapes)
        ours = jax.tree_util.tree_leaves(driver.sim._round_signature)
        assert [(s.shape, s.dtype) for s in theirs] == [(s.shape, s.dtype) for s in ours]
    assert driver.sim.round_scopes()


def test_a_failed_lowering_is_logged_and_not_raised(driven, monkeypatch, caplog):
    driver, _ = driven
    sim_ = driver.sim
    monkeypatch.setattr(sim_, "_round_scopes", None)
    monkeypatch.setattr(sim_, "_round_signature", ("not", "the", "round's", "arguments"))
    with caplog.at_level("WARNING"):
        assert sim_.round_scopes() is None
        assert sim_.round_scopes() is None
    assert sum("round_scopes" in r.getMessage() for r in caplog.records) == 1


def test_an_executable_from_a_cache_of_other_scopes_is_compiled_anew(driven, monkeypatch, caplog):
    """jax's persistent compilation cache leaves metadata out of its key: a round compiled
    before a scope was named comes back from it under the old names.  Every packed step
    opens ``fed.sgd``; a table without it is made again from a compile past the caches."""
    driver, _ = driven
    monkeypatch.setattr(driver.sim, "_round_scopes", None)
    real, calls = obs.program_scopes, []

    def stale_first(text):
        calls.append(1)
        table = real(text)
        return {k: v.replace("fed.sgd", "fed") for k, v in table.items()} if len(calls) == 1 else table

    monkeypatch.setattr(obs, "program_scopes", stale_first)
    with caplog.at_level("WARNING"):
        table = driver.sim.round_scopes()
    assert len(calls) == 2 and any("fed.sgd" in v for v in table.values())
    assert any("compiling it anew" in r.getMessage() for r in caplog.records)
    assert not jax.config.jax_compilation_cache_include_metadata_in_key


# -- (d) one parser, in the program ------------------------------------------------------

def test_program_scopes_is_what_the_benchmarks_parser_gives(driven):
    driver, _ = driven
    sim_ = driver.sim
    text = sim_._round_fn.lower(*sim_._round_signature).compile().as_text()
    text += ('\n  %ragged-dot.7 = bf16[8,4]{1,0} custom-call(%a, %b), metadata={op_name="ragged-dot.7"}'
             '\n  ROOT %fusion.9 = f32[] fusion(%c), kind=kLoop, metadata={op_type="add" '
             'op_name="jit(f)/fed.local_step/jvp(lm.head)/add" source_file="x.py"}'
             "\n  %copy.3 = f32[2]{0} copy(%d)\n")
    table = obs.program_scopes(text)
    assert table == scope_times.op_names(text)
    assert table["ragged-dot.7"] == "lm.moe.experts/ragged-dot.7"
    assert table["fusion.9"].endswith("jvp(lm.head)/add") and "copy.3" not in table
    assert len(table) > 100


# -- (e) the join: self time, first match wins ---------------------------------------------

TABLE = {"while.1": "jit(r)/while", "fusion.1": "jit(r)/while/body/fed.local_step/jvp(lm.attn)/dot",
         "fusion.2": "jit(r)/while/body/fed.local_step/jvp(lm.mtp)/mtp/block/lm.mla/dot",
         "fusion.3": "jit(r)/while/body/fed.local_step/add",
         "fusion.4": "jit(r)/while/body/fed.local_step/fed.sgd/sub",
         "fusion.5": "jit(r)/while/body/fed.flush/add", "fusion.6": "jit(r)/fed.server_step/div"}
# (name, start ns, duration ns): a while over five ops with 60 ns of its own, then two ops
EVENTS = [("%while.1 = (s32[]) while(%t)", 0, 1000), ("%fusion.1 = f32[] fusion(%a)", 10, 300),
          ("%fusion.2 = f32[] fusion(%a)", 320, 200), ("%fusion.3 = f32[] fusion(%a)", 530, 40),
          ("%copy.8 = f32[] copy(%a)", 580, 100), ("%fusion.4 = f32[] fusion(%a)", 690, 300),
          ("%fusion.5 = f32[] fusion(%a)", 1000, 50), ("%fusion.6 = f32[] fusion(%a)", 1100, 25)]


def test_the_disjoint_table_sums_to_the_events_self_time():
    seconds = scopes.op_name_seconds(EVENTS, TABLE)
    rows = dict(scopes.round_table(seconds))
    assert list(rows)[:len(scopes.SCOPES)] == list(scopes.SCOPES)
    assert sum(rows.values()) == pytest.approx(1075e-9)  # the union of the intervals
    assert rows["lm.attn"] == pytest.approx(300e-9)
    # two scopes of the vocabulary in one op_name: counted once, under the first in order
    assert rows["lm.mtp"] == pytest.approx(200e-9) and rows["lm.mla"] == 0.0
    assert rows["fed.sgd"] == pytest.approx(300e-9)
    assert rows[scopes.STEP_ALONE] == pytest.approx(40e-9)
    assert rows[scopes.NO_METADATA] == pytest.approx(100e-9)
    assert rows[scopes.OUTSIDE] == pytest.approx(60e-9)  # the while's own
    assert scopes.unscoped_seconds(seconds) == pytest.approx(140e-9)
    assert scopes.largest(seconds, scopes.STEP_ALONE) == [(TABLE["fusion.3"], pytest.approx(40e-9))]
    # asked for one scope alone, an op_name that holds it counts whatever else it holds
    assert obs.scope_seconds(EVENTS, TABLE, ("lm.mla",))["lm.mla"] == pytest.approx(200e-9)


def test_self_time_is_the_trace_reducers():
    events = [reduce_trace.Event(n, s, s + d) for n, s, d in EVENTS]
    reduce_trace._fill_self_time(events)
    want = sorted((e.name, e.self_ns / 1e9) for e in events)
    assert sorted(scopes.self_seconds(EVENTS)) == [(n, pytest.approx(s)) for n, s in want]


def test_the_benchmarks_readers_over_a_trace_of_the_programs_own_instructions(driven, capsys):
    """No device plane in a CPU trace: the op line is made up from the table's own names."""
    driver, _ = driven
    table = driver.sim.round_scopes()
    events, t = [], 0
    for name, op_name in table.items():
        events.append(reduce_trace.Event(f"%{name} = f32[] fusion(%x)", t, t + 1000, 1000))
        t += 1000
    events.append(reduce_trace.Event("%copy.99999 = f32[] copy(%x)", t, t + 500, 500))
    trace = types.SimpleNamespace(ops={0: events}, busy_s=(t + 500) / 1e9)
    ctx = types.SimpleNamespace(driver=driver, trace=trace, units=[{}, {}])
    count = lambda scope: sum(scope in v for v in table.values())  # noqa: E731
    for scope in ("fed.flush", "fed.sgd", "lm.head"):
        assert program_scopes.device_ms_per_round(ctx, scope) == pytest.approx(count(scope) * 1e-3 / 2)
    assert program_scopes.device_ms_per_round(ctx, "lm.kda") is None
    alone = sum("fed.local_step" in v and not any(s in v for s in scopes.SCOPES) for v in table.values())
    assert program_scopes.unscoped_ms_per_round(ctx) == pytest.approx((alone * 1e-3 + 0.5e-3) / 2)
    printed = capsys.readouterr().err
    assert "device time by scope" in printed and scopes.NO_METADATA in printed
    total = float(re.search(r"\n\s+sum\s+([0-9.]+)", printed).group(1))
    assert total == pytest.approx(1000.0 * trace.busy_s / 2, abs=0.006)  # printed to 0.01 ms


def test_the_readers_are_silent_without_a_trace_or_a_table(driven):
    driver, _ = driven
    no_trace = types.SimpleNamespace(driver=driver, trace=None, units=[{}])
    older = types.SimpleNamespace(driver=types.SimpleNamespace(sim=object()), units=[{}],
                                  trace=types.SimpleNamespace(ops={0: []}, busy_s=1.0))
    for ctx in (no_trace, older):
        assert program_scopes.device_ms_per_round(ctx, "fed.flush") is None
        assert program_scopes.unscoped_ms_per_round(ctx) is None


# -- the operator's file, and a run that reads no trace ------------------------------------

def test_the_profiler_leaves_round_scopes_json_beside_the_xplane(tmp_path):
    from test_round_tracing import _simulator

    sim_, _ = _simulator(False, "rt-scopes", comm_round=2, enable_profiler=True,
                         profiler_dir=str(tmp_path))
    sim_.train()
    assert reduce_trace.find_xplane(str(tmp_path))
    with open(tmp_path / "round_scopes.json") as f:
        kept = json.load(f)
    assert kept["scopes"] == list(scopes.SCOPES)
    assert kept["instructions"] == sim_.round_scopes()
    assert any("fed.flush" in v for v in kept["instructions"].values())


def test_an_untraced_benchmark_run_never_asks_for_the_table():
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="--xla_force_host_platform_device_count=1")
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "tests", "drive_scopes.py"), "--trace", "0"],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    assert json.loads(done.stdout.strip().splitlines()[-1])["correct"] is True
    assert "round_scopes() calls: 0" in done.stderr
