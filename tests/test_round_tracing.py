"""The in-mesh round seen from inside (ISSUE 25): the host phase spans under
the ``round`` root, ``round_log`` / ``startup_log``, the device scopes and
kernel names in the lowered programs, the start-up counters of ``core/obs``,
and the seam through which ``benchmark/tests`` build the round program on a
bare object.  CPU, tiny sizes."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import fedml_tpu
from fedml_tpu.arguments import Arguments
from fedml_tpu.core import mlops, obs
from fedml_tpu.core.mlops.sinks import FanoutSink, InMemorySink
from fedml_tpu.simulation.xla.fed_sim import PROFILED_ROUNDS, XLASimulator

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools"))

import trace_report  # noqa: E402

PHASES = ("select", "pack", "dispatch", "wait", "close")
SCOPES = ("fed.gather", "fed.local_step", "fed.flush", "fed.exchange", "fed.server_step")
ROUNDS = 3


def _simulator(obs_on: bool, run_id: str, **train):
    args = Arguments.from_dict({
        "common_args": {"training_type": "simulation", "random_seed": 0, "run_id": run_id},
        "data_args": {"dataset": "mnist", "data_cache_dir": "",
                      "partition_method": "hetero", "synthetic_train_size": 256},
        "model_args": {"model": "lr"},
        "train_args": {"federated_optimizer": "FedAvg", "client_num_in_total": 4,
                       "client_num_per_round": 4, "comm_round": ROUNDS, "epochs": 1,
                       "batch_size": 16, "client_optimizer": "sgd", "learning_rate": 0.1,
                       **train},
        "validation_args": {"frequency_of_the_test": 0},
        "comm_args": {"backend": "XLA"},
        "tracking_args": {"obs_trace": obs_on},
    }).validate()
    args = fedml_tpu.init(args, should_init_logs=False)
    mem = InMemorySink()
    if obs_on:
        fan = FanoutSink()
        fan.add(mem)
        mlops.init(args, fan)
    dataset, out_dim = fedml_tpu.data.load(args)
    model = fedml_tpu.models.create(args, out_dim)
    return XLASimulator(args, dataset, model), mem


@pytest.fixture(scope="module")
def traced():
    """One traced run and one with obs off."""
    try:
        sim, mem = _simulator(True, "rt-packed")
        sim.train()
    finally:
        mlops.finish()
    quiet, _ = _simulator(False, "rt-packed")
    quiet.train()
    return sim, mem, quiet


def _spans(mem):
    """{(round_idx, name): (start record, end record)} of the round trees."""
    starts = {r["span_id"]: r for r in mem.by_topic("span_start")}
    return {(starts[e["span_id"]].get("round_idx"), e["name"]): (starts[e["span_id"]], e)
            for e in mem.by_topic("span_end") if e["span_id"] in starts}


def test_round_span_tree_nests(traced):
    _, mem, _ = traced
    spans = _spans(mem)
    for r in range(ROUNDS):
        root_start, root_end = spans[(r, "round")]
        children = [spans[(r, "round." + p)] for p in PHASES]
        for start, _ in children:
            assert start["parent_span_id"] == root_start["span_id"]
            assert start["trace_id"] == root_start["trace_id"]
        assert sum(end["duration_s"] for _, end in children) <= root_end["duration_s"]
        # the order they ran in is the order of the table in docs/OBSERVABILITY.md
        order = [e["name"] for e in mem.by_topic("span_end")
                 if e["name"].startswith("round.") and e["trace_id"] == root_start["trace_id"]]
        assert order == ["round." + p for p in PHASES]
    assert (None, "sim.train") in spans and (None, "sim.build") in spans


def test_trace_report_reads_the_run_closed(traced):
    """Every round one closed tree under ``round``; ``sim.build`` and
    ``sim.train`` share the run's round-less trace, each a root."""
    _, mem, _ = traced
    traces = trace_report.build_traces(
        [dict(rec, topic=t) for t, rec in mem.records if t in trace_report.SPAN_TOPICS])
    assert len(traces) == ROUNDS + 1
    for tr in traces.values():
        assert tr.problems() == [], tr.problems()
    run = next(tr for tr in traces.values() if tr.round_idx() is None)
    assert sorted(r.name for r in run.roots()) == ["sim.build", "sim.train"]
    # a stray root in a round-less trace is still a problem
    stray = trace_report.build_traces([
        {"topic": "span_start", "trace_id": "t" * 32, "span_id": "a" * 16, "name": "upload"},
        {"topic": "span_end", "trace_id": "t" * 32, "span_id": "a" * 16, "name": "upload"}])
    assert stray["t" * 32].problems() == ["root span is 'upload' (expected 'round')"]


def test_round_span_attributes(traced):
    sim, mem, _ = traced
    spans = _spans(mem)
    for r, rec in enumerate(sim.round_log):
        assert spans[(r, "round.select")][1]["n_sampled"] == 4
        pack_end = spans[(r, "round.pack")][1]
        for key in ("s_bucket", "steps_max", "h2d_bytes"):
            assert pack_end[key] == rec[key]
        assert rec["steps_max"] > 0 and rec["h2d_bytes"] > 0
    events = [e["event"] for e in mem.by_topic("span_event")]
    assert events.count("bucket_compile") == 1


def test_round_log_is_the_spans_numbers(traced):
    sim, mem, _ = traced
    spans = _spans(mem)
    assert [rec["round"] for rec in sim.round_log] == list(range(ROUNDS))
    for r, rec in enumerate(sim.round_log):
        for p in PHASES:  # one pair of clock reads feeds both
            assert rec[p + "_s"] == spans[(r, "round." + p)][1]["duration_s"]
        assert rec["wall_s"] == sim.round_times[r]
        assert rec["loss"] == sim.round_losses[r]
        assert rec["samples"] == sim.samples_per_round[r]
        root_end = spans[(r, "round")][1]
        assert root_end["compile_s"] == round(rec["compile_s"], 6)
        # wall time ends at block_until_ready, before round.close
        assert rec["wall_s"] <= root_end["duration_s"] - rec["close_s"] + 1e-3
    assert sim.round_log[0]["compile_s"] > 0.0


def test_startup_log_is_the_spans_numbers(traced):
    sim, mem, _ = traced
    spans = _spans(mem)
    build_start, build_end = spans[(None, "sim.build")]
    assert sim.startup_log["build_s"] == build_end["duration_s"]
    for name in ("pack_data", "init_variables", "build_round_fn"):
        start, end = spans[(None, "sim." + name)]
        assert start["parent_span_id"] == build_start["span_id"]
        assert sim.startup_log[name + "_s"] == end["duration_s"]
    parts = sum(sim.startup_log[k + "_s"] for k in ("pack_data", "init_variables", "build_round_fn"))
    assert parts <= sim.startup_log["build_s"]


def test_logs_filled_with_obs_off(traced):
    sim, _, quiet = traced
    assert not obs.enabled()
    assert len(quiet.round_log) == ROUNDS
    assert set(quiet.round_log[-1]) == set(sim.round_log[-1])
    assert set(quiet.startup_log) == set(sim.startup_log)
    assert all(rec[p + "_s"] >= 0.0 for rec in quiet.round_log for p in PHASES)


def test_model_bit_identical_with_obs_on_and_off(traced):
    sim, _, quiet = traced
    on = jax.tree_util.tree_leaves(jax.tree_util.tree_map(np.asarray, sim.variables))
    off = jax.tree_util.tree_leaves(jax.tree_util.tree_map(np.asarray, quiet.variables))
    assert len(on) == len(off) and all(np.array_equal(a, b) for a, b in zip(on, off))
    assert sim.round_losses == quiet.round_losses


def test_taken_out_metrics_stay_out(traced):
    """ISSUE 25 E: ``round.compile_seconds`` went (the round span's
    ``compile_s`` and ``round_log`` carry it); what the docs read stays."""
    names = {r["metric"] for r in obs.registry().export()}
    assert "round.compile_seconds" not in names
    assert {"round.seconds", "agg.bytes_reduced", "startup.init_seconds"} <= names


def test_scopes_and_program_name_in_lowered_round():
    sim, _ = _simulator(False, "rt-lower", comm_round=1)
    real, seen = sim._round_fn, {}

    def spy(*inputs):
        seen["inputs"] = inputs
        return real(*inputs)

    sim._round_fn = spy
    sim.train()
    text = real.lower(*seen["inputs"]).as_text(debug_info=True)
    assert "module @jit_fedml_round_packed" in text
    for scope in SCOPES:
        assert scope + "/" in text, scope


@pytest.mark.parametrize("n_dev", [1, 4])
def test_round_builds_on_a_bare_object_as_the_benchmark_builds_it(n_dev):
    """``benchmark/tests/test_compile_v5e.py`` (not in tier-1) compiles the
    cells' rounds without a data upload: ``XLASimulator.__new__``, eleven
    attributes, ``_build_packed_round_fn()``, then twelve arguments shaped
    from ``s_max`` / ``slots``.  The same recipe, lowered here."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from fedml_tpu.ml.engine.train import init_variables
    from fedml_tpu.simulation.xla.algorithms import create_inmesh_algorithm

    args = Arguments.from_dict({
        "common_args": {"training_type": "simulation", "random_seed": 0},
        "model_args": {"model": "lr"}, "data_args": {"dataset": "mnist"},
        "train_args": {"federated_optimizer": "FedAvg", "client_num_in_total": 8,
                       "client_num_per_round": 8, "comm_round": 1, "epochs": 1,
                       "batch_size": 4, "client_optimizer": "sgd", "learning_rate": 0.1},
        "comm_args": {"backend": "XLA"}}).validate()
    sizes, width, b = [3, 5, 5, 7, 7, 9, 9, 11], 784, 4
    mesh = Mesh(np.array(jax.devices()[:n_dev]), ("client",))
    sim = XLASimulator.__new__(XLASimulator)
    sim.args, sim.module, sim.mesh, sim.n_dev = args, fedml_tpu.models.create(args, 10), mesh, n_dev
    sim.clients_per_round, sim.batch_size, sim.max_client_n = len(sizes), b, max(sizes)
    sim.needs_stack = sim.sharded_state = False
    sim.loss_kind, sim.algo = "ce", create_inmesh_algorithm(args)
    sim._build_packed_round_fn()
    assert sim.slots == len(sizes) // n_dev and sim.s_max == sim.slots * 3

    steps = -(-sum(-(-n // b) for n in sizes) // n_dev)
    quantum = max(1, -(-sim.s_max // 8))
    bucket = min(-(-steps // quantum) * quantum, sim.s_max)
    repl, split = NamedSharding(mesh, P()), NamedSharding(mesh, P("client"))

    def s(shape, dtype, sharding):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    variables = jax.tree_util.tree_map(
        lambda v: s(v.shape, v.dtype, repl),
        jax.eval_shape(lambda: init_variables(sim.module, jnp.zeros((1, width)), seed=0)))
    inputs = (variables, (), s((sum(sizes), width), jnp.float32, repl), s((sum(sizes),), jnp.int32, repl),
              s((n_dev, bucket, b), jnp.int32, split), s((n_dev, bucket, b), jnp.float32, split),
              s((n_dev, bucket), jnp.float32, split), s((n_dev, bucket), jnp.float32, split),
              s((n_dev, bucket), jnp.int32, split), s((n_dev,), jnp.int32, split),
              s((n_dev, 2), jnp.uint32, split), s((n_dev * sim.slots,), jnp.float32, split))
    text = sim._round_fn.lower(*inputs).as_text(debug_info=True)
    assert "module @jit_fedml_round_packed" in text
    for scope in SCOPES:
        assert scope + "/" in text, scope


def test_server_tail_has_its_scope():
    sim, _ = _simulator(False, "rt-tail", comm_round=1, server_state="sharded",
                        federated_optimizer="FedOpt", server_optimizer="adam")
    real, seen = sim._server_tail, {}

    def spy(*inputs):
        seen["text"] = real.lower(*inputs).as_text(debug_info=True)
        return real(*inputs)

    sim._server_tail = spy
    sim.train()
    assert "fed.server_step/" in seen["text"]


@pytest.mark.parametrize("kernel", ["flash_fwd", "flash_bwd_dq", "flash_bwd_dkv",
                                    "flash_shard_update"])
def test_kernel_names_in_lowered_text(kernel):
    from fedml_tpu.ops.flash_attention import flash_attention, flash_shard_update

    B, L, H, D = 2, 256, 8, 32
    qkv = (jax.ShapeDtypeStruct((B, L, H, D), jnp.float32),) * 3
    if kernel == "flash_shard_update":
        pos = jax.ShapeDtypeStruct((L,), jnp.int32)
        stat = jax.ShapeDtypeStruct((B, H, L), jnp.float32)
        fn, args = flash_shard_update, qkv + (pos, pos, stat, stat, qkv[0])
    elif kernel == "flash_fwd":
        fn, args = flash_attention, qkv
    else:
        def fn(q, k, v):
            return jax.grad(lambda *a: flash_attention(*a).sum(), argnums=(0, 1, 2))(q, k, v)
        args = qkv
    text = jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",)).as_text()
    assert f'kernel_name = "{kernel}"' in text


@pytest.fixture
def fresh_cache(tmp_path):
    """A persistent compilation cache of this test's own that keeps every
    program, and jax's settings put back afterwards."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    keys = ("jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    before = {k: getattr(jax.config, k) for k in keys}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    cc.reset_cache()
    try:
        yield
    finally:
        for k, v in before.items():
            jax.config.update(k, v)
        cc.reset_cache()


def _counts():
    series = {r["metric"]: r["value"] for r in obs.registry().export()
              if r["metric"] in ("xla.cache_hits", "xla.cache_misses")}
    return {"trace_s": obs.trace_seconds_total(), "compiles": obs.compiles_total(),
            "compile_s": obs.compile_seconds_total(),
            "hits": series.get("xla.cache_hits", 0), "misses": series.get("xla.cache_misses", 0)}


def test_startup_counters_count_a_first_call_and_not_a_second(fresh_cache):
    args = Arguments.from_dict({"common_args": {"run_id": "rt-counters"},
                                "tracking_args": {"obs_trace": True}})
    try:
        mlops.init(args, FanoutSink())

        def program(x):  # a program no other test has compiled
            return jnp.tanh(x * 25.0 + 0.25).sum()

        x = jnp.arange(25.0)
        c0 = _counts()
        step = jax.jit(program)
        step(x).block_until_ready()
        c1 = _counts()
        assert c1["trace_s"] > c0["trace_s"]
        assert c1["compile_s"] > c0["compile_s"]
        assert c1["compiles"] == c0["compiles"] + 1
        assert (c1["hits"], c1["misses"]) == (c0["hits"], c0["misses"] + 1)
        step(x).block_until_ready()  # a second call of a compiled program
        assert _counts() == c1
        # a new start: jax's in-memory caches gone, the persistent one warm
        jax.clear_caches()
        jax.jit(program)(x).block_until_ready()
        c2 = _counts()
        assert c2["trace_s"] > c1["trace_s"]
        assert c2["compiles"] == c1["compiles"], "a cache hit is no compile"
        assert (c2["hits"], c2["misses"]) == (c1["hits"] + 1, c1["misses"])
    finally:
        mlops.finish()


def test_startup_counters_are_off_with_obs_off():
    assert not obs.enabled()
    c0 = _counts()
    jax.jit(lambda x: jnp.cos(x * 7.0 + 0.125).sum())(jnp.arange(7.0)).block_until_ready()
    assert _counts() == c0


def test_profiled_rounds_constant():
    assert PROFILED_ROUNDS == (1, 3)
