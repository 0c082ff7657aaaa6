"""bench.py helpers (bench.py itself only measures on the chip; these cover
its start-up contract, the failure reporting, and the opt-in metric paths at
smoke scale on CPU)."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.heavy
def test_transformer_bench_metric_line(monkeypatch):
    sys.path.insert(0, ".")
    import bench

    for k, v in {"BENCH_TF_DMODEL": "64", "BENCH_TF_LAYERS": "2",
                 "BENCH_TF_HEADS": "4", "BENCH_TF_DFF": "256",
                 "BENCH_TF_SEQ": "128", "BENCH_TF_BATCH": "2",
                 "BENCH_TF_STEPS": "3"}.items():
        monkeypatch.setenv(k, v)
    # the CPU has no published peak; give the smoke run one so mfu divides
    import jax

    monkeypatch.setitem(bench.DEVICE_PEAKS, jax.devices()[0].device_kind,
                        {"bf16_tflops": 197.0, "hbm_gbps": 819.0})
    out = bench._measure_transformer()
    json.dumps(out)  # one JSON-serializable line
    assert out["unit"] == "tokens/s/chip"
    assert out["value"] > 0
    assert 0 <= out["mfu"] <= 1
    assert out["n_params"] > 0


class TestNoChipNoNumber:
    """bench.py measures on the TPU and nowhere else: no probe child, no
    re-exec, no CPU fallback that writes timings under speed keys."""

    def test_cpu_backend_emits_one_failed_line_and_measures_nothing(
            self, capsys, monkeypatch):
        sys.path.insert(0, ".")
        import bench

        def _must_not_run(*a, **k):
            raise AssertionError("bench went past the backend check")

        monkeypatch.setattr(bench, "_emitted", False)
        monkeypatch.setattr(bench, "_bench_args", _must_not_run)
        assert bench.main() == 1
        lines = [l for l in capsys.readouterr().out.splitlines() if l.strip()]
        assert len(lines) == 1
        rec = json.loads(lines[0])
        assert rec["mode"] == "failed" and rec["value"] is None
        assert "'cpu'" in rec["degraded_reason"]
        assert rec["device"]["platform"] == "cpu"
        assert rec["bench_schema"] == bench.BENCH_SCHEMA

    def test_script_exits_nonzero_in_seconds_on_cpu(self):
        import subprocess
        import time

        t0 = time.time()
        proc = subprocess.run(
            [sys.executable, os.path.join(ROOT, "bench.py")], cwd=ROOT,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
            capture_output=True, text=True, timeout=120)
        assert proc.returncode == 1
        assert time.time() - t0 < 60
        lines = [l for l in proc.stdout.splitlines() if l.strip()]
        assert len(lines) == 1 and json.loads(lines[0])["mode"] == "failed"

    def test_no_code_path_sets_the_platform_or_re_execs(self):
        for name in ("bench.py", "chip_smoke.py"):
            src = open(os.path.join(ROOT, name)).read()
            for banned in ("JAX_PLATFORMS", "os.execv", "subprocess.Popen",
                           "jax_compilation_cache_dir"):
                assert banned not in src, (name, banned)

    def test_peaks_are_keyed_by_device_kind_and_unknown_is_an_error(self):
        sys.path.insert(0, ".")
        import bench

        assert bench._device_peaks("TPU v5 lite")["bf16_tflops"] == 197.0
        with pytest.raises(RuntimeError, match="device_kind 'cpu'"):
            bench._device_peaks("cpu")


class TestPhaseFailuresAreLoud:
    """A measurement phase that raises is named in the record and makes the
    exit code nonzero; one that cannot run on this device count says so."""

    def test_run_phases_names_the_phase_that_raised(self, capsys):
        sys.path.insert(0, ".")
        import bench

        def _boom():
            raise ValueError("mesh needs 2 devices, have 1")

        out = {}
        failed = bench._run_phases(
            out, [("ok", lambda: {"a": 1}), ("remesh", _boom),
                  ("after", lambda: {"b": 2})])
        assert out == {"a": 1, "b": 2}  # the others still ran
        assert failed == ["remesh: ValueError: mesh needs 2 devices, have 1"]
        assert "Traceback" in capsys.readouterr().err

    def test_main_exits_nonzero_and_names_a_raising_phase(
            self, capsys, monkeypatch):
        """The whole _main path at lr/mnist size with the backend check and
        the peak table faked: one phase raises -> rc 1, the record is still
        emitted, carries the headline AND the failed phase's name."""
        import jax

        sys.path.insert(0, ".")
        import bench

        real_args = bench._bench_args

        def small_args(n_chips, compute_dtype="bf16"):
            args = real_args(n_chips, compute_dtype)
            args.model, args.dataset = "lr", "mnist"
            args.data_cache_dir = ""
            args.synthetic_train_size = 800
            args.client_num_per_round, args.comm_round = 8, 2
            return args

        def _boom():
            raise RuntimeError("secagg plane exploded")

        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(bench, "_device_peaks",
                            lambda kind: {"bf16_tflops": 1.0})
        monkeypatch.setattr(bench, "_bench_args", small_args)
        monkeypatch.setattr(bench, "_measure_eager_baseline", lambda *a: 1.0)
        monkeypatch.setattr(bench, "_measure_obs_overhead", lambda sim: {})
        for name in ("telemetry_overhead", "agg_step", "round_update",
                     "defended_round", "remesh", "upload_saturation",
                     "fanin", "async_throughput", "chunked",
                     "health_overhead", "round_throughput"):
            monkeypatch.setattr(bench, f"_measure_{name}", lambda: {})
        monkeypatch.setattr(bench, "_measure_secagg", _boom)
        monkeypatch.setattr(bench, "_emitted", False)
        assert bench.main() == 1
        lines = [l for l in capsys.readouterr().out.splitlines() if l.strip()]
        assert len(lines) == 1
        rec = json.loads(lines[0])
        assert rec["mode"] == "full" and rec["value"] > 0
        assert rec["failed_phases"] == [
            "secagg: RuntimeError: secagg plane exploded"]
        assert rec["dataset_is_synthetic"] is True
        assert rec["device"]["count"] == len(jax.devices())

    def test_remesh_says_when_one_device_cannot_shrink(self, monkeypatch):
        import jax

        sys.path.insert(0, ".")
        import bench

        one = jax.devices()[:1]
        monkeypatch.setattr(jax, "devices", lambda *a: one)
        assert bench._measure_remesh() == {
            "remesh_skipped": "needs >= 2 devices, have 1"}


class TestMetricLineContract:
    """Schema-2 stamping + the exactly-one-JSON-line guarantee on every
    exit path (tools/perf_gate.py rejects a round with an empty tail)."""

    def test_emit_stamps_schema_provenance(self, capsys, monkeypatch):
        sys.path.insert(0, ".")
        import bench

        monkeypatch.setattr(bench, "_emitted", False)
        bench._emit({"metric": "m", "value": 1.0, "unit": "u"}, "full")
        lines = [l for l in capsys.readouterr().out.splitlines() if l.strip()]
        assert len(lines) == 1
        rec = json.loads(lines[0])
        assert rec["bench_schema"] == bench.BENCH_SCHEMA
        assert rec["mode"] == "full"
        assert rec["git_rev"]  # short rev or "unknown", never absent
        assert rec["metric"] == "m" and rec["value"] == 1.0
        assert bench._emitted is True

    def test_nonzero_rc_without_a_line_still_leaves_a_failed_record(
            self, capsys, monkeypatch):
        sys.path.insert(0, ".")
        import bench

        monkeypatch.setattr(bench, "_emitted", False)
        monkeypatch.setattr(bench, "_main", lambda: 1)
        assert bench.main() == 1
        lines = [l for l in capsys.readouterr().out.splitlines() if l.strip()]
        assert len(lines) == 1  # the dark round still leaves a record
        rec = json.loads(lines[0])
        assert rec["mode"] == "failed" and rec["value"] is None
        assert rec["bench_schema"] == bench.BENCH_SCHEMA
        assert "without a metric line" in rec["degraded_reason"]

    def test_unhandled_exception_emits_failed_record_then_reraises(
            self, capsys, monkeypatch):
        sys.path.insert(0, ".")
        import bench

        def _boom():
            raise RuntimeError("boom")

        monkeypatch.setattr(bench, "_emitted", False)
        monkeypatch.setattr(bench, "_main", _boom)
        with pytest.raises(RuntimeError):
            bench.main()
        lines = [l for l in capsys.readouterr().out.splitlines() if l.strip()]
        assert len(lines) == 1
        rec = json.loads(lines[0])
        assert rec["mode"] == "failed"
        assert "RuntimeError" in rec["degraded_reason"]
        assert "boom" in rec["degraded_reason"]
