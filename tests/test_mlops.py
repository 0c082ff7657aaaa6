"""core/mlops: sinks, metrics, events, status FSM, sys stats, log daemon
(reference core/mlops parity, offline-first)."""

import logging
import os
import time

import pytest

from fedml_tpu.core import mlops
from fedml_tpu.core.mlops import (
    ClientStatus,
    FanoutSink,
    InMemorySink,
    JsonlFileSink,
    MLOpsProfilerEvent,
    MLOpsRuntimeLogDaemon,
    MLOpsStatus,
    ServerStatus,
    SysStats,
)


class _Args:
    def __init__(self, **kw):
        self.__dict__.update(kw)


@pytest.fixture(autouse=True)
def _reset_mlops():
    yield
    mlops.finish()
    MLOpsStatus._instance = None


def test_facade_noop_until_init():
    mlops.log({"acc": 1.0})  # must not raise
    assert not mlops.enabled()


def test_facade_log_round_and_status(tmp_path):
    mem = InMemorySink()
    mlops.init(_Args(run_id="r1", rank=0, log_file_dir=str(tmp_path)), FanoutSink([mem]))
    assert mlops.enabled()
    mlops.log({"acc": 0.9})
    mlops.log_round_info(10, 3)
    mlops.log_training_status(ClientStatus.INITIALIZING, edge_id=1)
    mlops.log_aggregation_status(ServerStatus.STARTING)
    mlops.event("train", event_started=True)
    mlops.event("train", event_started=False)
    topics = {t for t, _ in mem.records}
    assert {"train_metric", "round_info", "client_status", "server_status", "event"} <= topics
    # the JSONL file sink wrote the same records
    files = [f for f in os.listdir(tmp_path) if f.startswith("mlops_")]
    assert files and os.path.getsize(tmp_path / files[0]) > 0


def test_status_fsm_rejects_illegal_transition():
    st = MLOpsStatus.get_instance()
    st.set_client_status(5, ClientStatus.INITIALIZING)
    st.set_client_status(5, ClientStatus.TRAINING)
    st.set_client_status(5, ClientStatus.FINISHED)
    with pytest.raises(ValueError):
        st.set_client_status(5, ClientStatus.TRAINING)  # FINISHED is terminal


def test_profiler_event_duration():
    mem = InMemorySink()
    prof = MLOpsProfilerEvent("r", 0, FanoutSink([mem]))
    with prof.trace("span"):
        time.sleep(0.01)
    ev = mem.by_topic("event")
    assert ev[0]["phase"] == "started" and ev[1]["phase"] == "ended"
    assert ev[1]["duration_s"] >= 0.01


def test_sys_stats_schema():
    info = SysStats().produce_info()
    assert "system_memory_total" in info and "cpu_utilization" in info
    assert isinstance(info["devices"], list)


def test_log_daemon_ships_chunks(tmp_path):
    log_path = str(tmp_path / "run.log")
    mem = InMemorySink()
    daemon = MLOpsRuntimeLogDaemon(
        log_path, FanoutSink([mem]), chunk_lines=2, poll_interval_s=0.01
    ).start()
    with open(log_path, "w") as f:
        for i in range(5):
            f.write(f"line {i}\n")
    deadline = time.time() + 5
    while daemon.lines_shipped < 5 and time.time() < deadline:
        time.sleep(0.02)
    daemon.stop()
    chunks = mem.by_topic("log_chunk")
    shipped = [ln for c in chunks for ln in c["lines"]]
    assert shipped == [f"line {i}" for i in range(5)]


def test_broker_sink_roundtrip():
    from fedml_tpu.core.distributed.communication.mqtt_s3.broker import (
        BrokerClient,
        LocalBroker,
    )
    from fedml_tpu.core.mlops.sinks import BrokerSink

    broker = LocalBroker().start()
    got = []
    sub = BrokerClient("127.0.0.1", broker.port, on_message=lambda t, p: got.append((t, p)))
    sub.subscribe("fedml_mlops/run9/#")
    time.sleep(0.05)
    sink = BrokerSink("127.0.0.1", broker.port, "run9")
    sink.emit("train_metric", {"loss": 0.5})
    deadline = time.time() + 5
    while not got and time.time() < deadline:
        time.sleep(0.02)
    sink.close()
    sub.disconnect()
    broker.stop()
    assert got and got[0][0] == "fedml_mlops/run9/train_metric" and got[0][1]["loss"] == 0.5


class TestXLAProfilerCapture:
    def test_enable_profiler_writes_trace(self, tmp_path):
        """args.enable_profiler captures a TensorBoard-viewable XLA trace of
        the compiled round (the TPU-first half of the reference's profiler
        event reporting): bounded to rounds 1-3 of the run, with the round's
        host phase spans on the profiler's host line."""
        import glob
        import os

        import jax

        import fedml_tpu
        from fedml_tpu.arguments import Arguments
        from fedml_tpu.core import mlops
        from fedml_tpu.simulation.xla.fed_sim import XLASimulator

        args = Arguments.from_dict({
            "common_args": {"training_type": "simulation", "random_seed": 0,
                            "run_id": "prof"},
            "data_args": {"dataset": "mnist", "data_cache_dir": "",
                          "partition_method": "homo", "synthetic_train_size": 128},
            "model_args": {"model": "lr"},
            "train_args": {"federated_optimizer": "FedAvg",
                           "client_num_in_total": 4, "client_num_per_round": 4,
                           "comm_round": 6, "epochs": 1, "batch_size": 16,
                           "client_optimizer": "sgd", "learning_rate": 0.1},
            "validation_args": {"frequency_of_the_test": 0},
            "comm_args": {"backend": "XLA"},
            # the phase spans are obs spans: they reach the trace with obs on
            "tracking_args": {"using_mlops": True, "obs_trace": True},
        }).validate()
        args.enable_profiler = True
        args.profiler_dir = str(tmp_path / "trace")
        try:
            args = fedml_tpu.init(args, should_init_logs=False)
            dataset, out_dim = fedml_tpu.data.load(args)
            model = fedml_tpu.models.create(args, out_dim)
            XLASimulator(args, dataset, model).train()
        finally:
            mlops.finish()
        dumped = []
        for root, _, files in os.walk(args.profiler_dir):
            dumped += [f for f in files if f.endswith((".pb", ".json.gz", ".xplane.pb"))]
        assert dumped, "no trace files captured"
        # one trace, stopped after round 3: the rounds' spans on the host line
        (xplane,) = glob.glob(os.path.join(args.profiler_dir, "**", "*.xplane.pb"),
                              recursive=True)
        names = [ev.name for plane in jax.profiler.ProfileData.from_file(xplane).planes
                 if plane.name.startswith("/host:")
                 for line in plane.lines for ev in line.events]
        for phase in ("select", "pack", "dispatch", "wait", "close"):
            assert names.count("round." + phase) == 3, (phase, names.count("round." + phase))
        assert names.count("round") == 3
