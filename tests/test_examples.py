"""The examples/ tree is the integration-fixture matrix (reference CI runs
its examples/ dirs the same way — SURVEY.md §4): every config must load,
validate, and run its scenario end-to-end on tiny synthetic data."""

import glob
import os
import threading

import pytest
import yaml

import fedml_tpu
from fedml_tpu.arguments import Arguments

EXAMPLES = os.path.join(os.path.dirname(__file__), os.pardir, "examples")

pytestmark = pytest.mark.heavy  # e2e rounds + XLA compiles; see pytest.ini


def _load(cfg_path, **over):
    with open(cfg_path) as f:
        cfg = yaml.safe_load(f)
    args = Arguments.from_dict(cfg)
    for k, v in over.items():
        setattr(args, k, v)
    return args.validate()


def _all_configs(subdir):
    pat = os.path.join(EXAMPLES, subdir, "*", "fedml_config.yaml")
    return sorted(glob.glob(pat))


def test_examples_exist():
    assert len(_all_configs("simulation")) >= 10
    assert len(_all_configs("cross_silo")) >= 4


def _family(cfg):
    name = os.path.basename(os.path.dirname(cfg))
    if not name.startswith("xla_"):
        return "host"
    return "split" if name.startswith(("xla_fedgkt_", "xla_split_nn_", "xla_vfl_")) else "in_mesh"


def _simulation_configs(family):
    """The simulation examples of one family: ``host`` (the sp and mpi backends,
    run here), ``in_mesh`` (``xla_*``, run by ``tests/test_examples_xla.py``) or
    ``split`` (the in-mesh ones that train a model split between the clients and
    the server, run by ``tests/test_examples_xla_split.py``)."""
    return [c for c in _all_configs("simulation") if _family(c) == family]


def run_simulation_example(cfg):
    args = _load(cfg, run_id=f"ex-{os.path.basename(os.path.dirname(cfg))}")
    args = fedml_tpu.init(args, should_init_logs=False)
    device = fedml_tpu.device.get_device(args)
    dataset, out_dim = fedml_tpu.data.load(args)
    model = fedml_tpu.models.create(args, out_dim)
    from fedml_tpu.runner import FedMLRunner

    metrics = FedMLRunner(args, device, dataset, model).run()
    if str(getattr(args, "federated_optimizer", "")).lower() == "fedgan":
        # FedGAN reports adversarial health (d_fake_score), not accuracy
        assert metrics and "d_fake_score" in metrics
    else:
        assert metrics and "test_acc" in metrics


@pytest.mark.parametrize(
    "cfg", _simulation_configs("host"), ids=lambda p: p.split(os.sep)[-2]
)
def test_simulation_example(cfg):
    run_simulation_example(cfg)


@pytest.mark.parametrize(
    "cfg",
    [c for c in _all_configs("cross_silo")
     # (light)secagg: own protocol harnesses below; hierarchical: needs
     # spawned silo slave processes (test_hierarchical_cross_silo_example)
     if "secagg" not in c and "hierarchical" not in c],
    ids=lambda p: p.split(os.sep)[-2],
)
def test_cross_silo_example(cfg, tmp_path):
    name = os.path.basename(os.path.dirname(cfg))
    broker = None
    over = {"run_id": f"ex-{name}"}
    if "mqtt" in name:
        from fedml_tpu.core.distributed.communication.mqtt_s3.broker import LocalBroker

        broker = LocalBroker().start()
        over.update(mqtt_port=broker.port, s3_blob_root=str(tmp_path / "blobs"))
    try:
        args_s = _load(cfg, role="server", rank=0, **over)
        args_s = fedml_tpu.init(args_s, should_init_logs=False)
        dataset, out_dim = fedml_tpu.data.load(args_s)
        model = fedml_tpu.models.create(args_s, out_dim)
        from fedml_tpu.cross_silo.server.server import Server

        server = Server(args_s, None, dataset, model)

        clients = []
        for rank in range(1, int(args_s.client_num_in_total) + 1):
            args_c = _load(cfg, role="client", rank=rank, **over)
            args_c = fedml_tpu.init(args_c, should_init_logs=False)
            ds_c, od_c = fedml_tpu.data.load(args_c)
            from fedml_tpu.cross_silo.client.client import Client

            clients.append(Client(args_c, None, ds_c, fedml_tpu.models.create(args_c, od_c)))

        threads = [threading.Thread(target=c.run, daemon=True) for c in clients]
        for t in threads:
            t.start()
        history = server.run()
        for t in threads:
            t.join(timeout=30)
            assert not t.is_alive()
        assert history and 0.0 <= history[-1]["test_acc"] <= 1.0
    finally:
        if broker is not None:
            broker.stop()


def _hier_slave_proc(cfg_path, rank, pg_port, run_id):
    """One silo slave process: joins the silo's host pg, trains stride-shards
    until FINISH.  Spawned children skip conftest, so force CPU first."""
    from netutil import force_child_cpu

    force_child_cpu()
    import yaml as _yaml

    import fedml_tpu as _f
    from fedml_tpu.arguments import Arguments as _Args

    with open(cfg_path) as f:
        cfg = _yaml.safe_load(f)
    args = _Args.from_dict(cfg)
    args.role, args.rank, args.run_id = "client", rank, run_id
    args.proc_rank_in_silo = 1
    args.pg_master_port = pg_port
    args = _f.init(args.validate(), should_init_logs=False)
    ds, out_dim = _f.data.load(args)
    from fedml_tpu.cross_silo.client.client import Client as _Client

    _Client(args, None, ds, _f.models.create(args, out_dim)).run()


def test_hierarchical_cross_silo_example():
    """Hierarchical Octopus: 1 server + 2 client silos over GRPC, each silo
    = master thread + one spawned slave process synchronized over the host
    ProcessGroup plane (reference torchrun-spawned ClientSlaveManager)."""
    import multiprocessing as mp

    from netutil import free_port

    cfg = os.path.join(EXAMPLES, "cross_silo", "hierarchical_fedavg_mnist_lr",
                       "fedml_config.yaml")
    run_id = "ex-hier"
    args_s = _load(cfg, role="server", rank=0, run_id=run_id)
    args_s = fedml_tpu.init(args_s, should_init_logs=False)
    dataset, out_dim = fedml_tpu.data.load(args_s)
    model = fedml_tpu.models.create(args_s, out_dim)
    from fedml_tpu.cross_silo.server.server import Server

    server = Server(args_s, None, dataset, model)

    ctx = mp.get_context("spawn")
    pg_ports = {rank: free_port() for rank in (1, 2)}
    slaves = [ctx.Process(target=_hier_slave_proc,
                          args=(cfg, rank, pg_ports[rank], run_id), daemon=True)
              for rank in (1, 2)]
    for p in slaves:
        p.start()

    masters = []
    for rank in (1, 2):
        args_c = _load(cfg, role="client", rank=rank, run_id=run_id,
                       proc_rank_in_silo=0, pg_master_port=pg_ports[rank])
        args_c = fedml_tpu.init(args_c, should_init_logs=False)
        ds_c, od_c = fedml_tpu.data.load(args_c)
        from fedml_tpu.cross_silo.client.client import Client

        masters.append(Client(args_c, None, ds_c, fedml_tpu.models.create(args_c, od_c)))

    threads = [threading.Thread(target=c.run, daemon=True) for c in masters]
    for t in threads:
        t.start()
    history = server.run()
    for t in threads:
        t.join(timeout=60)
        assert not t.is_alive()
    for p in slaves:
        p.join(timeout=60)
        assert p.exitcode == 0
    assert history and 0.0 <= history[-1]["test_acc"] <= 1.0


def test_cross_device_example(tmp_path):
    """Beehive example: server + fake devices over the file model plane."""
    import importlib.util as ilu

    ex = os.path.join(EXAMPLES, "cross_device", "beehive_fedavg_synthetic_lr")
    spec = ilu.spec_from_file_location("beehive_example", os.path.join(ex, "main.py"))
    mod = ilu.module_from_spec(spec)
    spec.loader.exec_module(mod)
    history = mod.main(os.path.join(ex, "fedml_config.yaml"),
                       workdir=str(tmp_path))
    assert history and history[-1]["test_acc"] > 0.5


def test_lightsecagg_example():
    cfg = os.path.join(EXAMPLES, "cross_silo", "lightsecagg_mnist_lr", "fedml_config.yaml")
    args = _load(cfg, run_id="ex-lsa")
    args = fedml_tpu.init(args, should_init_logs=False)
    from fedml_tpu.cross_silo.lightsecagg import run_lightsecagg_topology_in_threads

    history = run_lightsecagg_topology_in_threads(
        args,
        lambda a: fedml_tpu.data.load(a),
        lambda a, out_dim: fedml_tpu.models.create(a, out_dim),
    )
    assert history


def test_secagg_example():
    from fedml_tpu.core.distributed.communication.loopback import LoopbackHub

    LoopbackHub.reset()
    cfg = os.path.join(EXAMPLES, "cross_silo", "secagg_mnist_lr", "fedml_config.yaml")
    args = _load(cfg, run_id="ex-sa")
    args = fedml_tpu.init(args, should_init_logs=False)
    from fedml_tpu.cross_silo.secagg import run_secagg_topology_in_threads

    history = run_secagg_topology_in_threads(
        args,
        lambda a: fedml_tpu.data.load(a),
        lambda a, out_dim: fedml_tpu.models.create(a, out_dim),
    )
    assert history and history[-1]["test_acc"] > 0.2
