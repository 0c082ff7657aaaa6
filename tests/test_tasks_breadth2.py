"""Seq2seq, link-prediction (ego + bipartite recsys), multi-task molecule,
and SpreadGNN task families (reference app/fednlp/seq2seq,
app/fedgraphnn/{ego_networks_link_pred,recsys_subgraph_link_pred},
research/SpreadGNN)."""

import numpy as np
import pytest

import fedml_tpu
from fedml_tpu.arguments import Arguments

pytestmark = pytest.mark.heavy  # transformer/GCN XLA compiles


def _cfg(dataset, model, **over):
    d = {
        "common_args": {"training_type": "simulation", "random_seed": 0,
                        "run_id": f"task-{dataset}"},
        "data_args": {"dataset": dataset, "data_cache_dir": "",
                      "partition_method": "homo", "synthetic_train_size": 512},
        "model_args": {"model": model},
        "train_args": {"federated_optimizer": "FedAvg", "client_num_in_total": 4,
                       "client_num_per_round": 4, "comm_round": 3, "epochs": 1,
                       "batch_size": 32, "client_optimizer": "adam",
                       "learning_rate": 0.002},
        "validation_args": {"frequency_of_the_test": 2},
        "comm_args": {"backend": "sp"},
    }
    args = Arguments.from_dict(d)
    for k, v in over.items():
        setattr(args, k, v)
    return args.validate()


def _run(args):
    args = fedml_tpu.init(args, should_init_logs=False)
    device = fedml_tpu.device.get_device(args)
    dataset, out_dim = fedml_tpu.data.load(args)
    model = fedml_tpu.models.create(args, out_dim)
    from fedml_tpu.simulation.simulator import create_simulator

    return create_simulator(args, device, dataset, model).run()


class TestSeq2Seq:
    def test_corpus_shape(self):
        from fedml_tpu.data.synthetic import make_seq2seq

        x, y = make_seq2seq(16, 8, 8, 32, seed=0)
        assert x.shape == (16, 16) and y.shape == (16, 16)
        assert (y[:, :8] == -1).all()  # source positions unlabeled
        assert (y[:, 8:] >= 2).all()   # targets are real tokens
        # teacher forcing: input after SEP is the shifted target
        assert (x[:, 8] == 1).all()
        assert (x[:, 9:] == y[:, 8:-1]).all()

    def test_learns_successor_copy(self):
        metrics = _run(_cfg("synthetic_s2s", "transformer_s2s", comm_round=4,
                            epochs=3, learning_rate=0.01,
                            synthetic_train_size=2048))
        # masked token accuracy: well above 1/62 chance on held-out sequences
        assert metrics["test_acc"] > 0.5, metrics


class TestLinkPrediction:
    def test_labels_balanced_and_disjoint(self):
        from fedml_tpu.data.synthetic import make_link_prediction

        x, y = make_link_prediction(8, 16, 8, seed=0)
        assert x.shape == (8, 16, 24) and y.shape == (8, 16, 16)
        pos, neg = (y == 1).sum(), (y == 0).sum()
        assert pos > 0 and neg > 0
        # held-out positives are NOT in the observed adjacency
        adj = x[..., 8:]
        assert (adj[y == 1] == 0).all()

    def test_learns_links(self):
        metrics = _run(_cfg("ego_linkpred", "gcn_linkpred", comm_round=4,
                            epochs=3, learning_rate=0.01))
        assert metrics["test_acc"] > 0.62, metrics  # balanced pairs: 0.5 chance

    def test_learns_bipartite_recsys(self):
        metrics = _run(_cfg("recsys_linkpred", "gcn_linkpred", comm_round=4,
                            epochs=3, learning_rate=0.01))
        assert metrics["test_acc"] > 0.62, metrics


class TestMultiTask:
    def test_partial_labels(self):
        from fedml_tpu.data.synthetic import make_multitask_graphs

        x, y = make_multitask_graphs(32, 16, 8, 8, seed=0)
        assert y.shape == (32, 8)
        frac = (y >= 0).mean()
        assert 0.5 < frac < 0.9  # partial observation
        assert set(np.unique(y)) <= {-1.0, 0.0, 1.0}

    def test_learns_multitask(self):
        metrics = _run(_cfg("moleculenet_mtl", "gcn_mtl", comm_round=4,
                            epochs=3, learning_rate=0.01))
        assert metrics["test_acc"] > 0.62, metrics  # per-task binary, 0.5 chance


class TestSpreadGNN:
    def test_decentralized_multitask(self):
        args = _cfg("moleculenet_mtl", "gcn_mtl", comm_round=3, epochs=2,
                    learning_rate=0.01, topology_neighbor_num=2)
        args.federated_optimizer = "SpreadGNN"
        args.client_num_in_total = args.client_num_per_round = 4
        metrics = _run(args)
        assert metrics["test_acc"] > 0.55, metrics

    def test_heads_stay_local_encoder_mixes(self):
        import jax
        import jax.numpy as jnp

        from fedml_tpu.simulation.sp.spreadgnn.spreadgnn_api import SpreadGNNAPI

        args = _cfg("moleculenet_mtl", "gcn_mtl", comm_round=1, epochs=1,
                    synthetic_train_size=128, topology_neighbor_num=2)
        args.federated_optimizer = "SpreadGNN"
        args.client_num_in_total = args.client_num_per_round = 4
        args = fedml_tpu.init(args, should_init_logs=False)
        device = fedml_tpu.device.get_device(args)
        dataset, out_dim = fedml_tpu.data.load(args)
        model = fedml_tpu.models.create(args, out_dim)
        api = SpreadGNNAPI(args, device, dataset, model)

        # distinct per-node models: head leaf i = i, encoder leaf i = i
        def make_node(i):
            return jax.tree_util.tree_map(
                lambda x: jnp.full_like(x, float(i)), api.w_global
            )

        stacked = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs, 0), *[make_node(i) for i in range(4)]
        )
        mixed = api._gossip(stacked, api.mix)
        flat = jax.tree_util.tree_flatten_with_path(mixed)[0]
        saw_head = saw_enc = False
        for path, leaf in flat:
            keys = {getattr(k, "key", getattr(k, "name", None)) for k in path}
            if "readout" in keys:
                saw_head = True  # untouched: node i keeps value i
                for i in range(4):
                    assert float(leaf[i].ravel()[0]) == float(i)
            else:
                saw_enc = True  # mixed: neighbor average != own value
                mixed_vals = [float(leaf[i].ravel()[0]) for i in range(4)]
                assert mixed_vals != [0.0, 1.0, 2.0, 3.0]
        assert saw_head and saw_enc


class TestIoTAnomaly:
    def test_benign_manifold_and_flags(self):
        from fedml_tpu.data.synthetic import make_iot_traffic

        x, flags = make_iot_traffic(256, 24, seed=0, anomaly_frac=0.1)
        assert x.shape == (256, 24)
        assert 20 <= flags.sum() <= 40
        xb, fb = make_iot_traffic(256, 24, seed=1, anomaly_frac=0.0)
        assert fb.sum() == 0

    def test_autoencoder_detects_anomalies(self):
        metrics = _run(_cfg("iot_anomaly", "autoencoder", comm_round=4,
                            epochs=3, learning_rate=0.01,
                            synthetic_train_size=2048))
        # benign reconstructs, anomalies don't: both overall accuracy and
        # recall on the anomalous tail must beat guessing
        assert metrics["test_acc"] > 0.85, metrics
        assert metrics["test_anomaly_recall"] > 0.7, metrics


class TestGraphNodeClf:
    def test_learns_node_communities(self):
        metrics = _run(_cfg("ego_nodeclf", "gcn_nodeclf", comm_round=4,
                            epochs=3, learning_rate=0.01))
        # per-node accuracy above 1/3 chance (community structure + features)
        assert metrics["test_acc"] > 0.6, metrics


class TestGraphRegression:
    def test_learns_property(self):
        metrics = _run(_cfg("freesolv", "gcn_reg", comm_round=4, epochs=3,
                            learning_rate=0.01,
                            partition_method="hetero"))
        # RMSE well below the target's std (signal = w.mean_feats + density)
        assert metrics["test_rmse"] < 0.6, metrics


class TestTasksOnXLABackend:
    """Task-specific losses now ride the compiled in-mesh round: the loss
    key is plumbed into the round's engine and eval goes through the task-aware
    aggregator (previously fail-loud -> sp only)."""

    @pytest.mark.parametrize("dataset,model,gate,extra", [
        ("synthetic_det", "tiny_detector", 0.5, {}),
        ("ego_linkpred", "gcn_linkpred", 0.62, {}),
        ("iot_anomaly", "autoencoder", 0.85, {}),
        ("synthetic_s2s", "transformer_s2s", 0.5, {"synthetic_train_size": 2048}),
    ])
    def test_task_learns_in_mesh(self, dataset, model, gate, extra):
        args = _cfg(dataset, model, comm_round=4, epochs=3, learning_rate=0.01,
                    **extra)
        args.backend = "XLA"
        metrics = _run(args)
        assert metrics["test_acc"] > gate, (dataset, metrics)

    def test_tag_prediction_in_mesh(self):
        """Int class ids are one-hot'd host-side at pack time so the bce
        loss (and tag eval probe) run in the compiled round."""
        args = _cfg("stackoverflow_lr", "lr", comm_round=6, epochs=3,
                    learning_rate=0.1, synthetic_train_size=1024)
        args.backend = "XLA"
        metrics = _run(args)
        # per-label-position accuracy; multi-hot is sparse so the floor is
        # high — require real learning via the F1 extra
        assert metrics["test_f1"] > 0.3, metrics
