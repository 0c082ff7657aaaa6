"""Model/data zoo widening + misc parity (SURVEY.md §2.4/§2.9): task
trainer/aggregator factories, EfficientNet, centralized baseline, cross-silo
split util, TCP (TRPC-slot) backend."""

import numpy as np
import pytest

import fedml_tpu
from fedml_tpu.arguments import Arguments

pytestmark = pytest.mark.heavy  # long XLA compiles; see pytest.ini


def _args(**over):
    base = {
        "common_args": {"training_type": "simulation", "random_seed": 0, "run_id": "z"},
        "data_args": {"dataset": "mnist", "data_cache_dir": "",
                      "partition_method": "homo", "synthetic_train_size": 320},
        "model_args": {"model": "lr"},
        "train_args": {
            "federated_optimizer": "FedAvg",
            "client_num_in_total": 4,
            "client_num_per_round": 2,
            "comm_round": 2,
            "epochs": 1,
            "batch_size": 32,
            "client_optimizer": "sgd",
            "learning_rate": 0.1,
        },
        "validation_args": {"frequency_of_the_test": 1},
        "comm_args": {"backend": "sp"},
    }
    args = Arguments.from_dict(base)
    for k, v in over.items():
        setattr(args, k, v)
    return args.validate()


class TestTaskFactories:
    def test_trainer_creator_dispatch(self):
        from fedml_tpu.ml.trainer.cls_trainer import ModelTrainerCLS
        from fedml_tpu.ml.trainer.nwp_trainer import ModelTrainerNWP
        from fedml_tpu.ml.trainer.tag_trainer import ModelTrainerTAGPred
        from fedml_tpu.ml.trainer.trainer_creator import create_model_trainer

        assert isinstance(create_model_trainer(None, _args()), ModelTrainerCLS)
        assert isinstance(
            create_model_trainer(None, _args(dataset="shakespeare")), ModelTrainerNWP
        )
        assert isinstance(
            create_model_trainer(None, _args(dataset="stackoverflow_lr")), ModelTrainerTAGPred
        )

    def test_nwp_fedavg_learns_tokens(self):
        args = _args(dataset="shakespeare", model="rnn_fedshakespeare",
                     synthetic_train_size=256, learning_rate=0.5, comm_round=3)
        args = fedml_tpu.init(args, should_init_logs=False)
        from fedml_tpu import FedMLRunner, data, models

        dataset, out_dim = data.load(args)
        model = models.create(args, out_dim)
        metrics = FedMLRunner(args, None, dataset, model).run()
        # markov corpus: well above uniform-vocab chance (1/90 ~= 0.011)
        assert metrics["test_acc"] > 0.025

    def test_tagpred_fedavg_runs(self):
        args = _args(dataset="stackoverflow_lr", model="lr",
                     synthetic_train_size=256, comm_round=2)
        args = fedml_tpu.init(args, should_init_logs=False)
        from fedml_tpu import FedMLRunner, data, models

        dataset, out_dim = data.load(args)
        model = models.create(args, out_dim)
        metrics = FedMLRunner(args, None, dataset, model).run()
        assert "test_acc" in metrics


class TestModels:
    def test_efficientnet_forward(self):
        import jax
        import jax.numpy as jnp

        from fedml_tpu.models.efficientnet import EfficientNet

        m = EfficientNet(num_classes=10)
        x = jnp.zeros((2, 32, 32, 3))
        params = jax.jit(m.init)(jax.random.PRNGKey(0), x)
        out = jax.jit(m.apply)(params, x)
        assert out.shape == (2, 10)

    def test_hub_key(self):
        from fedml_tpu import models

        m = models.create(_args(model="efficientnet", dataset="cifar10"), 10)
        assert m.__class__.__name__ == "EfficientNet"


class TestCentralizedBaseline:
    def test_centralized_beats_chance(self):
        from fedml_tpu.centralized import CentralizedTrainer

        args = _args(synthetic_train_size=512, comm_round=2)
        args = fedml_tpu.init(args, should_init_logs=False)
        trainer = CentralizedTrainer(args)
        metrics = trainer.train()
        assert metrics["test_acc"] > 0.8


class TestCrossSiloSplit:
    def test_split_preserves_all_samples(self):
        from fedml_tpu.data.data_loader_cross_silo import split_data_for_dist_trainers

        x = np.arange(100).reshape(100, 1)
        y = np.arange(100)
        shards = split_data_for_dist_trainers((x, y), 3)
        assert len(shards) == 3
        assert sum(len(sy) for _, sy in shards) == 100
        np.testing.assert_array_equal(np.concatenate([sy for _, sy in shards]), y)


class TestTCPBackend:
    def test_round_protocol_over_tcp(self):
        """1 server + 2 clients complete FedAvg rounds over raw TCP (the
        TRPC-slot backend), same protocol/topology as loopback/gRPC."""
        from test_cross_silo import _run_topology

        history = _run_topology("TRPC", "cs-tcp", comm_extra={"trpc_base_port": 29690})
        assert len(history) == 2
        assert history[-1]["test_acc"] > 0.2
