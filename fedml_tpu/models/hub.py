"""Model factory keyed on (model, dataset).

Parity with reference ``model/model_hub.py:20-85`` (``fedml.model.create``):
same model-name keys, flax modules instead of torch.  Returns an
uninitialized ``nn.Module``; parameter init happens in the trainer via
``ml.engine.train.init_variables`` (functional — no eager weights here).
"""

from __future__ import annotations

import logging
from typing import Any

import flax.linen as nn

logger = logging.getLogger(__name__)


_BF16_MODELS = {"resnet20", "resnet56", "resnet18", "resnet18_gn"}
# built from ``args.model_config`` (a published config.json's keys), which
# states its own compute dtype.  The module ``models/<name>.py`` has the
# classes ``<prefix>Config`` (with ``from_dict``) and ``<prefix>LM``
_CONFIG_MODELS = {"kimi_linear": "KimiLinear", "smallthinker": "SmallThinker",
                  "glm4_moe_lite": "Glm4MoeLite", "sdar_moe": "SdarMoe",
                  "nemotron_h": "NemotronH"}


def create(args: Any, output_dim: int) -> nn.Module:
    name = str(getattr(args, "model", "lr")).lower()
    dataset = str(getattr(args, "dataset", "")).lower()

    import jax.numpy as jnp

    if name in _CONFIG_MODELS:
        import importlib

        from .expert_lm import load_config

        config = getattr(args, "model_config", None)
        if config is None:
            raise ValueError(f"model {name!r} is built from model_config (a dict or a JSON "
                             "file with the published config.json's keys); none was given")
        module, prefix = importlib.import_module(f"{__package__}.{name}"), _CONFIG_MODELS[name]
        return getattr(module, prefix + "LM")(
            getattr(module, prefix + "Config").from_dict(load_config(config)))
    if _dtype(args) is not jnp.float32 and name not in _BF16_MODELS:
        logger.warning(
            "compute_dtype=%s is only plumbed into %s; model %r runs fp32",
            getattr(args, "compute_dtype", None), sorted(_BF16_MODELS), name,
        )

    if name in ("lr", "logistic_regression"):
        from .linear import LogisticRegression

        return LogisticRegression(output_dim=output_dim)
    if name in ("cnn", "cnn_dropout"):
        from .cnn import CNN_DropOut

        return CNN_DropOut(only_digits=(output_dim <= 10), num_classes=output_dim)
    if name in ("cnn_web",):
        from .cnn import CNN_WEB

        return CNN_WEB(output_dim=output_dim)
    if name in ("resnet20",):
        from .resnet import resnet20

        return resnet20(num_classes=output_dim, norm=_norm(args), dtype=_dtype(args))
    if name in ("resnet56",):
        from .resnet import resnet56

        return resnet56(num_classes=output_dim, norm=_norm(args), dtype=_dtype(args))
    if name in ("resnet18", "resnet18_gn"):
        from .resnet import resnet18_gn

        return resnet18_gn(num_classes=output_dim, dtype=_dtype(args))
    if name in ("mobilenet", "mobilenet_v1"):
        from .mobilenet import MobileNetV1

        return MobileNetV1(num_classes=output_dim)
    if name in ("mobilenet_v3",):
        from .mobilenet import MobileNetV3Small

        return MobileNetV3Small(num_classes=output_dim)
    if name in ("rnn", "rnn_fedavg", "rnn_originalfedavg"):
        from .rnn import RNN_OriginalFedAvg

        return RNN_OriginalFedAvg(vocab_size=max(output_dim, 90))
    if name in ("rnn_fedshakespeare",):
        from .rnn import RNN_FedShakespeare

        return RNN_FedShakespeare(vocab_size=max(output_dim, 90))
    if name in ("rnn_stackoverflow", "rnn_nwp"):
        from .rnn import RNN_StackOverFlow

        return RNN_StackOverFlow(vocab_size=output_dim)
    if name in ("lstm", "lstm_tagpred"):
        from .rnn import RNN_OriginalFedAvg

        return RNN_OriginalFedAvg(vocab_size=max(output_dim, 90))
    if name in ("transformer", "fedtransformer"):
        from .transformer import TransformerLM, TransformerConfig

        return TransformerLM(TransformerConfig(vocab_size=max(output_dim, 256)))
    if name in ("vgg11", "vgg16"):
        from .vgg import VGG

        return VGG(num_classes=output_dim, depth=int(name[3:]))
    if name in ("gan", "mnist_gan"):
        from .gan import MNISTGenerator

        return MNISTGenerator()
    if name in ("unet", "deeplabv3", "deeplabv3_plus"):
        from .unet import UNet

        return UNet(num_classes=output_dim)
    if name in ("gkt_client", "resnet8_gkt"):
        from .gkt import GKTClientNet

        return GKTClientNet(num_classes=output_dim)
    if name in ("gkt_server", "resnet55_gkt"):
        from .gkt import GKTServerNet

        return GKTServerNet(num_classes=output_dim)
    if name in ("darts", "darts_network"):
        from .darts import DARTSNetwork

        return DARTSNetwork(num_classes=output_dim)
    if name in ("transformer_cls", "bert_cls", "distilbert"):
        from ..data.data_loader import DATASET_SPECS
        from .nlp import TransformerClassifier

        vocab = int(DATASET_SPECS.get(dataset, {}).get("vocab", 2000))
        return TransformerClassifier(num_classes=output_dim, vocab_size=vocab)
    if name in ("transformer_tagger", "bert_tagger"):
        from ..data.data_loader import DATASET_SPECS
        from .nlp import TransformerTagger

        vocab = int(DATASET_SPECS.get(dataset, {}).get("vocab", 2000))
        return TransformerTagger(num_tags=output_dim, vocab_size=vocab)
    if name in ("transformer_span", "bert_qa"):
        from ..data.data_loader import DATASET_SPECS
        from .nlp import TransformerSpanExtractor

        vocab = int(DATASET_SPECS.get(dataset, {}).get("vocab", 200))
        # compact head: at CI data scales a wide encoder memorizes spans
        # instead of learning the extraction rule
        return TransformerSpanExtractor(vocab_size=vocab, d_model=48, d_ff=96)
    if name in ("tiny_detector", "yolo_lite"):
        from .detection import TinyDetector

        return TinyDetector(num_classes=output_dim)
    if name in ("gcn", "graphsage", "gat"):
        from ..data.data_loader import DATASET_SPECS

        from .gcn import GCN

        feat_dim = int(DATASET_SPECS.get(dataset, {}).get("feat_dim", 8))
        return GCN(num_classes=output_dim, feat_dim=feat_dim)
    if name in ("gcn_linkpred", "gcn_link_pred"):
        from ..data.data_loader import DATASET_SPECS
        from .gcn import GCNLinkPred

        feat_dim = int(DATASET_SPECS.get(dataset, {}).get("feat_dim", 8))
        return GCNLinkPred(feat_dim=feat_dim)
    if name in ("gcn_nodeclf", "gcn_node"):
        from ..data.data_loader import DATASET_SPECS
        from .gcn import GCNNodeClassifier

        feat_dim = int(DATASET_SPECS.get(dataset, {}).get("feat_dim", 8))
        return GCNNodeClassifier(num_classes=output_dim, feat_dim=feat_dim)
    if name in ("gcn_reg", "gcn_regressor"):
        from ..data.data_loader import DATASET_SPECS
        from .gcn import GCNRegressor

        feat_dim = int(DATASET_SPECS.get(dataset, {}).get("feat_dim", 8))
        return GCNRegressor(feat_dim=feat_dim)
    if name in ("gcn_mtl", "gcn_multitask"):
        from ..data.data_loader import DATASET_SPECS
        from .gcn import GCN

        spec = DATASET_SPECS.get(dataset, {})
        feat_dim = int(spec.get("feat_dim", 8))
        return GCN(num_classes=int(spec.get("num_tasks", output_dim)), feat_dim=feat_dim)
    if name in ("autoencoder", "ae", "anomaly_ae"):
        from ..data.data_loader import DATASET_SPECS
        from .autoencoder import AutoEncoder

        feat = int(DATASET_SPECS.get(dataset, {}).get("shape", (24,))[0])
        return AutoEncoder(feat_dim=feat)
    if name in ("transformer_s2s", "bart_s2s", "seq2seq"):
        from ..data.data_loader import DATASET_SPECS
        from .transformer import TransformerConfig, TransformerLM

        vocab = int(DATASET_SPECS.get(dataset, {}).get("vocab", max(output_dim, 64)))
        # causal decoder-only over [src ‖ SEP ‖ tgt] — the TPU-first seq2seq
        # (reference app/fednlp/seq2seq uses encoder-decoder BART; the task
        # contract is identical with loss masked to target positions)
        return TransformerLM(TransformerConfig(
            vocab_size=vocab, d_model=128, n_heads=4, n_layers=2, d_ff=256,
        ))
    if name in ("mlp",):
        from .linear import MLP

        return MLP(output_dim=output_dim)
    if name in ("efficientnet", "efficientnet_b0"):
        from .efficientnet import EfficientNet

        return EfficientNet(num_classes=output_dim)
    raise ValueError(f"unknown model {name!r} for dataset {dataset!r}")


def _norm(args: Any) -> str:
    return str(getattr(args, "model_norm", "gn")).lower()


def _parse_dtype(name: str, arg_name: str):
    """One dtype-string table for every dtype knob (compute/storage)."""
    import jax.numpy as jnp

    if name in ("fp32", "float32"):
        return jnp.float32
    if name in ("bf16", "bfloat16"):
        return jnp.bfloat16
    raise ValueError(f"unknown {arg_name} {name!r} (use fp32 or bf16)")


def _dtype(args: Any):
    """Compute dtype from ``args.compute_dtype`` — 'bf16' runs activations
    and MXU passes in bfloat16 while parameters stay fp32 (mixed precision:
    halves HBM traffic on the usual bandwidth-bound TPU regime)."""
    return _parse_dtype(
        str(getattr(args, "compute_dtype", "fp32") or "fp32").lower(), "compute_dtype"
    )


def data_storage_dtype(args: Any, module: Any = None):
    """HBM storage dtype for the simulator's packed dataset (fed_sim
    _pack_data).  The per-step row gather from the HBM-resident dataset is
    the measured #1 cost of the compiled round (PERF.md term 1) and it is
    bandwidth-bound, so the stored element width IS the gather cost.  When
    the model's entry cast sends the batch to bf16 anyway (compute_dtype
    bf16 + a model that plumbs it), storing bf16 halves that traffic with
    bitwise-identical model input: bf16(gather(fp32_x)) == gather(bf16_x).
    ``args.xla_data_dtype`` in {auto, fp32, bf16} overrides; 'auto' (default)
    applies exactly the condition under which the numerics cannot change."""
    import jax.numpy as jnp

    req = str(getattr(args, "xla_data_dtype", "auto") or "auto").lower()
    if req != "auto":
        return _parse_dtype(req, "xla_data_dtype")
    name = str(getattr(args, "model", "lr")).lower()
    if _dtype(args) is not jnp.bfloat16 or name not in _BF16_MODELS:
        return jnp.float32
    # key the guarantee off the ACTUAL module in use, not just the config
    # name: a user-supplied custom module (FedMLRunner accepts any flax
    # module) has no hub-made entry-cast promise — only downcast when the
    # module itself declares bf16 compute (the hub models' dtype field)
    if module is not None and getattr(module, "dtype", None) is not jnp.bfloat16:
        return jnp.float32
    return jnp.bfloat16
