"""Functional training engine: jitted local-training and eval closures.

This is the TPU-native replacement for the reference's eager per-batch torch
loops (``ml/trainer/my_model_trainer_classification.py:15-137``).  Local
training is ONE compiled XLA program: ``lax.scan`` over epochs, nested scan
over steps, per-epoch on-device shuffling, padding masked out of the loss.
The same compiled function serves every client with the same padded shape —
no per-client recompiles (the shape-bucketing that makes FL's ragged clients
XLA-friendly, cf. SURVEY.md §7 "hard parts").

Model state convention: a flax ``variables`` dict ``{"params": ...,
["batch_stats": ...]}``.  Both collections are aggregated by FedAvg (matching
torch ``state_dict`` averaging, which includes BN running stats).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import optax

Pytree = Any


# the rng stream ``noise`` of a module that owns its loss: the step's key folded
# with this (build_loss_fn); its evaluation's one key is ``PRNGKey(0)`` folded alike
NOISE_STREAM = 0xBD


class LocalTrainResult(NamedTuple):
    variables: Pytree
    loss: jnp.ndarray  # mean masked loss over the run
    seen: jnp.ndarray  # number of (valid) samples processed
    steps: Any = 0.0  # effective optimizer steps (FedNova tau_i)


def make_optimizer(args) -> optax.GradientTransformation:
    """Client optimizer factory (reference trainer's SGD/Adam switch)."""
    name = str(getattr(args, "client_optimizer", "sgd")).lower()
    lr = float(getattr(args, "learning_rate", 0.01))
    wd = float(getattr(args, "weight_decay", 0.0))
    momentum = float(getattr(args, "momentum", 0.0))
    if name == "sgd":
        tx = optax.sgd(lr, momentum=momentum if momentum > 0 else None)
    elif name == "adam":
        tx = optax.adam(lr)
    elif name == "adamw":
        tx = optax.adamw(lr, weight_decay=wd)
    else:
        raise ValueError(f"unknown client_optimizer {name!r}")
    if wd > 0 and name in ("sgd", "adam"):
        tx = optax.chain(optax.add_decayed_weights(wd), tx)
    return tx


def softmax_ce_loss(logits: jnp.ndarray, labels: jnp.ndarray, mask: jnp.ndarray):
    """Masked CE.  Handles both [B] labels and [B, L] per-token labels (NWP):
    a per-example mask [B] broadcasts over trailing label axes.  Logits are
    promoted to fp32 so bf16 compute mode keeps a stable softmax."""
    per = optax.softmax_cross_entropy_with_integer_labels(
        logits.astype(jnp.float32), labels
    )
    mask = mask.reshape(mask.shape + (1,) * (per.ndim - mask.ndim))
    total = jnp.sum(per * mask)
    count = jnp.maximum(jnp.sum(jnp.broadcast_to(mask, per.shape)), 1.0)
    return total / count, (total, count)


def sigmoid_bce_loss(logits: jnp.ndarray, labels: jnp.ndarray, mask: jnp.ndarray):
    """Masked multi-label BCE: labels are multi-hot [B, C] floats (tag
    prediction); per-example mask [B] broadcasts over label positions."""
    per = optax.sigmoid_binary_cross_entropy(logits.astype(jnp.float32), labels)
    mask = mask.reshape(mask.shape + (1,) * (per.ndim - mask.ndim))
    total = jnp.sum(per * mask)
    count = jnp.maximum(jnp.sum(jnp.broadcast_to(mask, per.shape)), 1.0)
    return total / count, (total, count)


def span_ce_loss(logits: jnp.ndarray, labels: jnp.ndarray, mask: jnp.ndarray):
    """Span extraction: logits [B, L, 2], labels [B, 2] = (start, end);
    CE over sequence positions for each endpoint (reference
    app/fednlp/span_extraction QA loss)."""
    start, end = logits[..., 0], logits[..., 1]
    per = optax.softmax_cross_entropy_with_integer_labels(
        start.astype(jnp.float32), labels[:, 0]
    ) + optax.softmax_cross_entropy_with_integer_labels(
        end.astype(jnp.float32), labels[:, 1]
    )
    total = jnp.sum(per * mask)
    count = jnp.maximum(jnp.sum(mask), 1.0)
    return total / count, (total, count)


def detection_loss(logits: jnp.ndarray, labels: jnp.ndarray, mask: jnp.ndarray,
                   box_weight: float = 5.0):
    """Single-object detection: logits [B, C+4] (class logits ‖ box),
    labels [B, 5] = (class, cx, cy, w, h) — CE + weighted smooth-L1 on the
    box (reference app/fedcv/object_detection composite loss shape)."""
    n_cls = logits.shape[-1] - 4
    cls_logits = logits[:, :n_cls].astype(jnp.float32)
    box = logits[:, n_cls:].astype(jnp.float32)
    per_cls = optax.softmax_cross_entropy_with_integer_labels(
        cls_logits, labels[:, 0].astype(jnp.int32)
    )
    diff = jnp.abs(box - labels[:, 1:])
    per_box = jnp.sum(jnp.where(diff < 1.0, 0.5 * diff * diff, diff - 0.5), axis=-1)
    per = per_cls + box_weight * per_box
    total = jnp.sum(per * mask)
    count = jnp.maximum(jnp.sum(mask), 1.0)
    return total / count, (total, count)


def seq2seq_ce_loss(logits: jnp.ndarray, labels: jnp.ndarray, mask: jnp.ndarray):
    """Seq2seq teacher-forced CE (reference app/fednlp/seq2seq, BART-style):
    logits [B, L, V] from a causal LM over the packed [src ‖ SEP ‖ tgt]
    sequence; labels [B, L] int with -1 marking non-target positions (the
    whole source prefix).  Per-token CE over target positions only."""
    tok_mask = (labels >= 0).astype(jnp.float32)
    per = optax.softmax_cross_entropy_with_integer_labels(
        logits.astype(jnp.float32), jnp.maximum(labels, 0)
    )
    mask = mask.reshape(mask.shape + (1,) * (per.ndim - mask.ndim))
    full = tok_mask * mask
    total = jnp.sum(per * full)
    count = jnp.maximum(jnp.sum(full), 1.0)
    return total / count, (total, count)


def masked_sentinel_bce_loss(logits: jnp.ndarray, labels: jnp.ndarray, mask: jnp.ndarray):
    """BCE over labeled entries only, with -1 sentinels marking unlabeled
    positions.  Serves both link prediction ("linkpred": [B, N, N] pairwise
    scores, labeled = held-out positives + sampled negatives — reference
    app/fedgraphnn ego_networks/recsys_subgraph link_pred) and multi-task
    property prediction with partial labels ("mtl_bce": [B, T] task logits,
    the SpreadGNN / moleculenet setting)."""
    labeled = (labels >= 0).astype(jnp.float32)
    per = optax.sigmoid_binary_cross_entropy(
        logits.astype(jnp.float32), jnp.maximum(labels, 0.0)
    )
    mask = mask.reshape(mask.shape + (1,) * (per.ndim - mask.ndim))
    full = labeled * mask
    total = jnp.sum(per * full)
    count = jnp.maximum(jnp.sum(full), 1.0)
    return total / count, (total, count)


def mse_loss(logits: jnp.ndarray, labels: jnp.ndarray, mask: jnp.ndarray):
    """Masked mean-squared error (reconstruction training — the IoT
    anomaly-detection autoencoder family, reference
    ``iot/anomaly_detection_for_cybersecurity``): labels are the
    regression/reconstruction targets, same shape as logits."""
    per = jnp.mean(
        jnp.square(logits.astype(jnp.float32) - labels.astype(jnp.float32)),
        axis=tuple(range(1, logits.ndim)),
    )
    mask = mask.astype(jnp.float32)
    total = jnp.sum(per * mask)
    count = jnp.maximum(jnp.sum(mask), 1.0)
    return total / count, (total, count)


LOSS_FNS = {"ce": softmax_ce_loss, "bce": sigmoid_bce_loss,
            "span": span_ce_loss, "det": detection_loss,
            "s2s": seq2seq_ce_loss, "linkpred": masked_sentinel_bce_loss,
            "mtl_bce": masked_sentinel_bce_loss, "mse": mse_loss}


def resolve_grad_hook(args, grad_hook: Optional[Callable]) -> Optional[Callable]:
    """Shared grad-hook resolution for both the padded and packed engines:
    an explicit hook wins; otherwise ``args.proximal_mu`` > 0 installs the
    FedProx hook (g + mu*(p - anchor))."""
    mu = float(getattr(args, "proximal_mu", 0.0) or 0.0)
    if grad_hook is None and mu > 0:
        def grad_hook(grads, params, anchor, extra):
            return jax.tree_util.tree_map(
                lambda g, p, a: g + mu * (p - a), grads, params, anchor
            )
    return grad_hook


def build_loss_fn(module, has_dropout: bool = True, loss: str = "ce",
                  counters: tuple = ()) -> Callable:
    """Shared masked-loss closure for both engines: applies the module with
    any mutable (non-param) collections threaded through, returns
    ``(loss_val, updated_collections)``.

    ``counters``: names the module sows into its ``counters`` collection in a
    training step (``module.round_counters``).  Given any, the closure returns
    ``(loss_val, (updated_collections, {name: sum over the layers that sowed
    it}))``: numbers that come out of the compiled step beside the loss and
    are no part of the model's state.

    A module that ``takes_targets`` (a decoder with a multi-token-prediction
    module, ``models/expert_lm.py``) trains more than its logits: it is handed
    ``targets=(labels, row mask)``, sows each further weighted loss term into
    its ``losses`` collection, and the step trains ``loss_kind(logits) + their
    sum``, which is the ``loss_val`` returned.  Where ``counters`` names
    ``lm.loss_main`` it is filled with ``loss_kind(logits)`` alone.

    A module class that ``owns_loss`` (block-diffusion training,
    ``models/sdar_moe.py``) returns its objective, given ``targets``: that is
    the step's loss and the engine adds no term of its own.  Whatever
    ``has_dropout`` says, it gets the rng stream ``noise``, whose key is
    ``jax.random.fold_in(rng, NOISE_STREAM)`` of the step's ``rng`` (the packed
    round's ``fold_in(device key, stream step)``, ``ml/engine/packed.py``)."""
    loss_kind = LOSS_FNS[loss]
    owns_loss = bool(getattr(module, "owns_loss", False))
    takes_targets = bool(getattr(module, "takes_targets", False))

    def loss_fn(params, other_vars, bx, by, bmask, rng):
        variables = dict(other_vars, params=params)
        mutable = ([k for k in other_vars.keys()] + (["counters"] if counters else [])
                   + (["losses"] if takes_targets and not owns_loss else []))
        rngs = {"dropout": rng} if has_dropout else None
        if owns_loss:
            rngs = dict(rngs or {}, noise=jax.random.fold_in(rng, NOISE_STREAM))
        targets = {"targets": (by, bmask)} if takes_targets else {}
        if mutable:
            logits, updated = module.apply(
                variables, bx, train=True, rngs=rngs, mutable=mutable, **targets
            )
        else:
            logits = module.apply(variables, bx, train=True, rngs=rngs, **targets)
            updated = {}
        updated = dict(updated)
        with jax.named_scope("fed.loss"):
            if owns_loss:  # the module's own objective, computed under this scope there
                loss_val = main = logits
            else:
                loss_val = main = loss_kind(logits, by, bmask)[0]
            if takes_targets and not owns_loss:
                loss_val = main + sum(jax.tree_util.tree_leaves(updated.pop("losses", {})))
        if not counters:
            return loss_val, updated
        sown = jax.tree_util.tree_flatten_with_path(updated.pop("counters", {}))[0]
        sums = {name: sum((v for path, v in sown if path[-1].key == name),
                          jnp.zeros((), jnp.float32)) for name in counters}
        if "lm.loss_main" in sums:
            sums["lm.loss_main"] = main
        return loss_val, (updated, sums)

    return loss_fn


def make_local_train_fn(
    module,
    args,
    batch_size: int,
    padded_n: int,
    epochs: Optional[int] = None,
    has_dropout: bool = True,
) -> Callable[[Pytree, jnp.ndarray, jnp.ndarray, jnp.ndarray, jax.Array], LocalTrainResult]:
    """Jitted local-training closure (see :func:`build_local_train`)."""
    return jax.jit(build_local_train(module, args, batch_size, padded_n, epochs, has_dropout))


def build_local_train(
    module,
    args,
    batch_size: int,
    padded_n: int,
    epochs: Optional[int] = None,
    has_dropout: bool = True,
    grad_hook: Optional[Callable] = None,
    loss: str = "ce",
) -> Callable[..., LocalTrainResult]:
    """Build the PURE local-training function (not jitted — composable inside
    shard_map/scan in the XLA simulator).

    Returned fn: ``(variables, x [padded_n,...], y [padded_n], n_valid, rng,
    extra=None) -> LocalTrainResult``.  Data must be valid-first; indices >=
    n_valid are padding and masked out of loss/gradients.

    ``grad_hook(grads, params, anchor, extra) -> grads`` runs per step, where
    ``anchor`` is the round-start params.  This one hook expresses the local
    variants of the algorithm zoo: FedProx (g + mu*(p - anchor)), SCAFFOLD
    (g - c_i + c from ``extra``), FedDyn (g - h_i + alpha*(p - anchor)) —
    cf. reference fedprox/fednova trainer subclasses (SURVEY.md §2.5).
    ``args.proximal_mu`` > 0 installs the FedProx hook automatically.
    """
    tx = make_optimizer(args)
    epochs = int(epochs if epochs is not None else getattr(args, "epochs", 1))
    steps_per_epoch = max(1, -(-padded_n // batch_size))

    grad_hook = resolve_grad_hook(args, grad_hook)
    loss_fn = build_loss_fn(module, has_dropout, loss)

    def train(variables, x, y, n_valid, rng, extra=None) -> LocalTrainResult:
        params = variables["params"]
        anchor = params
        other = {k: v for k, v in variables.items() if k != "params"}
        opt_state = tx.init(params)
        n_valid = jnp.asarray(n_valid, jnp.int32)

        def epoch_body(carry, ek):
            params, other, opt_state, loss_sum, cnt_sum, step_cnt = carry
            perm = jax.random.permutation(jax.random.fold_in(ek, 0), padded_n)

            def step_body(c, sk_i):
                params, other, opt_state, lsum, csum, scnt = c
                sk, i = sk_i
                idx = jax.lax.dynamic_slice_in_dim(perm, i * batch_size, batch_size)
                bx = jnp.take(x, idx, axis=0)
                by = jnp.take(y, idx, axis=0)
                bmask = (idx < n_valid).astype(jnp.float32)
                (loss, updated), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                    params, other, bx, by, bmask, sk
                )
                if grad_hook is not None:
                    grads = grad_hook(grads, params, anchor, extra)
                # Zero the step entirely if the batch is all padding.
                any_valid = jnp.sum(bmask) > 0
                with jax.named_scope("fed.sgd"):
                    updates, new_opt = tx.update(grads, opt_state, params)
                    new_params = optax.apply_updates(params, updates)
                params = jax.tree_util.tree_map(
                    lambda new, old: jnp.where(any_valid, new, old), new_params, params
                )
                opt_state = jax.tree_util.tree_map(
                    lambda new, old: jnp.where(any_valid, new, old), new_opt, opt_state
                )
                if updated:
                    other = jax.tree_util.tree_map(
                        lambda new, old: jnp.where(any_valid, new, old), updated, other
                    )
                scnt = scnt + any_valid.astype(jnp.float32)
                return (params, other, opt_state, lsum + loss * jnp.sum(bmask), csum + jnp.sum(bmask), scnt), None

            step_keys = jax.random.split(jax.random.fold_in(ek, 1), steps_per_epoch)
            (params, other, opt_state, loss_sum, cnt_sum, step_cnt), _ = jax.lax.scan(
                step_body,
                (params, other, opt_state, loss_sum, cnt_sum, step_cnt),
                (step_keys, jnp.arange(steps_per_epoch)),
            )
            return (params, other, opt_state, loss_sum, cnt_sum, step_cnt), None

        epoch_keys = jax.random.split(rng, epochs)
        (params, other, opt_state, loss_sum, cnt_sum, step_cnt), _ = jax.lax.scan(
            epoch_body, (params, other, opt_state, 0.0, 0.0, 0.0), epoch_keys
        )
        out_vars = dict(other, params=params)
        return LocalTrainResult(
            out_vars, loss_sum / jnp.maximum(cnt_sum, 1.0), cnt_sum, step_cnt
        )

    return train


def make_eval_fn(module) -> Callable:
    """Jitted masked eval: ``(variables, x, y, mask) -> (loss_sum, correct, count)``.

    For a module that ``owns_loss``: its own loss under one noise key for every
    batch (``fold_in(PRNGKey(0), NOISE_STREAM)``), ``loss_sum`` that loss times the batch's rows, and
    ``correct`` the share of the masked positions whose prediction is the
    token, times the rows (so ``correct / count`` is that accuracy)."""
    if getattr(module, "owns_loss", False):
        @jax.jit
        def evaluate_own(variables, x, y, mask):
            rows = jnp.sum(mask.astype(jnp.float32))
            loss, sown = module.apply(variables, x, train=False, targets=(y, mask),
                                      rngs={"noise": jax.random.fold_in(
                                          jax.random.PRNGKey(0), NOISE_STREAM)},
                                      mutable=["counters"])
            counted = sown["counters"]
            share = counted["bd.correct"] / jnp.maximum(counted["bd.masked"], 1.0)
            return loss * rows, share * rows, rows

        return evaluate_own

    @jax.jit
    def evaluate(variables, x, y, mask):
        logits = module.apply(variables, x, train=False).astype(jnp.float32)
        per = optax.softmax_cross_entropy_with_integer_labels(logits, y)
        pred = jnp.argmax(logits, axis=-1)
        mask = mask.astype(jnp.float32)
        mask = mask.reshape(mask.shape + (1,) * (per.ndim - mask.ndim))
        full = jnp.broadcast_to(mask, per.shape)
        return (
            jnp.sum(per * full),
            jnp.sum((pred == y).astype(jnp.float32) * full),
            jnp.sum(full),
        )

    return evaluate


def pad_to(x: jnp.ndarray, n: int) -> jnp.ndarray:
    """Pad axis 0 to length n (repeat-edge padding keeps dtypes/shapes sane)."""
    if x.shape[0] >= n:
        return x[:n]
    pad = [(0, n - x.shape[0])] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, pad, mode="edge")


def init_variables(module, sample_input: jnp.ndarray, seed: int = 0) -> Pytree:
    variables = module.init(jax.random.PRNGKey(seed), sample_input, train=False)
    return dict(variables)
