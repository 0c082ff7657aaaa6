"""The in-mesh round's data layout and walk: one packed stream of batches per
device.  How a round's client data is laid out and walked is decided here
and nowhere else (``XLASimulator`` composes ``device_fn`` under shard_map).

Per-step cost on TPU is essentially independent of which client a batch
belongs to, so no client is padded to another's size:

* each client contributes ceil(n_i/B) batches per epoch (its own padding is
  at most B-1 samples), clients back-to-back;
* two nested ``lax.while_loop``s walk the stream and share no branch: the
  OUTER one walks clients and carries only what outlives a client (the
  stream position, the weighted accumulator, the algorithm's contributions,
  the per-slot outputs, the sums); the INNER one walks one client's SGD
  steps from the round-start params/optimizer (its initial carry) until
  the step it has just run carried the client's BOUNDARY; after it, once a
  client, the boundary work (weighted accumulation + algorithm contributions
  + per-slot outputs).  A step that ends no client moves nothing but its
  own work: no ``lax.cond`` whose untaken branch would copy the model;
* both trip counts are TRACED (a device's ``n_steps`` and the stream's
  ``boundary`` marks, different per device and per round) over
  statically-shaped index buffers sized for the worst case — no recompile
  when the sampled client sizes change, and devices stop after their own
  last real step (one with no step runs neither loop).

Shuffling is host-side (numpy, seeded per (seed, round, client, epoch)) since
the batch order IS the data layout here; the device does not permute.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from .train import LocalTrainResult, build_loss_fn, make_optimizer, resolve_grad_hook

Pytree = Any


class PackedSchedule(NamedTuple):
    """Per-device packed batch stream (leading axis n_dev, then S_max)."""

    idx: np.ndarray       # [n_dev, S_max, B] int32 rows into x_all/y_all
    mask: np.ndarray      # [n_dev, S_max, B] f32 valid-sample mask
    boundary: np.ndarray  # [n_dev, S_max] f32 1.0 on a client's last step
    weight: np.ndarray    # [n_dev, S_max] f32 client sample count (at boundary)
    slot: np.ndarray      # [n_dev, S_max] i32 schedule-slot of the running client
    n_steps: np.ndarray   # [n_dev] i32 real steps this round


def pack_round(
    ids2d: np.ndarray,
    counts2d: np.ndarray,
    client_rows: Callable[[int], np.ndarray],
    batch_size: int,
    epochs: int,
    seed: int,
    round_idx: int,
    s_max: int,
) -> PackedSchedule:
    """Build the packed stream for one round.

    ``ids2d``/``counts2d``: [n_dev, slots] scheduled client ids and their
    real sample counts (0 = dummy slot).  ``client_rows(cid)`` returns the
    client's row indices into the global data arrays.  Slot numbering is
    DEVICE-LOCAL (the cex/outs arrays are sharded over the client axis, so
    each device sees its own [slots, ...] shard).
    """
    n_dev, slots = ids2d.shape
    B = batch_size
    idx = np.zeros((n_dev, s_max, B), np.int32)
    mask = np.zeros((n_dev, s_max, B), np.float32)
    boundary = np.zeros((n_dev, s_max), np.float32)
    weight = np.zeros((n_dev, s_max), np.float32)
    slot = np.zeros((n_dev, s_max), np.int32)
    n_steps = np.zeros((n_dev,), np.int32)
    for d in range(n_dev):
        cursor = 0
        for ls in range(slots):
            n_i = int(counts2d[d, ls])
            if n_i <= 0:
                continue
            cid = int(ids2d[d, ls])
            rows = np.asarray(client_rows(cid))[:n_i]
            steps_per_epoch = -(-n_i // B)
            total = steps_per_epoch * epochs
            if cursor + total > s_max:
                raise ValueError(
                    f"packed stream overflow: device {d} needs {cursor + total} "
                    f"steps > s_max {s_max}"
                )
            for e in range(epochs):
                rng = np.random.default_rng((seed, round_idx, cid, e))
                perm = rng.permutation(rows)
                padded = np.resize(perm, steps_per_epoch * B)
                m = np.zeros(steps_per_epoch * B, np.float32)
                m[:n_i] = 1.0
                sl = np.s_[cursor : cursor + steps_per_epoch]
                idx[d, sl] = padded.reshape(steps_per_epoch, B)
                mask[d, sl] = m.reshape(steps_per_epoch, B)
                slot[d, sl] = ls
                cursor += steps_per_epoch
            boundary[d, cursor - 1] = 1.0
            weight[d, cursor - 1] = float(n_i)
        n_steps[d] = cursor
    return PackedSchedule(idx, mask, boundary, weight, slot, n_steps)


def s_max_for(max_client_n: int, slots: int, batch_size: int, epochs: int) -> int:
    """Static worst-case stream length per device (buffer size only — the
    traced trip count is the real length)."""
    return slots * (-(-max_client_n // batch_size)) * epochs


def trim_to_bucket(sched: PackedSchedule, s_max: int) -> PackedSchedule:
    """Cut the stream buffers to a quantized bucket of the round's longest
    stream: the upload scales with the bucket, not the global worst case.
    Quantum = s_max/8 -> at most 8 distinct shapes per run (each compiles
    once, then caches) and <= one quantum of overshoot."""
    s_used = max(int(sched.n_steps.max()), 1)
    quantum = max(1, -(-s_max // 8))
    s_bucket = min(-(-s_used // quantum) * quantum, s_max)
    return PackedSchedule(*(a[:, :s_bucket] for a in sched[:5]), sched.n_steps)


def build_packed_device_fn(
    module,
    args,
    algo,
    batch_size: int,
    slots_per_device: int,
    loss: str = "ce",
    post_train=None,
    capture_updates: bool = False,
):
    """The per-device round body (composed under shard_map by the simulator).

    Returns ``fn(variables, server_state, x_all, y_all, idx, mask, boundary,
    weight, slot, n_steps, rng, cex) -> (acc, wsum, lsum, cnt, ext, outs,
    counters)`` where cex has leading axis slots_per_device and outs matches
    it.  ``counters``: the stream's sums of what the module sows a step
    (``module.round_counters``, e.g. an expert layer's loads), ``{}`` for a
    module that names none.

    ``capture_updates``: also record each slot's final (post-``post_train``)
    variables into the per-slot output buffer — ``outs`` becomes
    ``{"algo": <algo outs>, "update": <variables tree, leading slot axis>}``.
    The security layer (stacked attacks / robust aggregation) consumes this
    stack instead of the in-stream weighted sum.
    """
    tx = make_optimizer(args)
    grad_hook = resolve_grad_hook(args, algo.grad_hook())
    counter_names = tuple(getattr(module, "round_counters", ()))
    loss_and_updated = build_loss_fn(module, True, loss, counter_names)

    from ...simulation.xla.algorithms import InMeshAlgorithm

    uses_extra = type(algo).engine_extra is not InMeshAlgorithm.engine_extra

    def device_fn(variables, server_state, x_all, y_all, idx, mask, boundary,
                  weight, slot, n_steps, rng, cex):
        params0 = variables["params"]
        other0 = {k: v for k, v in variables.items() if k != "params"}
        opt0 = tx.init(params0)

        zeros_vars = jax.tree_util.tree_map(
            lambda v: jnp.zeros_like(v, jnp.float32), variables
        )
        ext0 = algo.zero_contrib(variables)
        out_t = algo.out_template(variables)
        if capture_updates:
            # "tau": the engine's per-client step count, captured so the
            # security tail can recompute ext contributions (FedNova's tau_i)
            # from the defended stack without re-deriving step semantics
            out_t = {"algo": out_t, "update": variables, "tau": jnp.zeros(())}
        outs0 = jax.tree_util.tree_map(
            lambda t: jnp.zeros((slots_per_device,) + t.shape, jnp.float32), out_t
        )

        def local_step(step, params, other, opt_state, bx, by):
            bmask = mask[step]
            key = jax.random.fold_in(rng, step)
            (lval, updated), grads = jax.value_and_grad(
                loss_and_updated, has_aux=True
            )(params, other, bx, by, bmask, key)
            counts = {}
            if counter_names:
                updated, counts = updated
            if grad_hook is not None:
                s = slot[step]  # device-local schedule slot
                extra = None
                if uses_extra:
                    cex_i = jax.tree_util.tree_map(
                        lambda t: jax.lax.dynamic_index_in_dim(t, s, keepdims=False),
                        cex,
                    )
                    extra = algo.engine_extra(cex_i, server_state)
                grads = grad_hook(grads, params, params0, extra)
            with jax.named_scope("fed.sgd"):
                updates, new_opt = tx.update(grads, opt_state, params)
                params = optax.apply_updates(params, updates)
            # every step the loop runs holds a real row (pack_round gives an
            # epoch ceil(n_i/B) steps and the loop stops at n_steps), so
            # optimizer state and mutable collections advance unconditionally
            return (params, updated or other, new_opt, lval, bmask, counts)

        def client_step(carry):
            (step, params, other, opt_state, c_steps, c_loss, c_cnt, ctr, _) = carry
            with jax.named_scope("fed.gather"):
                bx = jnp.take(x_all, idx[step], axis=0)
                by = jnp.take(y_all, idx[step], axis=0)
            with jax.named_scope("fed.local_step"):
                params, other, opt_state, lval, bmask, counts = local_step(
                    step, params, other, opt_state, bx, by)
            valid = (jnp.sum(bmask) > 0).astype(jnp.float32)
            ctr = {n: ctr[n] + valid * counts[n] for n in ctr}
            c_steps = c_steps + valid
            c_loss = c_loss + lval * jnp.sum(bmask)
            c_cnt = c_cnt + jnp.sum(bmask)
            return (step + 1, params, other, opt_state, c_steps, c_loss, c_cnt,
                    ctr, boundary[step] > 0)

        def client(carry):
            step, acc, wsum, lsum, cnt, ext, outs, ctr = carry
            # one client: from the round-start state until the step just run
            # carried its boundary (a stream ends on one; n_steps is the guard)
            (step, params, other, _, c_steps, c_loss, c_cnt, ctr, _) = jax.lax.while_loop(
                lambda c: ~c[-1] & (c[0] < n_steps), client_step,
                (step, params0, other0, opt0, 0.0, 0.0, 0.0, ctr, jnp.bool_(False)))
            last = step - 1  # the client's boundary step
            with jax.named_scope("fed.flush"):
                w = weight[last]
                real = (w > 0).astype(jnp.float32)
                out_vars = dict(other, params=params)
                if post_train is not None:
                    # in-mesh local DP: noise this client's update at its
                    # boundary, keyed by (device rng, stream position)
                    out_vars = post_train(
                        out_vars, jax.random.fold_in(rng, last + 104729)
                    )
                result = LocalTrainResult(
                    out_vars,
                    c_loss / jnp.maximum(c_cnt, 1.0),
                    c_cnt,
                    c_steps,
                )
                s = slot[last]
                # cex feeds client_contrib/client_out for ALL algorithms
                # (uses_extra only gates the grad-hook extra, not this)
                cex_i = jax.tree_util.tree_map(
                    lambda t: jax.lax.dynamic_index_in_dim(t, s, keepdims=False), cex
                )
                acc = jax.tree_util.tree_map(
                    lambda a, p: a + w * p.astype(jnp.float32), acc, out_vars
                )
                ext = jax.tree_util.tree_map(
                    jnp.add, ext,
                    algo.client_contrib(variables, result, w, real, cex_i, server_state),
                )
                out_i = algo.client_out(variables, result, real, cex_i, server_state)
                if capture_updates:
                    out_i = {"algo": out_i, "update": out_vars, "tau": c_steps}
                outs = jax.tree_util.tree_map(
                    lambda buf, o: jax.lax.dynamic_update_index_in_dim(
                        buf, o.astype(jnp.float32), s, axis=0
                    ),
                    outs, out_i,
                )
            return (step, acc, wsum + w, lsum + c_loss, cnt + c_cnt, ext, outs, ctr)

        init = (jnp.int32(0), zeros_vars, 0.0, 0.0, 0.0, ext0, outs0,
                {n: jnp.zeros((), jnp.float32) for n in counter_names})
        _, acc, wsum, lsum, cnt, ext, outs, ctr = jax.lax.while_loop(
            lambda c: c[0] < n_steps, client, init)
        return acc, wsum, lsum, cnt, ext, outs, ctr

    return device_fn
