"""Host replay of the in-mesh round's packed stream, for tests that hold the
compiled round to explicit host math: the same batches in the same order
(``pack_round``), the same per-step keys (``fold_in(device key, step)``), one
client after another with the optimizer reset at each boundary."""

import jax
import numpy as np
import optax

from fedml_tpu.ml.engine.packed import pack_round
from fedml_tpu.ml.engine.train import LocalTrainResult, make_optimizer, softmax_ce_loss


def device_keys(args, round_idx, n_dev):
    """The round's per-device keys: the simulator splits its stream once a
    round and folds the round index into the sub-key."""
    rng = jax.random.PRNGKey(int(args.random_seed) + 11)
    for _ in range(round_idx + 1):
        rng, sub = jax.random.split(rng)
    return jax.random.split(jax.random.fold_in(sub, round_idx), n_dev)


def replay_clients(sim, model, args, ids, real, round_idx, w_global,
                   grad_hook=None, extras=None, counters=None):
    """One round's real clients trained on the host, in stream order:
    ``[(cid, n_i, LocalTrainResult)]``.  ``ids`` / ``real`` are what
    ``sim._schedule`` returned for the round; ``extras[cid]`` is the grad
    hook's fourth argument.  Models with ``params`` alone.  ``counters``: a
    dict that gets, by name, the stream's sums of what the module sows a step
    into its ``counters`` collection."""
    assert set(w_global) == {"params"}
    counts = np.where(real > 0, np.asarray(sim.client_counts)[ids], 0)
    ids2d = np.asarray(ids).reshape(sim.n_dev, sim.slots)
    sched = pack_round(
        ids2d, counts.reshape(sim.n_dev, sim.slots), lambda cid: sim._client_rows[cid],
        sim.batch_size, int(args.epochs), int(args.random_seed), round_idx, sim.s_max)
    keys = device_keys(args, round_idx, sim.n_dev)
    x_all, y_all = np.asarray(sim.x_all), np.asarray(sim.y_all)
    tx = make_optimizer(args)
    params0 = w_global["params"]

    @jax.jit
    def step(params, opt_state, bx, by, bm, key, extra):
        def loss(p):
            logits, sown = model.apply({"params": p}, bx, train=True, rngs={"dropout": key},
                                       mutable=["counters"])
            return softmax_ce_loss(logits, by, bm)[0], sown

        (lval, sown), grads = jax.value_and_grad(loss, has_aux=True)(params)
        if grad_hook is not None:
            grads = grad_hook(grads, params, params0, extra)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, lval, sown

    out = []
    for d in range(sim.n_dev):
        params, opt_state, steps, loss_sum, seen = params0, tx.init(params0), 0, 0.0, 0.0
        for s in range(int(sched.n_steps[d])):
            cid = int(ids2d[d, sched.slot[d, s]])
            bm = sched.mask[d, s]
            params, opt_state, lval, sown = step(
                params, opt_state, x_all[sched.idx[d, s]], y_all[sched.idx[d, s]], bm,
                jax.random.fold_in(keys[d], s), None if extras is None else extras[cid])
            if counters is not None:
                for path, v in jax.tree_util.tree_flatten_with_path(sown)[0]:
                    counters[path[-1].key] = counters.get(path[-1].key, 0.0) + float(v)
            steps, loss_sum, seen = steps + 1, loss_sum + float(lval) * bm.sum(), seen + bm.sum()
            if sched.boundary[d, s] > 0:
                out.append((cid, float(sched.weight[d, s]), LocalTrainResult(
                    {"params": params}, loss_sum / seen, seen, float(steps))))
                params, opt_state, steps, loss_sum, seen = params0, tx.init(params0), 0, 0.0, 0.0
    return out
