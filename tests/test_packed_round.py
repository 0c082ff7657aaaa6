"""The in-mesh round's packed stream (ml/engine/packed.py): ``pack_round`` and
``s_max_for`` as units (the rule ``benchmark/reference.py`` re-derives for its
feed order), and the compiled round over it: it must train to the sp oracle's
quality, walk ceil(n_i/B) steps a client and support the in-mesh algorithm
zoo."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import fedml_tpu
from fedml_tpu.arguments import Arguments
from fedml_tpu.ml.engine.packed import pack_round, s_max_for
from fedml_tpu.parallel.mesh import create_fl_mesh
from fedml_tpu.simulation.xla.fed_sim import XLASimulator

heavy = pytest.mark.heavy  # long XLA compiles; see pytest.ini


def _args(**over):
    args = Arguments.from_dict(
        {
            "common_args": {"training_type": "simulation", "random_seed": 0, "run_id": "pk"},
            "data_args": {
                "dataset": "mnist",
                "data_cache_dir": "",
                "partition_method": "hetero",
                "partition_alpha": 0.5,
                "synthetic_train_size": 1600,
            },
            "model_args": {"model": "lr"},
            "train_args": {
                "federated_optimizer": "FedAvg",
                "client_num_in_total": 16,
                "client_num_per_round": 8,
                "comm_round": 4,
                "epochs": 2,
                "batch_size": 32,
                "client_optimizer": "sgd",
                "learning_rate": 0.1,
            },
            "validation_args": {"frequency_of_the_test": 2},
            "comm_args": {"backend": "XLA"},
        }
    )
    for k, v in over.items():
        setattr(args, k, v)
    return args.validate()


def _build(args):
    args = fedml_tpu.init(args, should_init_logs=False)
    dataset, out_dim = fedml_tpu.data.load(args)
    model = fedml_tpu.models.create(args, out_dim)
    return args, dataset, model


# (name, batch, epochs, counts [n_dev, slots]; 0 = a dummy slot)
SHAPES = [
    ("batch_one", 1, 1, [[3, 2], [4, 1]]),
    ("ragged_with_a_one_sample_client", 4, 1, [[1, 9, 4], [7, 8, 5]]),
    ("two_epochs", 4, 2, [[5, 8], [3, 12]]),
    ("a_dummy_slot", 3, 1, [[6, 0], [2, 7]]),
    ("more_slots_than_clients", 2, 2, [[5, 0, 0], [0, 0, 0], [0, 3, 0]]),
]


def _packed(batch, epochs, counts, seed=7, round_idx=2, ids2d=None):
    """A schedule over clients whose rows are consecutive ranges of the
    global arrays; returns (schedule, ids2d, counts2d, rows by client id)."""
    counts2d = np.asarray(counts, np.int64)
    if ids2d is None:
        ids2d = np.arange(counts2d.size).reshape(counts2d.shape)[:, ::-1].copy()
    starts = {}
    for cid, n in sorted(zip(ids2d.ravel().tolist(), counts2d.ravel().tolist())):
        starts[cid] = (sum(m for _, m in starts.values()), n)
    rows = {cid: np.arange(a, a + n) for cid, (a, n) in starts.items()}
    s_max = s_max_for(int(counts2d.max()), counts2d.shape[1], batch, epochs)
    # the simulator's table is wider than a short client's shard
    table = lambda cid: np.concatenate([rows[cid], np.zeros(3, np.int64)])  # noqa: E731
    return (pack_round(ids2d, counts2d, table, batch, epochs, seed, round_idx, s_max),
            ids2d, counts2d, rows)


def _client_steps(sched, d, ls):
    """The steps of device ``d`` that belong to local slot ``ls``, below n_steps."""
    live = np.arange(sched.idx.shape[1]) < sched.n_steps[d]
    return np.flatnonzero(live & (sched.slot[d] == ls))


shapes = pytest.mark.parametrize("batch,epochs,counts", [s[1:] for s in SHAPES],
                                 ids=[s[0] for s in SHAPES])


class TestPackRound:
    """Pure numpy: what the compiled stream relies on, and what
    ``benchmark/reference.py`` re-derives."""

    @shapes
    def test_every_row_is_fed_once_an_epoch(self, batch, epochs, counts):
        sched, ids2d, counts2d, rows = _packed(batch, epochs, counts)
        for d, ls in np.ndindex(*counts2d.shape):
            steps = _client_steps(sched, d, ls) if counts2d[d, ls] else []
            fed = sched.idx[d, steps][sched.mask[d, steps] > 0]
            want = np.repeat(rows[int(ids2d[d, ls])], epochs) if counts2d[d, ls] else []
            assert sorted(fed.tolist()) == sorted(np.asarray(want).tolist())

    @shapes
    def test_padding_sits_only_in_an_epochs_last_batch(self, batch, epochs, counts):
        sched, _, counts2d, _ = _packed(batch, epochs, counts)
        for d, ls in np.ndindex(*counts2d.shape):
            n = int(counts2d[d, ls])
            if not n:
                continue
            m = sched.mask[d, _client_steps(sched, d, ls)]
            assert m.sum() == n * epochs
            per_epoch = m.reshape(epochs, -(-n // batch), batch)
            assert (per_epoch[:, :-1] == 1).all()
            # a last batch is real rows first, then padding
            tail = per_epoch[:, -1]
            assert (tail.sum(axis=1) == n - (-(-n // batch) - 1) * batch).all()
            assert (np.diff(tail, axis=1) <= 0).all()

    @shapes
    def test_one_boundary_a_client_on_its_last_step_with_its_weight(self, batch, epochs, counts):
        sched, _, counts2d, _ = _packed(batch, epochs, counts)
        for d in range(counts2d.shape[0]):
            marked = np.flatnonzero(sched.boundary[d])
            real = [ls for ls in range(counts2d.shape[1]) if counts2d[d, ls]]
            assert marked.tolist() == [int(_client_steps(sched, d, ls)[-1]) for ls in real]
            assert sched.weight[d, marked].tolist() == [float(counts2d[d, ls]) for ls in real]
            assert set(np.unique(sched.boundary[d])) <= {0.0, 1.0}
            assert (np.delete(sched.weight[d], marked) == 0).all()

    @shapes
    def test_step_counts_and_no_step_that_is_all_padding(self, batch, epochs, counts):
        """``n_steps`` is the sum of ceil(n_i / B) x E, a dummy slot adds no
        step, nothing is set beyond it — and every step below it holds a
        real row, which is why the compiled step advances optimizer state
        and mutable collections without a guard."""
        sched, _, counts2d, _ = _packed(batch, epochs, counts)
        want = (-(-counts2d // batch) * epochs).sum(axis=1)
        assert sched.n_steps.tolist() == want.tolist()
        for d, n in enumerate(sched.n_steps):
            assert (sched.mask[d, :n].sum(axis=1) > 0).all()
            for field in (sched.idx, sched.mask, sched.boundary, sched.weight, sched.slot):
                assert not field[d, n:].any()

    @shapes
    def test_slot_is_device_local(self, batch, epochs, counts):
        sched, _, counts2d, _ = _packed(batch, epochs, counts)
        for d, n in enumerate(sched.n_steps):
            real = [ls for ls in range(counts2d.shape[1]) if counts2d[d, ls]]
            runs = [int(ls) for i, ls in enumerate(sched.slot[d, :n])
                    if i == 0 or ls != sched.slot[d, i - 1]]
            assert runs == real  # slots in order, each one run, none of another device

    @shapes
    def test_s_max_for_bounds_the_schedule(self, batch, epochs, counts):
        sched, _, counts2d, _ = _packed(batch, epochs, counts)
        s_max = s_max_for(int(counts2d.max()), counts2d.shape[1], batch, epochs)
        assert sched.idx.shape[1] == s_max and int(sched.n_steps.max()) <= s_max
        # the bound is reached by a device whose every slot holds the largest client
        full = np.full(counts2d.shape, counts2d.max())
        assert int(_packed(batch, epochs, full)[0].n_steps.max()) == s_max

    def test_same_seed_and_round_same_arrays_another_round_another_order(self):
        _, batch, epochs, counts = SHAPES[2]
        a, b = _packed(batch, epochs, counts)[0], _packed(batch, epochs, counts)[0]
        assert all(np.array_equal(x, y) for x, y in zip(a, b))
        other = _packed(batch, epochs, counts, round_idx=3)[0]
        assert not np.array_equal(a.idx, other.idx)
        for x, y in zip(a[1:], other[1:]):  # the layout is the sizes' alone
            assert np.array_equal(x, y)
        assert not np.array_equal(a.idx, _packed(batch, epochs, counts, seed=8)[0].idx)

    def test_a_clients_order_does_not_depend_on_where_it_was_scheduled(self):
        _, batch, epochs, counts = SHAPES[1]
        a, ids_a, counts_a, _ = _packed(batch, epochs, counts)
        moved = ids_a[::-1, ::-1].copy()  # every client on the other device, another slot
        b, ids_b, counts_b, _ = _packed(batch, epochs, np.asarray(counts)[::-1, ::-1], ids2d=moved)

        def order(sched, ids2d, cid):
            d, ls = map(int, np.argwhere(ids2d == cid)[0])
            steps = _client_steps(sched, d, ls)
            return sched.idx[d, steps].tolist(), sched.mask[d, steps].tolist()

        for cid in ids_a.ravel():
            assert order(a, ids_a, cid) == order(b, ids_b, cid)

    def test_overflow_past_s_max_raises(self):
        with pytest.raises(ValueError, match="overflow"):
            pack_round(np.array([[0, 1]]), np.array([[9, 9]]), lambda cid: np.arange(9),
                       4, 1, 0, 0, s_max=5)


def _round_builder(module, args, n_dev, slots, batch, max_client_n, stacked=False):
    """The round program on a client mesh of ``n_dev`` of the CPU's devices,
    without a simulator's data upload: a bare ``XLASimulator`` with what
    ``_build_packed_round_fn`` reads."""
    from fedml_tpu.simulation.xla.algorithms import create_inmesh_algorithm

    sim = XLASimulator.__new__(XLASimulator)
    sim.args, sim.module, sim.mesh, sim.n_dev = args, module, create_fl_mesh(n_dev), n_dev
    sim.clients_per_round, sim.batch_size, sim.max_client_n = n_dev * slots, batch, max_client_n
    sim.needs_stack, sim.sharded_state = stacked, False
    sim.loss_kind, sim.algo = "ce", create_inmesh_algorithm(args)
    sim._build_packed_round_fn()
    return sim


def _compiled_and_replayed(module, x_all, y_all, batch, epochs, counts, optimizer="FedAvg",
                           capture_updates=False, round_idx=2):
    """One round over ``counts``' schedule, twice: the round program
    (``_build_packed_round_fn`` on a client mesh of ``len(counts)`` of the
    CPU's devices) and tests/packed_replay.py's host loop.  Returns (what
    the program returned, the replay's ``[(cid, n_i, LocalTrainResult)]``,
    the replay's counters, and what both started from: variables, server
    state, per-slot client extras, ids2d, the algorithm)."""
    import types

    from jax.sharding import NamedSharding, PartitionSpec as P
    from packed_replay import device_keys, replay_clients

    sched, ids2d, counts2d, rows = _packed(batch, epochs, counts, seed=0, round_idx=round_idx)
    n_dev, slots = counts2d.shape
    args = _args(federated_optimizer=optimizer, epochs=epochs, batch_size=batch,
                 client_num_in_total=counts2d.size, client_num_per_round=counts2d.size)
    sim = _round_builder(module, args, n_dev, slots, batch, int(counts2d.max()), capture_updates)
    assert (sim.slots, sim.s_max) == (slots, sched.idx.shape[1])

    variables = jax.jit(lambda key: module.init(key, x_all[:1], train=False))(
        jax.random.PRNGKey(3))
    # a server state and client extras that are not zero, so that an
    # algorithm's hook, contribution and per-slot output all show
    noise = lambda tree, seed, lead=(): jax.tree_util.tree_map(  # noqa: E731
        lambda v: 0.05 * jax.random.normal(jax.random.PRNGKey(seed), lead + v.shape), tree)
    server_state = noise(sim.algo.init_server_state(variables), 4)
    table = sim.algo.init_client_state(counts2d.size, variables)
    ids = ids2d.reshape(-1)
    real = (counts2d.reshape(-1) > 0).astype(np.float32)
    cex = sim.algo.gather_client_extras(
        None if table is None else noise(table, 5), ids, real, round_idx)

    split = NamedSharding(sim.mesh, P("client"))
    got = sim._round_fn(
        variables, server_state, jnp.asarray(x_all), jnp.asarray(y_all),
        *(jax.device_put(jnp.asarray(a), split) for a in sched),
        jax.device_put(device_keys(args, round_idx, n_dev), split), jax.device_put(cex, split))

    by_cid = dict(zip(ids.tolist(), range(len(ids))))
    host = types.SimpleNamespace(
        n_dev=n_dev, slots=slots, batch_size=batch, s_max=sim.s_max, x_all=x_all, y_all=y_all,
        client_counts=np.asarray([counts2d.reshape(-1)[by_cid[c]] for c in range(len(ids))]),
        _client_rows={cid: np.concatenate([r, np.zeros(3, np.int64)]) for cid, r in rows.items()})
    extras = None
    if sim.algo.grad_hook() is not None:
        extras = {int(c): sim.algo.engine_extra(
            jax.tree_util.tree_map(lambda t: t[by_cid[c]], cex), server_state) for c in ids}
    counters = {}
    clients = replay_clients(host, module, args, ids, real, round_idx, variables,
                             grad_hook=sim.algo.grad_hook(), extras=extras, counters=counters)
    return got, clients, counters, (variables, server_state, cex, ids2d, sim)


def _lr_data(n_rows=64):
    from fedml_tpu.models.linear import LogisticRegression

    rng = np.random.default_rng(1)
    return (LogisticRegression(10), rng.normal(size=(n_rows, 12)).astype(np.float32),
            rng.integers(0, 10, n_rows).astype(np.int32))


def _close(got, want, rtol=2e-5, atol=2e-6):
    assert jax.tree_util.tree_structure(got) == jax.tree_util.tree_structure(want)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=rtol, atol=atol),
        got, want)


class TestCompiledRoundIsTheHostReplay:
    """The round program's two loops — clients outside, a client's steps
    inside — against the host replay of the same stream: a client of one
    step, two epochs, dummy slots and a device with nothing to run."""

    @shapes
    def test_new_global_state_loss_and_per_slot_outs(self, batch, epochs, counts):
        """SCAFFOLD reads everything a client's boundary has: the grad hook's
        extra a step, ``client_contrib``, ``client_out`` and its step count."""
        got, clients, _, (variables, c, cex, ids2d, sim) = _compiled_and_replayed(
            *_lr_data(), batch, epochs, counts, optimizer="SCAFFOLD")
        new_global, new_c, mean_loss, outs = got
        slot_of = {int(cid): i for i, cid in enumerate(ids2d.reshape(-1))}
        acc = jax.tree_util.tree_map(jnp.zeros_like, variables)
        ext = sim.algo.zero_contrib(variables)
        want_outs = jax.tree_util.tree_map(np.zeros_like, jax.tree_util.tree_map(np.asarray, outs))
        for cid, n_i, result in clients:
            cex_i = jax.tree_util.tree_map(lambda t: t[slot_of[cid]], cex)
            acc = jax.tree_util.tree_map(lambda a, p: a + n_i * p, acc, result.variables)
            ext = jax.tree_util.tree_map(
                jnp.add, ext, sim.algo.client_contrib(variables, result, n_i, 1.0, cex_i, c))
            out_i = sim.algo.client_out(variables, result, 1.0, cex_i, c)
            for buf, o in zip(jax.tree_util.tree_leaves(want_outs), jax.tree_util.tree_leaves(out_i)):
                buf[slot_of[cid]] = np.asarray(o)
        wsum = sum(n_i for _, n_i, _ in clients)
        assert wsum == float(np.sum(counts))
        want_global, want_c = sim.algo.server_update(acc, wsum, ext, variables, c)
        _close(new_global, want_global)
        _close(new_c, want_c)
        _close(outs, want_outs)  # a dummy slot's stays zero
        seen = sum(float(r.seen) for _, _, r in clients)
        assert seen == float(np.sum(counts)) * epochs
        np.testing.assert_allclose(
            float(mean_loss), sum(float(r.loss) * float(r.seen) for _, _, r in clients) / seen,
            rtol=2e-5)
        # the walk itself: ceil(n_i / B) steps an epoch, a one-step client among them
        assert ([float(r.steps) for _, _, r in clients]
                == [float(-(-int(n) // batch) * epochs) for n in np.ravel(counts) if n])

    @shapes
    def test_captured_updates_and_tau_of_each_slot(self, batch, epochs, counts):
        """The defended round's stack: a slot's ``update`` is its client's
        final variables, ``tau`` its step count, a dummy slot's both zero."""
        got, clients, _, (variables, _, _, ids2d, sim) = _compiled_and_replayed(
            *_lr_data(), batch, epochs, counts, capture_updates=True)
        mean_loss, outs, ext = got
        slot_of = {int(cid): i for i, cid in enumerate(ids2d.reshape(-1))}
        want = jax.tree_util.tree_map(np.zeros_like, jax.tree_util.tree_map(np.asarray, outs))
        for cid, _, result in clients:
            want["tau"][slot_of[cid]] = float(result.steps)
            for buf, v in zip(jax.tree_util.tree_leaves(want["update"]),
                              jax.tree_util.tree_leaves(result.variables)):
                buf[slot_of[cid]] = np.asarray(v)
        assert float(np.sum(want["tau"])) == float(sum(-(-n // batch) * epochs for n in np.ravel(counts)))
        _close(outs, want)
        assert np.isfinite(float(mean_loss)) and float(ext) == 0.0

    def test_a_device_with_nothing_to_run_adds_nothing(self):
        """``n_steps == 0``: neither loop runs, the device hands back zeros."""
        from fedml_tpu.ml.engine.packed import build_packed_device_fn
        from fedml_tpu.simulation.xla.algorithms import create_inmesh_algorithm

        _, batch, epochs, counts = SHAPES[4]
        sched = _packed(batch, epochs, counts)[0]
        d = int(np.flatnonzero(sched.n_steps == 0)[0])
        module, x_all, y_all = _lr_data()
        args = _args(epochs=epochs, batch_size=batch)
        fn = jax.jit(build_packed_device_fn(
            module, args, create_inmesh_algorithm(args), batch, len(counts[0]), capture_updates=True))
        variables = module.init(jax.random.PRNGKey(3), x_all[:1], train=False)
        acc, wsum, lsum, cnt, ext, outs, counters = fn(
            variables, (), jnp.asarray(x_all), jnp.asarray(y_all), *(a[d] for a in sched),
            jax.random.PRNGKey(0), jnp.zeros(len(counts[0])))
        assert (float(wsum), float(lsum), float(cnt), float(ext), counters) == (0.0, 0.0, 0.0, 0.0, {})
        for leaf in jax.tree_util.tree_leaves((acc, outs)):
            assert not np.asarray(leaf).any()

    def test_a_modules_round_counters_are_the_replays(self):
        """The tiny ``kimi_linear`` preset names ``round_counters``: the sums
        that come out of the round beside the loss are the host loop's."""
        import os

        import benchmark

        config = os.path.join(os.path.dirname(benchmark.__file__), "configs", "tiny-kimi-linear.json")
        args = Arguments.from_dict({"model_args": {"model": "kimi_linear", "model_config": config}})
        module = fedml_tpu.models.create(args.validate(for_training=False), 64)
        rng = np.random.default_rng(3)
        x_all, y_all = (rng.integers(0, 64, (12, 24)).astype(np.int32) for _ in range(2))
        _, batch, epochs, counts = SHAPES[0]
        got, clients, counters, (variables, *_) = _compiled_and_replayed(
            module, x_all, y_all, batch, epochs, counts)
        new_global, _, mean_loss, _, counted = got
        assert set(counted) == set(module.round_counters) == set(counters)
        steps = int(np.sum(counts))  # batch 1, one epoch
        assert float(counted["moe.assignments_total"]) == counters["moe.assignments_total"]
        assert counters["moe.assignments_total"] % steps == 0 and counters["moe.assignments_total"] > 0
        assert float(counted["moe.assignments_dropped"]) == counters["moe.assignments_dropped"] == 0.0
        # where the assignments went is the router's: a token whose scores part
        # by less than the two programs' rounding changes expert (one in a
        # thousand here on most seeds), and its expert's update with it
        for name in module.round_counters:
            np.testing.assert_allclose(float(counted[name]), counters[name], rtol=1e-2)
        wsum = sum(n_i for _, n_i, _ in clients)
        want = jax.tree_util.tree_map(
            lambda *ps: sum(n_i * p for (_, n_i, _), p in zip(clients, ps)) / wsum,
            *(r.variables for _, _, r in clients))

        def distance(a, b):
            return float(np.sqrt(sum(np.sum((np.asarray(x) - np.asarray(y)) ** 2) for x, y in zip(
                jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)))))

        assert distance(new_global, want) < 0.1 * distance(variables, want)
        seen = sum(float(r.seen) for _, _, r in clients)
        np.testing.assert_allclose(
            float(mean_loss), sum(float(r.loss) * float(r.seen) for _, _, r in clients) / seen,
            rtol=1e-3)

    def test_the_lowered_round_is_two_nested_loops_and_no_branch(self):
        """No ``lax.cond`` in the stream: a step that ends no client moves
        nothing but its own work.  The three scopes still name operations."""
        _, batch, epochs, counts = SHAPES[1]
        sched = _packed(batch, epochs, counts)[0]
        module, x_all, y_all = _lr_data()
        sim = _round_builder(module, _args(epochs=epochs, batch_size=batch), 2, 3, batch, 9)
        variables = module.init(jax.random.PRNGKey(3), x_all[:1], train=False)
        lowered = sim._round_fn.lower(
            variables, (), jnp.asarray(x_all), jnp.asarray(y_all), *(jnp.asarray(a) for a in sched),
            jax.random.split(jax.random.PRNGKey(0), 2), jnp.zeros(6))
        text = lowered.as_text()
        assert "stablehlo.if" not in text and "stablehlo.case" not in text
        # the round itself (threefry's rounds are a loop in a function of their own)
        main = text[text.index("func.func public @main"):].split("func.func private")[0]
        at = [i for i in range(len(main)) if main.startswith("stablehlo.while", i)]
        assert len(at) == 2
        depth = [main[:i].count("{") - main[:i].count("}") for i in at]
        assert depth[1] > depth[0]  # the second opens inside the first's body
        named = lowered.as_text(debug_info=True)
        for scope in ("fed.flush", "fed.gather", "fed.local_step"):
            assert scope in named, scope


class TestStreamCounters:
    def test_round_log_and_registry_carry_the_schedules_sums(self, monkeypatch):
        """``round.local_steps`` and ``round.client_boundaries``: how often
        the inner loop runs and how often it ends a client, from the schedule
        the host has just packed."""
        from fedml_tpu.core import obs
        from fedml_tpu.simulation.xla import fed_sim

        packed = []

        def spy(*a, **k):
            packed.append(pack_round(*a, **k))
            return packed[-1]

        monkeypatch.setattr(fed_sim, "pack_round", spy)

        def totals():
            return {r["metric"]: r["value"] for r in obs.registry().export()
                    if r["metric"] in ("round.local_steps", "round.client_boundaries")}

        args, dataset, model = _build(_args(comm_round=2))
        sim = XLASimulator(args, dataset, model)
        before = totals()
        sim.train()
        assert len(packed) == len(sim.round_log) == 2
        for rec, sched in zip(sim.round_log, packed):
            assert rec["round.local_steps"] == float(sched.n_steps.sum()) > 0
            live = np.arange(sched.boundary.shape[1])[None, :] < sched.n_steps[:, None]
            assert rec["round.client_boundaries"] == float(sched.boundary[live].sum())
            assert 0 < rec["round.client_boundaries"] <= int(args.client_num_per_round)
            assert rec["round.client_boundaries"] < rec["round.local_steps"]  # two epochs
        for name, total in totals().items():
            assert total - before.get(name, 0.0) == sum(rec[name] for rec in sim.round_log)
        assert set(totals()) == {"round.local_steps", "round.client_boundaries"}


@heavy
class TestPackedRound:
    def test_learns_on_8dev_mesh(self):
        args, dataset, model = _build(_args())
        sim = XLASimulator(args, dataset, model)
        metrics = sim.train()
        assert metrics["test_acc"] > 0.5

    def test_matches_sp_fedavg_quality(self):
        """The stream and the sp FedAvg oracle shuffle differently, so
        results differ bitwise, but trained quality must match closely."""
        from fedml_tpu.simulation.simulator import create_simulator

        args_p, dataset, model = _build(_args())
        m_packed = XLASimulator(args_p, dataset, model).train()

        args_sp, dataset_sp, model_sp = _build(_args(backend="sp"))
        m_sp = create_simulator(
            args_sp, fedml_tpu.device.get_device(args_sp), dataset_sp, model_sp).run()
        assert abs(m_packed["test_acc"] - m_sp["test_acc"]) < 0.1, (m_packed, m_sp)

    def test_packed_step_count_is_ragged(self):
        """The packed stream runs ceil(n_i/B) steps per client, not the
        global max."""
        args, dataset, model = _build(_args())
        sim = XLASimulator(args, dataset, model)
        sampled = sim._client_sampling(0)
        ids, real = sim._schedule(sampled)
        counts = np.where(real > 0, np.asarray(sim.client_counts)[ids], 0)
        sched = pack_round(
            np.asarray(ids).reshape(sim.n_dev, sim.slots),
            counts.reshape(sim.n_dev, sim.slots),
            lambda cid: sim._client_rows[cid],
            sim.batch_size, 2, 0, 0, sim.s_max,
        )
        expected = sum(2 * (-(-int(c) // sim.batch_size)) for c in counts if c > 0)
        assert int(sched.n_steps.sum()) == expected
        padded_steps = 2 * (-(-sim.max_client_n // sim.batch_size)) * (counts > 0).sum()
        assert expected < padded_steps  # strictly less work than padding to the max

    def test_async_fedavg_packed_trains(self):
        """Regression: algorithms that consume cex in client_contrib WITHOUT
        overriding engine_extra (async_fedavg's staleness counter) must get
        the real per-slot cex in the packed flush, not None."""
        args, dataset, model = _build(_args(
            federated_optimizer="async_fedavg", comm_round=2,
        ))
        sim = XLASimulator(args, dataset, model)
        metrics = sim.train()
        assert np.isfinite(metrics["test_acc"])

    def test_scaffold_packed_matches_host_math(self):
        """Control-variate algorithm on the packed path: equivalence against
        an explicit host replay with the same host-side shuffles."""
        import jax.numpy as jnp

        N = 4
        args, dataset, model = _build(_args(
            federated_optimizer="SCAFFOLD", client_num_in_total=N,
            client_num_per_round=N, comm_round=2, epochs=1,
            partition_method="homo", synthetic_train_size=640,
        ))
        sim = XLASimulator(args, dataset, model, mesh=create_fl_mesh(4))
        w0 = sim.variables
        schedules = []
        orig = sim._schedule

        def capture(sampled):
            ids, real = orig(sampled)
            schedules.append((np.asarray(ids), np.asarray(real)))
            return ids, real

        sim._schedule = capture
        sim.train()
        got = sim.variables

        # host replay: same packed batch order, explicit SGD + SCAFFOLD math
        lr = float(args.learning_rate)
        x_all = np.asarray(sim.x_all)
        y_all = np.asarray(sim.y_all)
        zeros_p = jax.tree_util.tree_map(jnp.zeros_like, w0["params"])
        w = w0
        c_server = zeros_p
        c_clients = {i: zeros_p for i in range(N)}

        import optax

        from fedml_tpu.ml.engine.train import softmax_ce_loss

        def batch_step(params, bx, by, bm, c_i, c):
            def loss(p):
                logits = model.apply(dict(w, params=p), bx, train=True,
                                     rngs={"dropout": jax.random.PRNGKey(0)})
                return softmax_ce_loss(logits, by, bm)[0]

            g = jax.grad(loss)(params)
            g = jax.tree_util.tree_map(lambda gg, ci, cg: gg - ci + cg, g, c_i, c)
            return jax.tree_util.tree_map(lambda p, gg: p - lr * gg, params, g)

        for r in range(2):
            ids, real = schedules[r]
            counts = np.where(real > 0, np.asarray(sim.client_counts)[ids], 0)
            sched = pack_round(
                np.asarray(ids).reshape(sim.n_dev, sim.slots),
                counts.reshape(sim.n_dev, sim.slots),
                lambda cid: sim._client_rows[cid],
                sim.batch_size, 1, 0, r, sim.s_max,
            )
            acc = jax.tree_util.tree_map(jnp.zeros_like, w0)
            wsum = 0.0
            dc_sum = zeros_p
            for d in range(sim.n_dev):
                params = w["params"]
                step_in_client = 0
                for s in range(int(sched.n_steps[d])):
                    bx = jnp.asarray(x_all[sched.idx[d, s]])
                    by = jnp.asarray(y_all[sched.idx[d, s]])
                    bm = jnp.asarray(sched.mask[d, s])
                    ls = int(sched.slot[d, s])
                    cid = int(ids.reshape(sim.n_dev, sim.slots)[d, ls])
                    params = batch_step(params, bx, by, bm, c_clients[cid], c_server)
                    step_in_client += 1
                    if sched.boundary[d, s] > 0:
                        n_i = float(sched.weight[d, s])
                        K = float(step_in_client)
                        new_ci = jax.tree_util.tree_map(
                            lambda ci, cg, wg, wi: ci - cg + (wg - wi) / (K * lr),
                            c_clients[cid], c_server, w["params"], params,
                        )
                        dc_sum = jax.tree_util.tree_map(
                            lambda sacc, nn, oo: sacc + (nn - oo),
                            dc_sum, new_ci, c_clients[cid],
                        )
                        c_clients[cid] = new_ci
                        acc = jax.tree_util.tree_map(
                            lambda a, p: a + n_i * p, acc, dict(w, params=params)
                        )
                        wsum += n_i
                        params = w["params"]
                        step_in_client = 0
            w = jax.tree_util.tree_map(lambda a: a / wsum, acc)
            c_server = jax.tree_util.tree_map(
                lambda c, dcv: c + dcv / N, c_server, dc_sum
            )
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5
            ),
            got, w,
        )


@heavy
class TestStepScheduling:
    """The packed round schedules and models runtime in its native unit:
    compiled steps (ceil(n/B)*E), with a quantized stream bucket."""

    def test_scheduler_receives_step_costs(self):
        args, dataset, model = _build(_args(comm_round=1))
        sim = XLASimulator(args, dataset, model)
        captured = {}
        orig = sim.scheduler.schedule

        def spy(ids, sizes):
            captured["sizes"] = list(sizes)
            return orig(ids, sizes)

        sim.scheduler.schedule = spy
        sampled = sim._client_sampling(0)
        sim._schedule(sampled)
        b, e = int(args.batch_size), int(args.epochs)
        expect = [-(-int(sim.local_num_dict[int(c)]) // b) * e for c in sampled]
        assert captured["sizes"] == expect

    def test_runtime_model_records_steps(self):
        args, dataset, model = _build(_args(comm_round=4))
        sim = XLASimulator(args, dataset, model)
        sim.train()
        obs = sim.runtime_estimator._obs[0]
        # rounds 1..3, minus any round whose bucket shape first compiled
        assert 1 <= len(obs) <= 3
        max_steps_possible = sim.slots * (-(-sim.max_client_n // sim.batch_size)) \
            * int(args.epochs)
        for x, t in obs:
            assert 1 <= x <= max_steps_possible
            assert x == int(x)  # step counts, not raw sample sums
            assert t > 0

    def test_bucket_quantized_not_power_of_two(self):
        args, dataset, model = _build(_args(comm_round=2))
        sim = XLASimulator(args, dataset, model)
        sim.train()
        quantum = max(1, -(-sim.s_max // 8))
        assert sim._s_bucket % quantum == 0 or sim._s_bucket == sim.s_max
        assert sim._s_bucket <= sim.s_max

    def test_bucket_tracks_round_usage(self):
        """The bucket equals the quantized round usage — computed from the
        actual schedule, not assumed from the sampling draw."""
        args, dataset, model = _build(
            _args(comm_round=1, client_num_per_round=2, epochs=1)
        )
        sim = XLASimulator(args, dataset, model)
        sim.train()
        sampled = sim._client_sampling(0)
        ids, real = sim._schedule(sampled)
        steps = np.array([
            sim._client_steps(sim.local_num_dict[int(c)]) if r else 0
            for c, r in zip(ids, real)
        ])
        s_used = max(int(steps.reshape(sim.n_dev, -1).sum(axis=1).max()), 1)
        quantum = max(1, -(-sim.s_max // 8))
        expect = min(-(-s_used // quantum) * quantum, sim.s_max)
        assert sim._s_bucket == expect, (sim._s_bucket, expect, s_used, sim.s_max)


@heavy
class TestDataStorageDtype:
    def test_bf16_storage_matches_fp32_storage(self):
        """Under bf16 compute the model's entry cast makes a stored-bf16
        gather bitwise-identical to gather-then-cast of fp32 storage, so
        halving the dataset's HBM footprint/gather traffic must not change
        the round outputs at all."""
        outs = {}
        for store in ("fp32", "bf16"):
            args, dataset, model = _build(_args(
                dataset="cifar10", model="resnet20", compute_dtype="bf16",
                xla_data_dtype=store, synthetic_train_size=256,
                client_num_in_total=4, client_num_per_round=4,
                comm_round=2, epochs=1, batch_size=16,
                frequency_of_the_test=0,
            ))
            sim = XLASimulator(args, dataset, model)
            assert str(sim.x_all.dtype) == ("bfloat16" if store == "bf16" else "float32")
            sim.train()
            outs[store] = [np.asarray(l) for l in jax.tree_util.tree_leaves(sim.variables)]
        for a, b in zip(outs["fp32"], outs["bf16"]):
            np.testing.assert_allclose(a, b, rtol=0, atol=0)

    def test_auto_keeps_fp32_for_unplumbed_models(self):
        """'auto' must not downcast the dataset for models that ignore
        compute_dtype (they'd consume degraded fp32 inputs)."""
        args, dataset, model = _build(_args(compute_dtype="bf16"))  # lr model
        sim = XLASimulator(args, dataset, model)
        assert str(sim.x_all.dtype) == "float32"

    def test_integer_token_data_never_downcast(self):
        """Token-id inputs (s2s/NWP) must keep their integer dtype even
        under an explicit bf16 storage request — nn.Embed requires ints
        (regression: the first bf16-storage cut cast them to float and the
        in-mesh s2s task crashed)."""
        args, dataset, model = _build(_args(
            dataset="synthetic_s2s", model="transformer_s2s",
            xla_data_dtype="bf16", synthetic_train_size=128,
            client_num_in_total=4, client_num_per_round=4, batch_size=16,
            comm_round=1, frequency_of_the_test=0,
        ))
        sim = XLASimulator(args, dataset, model)
        assert np.issubdtype(np.asarray(sim.x_all[:1]).dtype, np.integer)


RETIRED = [key for key, _ in Arguments.RETIRED_ROUND_KEYS]  # pack, stream, pre-gather, client chunk


class TestRetiredKeys:
    """The keys that selected among rounds that no longer exist: a config
    that still asks for one of them is refused, not silently trained on
    another."""

    @pytest.mark.parametrize("key,value", zip(RETIRED, (False, "scan", True, 4)), ids=RETIRED)
    def test_a_value_that_asks_for_a_removed_round_is_refused(self, key, value):
        with pytest.raises(ValueError, match=key + ".*packed stream is the only round"):
            _args(**{key: value})

    @heavy
    def test_the_values_that_meant_the_packed_stream_still_build_and_train(self):
        args, dataset, model = _build(_args(
            comm_round=1, **dict(zip(RETIRED, (True, "while", False, 1)))))
        sim = XLASimulator(args, dataset, model)
        sim.train()
        assert np.isfinite(sim.round_losses[-1]) and sim.round_log[-1]["steps_max"] > 0
