"""Algorithm zoo on the XLA fast path: in-mesh strategies must match the
single-process server math (reference ``simulation/mpi/{fedopt,fednova,...}``
semantics) exactly.

Each test runs the compiled in-mesh simulator for 2 rounds, then replays the
same rounds on the host with an INDEPENDENT formulation: a per-client replay of
the round's packed stream (tests/packed_replay.py) plus the explicit published
update rule (the same formulas the sp implementations use), and asserts the
final global variables match."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import fedml_tpu
from fedml_tpu.arguments import Arguments
from fedml_tpu.parallel.mesh import create_fl_mesh
from fedml_tpu.simulation.xla.fed_sim import XLASimulator
from packed_replay import replay_clients

pytestmark = pytest.mark.heavy  # long XLA compiles; see pytest.ini

N_CLIENTS = 4
ROUNDS = 2


def _args(**over):
    args = Arguments.from_dict(
        {
            "common_args": {"training_type": "simulation", "random_seed": 0, "run_id": "zoo"},
            "data_args": {
                "dataset": "mnist",
                "data_cache_dir": "",
                "partition_method": "homo",
                "synthetic_train_size": 640,
            },
            "model_args": {"model": "lr"},
            "train_args": {
                "federated_optimizer": "FedAvg",
                "client_num_in_total": N_CLIENTS,
                "client_num_per_round": N_CLIENTS,
                "comm_round": ROUNDS,
                "epochs": 1,
                "batch_size": 32,
                "client_optimizer": "sgd",
                "learning_rate": 0.1,
            },
            "validation_args": {"frequency_of_the_test": 100},
            "comm_args": {"backend": "XLA"},
        }
    )
    for k, v in over.items():
        setattr(args, k, v)
    return args.validate()


class Replay:
    """Capture the in-mesh run's schedules, then drive a host-side replay
    with identical data slices and rng streams."""

    def __init__(self, **over):
        args = fedml_tpu.init(_args(**over), should_init_logs=False)
        dataset, out_dim = fedml_tpu.data.load(args)
        model = fedml_tpu.models.create(args, out_dim)
        self.args, self.model = args, model
        self.sim = XLASimulator(args, dataset, model, mesh=create_fl_mesh(4))
        self.w0 = self.sim.variables
        self.schedules = []
        orig = self.sim._schedule

        def capture(sampled):
            ids, real = orig(sampled)
            self.schedules.append((np.asarray(ids), np.asarray(real)))
            return ids, real

        self.sim._schedule = capture

    def run_sim(self):
        self.sim.train()
        return self.sim.variables

    def local_results(self, round_idx, w_global, grad_hook=None, extras=None):
        """Per-client host runs for one round, in stream order.
        Returns [(cid, n_i, LocalTrainResult)] for real clients."""
        ids, real = self.schedules[round_idx]
        return replay_clients(self.sim, self.model, self.args, ids, real, round_idx,
                              w_global, grad_hook=grad_hook, extras=extras)


def assert_trees_close(a, b, rtol=2e-4, atol=2e-5):
    jax.tree_util.tree_map(
        lambda x, y: np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                                rtol=rtol, atol=atol),
        a, b,
    )


def wavg(results, like):
    tot = sum(n for _, n, _ in results)
    return jax.tree_util.tree_map(
        lambda *leaves: sum(
            n * l.astype(jnp.float32) for (_, n, _), l in zip(results, leaves)
        ) / tot,
        *[r.variables for _, _, r in results],
    )


class TestXLAZoo:
    def test_fedopt_matches_host_math(self):
        import optax

        from fedml_tpu.simulation.sp.fedopt.fedopt_api import make_server_optimizer

        rp = Replay(federated_optimizer="FedOpt", server_optimizer="adam", server_lr=0.05)
        got = rp.run_sim()

        tx = make_server_optimizer(rp.args)
        w = rp.w0
        opt_state = tx.init(w["params"])
        for r in range(ROUNDS):
            results = rp.local_results(r, w)
            avg = wavg(results, w)
            pseudo = jax.tree_util.tree_map(
                lambda p, a: p - a, w["params"], avg["params"]
            )
            updates, opt_state = tx.update(pseudo, opt_state, w["params"])
            w = dict(avg, params=optax.apply_updates(w["params"], updates))
        assert_trees_close(got, w)

    def test_fedprox_matches_host_math(self):
        """The proximal pull toward the round-start parameters (the engine's
        hook from ``proximal_mu``, anchored at the stream's ``params0``)."""
        rp = Replay(federated_optimizer="FedProx", proximal_mu=0.1)
        got = rp.run_sim()

        def hook(grads, params, anchor, extra):
            return jax.tree_util.tree_map(
                lambda g, p, a: g + 0.1 * (p - a), grads, params, anchor)

        w = rp.w0
        for r in range(ROUNDS):
            w = wavg(rp.local_results(r, w, grad_hook=hook), w)
        assert_trees_close(got, w)
        # the pull is in the trajectory: plain FedAvg ends elsewhere
        plain = Replay().run_sim()
        assert max(float(jnp.abs(a - b).max()) for a, b in zip(
            jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(plain))) > 1e-5

    def test_adam_clients_on_uneven_streams_match_host_math(self):
        """A stateful client optimizer: its state advances on every step of
        a client and starts anew at each boundary, on devices whose streams
        end at different steps."""
        rp = Replay(client_optimizer="adam", learning_rate=0.01,
                    client_num_in_total=8, client_num_per_round=8,
                    partition_method="hetero", partition_alpha=0.5,
                    synthetic_train_size=1280)
        got = rp.run_sim()
        w = rp.w0
        for r in range(ROUNDS):
            results = rp.local_results(r, w)
            w = wavg(results, w)
        assert_trees_close(got, w)
        steps = [-(-int(rp.sim.local_num_dict[int(c)]) // int(rp.args.batch_size))
                 for c in rp.schedules[0][0]]
        per_device = np.asarray(steps).reshape(rp.sim.n_dev, -1).sum(axis=1)
        assert len(set(per_device.tolist())) > 1, per_device

    def test_fednova_matches_host_math(self):
        rp = Replay(federated_optimizer="FedNova")
        got = rp.run_sim()

        w = rp.w0
        for r in range(ROUNDS):
            results = rp.local_results(r, w)
            tot = sum(n for _, n, _ in results)
            taus = [max(float(res.steps), 1.0) for _, _, res in results]
            ps = [n / tot for _, n, _ in results]
            tau_eff = sum(p * t for p, t in zip(ps, taus))
            d = jax.tree_util.tree_map(jnp.zeros_like, w)
            for (cid, n, res), p, tau in zip(results, ps, taus):
                d = jax.tree_util.tree_map(
                    lambda acc, g, wi: acc + p * (g - wi) / tau, d, w, res.variables
                )
            w = jax.tree_util.tree_map(lambda g, di: g - tau_eff * di, w, d)
        assert_trees_close(got, w)

    def test_scaffold_matches_host_math(self):
        rp = Replay(federated_optimizer="SCAFFOLD")
        lr = float(rp.args.learning_rate)
        got = rp.run_sim()

        def hook(grads, params, anchor, extra):
            c_i, c = extra
            return jax.tree_util.tree_map(lambda g, ci, cg: g - ci + cg, grads, c_i, c)

        zeros_p = jax.tree_util.tree_map(jnp.zeros_like, rp.w0["params"])
        w = rp.w0
        c_server = zeros_p
        c_clients = {i: zeros_p for i in range(N_CLIENTS)}
        for r in range(ROUNDS):
            extras = {i: (c_clients[i], c_server) for i in range(N_CLIENTS)}
            results = rp.local_results(r, w, grad_hook=hook, extras=extras)
            dc_sum = zeros_p
            for cid, n, res in results:
                K = max(float(res.steps), 1.0)
                new_ci = jax.tree_util.tree_map(
                    lambda ci, cg, wg, wi: ci - cg + (wg - wi) / (K * lr),
                    c_clients[cid], c_server, w["params"], res.variables["params"],
                )
                dc_sum = jax.tree_util.tree_map(
                    lambda s, n_, o: s + (n_ - o), dc_sum, new_ci, c_clients[cid]
                )
                c_clients[cid] = new_ci
            w = wavg(results, w)
            c_server = jax.tree_util.tree_map(
                lambda c, d: c + d / N_CLIENTS, c_server, dc_sum
            )
        assert_trees_close(got, w)
        # server control variate state must match too
        assert_trees_close(rp.sim.server_state, c_server)

    def test_feddyn_matches_host_math(self):
        rp = Replay(federated_optimizer="FedDyn", feddyn_alpha=0.1)
        alpha = 0.1
        got = rp.run_sim()

        def hook(grads, params, anchor, extra):
            return jax.tree_util.tree_map(
                lambda g, h, p, a: g - h + alpha * (p - a), grads, extra, params, anchor
            )

        zeros_p = jax.tree_util.tree_map(jnp.zeros_like, rp.w0["params"])
        w = rp.w0
        h_clients = {i: zeros_p for i in range(N_CLIENTS)}
        for r in range(ROUNDS):
            extras = {i: h_clients[i] for i in range(N_CLIENTS)}
            results = rp.local_results(r, w, grad_hook=hook, extras=extras)
            for cid, n, res in results:
                h_clients[cid] = jax.tree_util.tree_map(
                    lambda h, wi, wg: h - alpha * (wi - wg),
                    h_clients[cid], res.variables["params"], w["params"],
                )
            avg = wavg(results, w)
            h_mean = jax.tree_util.tree_map(
                lambda *hs: sum(hs) / N_CLIENTS, *h_clients.values()
            )
            params = jax.tree_util.tree_map(
                lambda p, h: p - h / alpha, avg["params"], h_mean
            )
            w = dict(avg, params=params)
        assert_trees_close(got, w)

    def test_async_buffered_matches_host_math(self):
        # 8 clients, 4 per round: participation varies, so staleness kicks in
        rp = Replay(federated_optimizer="Async_FedAvg", client_num_in_total=8,
                    client_num_per_round=4, async_alpha=0.6, async_beta=0.5,
                    synthetic_train_size=1280)
        got = rp.run_sim()

        w = rp.w0
        last = {}
        for r in range(ROUNDS):
            results = rp.local_results(r, w)
            K = len(results)
            delta = jax.tree_util.tree_map(jnp.zeros_like, w)
            for cid, n, res in results:
                stale = r - last.get(cid, r)
                a_i = 0.6 / (1.0 + stale) ** 0.5
                delta = jax.tree_util.tree_map(
                    lambda d, wi, wg: d + a_i * (wi - wg), delta, res.variables, w
                )
            for cid, _, _ in results:
                last[cid] = r
            w = jax.tree_util.tree_map(lambda g, d: g + d / K, w, delta)
        assert_trees_close(got, w)

    def test_fednova_krum_composition_matches_host(self):
        """Defense x ext-aggregating algorithm: the in-mesh security tail
        (ext_from_rows over the defended row space) must equal the sp
        composition — defend_before_aggregation filters the update list,
        taus follow the survivors, FedNova aggregates them
        (sp/fednova/fednova_api.py server_update)."""
        from fedml_tpu.core.security.fedml_defender import FedMLDefender

        FedMLDefender._defender_instance = None
        d = FedMLDefender.get_instance()
        try:
            # hetero partition: distinguishable client updates (a homo
            # split of the tiny synthetic set yields EXACT krum-score ties,
            # where host argsort and jnp argsort may break differently)
            # 8 clients (not the default 4): with n=4 and byz=1 the krum
            # score degenerates to the single nearest-neighbour distance,
            # which ties EXACTLY for mutual nearest neighbours — host and
            # stacked argsort may break the tie differently.  n=8 sums 5
            # distances per score; ties vanish.
            rp = Replay(federated_optimizer="FedNova", enable_defense=True,
                        defense_type="krum", byzantine_client_num=1,
                        partition_method="hetero", partition_alpha=0.5,
                        client_num_in_total=8, client_num_per_round=8,
                        synthetic_train_size=1280)
            d.init(rp.args)
            got = rp.run_sim()

            w = rp.w0
            for r in range(ROUNDS):
                results = rp.local_results(r, w)
                updates = [(n, res.variables) for _, n, res in results]
                tau_by_id = {
                    id(p): max(float(res.steps), 1.0)
                    for (_, _, res), (_, p) in zip(results, updates)
                }
                survivors = d.defend_before_aggregation(updates, w)
                taus = [tau_by_id.get(id(p), 1.0) for _, p in survivors]
                tot = sum(n for n, _ in survivors)
                ps = [n / tot for n, _ in survivors]
                tau_eff = sum(p * t for p, t in zip(ps, taus))
                dsum = jax.tree_util.tree_map(jnp.zeros_like, w)
                for (n, wi), p, tau in zip(survivors, ps, taus):
                    dsum = jax.tree_util.tree_map(
                        lambda acc, g, v: acc + p * (g - v) / tau, dsum, w, wi
                    )
                w = jax.tree_util.tree_map(
                    lambda g, di: g - tau_eff * di, w, dsum
                )
            assert_trees_close(got, w)
        finally:
            FedMLDefender._defender_instance = None

    def test_async_krum_composition_matches_host(self):
        """Same composition for the buffered-async strategy: survivors keep
        their own staleness discounts, k drops to the surviving count."""
        from fedml_tpu.core.security.fedml_defender import FedMLDefender

        FedMLDefender._defender_instance = None
        d = FedMLDefender.get_instance()
        try:
            # 6 sampled per round (krum scores sum 3 distances: no
            # mutual-NN exact ties; see the FedNova test above)
            rp = Replay(federated_optimizer="Async_FedAvg",
                        client_num_in_total=8, client_num_per_round=6,
                        async_alpha=0.6, async_beta=0.5,
                        synthetic_train_size=1280,
                        enable_defense=True, defense_type="krum",
                        byzantine_client_num=1,
                        partition_method="hetero", partition_alpha=0.5)
            d.init(rp.args)
            got = rp.run_sim()

            w = rp.w0
            last = {}
            for r in range(ROUNDS):
                results = rp.local_results(r, w)
                updates = [(n, res.variables) for _, n, res in results]
                cid_by_id = {id(p): cid for (cid, _, _), (_, p)
                             in zip(results, updates)}
                survivors = d.defend_before_aggregation(updates, w)
                K = len(survivors)
                delta = jax.tree_util.tree_map(jnp.zeros_like, w)
                for _, wi in survivors:
                    stale = r - last.get(cid_by_id[id(wi)], r)
                    a_i = 0.6 / (1.0 + stale) ** 0.5
                    delta = jax.tree_util.tree_map(
                        lambda dl, v, wg: dl + a_i * (v - wg), delta, wi, w
                    )
                # host_round_end marks EVERY participant (survivor or not)
                for cid, _, _ in results:
                    last[cid] = r
                w = jax.tree_util.tree_map(lambda g, dl: g + dl / K, w, delta)
            assert_trees_close(got, w)
        finally:
            FedMLDefender._defender_instance = None

    def test_unsupported_zoo_algorithm_fails_loud(self):
        # XLASimulator owns only the shared FedAvg-family round; every
        # structurally-distinct optimizer (turbo/GAN/NAS/gossip/...) has its
        # own mesh program reached through SimulatorXLA's dispatch.  Handed
        # such an optimizer DIRECTLY, XLASimulator must refuse rather than
        # silently run plain FedAvg.
        args = fedml_tpu.init(_args(federated_optimizer="turbo_aggregate"), should_init_logs=False)
        dataset, out_dim = fedml_tpu.data.load(args)
        model = fedml_tpu.models.create(args, out_dim)
        with pytest.raises(NotImplementedError, match="in-mesh"):
            XLASimulator(args, dataset, model, mesh=create_fl_mesh(4))

    def test_scaffold_learns(self):
        rp = Replay(federated_optimizer="SCAFFOLD", comm_round=4,
                    frequency_of_the_test=2, partition_method="hetero",
                    partition_alpha=0.5, synthetic_train_size=1600,
                    client_num_in_total=16, client_num_per_round=8)
        metrics = rp.sim.train()
        assert metrics["test_acc"] > 0.5
