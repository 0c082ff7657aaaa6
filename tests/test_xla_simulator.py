"""Parrot-XLA simulator tests on the 8-device virtual CPU mesh."""

import jax
import numpy as np
import pytest

import fedml_tpu
from fedml_tpu.arguments import Arguments
from fedml_tpu.parallel.mesh import create_fl_mesh
from fedml_tpu.simulation.xla.fed_sim import XLASimulator

pytestmark = pytest.mark.heavy  # long XLA compiles; see pytest.ini


def _args(**over):
    args = Arguments.from_dict(
        {
            "common_args": {"training_type": "simulation", "random_seed": 0, "run_id": "xt"},
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
                "epochs": 1,
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


class TestXLASimulator:
    def test_learns_on_8dev_mesh(self):
        args, dataset, model = _build(_args())
        sim = XLASimulator(args, dataset, model)
        assert sim.n_dev == 8
        metrics = sim.train()
        assert metrics["test_acc"] > 0.5

    def test_uneven_clients_pad_with_dummies(self):
        # 6 clients per round over 8 devices -> 2 dummy slots
        args, dataset, model = _build(_args(client_num_per_round=6, comm_round=2))
        sim = XLASimulator(args, dataset, model)
        metrics = sim.train()
        assert "test_acc" in metrics

    def test_matches_host_aggregation(self):
        """One XLA round == host-side weighted average of per-client results."""
        args, dataset, model = _build(
            _args(client_num_in_total=4, client_num_per_round=4, comm_round=1,
                  partition_method="homo", synthetic_train_size=640)
        )
        mesh = create_fl_mesh(4)
        sim = XLASimulator(args, dataset, model, mesh=mesh)
        w0 = sim.variables

        # replay the round's packed stream on the host, client by client
        from fedml_tpu.core.aggregate import weighted_mean
        from packed_replay import replay_clients

        ids, real = sim._schedule(sim._client_sampling(0))
        updates = [(n, res.variables) for _, n, res in
                   replay_clients(sim, model, args, ids, real, 0, w0)]
        expected = weighted_mean(updates)

        sim.train()
        got = sim.variables
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5),
            expected,
            got,
        )

    def test_throughput_reported(self):
        args, dataset, model = _build(_args(comm_round=3))
        sim = XLASimulator(args, dataset, model)
        sim.train()
        tp = sim.throughput()
        assert tp["rounds_per_sec"] > 0 and tp["samples_per_sec"] > 0


class TestGraftEntry:
    def test_entry_compiles(self):
        import __graft_entry__ as ge

        fn, example_args = ge.entry()
        out = jax.jit(fn)(*example_args)
        assert out.shape == (8, 10)

    def test_dryrun_multichip_8(self):
        import __graft_entry__ as ge

        ge.dryrun_multichip(8)


class TestDeterministicReplay:
    """SURVEY §5 race-detection rebuild note: JAX's functional model replaces
    sanitizers with determinism guarantees — same seed, bitwise-same round
    outputs."""

    def test_two_runs_bitwise_identical(self):
        outs = []
        for _ in range(2):
            args, dataset, model = _build(_args(comm_round=2))
            sim = XLASimulator(args, dataset, model)
            sim.train()
            outs.append([np.asarray(l) for l in jax.tree_util.tree_leaves(sim.variables)])
        for a, b in zip(*outs):
            np.testing.assert_array_equal(a, b)


class TestInMeshLocalDP:
    """Local DP rides the compiled round: per-client noise before
    aggregation (the mechanism's add_noise is jax-pure), budget accounted
    host-side per participating client."""

    def test_ldp_noises_and_accounts(self):
        from fedml_tpu.core.dp.fedml_differential_privacy import (
            FedMLDifferentialPrivacy,
        )

        results = {}
        for enable in (False, True):
            args, dataset, model = _build(_args(comm_round=2))
            args.enable_dp = enable
            args.dp_type = "ldp"
            args.mechanism_type = "gaussian"
            args.epsilon = 50.0
            args.delta = 1e-5
            FedMLDifferentialPrivacy._instance = None
            dp = FedMLDifferentialPrivacy.get_instance()
            dp.init(args)
            sim = XLASimulator(args, dataset, model)
            sim.train()
            results[enable] = [np.asarray(l) for l in
                               jax.tree_util.tree_leaves(sim.variables)]
            if enable:
                # 2 rounds x all sampled clients must be accounted
                assert len(dp.accountant) == 2 * int(args.client_num_per_round)
        # noise changed the trajectory
        diffs = [np.abs(a - b).max() for a, b in zip(results[False], results[True])]
        assert max(diffs) > 1e-6


def _reset_security():
    from fedml_tpu.core.security.fedml_attacker import FedMLAttacker
    from fedml_tpu.core.security.fedml_defender import FedMLDefender

    FedMLAttacker._attacker_instance = None
    FedMLDefender._defender_instance = None
    return FedMLAttacker.get_instance(), FedMLDefender.get_instance()


def _run_security(attack=None, defense=None, comm_round=2, **extra):
    """One XLA run with the given attack/defense config; returns (sim, metrics)."""
    args, dataset, model = _build(_args(comm_round=comm_round))
    for k, v in extra.items():
        setattr(args, k, v)
    if attack:
        args.enable_attack = True
        args.attack_type = attack
    if defense:
        args.enable_defense = True
        args.defense_type = defense
    attacker, defender = _reset_security()
    try:
        attacker.init(args)
        defender.init(args)
        sim = XLASimulator(args, dataset, model)
        metrics = sim.train()
    finally:
        _reset_security()  # even on expected raises: singletons are global
    return sim, metrics


class TestInMeshDefense:
    """Robust aggregation on the XLA backend: the compiled round returns the
    sharded per-client update stack; a second jitted program substitutes the
    robust aggregate (core/security/stacked.py) — every aggregates_via_acc
    algorithm."""

    @pytest.mark.parametrize("defense,extra", [
        ("coordinate_wise_median", {}),
        ("krum", {"byzantine_client_num": 1}),
        ("norm_diff_clipping", {"norm_bound": 5.0}),
        ("geometric_median", {}),
    ])
    def test_defended_round_learns(self, defense, extra):
        sim, metrics = _run_security(defense=defense, **extra)
        assert metrics["test_acc"] > 0.5, (defense, metrics)

    def test_defense_changes_aggregate(self):
        _, clean = _run_security()
        _, defended = _run_security(defense="coordinate_wise_median")
        # median != weighted mean on heterogeneous clients
        assert clean["test_loss"] != defended["test_loss"]

    def test_defense_composes_with_scaffold(self):
        _, metrics = _run_security(
            defense="coordinate_wise_median", federated_optimizer="SCAFFOLD",
        )
        assert metrics["test_acc"] > 0.5, metrics

    @pytest.mark.parametrize("optimizer", ["FedNova", "async_fedavg"])
    @pytest.mark.parametrize("defense,extra", [
        ("krum", {"byzantine_client_num": 1}),          # before: selection
        ("coordinate_wise_median", {}),                 # on: aggregate-replacing
        # on: trust-reweighting — rows mode must broadcast its aggregate
        # (normalized trust weights would collapse async's relative factor)
        ("foolsgold", {}),
    ])
    def test_ext_aggregators_compose_with_defense(self, optimizer, defense, extra):
        """FedNova/async aggregate through ext, not the weighted acc — the
        security tail recomputes their per-client contributions from the
        defended row space (ext_from_rows; sp composition for before-
        defenses, consensus-row semantics for aggregate-replacers)."""
        _, metrics = _run_security(
            defense=defense, federated_optimizer=optimizer, **extra
        )
        assert metrics["test_acc"] > 0.5, (optimizer, defense, metrics)

    @pytest.mark.parametrize("optimizer,defense,extra", [
        ("FedOpt", "norm_diff_clipping", {"norm_bound": 5.0}),
        ("FedNova", "krum", {"byzantine_client_num": 1}),
    ])
    def test_sharded_state_composes_with_defense_bitwise(
            self, optimizer, defense, extra):
        """The defended + model-sharded composition (the old fed_sim gate
        silently degraded sharded_state to replicated whenever the security
        tail was active): the security program now ends at the psum'd
        accumulator and the model-sharded GSPMD tail applies the server
        step — and the run is BITWISE the replicated defended run, for
        both the via-acc and the rows (ext2) security branches."""
        knobs = dict(defense=defense, federated_optimizer=optimizer,
                     server_optimizer="adam", **extra)
        sim_r, m_r = _run_security(**knobs)
        sim_s, m_s = _run_security(server_state="sharded", **knobs)
        assert sim_s.sharded_state and not sim_r.sharded_state
        for a, b in zip(jax.tree_util.tree_leaves(sim_r.variables),
                        jax.tree_util.tree_leaves(sim_s.variables)):
            assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
        assert m_r["test_acc"] == m_s["test_acc"]

    def test_fednova_byzantine_degrades_and_krum_recovers(self):
        _, clean = _run_security(comm_round=3, federated_optimizer="FedNova")
        _, attacked = _run_security(
            attack="byzantine", comm_round=3, federated_optimizer="FedNova",
            attack_mode="random", byzantine_client_num=8,
        )
        _, defended = _run_security(
            attack="byzantine", defense="krum", comm_round=3,
            federated_optimizer="FedNova",
            attack_mode="random", byzantine_client_num=8,
        )
        assert attacked["test_acc"] < clean["test_acc"] - 0.1, (clean, attacked)
        assert defended["test_acc"] > attacked["test_acc"] + 0.1, (attacked, defended)


class TestDefenseStateCheckpoint:
    def test_foolsgold_history_survives_resume(self, tmp_path):
        """Cross-round defense state (foolsgold similarity history) must ride
        the checkpoint: a resumed run that re-zeroed it would silently
        re-pardon already-attenuated sybils."""
        from fedml_tpu.core.security.fedml_defender import FedMLDefender

        def build(rounds):
            args, dataset, model = _build(_args(
                comm_round=rounds, client_num_per_round=16,
                client_num_in_total=16,  # full participation: stable slots
            ))
            args.enable_defense = True
            args.defense_type = "foolsgold"
            args.checkpoint_dir = str(tmp_path / "ckpt")
            FedMLDefender._defender_instance = None
            FedMLDefender.get_instance().init(args)
            return XLASimulator(args, dataset, model)

        try:
            sim = build(2)
            sim.train()
            hist_before = np.asarray(sim._defense_state["fg_hist"])
            assert np.abs(hist_before).sum() > 0
            # resume into a fresh simulator: state must come back from disk
            sim2 = build(3)
            sim2.train()  # restores round 0-1, runs round 2
            assert sim2._defense_n == 16
            hist_after = np.asarray(sim2._defense_state["fg_hist"])
            # history kept accumulating from the restored value, not from zero
            assert np.abs(hist_after).sum() > np.abs(hist_before).sum()
        finally:
            FedMLDefender._defender_instance = None


class TestInMeshAttack:
    """The sp security matrix reproduced on the XLA backend: data poisoning
    stamps at pack time, model attacks run in the stacked security program
    (reference fedml_attacker.py:28-30 — one simulator runs the whole
    matrix)."""

    def test_byzantine_degrades_and_krum_recovers(self):
        _, clean = _run_security(comm_round=3)
        _, attacked = _run_security(
            attack="byzantine", comm_round=3,
            attack_mode="random", byzantine_client_num=8,
        )
        _, defended = _run_security(
            attack="byzantine", defense="krum", comm_round=3,
            attack_mode="random", byzantine_client_num=8,
        )
        # 8/16 random-garbage clients wreck plain FedAvg; krum survives
        assert attacked["test_acc"] < clean["test_acc"] - 0.1, (clean, attacked)
        assert defended["test_acc"] > attacked["test_acc"] + 0.1, (attacked, defended)

    def test_label_flip_poisons_pack(self):
        sim, _ = _run_security(
            attack="label_flipping", comm_round=1,
            original_class=1, target_class=7, byzantine_client_num=16,
        )
        clean_sim, _ = _run_security(comm_round=1)
        # every client malicious: no label-1 row survives in the packed data
        assert not bool((np.asarray(sim.y_all) == 1).any())
        assert bool((np.asarray(clean_sim.y_all) == 1).any())

    def test_model_replacement_mitigated_by_clipping(self):
        """The scaled push drags the aggregate away from the clean trajectory;
        norm clipping pulls it back (parameter-space distances — the LR task
        is too easy for accuracy to separate the runs)."""
        def _vec(sim):
            from jax.flatten_util import ravel_pytree

            return np.asarray(ravel_pytree(sim.variables)[0])

        clean_sim, _ = _run_security(comm_round=2)
        atk_sim, _ = _run_security(
            attack="model_replacement", comm_round=2,
            attack_scale=25.0, byzantine_client_num=4,
        )
        def_sim, _ = _run_security(
            attack="model_replacement", defense="norm_diff_clipping",
            comm_round=2, attack_scale=25.0, byzantine_client_num=4,
            norm_bound=0.5,
        )
        d_atk = np.linalg.norm(_vec(atk_sim) - _vec(clean_sim))
        d_def = np.linalg.norm(_vec(def_sim) - _vec(clean_sim))
        assert d_atk > 2.0 * d_def, (d_atk, d_def)

    def test_dlg_reconstruction_runs_in_round(self):
        args, dataset, model = _build(_args(comm_round=1))
        args.enable_attack = True
        args.attack_type = "dlg"
        args.dlg_steps = 20
        attacker, _ = _reset_security()
        attacker.init(args)
        sim = XLASimulator(args, dataset, model)
        sim.train()
        x_rec, y_soft = attacker.last_reconstruction
        assert np.all(np.isfinite(np.asarray(x_rec)))
        assert x_rec.shape[1:] == sim.x_all.shape[1:]
        _reset_security()

    def test_invert_gradient_reconstruction_runs_in_round(self):
        """The second analysis primitive (cosine matching + TV prior,
        reference invert_gradient_attack.py) runs in-mesh off the same
        intercepted-update stack dlg uses."""
        args, dataset, model = _build(_args(comm_round=1))
        args.enable_attack = True
        args.attack_type = "invert_gradient"
        args.dlg_steps = 20
        attacker, _ = _reset_security()
        attacker.init(args)
        sim = XLASimulator(args, dataset, model)
        sim.train()
        x_rec, _ = attacker.last_reconstruction
        assert np.all(np.isfinite(np.asarray(x_rec)))
        assert x_rec.shape[1:] == sim.x_all.shape[1:]
        _reset_security()

    def test_revealing_labels_reveals_victim_classes(self):
        """iDLG bias-sign revelation on the intercepted in-mesh update: the
        classes flagged present must actually appear in the victim client's
        local label set."""
        args, dataset, model = _build(_args(comm_round=1))
        args.enable_attack = True
        args.attack_type = "revealing_labels_from_gradients"
        attacker, _ = _reset_security()
        attacker.init(args)
        sim = XLASimulator(args, dataset, model)
        sim.train()
        order, present = attacker.last_revealed_labels
        assert present.shape == (sim.class_num,)
        # the round's victim: first malicious client in schedule order, else
        # the first real slot (mirrors the train() victim pick)
        sampled = sim._client_sampling(0)
        ids, real = sim._schedule(sampled)
        counts = np.where(real > 0, np.asarray(sim.client_counts)[ids], 0)
        real_sel = np.where(counts > 0)[0]
        bad = set(attacker.get_byzantine_idxs(sim.num_clients))
        victims = [int(i) for i in real_sel if int(ids[i]) in bad] or [int(real_sel[0])]
        vid = int(ids[victims[0]])
        vrows = np.asarray(sim._client_rows[vid])[: sim.local_num_dict[vid]]
        vlabels = set(np.asarray(sim.y_all)[vrows].tolist())
        # top-ranked class is one the victim actually holds
        assert int(np.asarray(order)[0]) in vlabels, (vlabels, np.asarray(order)[:3])
        _reset_security()
