"""The ``sdar_moe`` decoder trained by block diffusion against its plain reference, on the
CPU in float32 at tiny widths with the published ratios (``benchmark/configs/tiny-sdar.json``):
the mixer with and without q/k norms, the expert layer and the shares of a deployment,
the whole model's loss and gradients, the noise the program draws against the keys the
reference re-derives, the engine's loss and eval, one packed FedAvg round through
``FedMLRunner`` against the reference's round, the round's counters, and the validation of
``model_config``."""

import hashlib
import importlib
import json
import os
import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import compare, reference as plain, reference_sdar as ref
from benchmark.drivers import sim_kimi_linear, sim_sdar
from fedml_tpu.ml.engine import train as engine
from fedml_tpu.models import expert_lm, sdar_moe, smallthinker

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = os.path.join(ROOT, "benchmark", "configs")
TINY = os.path.join(CONFIGS, "tiny-sdar.json")
fa = importlib.import_module("fedml_tpu.ops.flash_attention")


@pytest.fixture(scope="module")
def model():
    with open(TINY) as f:
        return json.load(f)


def _assert_close(a, b, tol):
    for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b), strict=True):
        scale = float(jnp.max(jnp.abs(x))) + 1e-12
        assert float(jnp.max(jnp.abs(x - y))) <= tol * scale


# -- (b) the mixer ------------------------------------------------------------------------

def _smallthinker_cfg():
    with open(os.path.join(CONFIGS, "tiny-smallthinker.json")) as f:
        return smallthinker.SmallThinkerConfig.from_dict(json.load(f))


# sha256 of the tiny SmallThinker GQA mixer (window 16 and global, rotated) lowered with
# its gradients on one CPU device, ``@name_<n>`` cut, on the PARENT's tree (PR 36, commit
# 51d079f, jax 0.9.0): the q/k norms and the block-diffusion mode leave it as it was
GQA_MIXER_BEFORE = {
    16: "68bdb667c64d2b08b92fa2f5182150050daa5c9f9e26358388e7ea42e7fed0fa",
    None: "c89ca8f427d4f94359cdd52fd524f71ce84d7458663f490377835e41227c4a51",
}


def _mixer_text(window):
    cfg = _smallthinker_cfg()
    mixer = smallthinker.GQAMixer(cfg, window, True)
    a = jax.ShapeDtypeStruct((2, 40, cfg.hidden_size), jnp.float32)
    w = jax.eval_shape(lambda: mixer.init(jax.random.PRNGKey(0), jnp.zeros(a.shape)))
    text = jax.jit(jax.value_and_grad(lambda p, x: jnp.sum(mixer.apply(p, x) ** 2))).lower(
        w, a).as_text()
    return re.sub(r"@(\w+?)_\d+\b", r"@\1", text)


@pytest.mark.parametrize("window", [16, None], ids=["window", "global"])
def test_smallthinker_mixer_without_qk_norm_lowers_as_it_did(window):
    assert not smallthinker.GQAMixer(_smallthinker_cfg(), window, True).qk_norm
    text = _mixer_text(window)
    assert hashlib.sha256(text.encode()).hexdigest() == GQA_MIXER_BEFORE[window]


def test_mixer_is_the_references(model):
    cfg = sdar_moe.SdarMoeConfig.from_dict(model)
    mixer = expert_lm.GQAMixer(cfg, None, True, cfg.block_length, cfg.qk_norm)
    a = jax.random.normal(jax.random.PRNGKey(4), (2, 64, model["hidden_size"]))
    w = jax.jit(mixer.init)(jax.random.PRNGKey(5), a)["params"]
    w = dict(w, q_norm=1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(6), w["q_norm"].shape),
             k_norm=1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(7), w["k_norm"].shape))
    assert set(w) == {"wq", "wk", "wv", "wo", "q_norm", "k_norm"}

    def grads(fn):
        return jax.jit(jax.value_and_grad(lambda p, x: jnp.sum(jnp.sin(fn(p, x))), (0, 1)))(w, a)

    got = grads(lambda p, x: mixer.apply({"params": p}, x))
    want = grads(lambda p, x: ref.gqa_mixer(x, p, model, "highest"))
    _assert_close(want, got, 2e-5)


# -- (c) the expert layer and the whole model ----------------------------------------------

def _program_block(model, held):
    cfg = sdar_moe.SdarMoeConfig.from_dict(
        dict(model, experts_held=list(held), num_experts=held[1] - held[0]))
    return sdar_moe.Block(cfg, 0)


def test_shares_of_a_deployment_add_up_to_the_uncut_layer(model):
    """The eight shares [0, 4) ... [28, 32) of one layer (32 router outputs, as 16 of 128
    is an eighth), the attention and the residual counted once, against the reference's
    layer with all 32 experts."""
    whole = dict(model, experts_held=[0, 32], num_experts=32)
    w = ref.make_weights(whole, 9)["layers"][0]
    x = jax.random.normal(jax.random.PRNGKey(10), (2, 64, model["hidden_size"]))
    want = ref.block(x, w, whole, "highest")
    after = x + ref.gqa_mixer(plain.rms_norm(x, w["attn_norm"], model["rms_norm_eps"]),
                              w["attn"], whole, "highest")
    total = after
    for lo in range(0, 32, 4):
        moe = {n: w["moe"][n][lo:lo + 4] for n in ("e_gate", "e_up", "e_down")}
        total = total + _program_block(model, (lo, lo + 4)).apply(
            {"params": dict(w, moe=moe)}, x) - after
    np.testing.assert_allclose(total, want, atol=2e-5)
    m = plain.rms_norm(after, w["ffn_norm"], model["rms_norm_eps"])
    parts = sum(ref.expert_layer(m, w, whole, "highest", held=(lo, lo + 4))
                for lo in range(0, 32, 4))
    np.testing.assert_allclose(after + parts, want, atol=2e-5)


@pytest.fixture(scope="module")
def built(model):
    """(module, the seed's weights as the reference lays them out, tokens)."""
    import fedml_tpu
    from fedml_tpu.arguments import Arguments

    args = Arguments.from_dict({"model_args": {"model": "sdar_moe", "model_config": TINY}})
    module = fedml_tpu.models.create(args.validate(for_training=False), model["vocab_size"])
    ids = np.random.default_rng(0).integers(0, model["vocab_size"] - 1, (2, 32))
    return module, ref.make_weights(model, 11), jnp.asarray(ids, jnp.int32)


def _program_loss(module, variables, tokens, mask, rng):
    loss_fn = engine.build_loss_fn(module, True, "ce", module.round_counters)
    total, (_, sums) = loss_fn(variables["params"], {}, tokens, tokens, mask, rng)
    return total, sums


def test_model_has_the_references_tree(built, model):
    module, weights, tokens = built
    init = jax.jit(lambda k: module.init(k, tokens[:1], train=False))(jax.random.PRNGKey(0))
    program = sim_kimi_linear.to_program(weights)
    assert list(init) == ["params"]
    assert (jax.tree_util.tree_map(jnp.shape, init["params"])
            == jax.tree_util.tree_map(jnp.shape, program["params"]))
    assert module.owns_loss and module.takes_targets
    assert module.round_counters == expert_lm.COUNTERS + sdar_moe.BD_COUNTERS


@pytest.fixture(scope="module")
def losses_and_grads(built, model):
    """The program's and the reference's loss-and-gradient under one step key, each jitted
    once with the row mask as an argument."""
    module, _, tokens = built
    step_key = jax.random.PRNGKey(12)
    noise = jax.random.fold_in(step_key, ref.NOISE_STREAM)
    program = jax.jit(jax.value_and_grad(
        lambda v, mask: _program_loss(module, v, tokens, mask, step_key), has_aux=True))
    reference = jax.jit(jax.value_and_grad(
        lambda w, mask: ref.loss_fn(w, tokens, mask, noise, model, "highest")))
    return program, reference, noise


@pytest.mark.parametrize("mask", [(1.0, 1.0), (1.0, 0.0)], ids=["full_batch", "half_empty_batch"])
def test_loss_and_every_gradient_are_the_references(built, model, losses_and_grads, mask):
    """Tolerances: the loss to 2e-6 relative (float32, one sum in another order); every
    leaf's gradient to 1e-4 of its largest entry (the remat's second forward and the
    grouped products' sums form FMAs elsewhere than the reference's, PR 32)."""
    module, weights, tokens = built
    program_fn, reference_fn, noise = losses_and_grads
    mask = jnp.asarray(mask)
    program = sim_kimi_linear.to_program(weights)
    (total, sums), grads = program_fn(program, mask)
    want = reference_fn(weights, mask)
    assert abs(float(total) - float(want[0])) < 2e-6 * float(want[0])
    _assert_close(want[1], sim_kimi_linear.from_program(grads), 1e-4)
    assert float(sums["bd.positions"]) == 2 * 32 * float(mask.sum())
    assert float(sums["bd.masked"]) == ref.masked_count(tokens, mask, noise, model)
    assert float(sums["moe.assignments_dropped"]) == 0.0
    # layer 0 routes every position of both copies of both rows, the last layer the noised
    # copy's alone (its clean half runs only through k and v)
    assert float(sums["moe.assignments_total"]) == (2 + 1) * 2 * 32 * model["num_experts_per_tok"]


def _bd_calls(jaxpr, found=None):
    """(kernel name, leading dim of its q operand) of every ``bd_flash_*`` call in the order
    the jaxpr holds them, sub-jaxprs included."""
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call" and eqn.params["name"].startswith("bd_"):
            found.append((eqn.params["name"], eqn.invars[0].aval.shape[0]))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _bd_calls(sub, found)
    return found


def test_the_last_block_runs_its_clean_half_only_through_k_and_v(built, model, monkeypatch):
    """One training step of the tiny preset (two layers): layer 0's expert layer routes both
    copies of both rows, the last layer's the noised copy alone (rows x L x k assignments);
    traced with the bd kernels in, layer 0 calls each of them with q over both halves (2 x 8
    query heads a row), the last layer with q over the noised half (8); ``bd.kv_only_layers``
    reads 1."""
    from fedml_tpu.core import obs

    module, weights, tokens = built
    program, mask, key = sim_kimi_linear.to_program(weights), jnp.ones(2), jax.random.PRNGKey(12)
    rows, length, k = tokens.shape + (model["num_experts_per_tok"],)
    _, sown = jax.jit(lambda v: module.apply(
        v, tokens, train=True, rngs={"noise": key}, mutable=["counters"],
        targets=(tokens, mask)))(program)
    assignments = [float(sown["counters"][f"layer{i}"]["moe"]["moe.assignments_total"])
                   for i in range(model["num_hidden_layers"])]
    assert assignments == [2 * rows * length * k, rows * length * k]

    monkeypatch.setattr(fa, "attention", lambda q, k, v, block_diffusion=None: (
        fa.bd_flash_attention(q, k, v, block_diffusion, None, True)))
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda v: _program_loss(module, v, tokens, mask, key)[0]))(program).jaxpr
    heads = rows * model["num_attention_heads"]
    calls = _bd_calls(jaxpr)
    assert [n for name, n in calls if name == "bd_flash_fwd"] == [2 * heads, heads]
    for kernel in ("bd_flash_bwd_dq", "bd_flash_bwd_dkv"):  # the backward walks the layers back
        assert [n for name, n in calls if name == kernel] == [heads, 2 * heads], kernel
    gauge = [r["value"] for r in obs.registry().export() if r["metric"] == "bd.kv_only_layers"]
    assert gauge == [1]


def test_the_engine_hands_the_step_key_on_as_the_noise_stream(built, model):
    """The key is ``fold_in(step key, NOISE_STREAM)`` whatever ``has_dropout`` says, and
    another step key draws another mask."""
    module, weights, tokens = built
    program, mask = sim_kimi_linear.to_program(weights), jnp.ones(2)
    seen = []
    real = sdar_moe.draw_noise
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(sdar_moe, "draw_noise", lambda key, *a: seen.append(key) or real(key, *a))
        for has_dropout in (True, False):
            loss_fn = engine.build_loss_fn(module, has_dropout, "ce", module.round_counters)
            loss_fn(program["params"], {}, tokens, tokens, mask, jax.random.PRNGKey(5))
    want = jax.random.fold_in(jax.random.PRNGKey(5), engine.NOISE_STREAM)
    assert len(seen) == 2 and all(np.array_equal(k, want) for k in seen)
    masked = jax.jit(lambda key: _program_loss(module, program, tokens, mask, key)[1]["bd.masked"])
    a, b = masked(jax.random.PRNGKey(5)), masked(jax.random.PRNGKey(6))
    assert float(a) != float(b)


def test_the_reference_re_derives_the_simulators_keys():
    """``noise_keys`` against the simulator's chain, written here from its code: the
    round's split of ``PRNGKey(seed + 11)``, the devices' split of the round's sub-key
    folded with the round, the step's fold, the stream's fold."""
    seed = 2147483700
    key = jax.random.PRNGKey(seed + 11)
    for unit in range(2):
        key, sub = jax.random.split(key)
        device = jax.random.split(jax.random.fold_in(sub, 0), 1)[0]
        want = [jax.random.fold_in(jax.random.fold_in(device, s), engine.NOISE_STREAM)
                for s in range(3)]
        got = ref.noise_keys(seed, unit, 3)
        assert all(np.array_equal(a, b) for a, b in zip(want, got, strict=True))
    assert ref.NOISE_STREAM == engine.NOISE_STREAM
    assert ref.stream_order([1, 3, 2, 3]) == [1, 3, 2, 0]


def test_eval_reports_its_own_loss_and_the_masked_accuracy(built, model):
    module, weights, tokens = built
    program = sim_kimi_linear.to_program(weights)
    loss_sum, correct, count = engine.make_eval_fn(module)(program, tokens, tokens, jnp.ones(2))
    key = jax.random.fold_in(jax.random.PRNGKey(0), engine.NOISE_STREAM)
    want = jax.jit(lambda w: ref.loss_fn(w, tokens, jnp.ones(2), key, model, "highest"))(weights)
    assert float(count) == 2.0 and float(loss_sum / count) == pytest.approx(float(want), rel=1e-5)
    assert 0.0 <= float(correct / count) <= 1.0
    # the same batch reads the same: one key for every batch
    again = engine.make_eval_fn(module)(program, tokens, tokens, jnp.ones(2))
    assert float(again[0]) == float(loss_sum)


def test_padded_engine_takes_the_model(built, model):
    """``build_local_train`` goes through the same ``build_loss_fn``; its step's key is the
    scan's, so the first step's loss is the reference's under that key."""
    module, weights, tokens = built
    args = types.SimpleNamespace(client_optimizer="sgd", learning_rate=0.002, epochs=1)
    train = jax.jit(engine.build_local_train(module, args, batch_size=2, padded_n=2))
    result = train(sim_kimi_linear.to_program(weights), tokens, tokens, 2, jax.random.PRNGKey(0))
    assert np.isfinite(float(result.loss)) and float(result.loss) > 0.0


def test_init_and_a_bare_forward_noise_nothing(built, model):
    module, weights, tokens = built
    logits = jax.jit(module.apply)(sim_kimi_linear.to_program(weights), tokens)
    assert logits.shape == tokens.shape + (model["vocab_size"],)
    with pytest.raises(ValueError, match="blocks of 4"):
        module.apply(sim_kimi_linear.to_program(weights), tokens[:, :30])


def test_the_round_holds_the_scopes(model):
    from benchmark import run
    from benchmark.tests.test_compile_v5e_glm47_flash import lowered_round

    named = lowered_round(jax.devices(), model, run.load_traffic("tiny.fedavg.sdar"), sim_sdar,
                          ref.make_weights, sim_kimi_linear.to_program, "cpu").as_text(
                              debug_info=True)
    for scope in ("lm.attn.bd", "lm.bd.noise", "fed.loss", "lm.head", "lm.embed",
                  "lm.moe.route", "lm.moe.experts", "fed.local_step"):
        assert scope in named, scope
    assert "lm.attn.global" not in named and "lm.mtp" not in named


# -- (d) the round through the runner --------------------------------------------------------

def test_packed_round_through_the_runner_is_the_references_round(model, monkeypatch):
    """``fedml_tpu.init`` -> ``models.create`` -> ``FedMLRunner.run()`` -> ``XLASimulator``
    (packed), one round of 8 ragged clients on one device, against the reference's FedAvg
    round with the masks it re-derives: the round's ``bd.masked`` is the reference's to the
    position, and a round whose counters say no objective was trained fails its unit."""
    from benchmark import run
    from jax.sharding import Mesh

    traffic = run.load_traffic("tiny.fedavg.sdar")
    # the cell's one device (tests/conftest.py gives the process eight)
    monkeypatch.setattr("fedml_tpu.simulation.xla.fed_sim.create_fl_mesh",
                        lambda: Mesh(np.asarray(jax.devices()[:1]), ("client",)))
    driver = sim_sdar.Driver(model, traffic, 2147483700, 1, "cpu")
    driver.setup()
    assert driver.sim.mesh.devices.size == 1
    driver.first_units()
    record = driver.sim.round_log[-1]
    steps, length = sum(traffic["shard_sequences"]), traffic["sequence_length"]  # batch 1
    assert record["bd.positions"] == 2 * steps * length
    assert 0 < record["bd.masked"] < steps * length
    assert record["moe.assignments_dropped"] == 0.0
    unit = driver.run_unit()
    assert not unit["failed"]
    program = driver.program
    masked = record["bd.masked"]
    for planted in (0.0, float(steps * length)):  # no mask drawn, every position masked
        monkeypatch.setattr(sim_kimi_linear.Driver, "run_unit", lambda self: dict(unit))
        driver.sim.round_log[-1]["bd.masked"] = planted
        assert driver.run_unit()["failed"]
    driver.sim.round_log[-1]["bd.masked"] = masked
    driver.release()
    correct, table = compare.judge(compare.numbers(program, driver.reference_readings()),
                                   traffic["limits"])
    assert correct, table
    assert driver.reference_masked == [masked]


# -- (e) validation ------------------------------------------------------------------------

def test_model_config_is_validated(model):
    cfg = sdar_moe.SdarMoeConfig.from_dict(model)
    assert cfg.experts_held == (0, 4) and cfg.n_routed_experts == 32
    assert cfg.block_length == 4 and cfg.qk_norm
    with open(os.path.join(CONFIGS, "sdar-30b-a3b-sim.json")) as f:
        cell = sdar_moe.SdarMoeConfig.from_dict(json.load(f))
    assert (cell.hidden_size, cell.num_attention_heads, cell.num_key_value_heads, cell.head_dim,
            cell.moe_intermediate_size, cell.n_routed_experts, cell.num_experts_per_token) == (
                2048, 32, 4, 128, 768, 128, 8)
    assert cell.experts_held == (0, 16) and cell.block_length == 4


@pytest.mark.parametrize("key,value,error,says", [
    ("rope_scaling", {"type": "yarn"}, NotImplementedError, "rope_scaling"),
    ("tie_word_embeddings", True, NotImplementedError, "tie_word_embeddings"),
    ("mlp_only_layers", [1], NotImplementedError, "mlp_only_layers"),
    ("decoder_sparse_step", 2, NotImplementedError, "decoder_sparse_step"),
    ("use_sliding_window", True, NotImplementedError, "use_sliding_window"),
    ("model_type", "qwen3_moe", NotImplementedError, "model_type"),
    ("shared_expert_intermediate_size", 768, ValueError, "unknown keys"),
    ("experts_held", [0, 8], ValueError, "counts the experts held"),
    ("num_key_value_heads", 3, ValueError, "key/value heads"),
    ("block_length", 3, ValueError, "block_length"),
    ("noise_t_range", [0.0, 1.0], ValueError, "noise_t_range")])
def test_model_config_refuses(model, key, value, error, says):
    with pytest.raises(error, match=says):
        sdar_moe.SdarMoeConfig.from_dict(dict(model, **{key: value}))
