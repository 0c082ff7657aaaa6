"""The ``glm4_moe_lite`` decoder against its plain reference, on the CPU in float32 at tiny
widths with the published ratios (``benchmark/configs/tiny-glm47-flash.json``): the shared
latent-attention mixer in its four forms (and ``kimi_linear``'s form bit for bit what it was),
the flash kernels at equal and unequal q/k and v widths (interpret mode, small explicit
blocks), the expert layer and the eight shares of a deployment, the whole model's two
losses and gradients (the embedding's and the head's leaf by leaf: two paths reach them),
what the prediction module does and does not touch, the engine's loss, one packed FedAvg
round through ``FedMLRunner`` against the reference's round, a model without the module
lowering to the round it had, and the validation of ``model_config``."""

import hashlib
import importlib
import json
import os
import re
import types

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import compare, reference as plain, reference_glm47_flash as ref
from benchmark import reference_kimi_linear as ref_kimi
from benchmark.drivers import sim_glm47_flash, sim_kimi_linear
from fedml_tpu.ml.engine import train as engine
from fedml_tpu.models import expert_lm, glm4_moe_lite as glm, kimi_linear as kl
from fedml_tpu.models.latent_attention import MLAMixer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = os.path.join(ROOT, "benchmark", "configs")
TINY = os.path.join(CONFIGS, "tiny-glm47-flash.json")
fa = importlib.import_module("fedml_tpu.ops.flash_attention")


@pytest.fixture(scope="module")
def model():
    with open(TINY) as f:
        return json.load(f)


def _value_and_grads(fn, args):
    return jax.jit(jax.value_and_grad(lambda *a: jnp.sum(jnp.sin(fn(*a))),
                                      tuple(range(len(args)))))(*args)


def _assert_close(a, b, tol):
    for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b), strict=True):
        scale = float(jnp.max(jnp.abs(x))) + 1e-12
        assert float(jnp.max(jnp.abs(x - y))) <= tol * scale


def _batch(model, rows=2, length=80, seed=0):
    """Tokens and their next tokens, as ``benchmark/traffic.py`` cuts them from one draw."""
    ids = np.random.default_rng(seed).integers(0, model["vocab_size"], (rows, length + 1))
    return jnp.asarray(ids[:, :-1], jnp.int32), jnp.asarray(ids[:, 1:], jnp.int32)


# -- (a) the shared mixer ------------------------------------------------------

def _plain_mixer(a, w, model, q_lora_rank, rope_theta):
    """The reference's attention in any of the four forms, from the reference's parts."""
    eps, rank, nope = model["rms_norm_eps"], model["kv_lora_rank"], model["qk_nope_head_dim"]
    if q_lora_rank is None:
        q = plain._einsum("bld,dhk->blhk", a, w["wq"], "highest")
    else:
        c_q = plain.rms_norm(plain._einsum("bld,dr->blr", a, w["w_q_down"], "highest"),
                             w["q_norm"], eps)
        q = plain._einsum("blr,rhk->blhk", c_q, w["w_q_up"], "highest")
    kv = plain._einsum("bld,dr->blr", a, w["w_kv_down"], "highest")
    up = plain._einsum("blr,rhk->blhk", plain.rms_norm(kv[..., :rank], w["kv_norm"], eps),
                       w["w_kv_up"], "highest")
    k_pe = kv[..., None, rank:]
    if rope_theta is not None:
        q = jnp.concatenate([q[..., :nope], plain.rotate_half(q[..., nope:], rope_theta)], -1)
        k_pe = plain.rotate_half(k_pe, rope_theta)
    k = jnp.concatenate([up[..., :nope], jnp.broadcast_to(
        k_pe, kv.shape[:2] + (q.shape[2], k_pe.shape[-1]))], -1)
    o = ref_kimi.causal_softmax_attention(q, k, up[..., nope:], "highest", rows=32)
    return plain._einsum("blhk,hkd->bld", o, w["wo"], "highest")


@pytest.mark.parametrize("q_lora_rank", [None, 12], ids=["full_rank_q", "low_rank_q"])
@pytest.mark.parametrize("rope_theta", [None, 1e6], ids=["nope", "rotated"])
def test_shared_mixer_in_its_four_forms_is_the_reference(model, q_lora_rank, rope_theta):
    cfg = glm.Glm4MoeLiteConfig.from_dict(model)
    mixer = MLAMixer(cfg, q_lora_rank, rope_theta)
    a = jax.random.normal(jax.random.PRNGKey(4), (2, 50, model["hidden_size"]))
    w = jax.jit(mixer.init)(jax.random.PRNGKey(5), a)["params"]
    assert ("wq" in w) == (q_lora_rank is None) and ("w_q_up" in w) == (q_lora_rank is not None)
    got = _value_and_grads(lambda p, x: mixer.apply({"params": p}, x), (w, a))
    want = _value_and_grads(lambda p, x: _plain_mixer(x, p, model, q_lora_rank, rope_theta), (w, a))
    _assert_close(want, got, 2e-5)
    if q_lora_rank is not None and rope_theta is not None:  # this model's form: the reference's own
        own = _value_and_grads(lambda p, x: ref.mla_mixer(x, p, model, "highest"), (w, a))
        _assert_close(own, got, 2e-5)


class _MixerBeforeItMoved(nn.Module):
    """``kimi_linear.MLAMixer`` as it stood before ``latent_attention.py`` (PR 32's tree)."""
    cfg: kl.KimiLinearConfig

    @nn.compact
    def __call__(self, h):
        cfg = self.cfg
        d, H, dt = cfg.hidden_size, cfg.num_attention_heads, cfg.dtype
        nope, pe, dv, rank = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim,
                              cfg.kv_lora_rank)

        def param(name, shape, fan_in):
            return self.param(name, expert_lm._normal(fan_in), shape, jnp.float32).astype(dt)

        q = jnp.einsum("bld,dhk->blhk", h, param("wq", (d, H, nope + pe), d))
        kv = jnp.einsum("bld,dr->blr", h, param("w_kv_down", (d, rank + pe), d))
        kv_norm = self.param("kv_norm", nn.initializers.ones, (rank,), jnp.float32)
        c = expert_lm.rms_norm(kv[..., :rank], kv_norm, cfg.rms_norm_eps)
        up = jnp.einsum("blr,rhk->blhk", c, param("w_kv_up", (rank, H, nope + dv), rank))
        k_pe = jnp.broadcast_to(kv[..., None, rank:], kv.shape[:2] + (H, pe))
        k = jnp.concatenate([up[..., :nope], k_pe], -1)
        o = fa.attention(q, k, up[..., nope:], causal=True)
        return jnp.einsum("blhk,hkd->bld", o, param("wo", (H, dv, d), H * dv))


def test_kimi_linear_keeps_its_mixer_bit_for_bit_and_its_tree():
    with open(os.path.join(CONFIGS, "tiny-kimi-linear.json")) as f:
        kimi = json.load(f)
    cfg = kl.KimiLinearConfig.from_dict(kimi)
    assert kl.MLAMixer is MLAMixer  # one mixer, shared, not copied
    a = jax.random.normal(jax.random.PRNGKey(1), (2, 40, cfg.hidden_size))
    before, now = _MixerBeforeItMoved(cfg), MLAMixer(cfg)
    w_before, w_now = (jax.jit(m.init)(jax.random.PRNGKey(2), a) for m in (before, now))
    for x, y in zip(jax.tree_util.tree_leaves_with_path(w_before),
                    jax.tree_util.tree_leaves_with_path(w_now), strict=True):
        assert x[0] == y[0]
        np.testing.assert_array_equal(x[1], y[1])  # the same names, the same draws
    got = _value_and_grads(lambda p, x: now.apply(p, x), (w_now, a))
    want = _value_and_grads(lambda p, x: before.apply(p, x), (w_now, a))
    for x, y in zip(jax.tree_util.tree_leaves(want), jax.tree_util.tree_leaves(got), strict=True):
        np.testing.assert_array_equal(x, y)
    module = kl.KimiLinearLM(cfg)
    init = jax.eval_shape(lambda: module.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    want = sim_kimi_linear.to_program(jax.eval_shape(lambda: ref_kimi.make_weights(kimi, 0)))
    assert (jax.tree_util.tree_map(lambda x: x.shape, init["params"])
            == jax.tree_util.tree_map(lambda x: x.shape, want["params"]))
    assert not module.takes_targets and module.round_counters == expert_lm.COUNTERS


# -- (b) the kernels at this model's widths ----------------------------------------

# q/k as wide as v (this model: 256 / 256) and wider (kimi-linear: 192 / 128), lengths that
# are and are not multiples of the blocks, blocks of unequal size
@pytest.mark.parametrize("D,Dv,L,bq,bk", [
    (16, 16, 128, 32, 32), (16, 16, 100, 32, 64), (16, 16, 96, 64, 32),
    (24, 16, 128, 32, 32), (24, 16, 72, 16, 32)])
def test_flash_kernels_at_equal_and_unequal_widths(D, Dv, L, bq, bk):
    ks = jax.random.split(jax.random.PRNGKey(L + D), 3)
    q, k = (jax.random.normal(key, (2, L, 3, D)) for key in ks[:2])
    v = jax.random.normal(ks[2], (2, L, 3, Dv))
    want = _value_and_grads(lambda *a: fa.reference_attention(*a, True), (q, k, v))
    got = _value_and_grads(lambda *a: fa.flash_attention(*a, True, bq, bk, True), (q, k, v))
    assert got[0].shape == () and [g.shape for g in got[1]] == [q.shape, k.shape, v.shape]
    _assert_close(want, got, 2e-5)


@pytest.mark.parametrize("kernel,D,Dv,want", [
    ("flash_fwd", 256, 256, (1024, 1024)), ("flash_bwd_dq", 256, 256, (512, 1024)),
    ("flash_bwd_dkv", 256, 256, (512, 512)),
    # the accepted cells' calls keep their geometry: 128 / 128 and 256 (192 padded) / 128
    ("flash_fwd", 128, 128, (1024, 1024)), ("flash_bwd_dq", 128, 128, (1024, 1024)),
    ("flash_bwd_dkv", 128, 128, (512, 512)), ("flash_fwd", 256, 128, (1024, 1024)),
    ("flash_bwd_dq", 256, 128, (1024, 1024)), ("flash_bwd_dkv", 256, 128, (512, 512))])
def test_blocks_chosen_at_the_cells_widths(kernel, D, Dv, want):
    """At 256 / 256 the forward's step is reckoned at the 16 MiB budget to the byte and
    keeps 1,024 x 1,024; dQ's (q, dq, dO at 256) is over it and steps its q block down."""
    assert fa._choose_blocks(kernel, 8192, D, jnp.bfloat16, Dv) == want
    assert fa._vmem_bytes(kernel, *want, D, 2, Dv) <= fa._VMEM_BUDGET


# -- (c) the expert layer and the whole model ---------------------------------------

def _program_block(model, held, layer=1):
    cfg = glm.Glm4MoeLiteConfig.from_dict(
        dict(model, experts_held=list(held), n_routed_experts=held[1] - held[0]))
    return glm.Block(cfg, layer)


@pytest.mark.parametrize("layer", [0, 1], ids=["dense", "experts"])
def test_block_is_the_reference_and_drops_nothing(model, layer):
    w = ref.make_weights(model, 5)["layers"][layer]
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 40, model["hidden_size"]))
    block = _program_block(model, (0, 8), layer)

    def program(p, h):
        out, sown = block.apply({"params": p}, h, True, mutable=["counters"])
        return out, sown.get("counters", {})

    got = _value_and_grads(lambda p, h: program(p, h)[0], (w, x))
    want = _value_and_grads(lambda p, h: ref.block(h, p, model, "highest"), (w, x))
    _assert_close(want, got, 2e-5)
    if layer:
        counters = jax.jit(program)(w, x)[1]["moe"]
        total = 2 * 40 * model["num_experts_per_tok"]
        assert float(counters["moe.assignments_total"]) == total
        assert float(counters["moe.assignments_dropped"]) == 0.0
        assert 0 < float(counters["moe.assignments_local"]) < total
        assert float(jnp.max(jnp.abs(got[1][0]["moe"]["router_bias"]))) == 0.0  # in the choice alone
        assert float(jnp.max(jnp.abs(got[1][0]["moe"]["router"]))) > 0.0


def test_shares_of_a_deployment_add_up_to_the_uncut_layer(model):
    """The eight shares [0, 8) ... [56, 64) of one expert layer (64 routed experts), the
    attention, the residual and the shared expert counted once, against the reference's
    layer with all 64 experts."""
    whole = dict(model, experts_held=[0, 64], n_routed_experts=64)
    w = ref.make_weights(whole, 9)["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(10), (2, 40, model["hidden_size"]))
    want = ref.block(x, w, whole, "highest")
    eps = model["rms_norm_eps"]
    after = x + ref.mla_mixer(plain.rms_norm(x, w["mixer_norm"], eps), w["mla"], whole, "highest")
    shared = ref_kimi.swiglu(plain.rms_norm(after, w["ffn_norm"], eps), w["moe"]["shared"]["w_gate"],
                             w["moe"]["shared"]["w_up"], w["moe"]["shared"]["w_down"], "highest")
    once = after + shared  # what every chip computes alike
    total = once
    for lo in range(0, 64, 8):
        moe = dict(w["moe"], **{n: w["moe"][n][lo:lo + 8] for n in ("e_gate", "e_up", "e_down")})
        total = total + _program_block(model, (lo, lo + 8)).apply(
            {"params": dict(w, moe=moe)}, x) - once
    np.testing.assert_allclose(total, want, atol=2e-5)
    # and the reference's own shares add up alike
    m = plain.rms_norm(after, w["ffn_norm"], eps)
    parts = sum(ref.expert_layer(m, w["moe"], whole, "highest", held=(lo, lo + 8))[0] - shared
                for lo in range(0, 64, 8))
    np.testing.assert_allclose(after + shared + parts, want, atol=2e-5)


@pytest.fixture(scope="module")
def built(model):
    """(module, the seed's weights as the reference lays them out, tokens, next tokens)."""
    import fedml_tpu
    from fedml_tpu.arguments import Arguments

    args = Arguments.from_dict({"model_args": {"model": "glm4_moe_lite", "model_config": TINY}})
    module = fedml_tpu.models.create(args.validate(for_training=False), model["vocab_size"])
    return (module, ref.make_weights(model, 11), *_batch(model))


def _program_losses(module, variables, tokens, targets, mask):
    """(main, module's, positions) as the engine's loss reads them off the model."""
    loss_fn = engine.build_loss_fn(module, True, "ce", module.round_counters)
    total, (_, sums) = loss_fn(variables["params"], {}, tokens, targets, mask, jax.random.PRNGKey(0))
    return total, sums


def test_model_has_the_references_tree_and_counters(built, model):
    module, weights, tokens, _ = built
    init = jax.jit(lambda k: module.init(k, tokens[:1, :8], train=False))(jax.random.PRNGKey(0))
    program = sim_glm47_flash.to_program(weights)
    assert list(init) == ["params"]  # no counters or losses among the model's state
    assert (jax.tree_util.tree_map(jnp.shape, init["params"])
            == jax.tree_util.tree_map(jnp.shape, program["params"]))
    assert set(init["params"]["mtp"]) == {"h_norm", "e_norm", "w_eh", "block", "norm"}
    assert module.round_counters == expert_lm.COUNTERS + expert_lm.MTP_COUNTERS
    assert module.takes_targets
    back = sim_glm47_flash.from_program(program)
    assert jax.tree_util.tree_structure(back) == jax.tree_util.tree_structure(weights)


@pytest.fixture(scope="module")
def losses_and_grads(built, model):
    """The program's and the reference's loss-and-gradient and the reference's three
    readings, each jitted once with the row mask as an argument."""
    module, _, tokens, targets = built
    program = jax.jit(jax.value_and_grad(
        lambda v, mask: _program_losses(module, v, tokens, targets, mask), has_aux=True))
    reference = jax.jit(jax.value_and_grad(
        lambda w, mask: ref.loss_fn(w, tokens, targets, mask, model, "highest")))
    readings = jax.jit(lambda w, mask: ref.losses(w, tokens, targets, mask, model, "highest"))
    return program, reference, readings


@pytest.mark.parametrize("mask", [(1.0, 1.0), (1.0, 0.0)], ids=["full_batch", "half_empty_batch"])
def test_both_losses_and_every_gradient_are_the_references(built, model, losses_and_grads, mask):
    module, weights, tokens, targets = built
    program_fn, reference_fn, readings_fn = losses_and_grads
    mask = jnp.asarray(mask)
    program = sim_glm47_flash.to_program(weights)
    got = program_fn(program, mask)
    want = reference_fn(weights, mask)
    main, mtp, positions = readings_fn(weights, mask)
    (total, sums), grads = got
    assert abs(float(total) - float(want[0])) < 2e-6 * float(want[0])
    assert abs(float(sums["lm.loss_main"]) - float(main)) < 2e-6 * float(main)
    assert abs(float(sums["mtp.loss"]) - float(mtp)) < 2e-6 * float(mtp)
    assert float(total) == pytest.approx(float(main) + model["mtp_loss_weight"] * float(mtp), rel=1e-6)
    assert float(sums["mtp.positions"]) == float(positions) == float(mask.sum()) * (80 - 1)
    assert float(sums["moe.assignments_dropped"]) == 0.0
    # three expert layers (two of the model, the module's) route every position of both rows
    assert float(sums["moe.assignments_total"]) == 3 * 2 * 80 * model["num_experts_per_tok"]
    grads = sim_glm47_flash.from_program(grads)
    _assert_close(want[1], grads, 1e-4)
    for leaf in ("embed", "head"):  # the main path and the module's both reach these
        x, y = want[1][leaf], grads[leaf]
        assert float(jnp.max(jnp.abs(x - y))) <= 2e-5 * float(jnp.max(jnp.abs(x)))


# -- (e) what the module touches ---------------------------------------------------

def test_zero_weight_trains_the_main_model_alone(built, model):
    module, weights, tokens, targets = built
    silent = glm.Glm4MoeLiteLM(glm.Glm4MoeLiteConfig.from_dict(dict(model, mtp_loss_weight=0.0)))
    program, mask = sim_glm47_flash.to_program(weights), jnp.ones(2)
    (total, sums), grads = jax.jit(jax.value_and_grad(
        lambda v: _program_losses(silent, v, tokens, targets, mask), has_aux=True))(program)
    assert float(total) == float(sums["lm.loss_main"]) and float(sums["mtp.loss"]) > 0.0
    for leaf in jax.tree_util.tree_leaves(grads["params"]["mtp"]):
        assert float(jnp.max(jnp.abs(leaf))) == 0.0
    # the main model's gradients are those of a model that has no module at all
    bare_cfg = dict(model, num_nextn_predict_layers=0, mtp_loss_weight=0.0)
    bare = glm.Glm4MoeLiteLM(glm.Glm4MoeLiteConfig.from_dict(bare_cfg))
    assert not bare.takes_targets
    without = {k: v for k, v in program["params"].items() if k != "mtp"}
    (bare_total, _), bare_grads = jax.jit(jax.value_and_grad(
        lambda p: _program_losses(bare, {"params": p}, tokens, targets, mask), has_aux=True))(without)
    assert float(bare_total) == float(total)
    _assert_close(bare_grads, {k: v for k, v in grads["params"].items() if k != "mtp"}, 1e-6)
    # and the reference with the fault planted trains the same
    want = jax.jit(jax.grad(lambda w: ref.loss_fn(w, tokens, targets, mask, model, "highest",
                                                   fault="no_mtp")))(weights)
    _assert_close(want, sim_glm47_flash.from_program(grads), 1e-4)


def test_without_train_the_module_is_not_run(built, model, monkeypatch):
    module, weights, tokens, targets = built
    program = sim_glm47_flash.to_program(weights)
    ran = []
    real = expert_lm.PredictionModule.__call__
    monkeypatch.setattr(expert_lm.PredictionModule, "__call__",
                        lambda self, *a, **k: ran.append(1) or real(self, *a, **k))
    logits = module.apply(program, tokens, train=False)
    assert logits.shape == tokens.shape + (model["vocab_size"],) and not ran
    trained, sown = module.apply(program, tokens, train=True, targets=(targets, jnp.ones(2)),
                                 mutable=["losses", "counters"])
    assert ran and set(sown) == {"losses", "counters"}
    np.testing.assert_array_equal(logits, trained)  # one set of logits either way
    with pytest.raises(ValueError, match="targets"):
        module.apply(program, tokens, train=True)
    # eval is the engine's as for any model
    loss_sum, _, count = engine.make_eval_fn(module)(program, tokens, targets, jnp.ones(2))
    main = jax.jit(lambda w: ref.losses(w, tokens, targets, jnp.ones(2), model, "highest")[0])(
        weights)
    assert float(loss_sum / count) == pytest.approx(float(main), rel=1e-5)


def test_padded_engine_takes_the_model(built, model):
    """``build_local_train`` goes through the same ``build_loss_fn``: it trains the same
    ``L``; the first step's loss is the reference's."""
    module, weights, tokens, targets = built
    args = types.SimpleNamespace(client_optimizer="sgd", learning_rate=0.002, epochs=1)
    train = jax.jit(engine.build_local_train(module, args, batch_size=2, padded_n=2, loss="ce"))
    result = train(sim_glm47_flash.to_program(weights), tokens, targets, 2, jax.random.PRNGKey(0))
    want = jax.jit(lambda w: ref.loss_fn(w, tokens, targets, jnp.ones(2), model, "highest"))(
        weights)
    assert float(result.loss) == pytest.approx(float(want), rel=1e-5)
    moved = jax.tree_util.tree_map(lambda a, b: float(jnp.max(jnp.abs(a - b))),
                                   result.variables["params"]["mtp"]["w_eh"],
                                   sim_glm47_flash.to_program(weights)["params"]["mtp"]["w_eh"])
    assert moved > 0.0


def test_module_is_under_the_blocks_remat_and_keeps_its_kernels_results(model, monkeypatch):
    """With the Pallas kernels on the path (interpret mode): four blocks — three layers and
    the module's — call each flash kernel ONCE in forward + backward; the second forward of
    the remat finds the forward's named results."""
    from tests.test_remat_kept import _kernel_calls

    monkeypatch.setattr(fa, "attention", lambda q, k, v, causal=True, window=None:
                        fa.flash_attention(q, k, v, causal, 32, 32, True, window))
    module = glm.Glm4MoeLiteLM(glm.Glm4MoeLiteConfig.from_dict(model))
    weights, (tokens, targets) = ref.make_weights(model, 3), _batch(model, 1, 64)
    program = sim_glm47_flash.to_program(weights)
    loss = lambda v: _program_losses(module, v, tokens, targets, jnp.ones(1))[0]  # noqa: E731
    calls = _kernel_calls(jax.make_jaxpr(jax.grad(loss))(program).jaxpr)
    assert calls == {"flash_fwd": 4, "flash_bwd_dq": 4, "flash_bwd_dkv": 4}
    want = jax.jit(lambda w: ref.loss_fn(w, tokens, targets, jnp.ones(1), model, "highest"))(
        weights)
    assert float(jax.jit(loss)(program)) == pytest.approx(float(want), rel=2e-6)


# -- (f) a model without the module ------------------------------------------------

def _lowered_round(model, traffic, driver_mod, make_weights, to_program):
    """The packed round lowered on one CPU device."""
    from benchmark.tests.test_compile_v5e_glm47_flash import lowered_round

    return lowered_round(jax.devices(), model, traffic, driver_mod, make_weights, to_program, "cpu")


# sha256 of ``_lowered_round`` of the tiny ``kimi_linear`` preset on PR 32's tree (commit
# c4de30e, jax 0.9.0, one CPU device): 2,339,885 characters
KIMI_ROUND_BEFORE = "60ca6490970af7e4c6e6a88a2f39a8121b5ebc15fa1324cd4e37d4cf3cdccdfd"


def test_a_model_without_the_module_lowers_to_the_round_it_had(monkeypatch):
    """The mixer moved, the LM shell and the engine's loss learned of the module: the round
    of a model that has none is instruction for instruction what it was (the accepted cells'
    programs do not move).  Since PR 34 an expert layer whose block is a large share of its
    assignments moves its rows by gathers, which the tiny preset's is (4 of 16) and
    ``kimi-linear``'s is not (8 of 256): held under the constant as that cell is, the
    preset's round is still PR 32's."""
    from benchmark import run
    from fedml_tpu.models import expert_lm

    monkeypatch.setattr(expert_lm, "GATHERED_SHARE", 2.0)
    with open(os.path.join(CONFIGS, "tiny-kimi-linear.json")) as f:
        kimi = json.load(f)
    lowered = _lowered_round(kimi, run.load_traffic("tiny.fedavg.kimi-linear"), sim_kimi_linear,
                             ref_kimi.make_weights, sim_kimi_linear.to_program)
    named = lowered.as_text(debug_info=True)
    assert "lm.mla" in named and "lm.mtp" not in named and "lm.mla.rope" not in named
    # the numbers jax gives its private functions are cut out
    text = re.sub(r"@(\w+?)_\d+\b", r"@\1", lowered.as_text())
    assert hashlib.sha256(text.encode()).hexdigest() == KIMI_ROUND_BEFORE


def test_this_models_round_holds_the_module_and_its_scopes(model):
    from benchmark import run

    named = _lowered_round(model, run.load_traffic("tiny.fedavg.glm47-flash"), sim_glm47_flash,
                           ref.make_weights, sim_glm47_flash.to_program).as_text(debug_info=True)
    for scope in ("lm.mla", "lm.mla.rope", "lm.mtp/", "lm.mtp.merge", "lm.mtp.head",
                  "lm.mtp/mtp/block/lm.mla", "lm.moe.route", "lm.moe.experts", "fed.local_step"):
        assert scope in named, scope


# -- (g) the round through the runner ------------------------------------------------

def test_packed_round_through_the_runner_is_the_references_round(model, monkeypatch):
    """``fedml_tpu.init`` -> ``models.create`` -> ``FedMLRunner.run()`` -> ``XLASimulator``
    (packed), one round of 8 ragged clients, against the reference's FedAvg round; both
    losses and the module's positions come out of the program as counters."""
    from benchmark import run
    from fedml_tpu.core import obs

    traffic = run.load_traffic("tiny.fedavg.glm47-flash")
    driver = sim_glm47_flash.Driver(model, traffic, 2147483700, len(jax.devices()), "cpu")
    driver.setup()
    driver.first_units()
    record = driver.sim.round_log[-1]
    steps, length = sum(traffic["shard_sequences"]), traffic["sequence_length"]  # batch 1
    expert_layers = model["num_hidden_layers"] - model["first_k_dense_replace"] + 1
    assert record["moe.assignments_total"] == steps * length * model["num_experts_per_tok"] * expert_layers
    assert 0 < record["moe.assignments_local"] < record["moe.assignments_total"]
    assert record["moe.assignments_dropped"] == 0.0
    assert record["mtp.positions"] == steps * (length - 1)
    # the counters are sums over the round's steps; the round's loss is the trained L
    main, mtp = record["lm.loss_main"] / steps, record["mtp.loss"] / steps
    assert 0.0 < mtp and 0.0 < main
    assert driver.sim.round_losses[-1] == pytest.approx(main + model["mtp_loss_weight"] * mtp, rel=1e-5)
    gauges = {r["metric"]: r["value"] for r in obs.registry().export() if r["kind"] == "gauge"
              and not r["labels"]}
    assert gauges["moe.experts_held"] == 8 and gauges["moe.experts_total"] == 64
    assert gauges["mla.q_lora_rank"] == 12 and gauges["mla.rope_dim"] == 4
    assert gauges["mtp.modules"] == 1 and gauges["mtp.loss_weight"] == pytest.approx(0.3)
    unit = driver.run_unit()
    assert not unit["failed"]
    # a round that trained fewer positions than sequences x (L - 1) is not this cell's
    monkeypatch.setattr(sim_kimi_linear.Driver, "run_unit", lambda self: dict(unit))
    driver.sim.round_log[-1]["mtp.positions"] -= 1
    assert driver.run_unit()["failed"]
    program = driver.program
    driver.release()
    correct, table = compare.judge(compare.numbers(program, driver.reference_readings()),
                                   traffic["limits"])
    assert correct, table
    # the reference that trains no second loss is not this round
    wrong, table = compare.judge(compare.numbers(program, driver.reference_readings(fault="no_mtp")),
                                 traffic["limits"])
    assert not wrong, table


# -- (h) validation ------------------------------------------------------------------

def test_model_config_is_validated(model):
    from fedml_tpu import models
    from fedml_tpu.arguments import Arguments

    with pytest.raises(ValueError, match="model_config"):
        models.create(Arguments.from_dict({"model": "glm4_moe_lite"}), 10)
    cfg = glm.Glm4MoeLiteConfig.from_dict(model)
    assert cfg.experts_held == (0, 8) and cfg.n_routed_experts == 64
    assert cfg.num_experts_per_token == 4 and cfg.moe_renormalize and cfg.num_shared_experts == 1
    assert cfg.num_nextn_predict_layers == 1 and cfg.mtp_loss_weight == 0.3
    whole = {k: v for k, v in model.items() if k not in ("experts_held", "n_router_outputs")}
    assert glm.Glm4MoeLiteConfig.from_dict(whole).experts_held == (0, 8)  # a whole model of 8
    with open(os.path.join(CONFIGS, "glm-4.7-flash-sim.json")) as f:
        cell = glm.Glm4MoeLiteConfig.from_dict(json.load(f))  # the cell's own file, notes and all
    assert (cell.q_lora_rank, cell.kv_lora_rank, cell.qk_nope_head_dim + cell.qk_rope_head_dim,
            cell.v_head_dim, cell.num_attention_heads) == (768, 512, 256, 256, 20)


@pytest.mark.parametrize("key,value,error,says", [
    ("rope_scaling", {"type": "yarn"}, NotImplementedError, "rope_scaling"),
    ("tie_word_embeddings", True, NotImplementedError, "tie_word_embeddings"),
    ("attention_bias", True, NotImplementedError, "attention_bias"),
    ("topk_method", "greedy", NotImplementedError, "topk_method"),
    ("n_group", 8, NotImplementedError, "n_group"),
    ("partial_rotary_factor", 0.5, NotImplementedError, "partial_rotary_factor"),
    ("q_lora_rank", None, NotImplementedError, "q_lora_rank"),
    ("num_nextn_predict_layers", 2, NotImplementedError, "num_nextn_predict_layers"),
    ("model_type", "kimi_linear", NotImplementedError, "model_type"),
    ("index_topk", 2048, ValueError, "unknown keys"),
    ("experts_held", [60, 68], ValueError, "experts_held"),
    ("experts_held", [0, 16], ValueError, "counts the experts held"),
    ("num_key_value_heads", 2, ValueError, "key/value head"),
    ("num_experts_per_tok", 65, ValueError, "experts a token"),
    ("first_k_dense_replace", 4, ValueError, "leading dense"),
    ("mtp_loss_weight", -0.1, ValueError, "mtp_loss_weight")])
def test_model_config_refuses(model, key, value, error, says):
    with pytest.raises(error, match=says):
        glm.Glm4MoeLiteConfig.from_dict(dict(model, **{key: value}))


def test_kimi_linear_still_refuses_what_it_has_no_code_for():
    with open(os.path.join(CONFIGS, "tiny-kimi-linear.json")) as f:
        kimi = json.load(f)
    for key, value in (("q_lora_rank", 12), ("mla_use_nope", False), ("num_nextn_predict_layers", 1)):
        with pytest.raises(NotImplementedError, match=key):
            kl.KimiLinearConfig.from_dict(dict(kimi, **{key: value}))
