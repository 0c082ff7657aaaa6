"""The SmallThinker decoder against its plain reference, on the CPU in float32 at
tiny widths with the published ratios (``benchmark/configs/tiny-smallthinker.json``):
the flash kernels with a window and grouped kv heads (interpret mode, small explicit
blocks) against ``reference_attention``, ``window=None`` with equal heads left as it
was, each mixer, the expert layer (nothing dropped, the block function, the four shares
of a deployment adding up to the uncut layer), the whole model's loss and gradients,
one packed FedAvg round through ``FedMLRunner`` against the reference's round, and the
validation of ``model_config``."""

import importlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import compare, reference_smallthinker as ref
from benchmark.drivers import sim_kimi_linear, sim_smallthinker
from fedml_tpu.models import expert_lm, smallthinker as st

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = os.path.join(ROOT, "benchmark", "configs", "tiny-smallthinker.json")
fa = importlib.import_module("fedml_tpu.ops.flash_attention")


@pytest.fixture(scope="module")
def model():
    with open(TINY) as f:
        return json.load(f)


def _value_and_grads(fn, args):
    return jax.jit(jax.value_and_grad(lambda *a: jnp.sum(jnp.sin(fn(*a))),
                                      tuple(range(len(args)))))(*args)


def _assert_close(a, b, tol):
    for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        scale = float(jnp.max(jnp.abs(x))) + 1e-12
        assert float(jnp.max(jnp.abs(x - y))) <= tol * scale


def _qkv(L, group, Hkv=2, B=2, D=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (B, L, Hkv * group, D)),
            jax.random.normal(ks[1], (B, L, Hkv, D)), jax.random.normal(ks[2], (B, L, Hkv, D)))


# window < L, window >= L, window no multiple of a block, L no multiple of a block, a
# window under one block, blocks of unequal size, groups of 1 / 2 / 7, no window at all
@pytest.mark.parametrize("L,window,group,bq,bk", [
    (256, 64, 2, 32, 32), (200, 300, 7, 32, 64), (130, 50, 1, 32, 32), (256, 100, 7, 64, 32),
    (96, 33, 2, 32, 16), (160, 8, 7, 32, 32), (200, None, 7, 32, 32), (72, 3, 2, 16, 16)])
def test_flash_kernels_with_a_window_and_grouped_kv_heads(L, window, group, bq, bk):
    q, k, v = _qkv(L, group, seed=L)
    want = _value_and_grads(lambda *a: fa.reference_attention(*a, True, window), (q, k, v))
    got = _value_and_grads(lambda *a: fa.flash_attention(*a, True, bq, bk, True, window), (q, k, v))
    assert [g.shape for g in got[1]] == [q.shape, k.shape, v.shape]  # dK, dV at the kv heads
    _assert_close(want, got, 2e-5)


def test_reference_attention_repeats_kv_heads_and_masks_the_window():
    q, k, v = _qkv(40, 7, B=1)
    got = fa.reference_attention(q, k, v, True, 9)
    t = jnp.arange(40)
    seen = (t[:, None] >= t[None, :]) & (t[:, None] - t[None, :] < 9)
    kr, vr = (jnp.repeat(x, 7, axis=2) for x in (k, v))
    scores = jnp.where(seen, jnp.einsum("blhd,bmhd->bhlm", q, kr) / 4.0, -jnp.inf)
    want = jnp.einsum("bhlm,bmhd->blhd", jax.nn.softmax(scores, -1), vr)
    np.testing.assert_allclose(got, want, atol=2e-6)
    assert fa.attention(q, k, v, window=9).shape == q.shape
    with pytest.raises(ValueError, match="multiple"):
        fa.reference_attention(q[:, :, :13], k, v)
    with pytest.raises(ValueError, match="window"):
        fa.flash_attention(q, k, v, False, 16, 16, True, 9)


@pytest.mark.parametrize("blocks", [(32, 16), (16, 32), (64, 16), (16, 16)],
                         ids=lambda b: f"{b[0]}x{b[1]}")
@pytest.mark.parametrize("window", [1, 16, 24, 40, 200])
def test_clamped_index_maps_stay_on_live_tiles_under_a_window(blocks, window):
    """On every step of either grid the clamped index names a live tile of its row
    (column), on a live step the step's own tile; an interior tile's mask is all true,
    and the live tiles are exactly those that hold a pair the window leaves."""
    bq, bk = blocks
    L, padded = 88, 128
    tile = dict(block_q=bq, block_k=bk, causal=True, valid_len=L, window=window)
    t = np.arange(padded)
    pair = ((t[:, None] >= t[None, :]) & (t[:, None] - t[None, :] < window)
            & (t[:, None] < L) & (t[None, :] < L))
    for i in range(padded // bq):
        for j in range(padded // bk):
            live = bool(fa._tile_live(i, j, **tile))
            assert live == bool(pair[i * bq:(i + 1) * bq, j * bk:(j + 1) * bk].any())
            kj, qi = int(fa._live_k_block(i, j, **tile)), int(fa._live_q_block(i, j, **tile))
            if live:
                assert (kj, qi) == (j, i)
            if i * bq < L:
                assert fa._tile_live(i, kj, **tile)
            if j * bk < L:
                assert fa._tile_live(qi, j, **tile)
            mask = np.asarray(fa._tile_mask(i, j, (bq, bk), 0, **tile))
            if live:
                rows = min(bq, L - i * bq)  # padded query rows are cut off, not masked
                np.testing.assert_array_equal(
                    mask[:rows], pair[i * bq:i * bq + rows, j * bk:(j + 1) * bk])
            if fa._tile_interior(i, j, **tile) and i * bq < L:
                assert live and mask.all()


def test_no_window_and_equal_heads_keep_their_geometry_and_outputs():
    """What the accepted cells run did not move: the tile parameters hold no window, the
    gauges' labels are the kernel's name alone, and the outputs are bit for bit those of
    a call that never names the new arguments."""
    from fedml_tpu.core import obs

    q, k, v = _qkv(200, 1, seed=3)
    old = _value_and_grads(lambda *a: fa.flash_attention(*a, True, 32, 32, True), (q, k, v))
    new = _value_and_grads(lambda *a: fa.flash_attention(*a, True, 32, 32, True, None), (q, k, v))
    for a, b in zip(jax.tree_util.tree_leaves(old), jax.tree_util.tree_leaves(new)):
        np.testing.assert_array_equal(a, b)
    tile = fa._tiling("flash_fwd", 2048, (1024, 1024), True, 2048)[0]
    assert tile == dict(block_q=1024, block_k=1024, causal=True, valid_len=2048)
    assert fa._kv_head(1)(5) == 5
    assert fa._choose_blocks("flash_bwd_dkv", 16384, 128, jnp.bfloat16) == (512, 512)
    assert fa._choose_blocks("flash_fwd", 16384, 128, jnp.bfloat16) == (1024, 1024)
    # a windowed call leaves its own series beside the global one's
    fa._tiling("flash_fwd", 16384, (1024, 1024), True, 16384, 4096, 7)
    fa._tiling("flash_fwd", 16384, (1024, 1024), True, 16384, None, 7)
    gauges = {(r["metric"], r["labels"].get("kernel"), r["labels"].get("window")): r["value"]
              for r in obs.registry().export() if r["metric"].startswith("flash.")}
    # the grids hold their live tiles: 8 pairs of rows at 10 steps under the window (the
    # band's first rows are short), 17 steps a pair of the whole triangle
    assert gauges[("flash.live_step_share", "flash_fwd", "4096")] == 70 / 80
    assert gauges[("flash.live_step_share", "flash_fwd", None)] == 1.0
    assert gauges[("flash.grid_steps", "flash_fwd", "4096")] == 80
    assert gauges[("flash.grid_steps", "flash_fwd", None)] == 136
    assert gauges[("flash.window", "flash_fwd", "4096")] == 4096
    assert gauges[("flash.window", "flash_fwd", None)] == 0
    assert gauges[("flash.kv_group", "flash_fwd", "4096")] == 7


@pytest.mark.parametrize("layer", [0, 1], ids=["global_nope", "window_rope"])
def test_mixer_is_the_reference(model, layer):
    cfg = st.SmallThinkerConfig.from_dict(model)
    w = ref.make_weights(model, 3)["layers"][layer]["attn"]
    a = jax.random.normal(jax.random.PRNGKey(4), (2, 50, model["hidden_size"]))
    window = model["sliding_window_size"] if model["sliding_window_layout"][layer] else None
    mixer = st.GQAMixer(cfg, window, bool(model["rope_layout"][layer]))
    got = _value_and_grads(lambda p, x: mixer.apply({"params": p}, x), (w, a))
    want = _value_and_grads(lambda p, x: ref.gqa_mixer(x, p, model, layer, "highest"), (w, a))
    _assert_close(want, got, 2e-5)


def _program_block(model, held, layer=1):
    cfg = st.SmallThinkerConfig.from_dict(
        dict(model, experts_held=list(held), moe_num_primary_experts=held[1] - held[0]))
    return st.Block(cfg, layer)


def test_block_is_the_reference_and_drops_nothing(model):
    w = ref.make_weights(model, 5)["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 40, model["hidden_size"]))
    block = _program_block(model, (0, 4))

    def program(p, h):
        out, sown = block.apply({"params": p}, h, True, mutable=["counters"])
        return out, sown["counters"]["moe"]

    got = _value_and_grads(lambda p, h: program(p, h)[0], (w, x))
    want = _value_and_grads(lambda p, h: ref.block(h, p, model, 1, "highest"), (w, x))
    _assert_close(want, got, 2e-5)
    assert float(jnp.max(jnp.abs(got[1][0]["router"]))) > 0.0  # the softmax's weights train it
    counters = jax.jit(program)(w, x)[1]
    total = 2 * 40 * model["moe_num_active_primary_experts"]
    assert float(counters["moe.assignments_total"]) == total
    assert float(counters["moe.assignments_dropped"]) == 0.0
    assert 0 < float(counters["moe.assignments_local"]) < total


def test_nothing_dropped_when_every_token_routes_to_held_experts(model):
    """The worst case of the top tier: all T * k assignments land here."""
    cfg = st.SmallThinkerConfig.from_dict(model)
    T, k, d = 80, cfg.num_experts_per_token, cfg.hidden_size
    w = ref.make_weights(model, 7)["layers"][1]["moe"]
    m = jax.random.normal(jax.random.PRNGKey(8), (T, d))
    chosen = jnp.tile(jnp.arange(k), (T, 1))  # experts 0..k-1, all held
    weights = jnp.full((T, k), 1.0 / k)
    out, counters = expert_lm.grouped_experts(
        m, chosen, weights, cfg.experts_held, w["e_gate"], w["e_up"], w["e_down"],
        cfg.n_routed_experts, jax.nn.relu)
    assert float(counters["moe.assignments_local"]) == T * k
    assert float(counters["moe.assignments_dropped"]) == 0.0
    want = ref.expert_layer(m[None], chosen[None], weights[None], w, model, "highest")[0]
    np.testing.assert_allclose(out, want, atol=2e-5)


@pytest.mark.parametrize("total,held,routed,want", [
    (65536, 8, 256, (8192, (1, 2, 8), False)),    # kimi-linear's cell: as before this function
    (98304, 16, 64, (73728, (1, 2), True)),       # this model's cell
    (240, 4, 16, (180, (1, 2), True)),            # the tiny presets
    (98304, 4, 64, (18432, (1, 2, 6), True)),     # three times the even share just over the floor
    (98304, 64, 64, (98304, (1,), True)),         # a whole model: one block, nothing to pad
    (5, 8, 256, (1, (1, 2, 5), False))])
def test_block_and_tier_function(total, held, routed, want):
    """8 of 256 keeps its blocks of T k / 8 in tiers of 1 / 2 / 8; the even share lies
    at a third of the first tier or below; the top tier always holds every assignment;
    padding goes with a block that the share set."""
    base, tiers, padded = expert_lm.expert_blocks(total, held, routed)
    assert (base, tiers, padded) == want
    assert base * tiers[-1] >= total and min(total, 3 * total * held / routed) <= base * tiers[0]


@pytest.mark.parametrize("share", [0.1, 0.45, 0.7, 1.0])
def test_padded_tiers_give_the_unpadded_result(model, share):
    """The rows a tier pads its last group with are zeros in and out: whatever part of
    the assignments lands here (under the first tier, over it, all of them), the layer's
    result and gradients are the reference's, and nothing is dropped."""
    cfg = st.SmallThinkerConfig.from_dict(model)
    T, k = 80, cfg.num_experts_per_token
    w = ref.make_weights(model, 13)["layers"][2]["moe"]
    m = jax.random.normal(jax.random.PRNGKey(14), (T, cfg.hidden_size))
    # the first ``share`` of the tokens choose held experts (0..k-1), the rest absent ones
    here = (jnp.arange(T) < share * T)[:, None]
    chosen = jnp.where(here, jnp.arange(k)[None], 4 + jnp.arange(k)[None])
    weights = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(15), (T, k)), -1)

    def program(m, w):
        return expert_lm.grouped_experts(m, chosen, weights, cfg.experts_held, w["e_gate"],
                                         w["e_up"], w["e_down"], cfg.n_routed_experts, jax.nn.relu)

    counters = program(m, w)[1]
    assert float(counters["moe.assignments_local"]) == int(share * T) * k
    assert float(counters["moe.assignments_dropped"]) == 0.0
    got = _value_and_grads(lambda m, w: program(m, w)[0], (m, w))
    want = _value_and_grads(lambda m, w: ref.expert_layer(
        m[None], chosen[None], weights[None], w, model, "highest")[0], (m, w))
    _assert_close(want, got, 2e-5)


def test_shares_of_a_deployment_add_up_to_the_uncut_layer(model):
    """The four shares [0, 4) ... [12, 16) of one layer (16 routed experts), the
    attention (and the residual) counted once, against the reference's layer with all
    16 experts."""
    whole = dict(model, experts_held=[0, 16], moe_num_primary_experts=16)
    w = ref.make_weights(whole, 9)["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(10), (2, 40, model["hidden_size"]))
    want = ref.block(x, w, whole, 1, "highest")
    a = ref.rms_norm(x, w["attn_norm"], model["rms_norm_eps"])
    once = x + ref.gqa_mixer(a, w["attn"], whole, 1, "highest")  # what every chip computes alike
    total = once
    for lo in range(0, 16, 4):
        part = dict(w, moe={n: e[lo:lo + 4] for n, e in w["moe"].items()})
        total = total + _program_block(model, (lo, lo + 4)).apply({"params": part}, x) - once
    np.testing.assert_allclose(total, want, atol=2e-5)
    # and the reference's own shares add up alike
    parts = sum(ref.block(x, w, whole, 1, "highest", held=(lo, lo + 4)) - once
                for lo in range(0, 16, 4))
    np.testing.assert_allclose(parts + once, want, atol=2e-5)


def test_model_loss_and_gradients_are_the_references(model):
    import fedml_tpu
    from fedml_tpu.arguments import Arguments

    args = Arguments.from_dict({"model_args": {"model": "smallthinker", "model_config": TINY}})
    module = fedml_tpu.models.create(args.validate(for_training=False), model["vocab_size"])
    weights = ref.make_weights(model, 11)
    rng = np.random.default_rng(0)
    tokens, targets = (jnp.asarray(rng.integers(0, model["vocab_size"], (2, 80)), jnp.int32)
                       for _ in range(2))
    init = jax.eval_shape(lambda k: module.init(k, tokens[:1, :8], train=False),
                          jax.random.PRNGKey(0))
    program = sim_kimi_linear.to_program(weights)
    assert list(init) == ["params"]  # no counters among the model's state
    assert (jax.tree_util.tree_map(jnp.shape, init["params"])
            == jax.tree_util.tree_map(jnp.shape, program["params"]))
    assert module.round_counters == expert_lm.COUNTERS

    def program_loss(variables):
        logp = jax.nn.log_softmax(module.apply(variables, tokens, train=True), -1)
        return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], -1))

    got = jax.jit(jax.value_and_grad(program_loss))(program)
    want = jax.jit(jax.value_and_grad(
        lambda w: ref.loss_fn(w, tokens, targets, jnp.ones(2), model, "highest")))(weights)
    assert abs(float(got[0]) - float(want[0])) < 2e-6 * float(want[0])
    _assert_close(want[1], sim_kimi_linear.from_program(got[1]), 1e-4)


def test_model_config_is_validated(model):
    from fedml_tpu import models
    from fedml_tpu.arguments import Arguments

    with pytest.raises(ValueError, match="model_config"):
        models.create(Arguments.from_dict({"model": "smallthinker"}), 10)
    cfg = st.SmallThinkerConfig.from_dict(model)
    assert cfg.experts_held == (0, 4) and cfg.n_routed_experts == 16
    assert cfg.sliding_window_layout == (0, 1, 1, 1) and cfg.num_experts_per_token == 3
    # the published 52-entry layouts serve a model of fewer layers
    long = dict(model, sliding_window_layout=[0, 1, 1, 1] * 13, rope_layout=[0, 1, 1, 1] * 13)
    assert st.SmallThinkerConfig.from_dict(long) == cfg
    for key, value, error, says in (
            ("rope_scaling", {"type": "yarn"}, NotImplementedError, "rope_scaling"),
            ("tie_word_embeddings", True, NotImplementedError, "tie_word_embeddings"),
            ("moe_primary_router_apply_softmax", False, NotImplementedError, "apply_softmax"),
            ("moe_num_secondary_experts", 8, NotImplementedError, "secondary"),
            ("experts_held", [4, 20], ValueError, "experts_held"),
            ("experts_held", [0, 8], ValueError, "counts the experts held"),
            ("num_key_value_heads", 3, ValueError, "no multiple"),
            ("sliding_window_layout", [0, 1], ValueError, "sliding_window_layout"),
            ("rope_layout", [0, 2, 1, 1], ValueError, "rope_layout"),
            ("moe_num_active_primary_experts", 17, ValueError, "experts a token")):
        with pytest.raises(error, match=says):
            st.SmallThinkerConfig.from_dict(dict(model, **{key: value}))
    whole = {k: v for k, v in model.items() if k not in ("experts_held", "n_routed_experts")}
    assert st.SmallThinkerConfig.from_dict(whole).experts_held == (0, 4)  # a whole model of 4


def test_kimi_linear_keeps_its_parameter_tree():
    """The shared parts moved to ``models/expert_lm.py``; ``kimi_linear``'s leaves are
    where its reference's map by name expects them."""
    from benchmark import reference_kimi_linear
    from fedml_tpu.models import kimi_linear as kl

    with open(os.path.join(ROOT, "benchmark", "configs", "tiny-kimi-linear.json")) as f:
        kimi = json.load(f)
    module = kl.KimiLinearLM(kl.KimiLinearConfig.from_dict(kimi))
    init = jax.eval_shape(lambda: module.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    want = sim_kimi_linear.to_program(jax.eval_shape(
        lambda: reference_kimi_linear.make_weights(kimi, 0)))
    assert (jax.tree_util.tree_map(lambda x: x.shape, init["params"])
            == jax.tree_util.tree_map(lambda x: x.shape, want["params"]))
    assert kl.ExpertShare is expert_lm.ExpertShare  # one copy of the expert layer


def test_packed_round_through_the_runner_is_the_references_round(model):
    """``fedml_tpu.init`` -> ``models.create`` -> ``FedMLRunner.run()`` ->
    ``XLASimulator`` (packed), one round of 8 ragged clients, against the reference's
    FedAvg round; the round's counters come out of the program."""
    from benchmark import run
    from fedml_tpu.core import obs

    traffic = run.load_traffic("tiny.fedavg.smallthinker")
    driver = sim_smallthinker.Driver(model, traffic, 2147483700, len(jax.devices()), "cpu")
    driver.setup()
    driver.first_units()
    record = driver.sim.round_log[-1]
    steps, layers = sum(traffic["shard_sequences"]), model["num_hidden_layers"]  # batch 1
    per_step = traffic["sequence_length"] * model["moe_num_active_primary_experts"] * layers
    assert record["moe.assignments_total"] == steps * per_step
    assert 0 < record["moe.assignments_local"] < record["moe.assignments_total"]
    assert record["moe.assignments_dropped"] == 0.0
    assert record["moe.expert_load_max"] >= record["moe.expert_load_mean"] > 0
    gauges = {r["metric"]: r["value"] for r in obs.registry().export() if r["kind"] == "gauge"}
    assert gauges["moe.experts_held"] == 4 and gauges["moe.experts_total"] == 16
    program = driver.program
    driver.release()
    correct, table = compare.judge(compare.numbers(program, driver.reference_readings()),
                                   traffic["limits"])
    assert correct, table
