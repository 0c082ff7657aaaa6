"""The ``nemotron_h`` decoder against its plain reference, on the CPU in float32 at tiny
widths with the published ratios (``benchmark/configs/tiny-nemotron-h.json``): the SSD
scan's three forms against the recurrence (the Pallas kernels in interpret mode), the
reference's chunked scan against its per-token one, the Mamba-2 mixer, the expert layer
and the shares of a deployment, the whole model's loss and gradients, one packed FedAvg
round through ``FedMLRunner`` against the reference's round, the round's scopes and
counters, the validation of ``model_config``, and the gated expert layer and
``kimi_linear``'s convolution of the other decoders, which must lower as they did."""

import collections
import hashlib
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import compare, reference_nemotron_h as ref
from benchmark.drivers import sim_kimi_linear, sim_nemotron_h
from fedml_tpu.ml.engine import train as engine
from fedml_tpu.models import expert_lm, nemotron_h
from fedml_tpu.ops import ssd

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = os.path.join(ROOT, "benchmark", "configs")
TINY = os.path.join(CONFIGS, "tiny-nemotron-h.json")


@pytest.fixture(scope="module")
def model():
    with open(TINY) as f:
        return json.load(f)


def _assert_close(a, b, tol):
    for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b), strict=True):
        scale = float(jnp.max(jnp.abs(x))) + 1e-12
        assert float(jnp.max(jnp.abs(x - y))) <= tol * scale


# -- (a) the scan ---------------------------------------------------------------------------

def _ssd_operands(L, H, P, G, N, strong, seed=0):
    """x, dt, A, B, C and a cotangent; ``strong``: decays exp(dt A) down to e^-40 a token
    (dt about 1.3, A down to -16); else dt about 0.05."""
    ks = jax.random.split(jax.random.PRNGKey(seed + L), 6)
    x = jax.random.normal(ks[0], (1, L, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (1, L, H)) + (1.0 if strong else -3.0))
    A = -jnp.exp(jax.random.uniform(ks[2], (H,), minval=0.0, maxval=np.log(16.0)))
    B, C = (0.3 * jax.random.normal(k, (1, L, G, N)) for k in ks[3:5])
    return (x, dt, A, B, C), jax.random.normal(ks[5], (1, L, H, P))


def _value_and_vjp(fn, operands, cotangent):
    out, vjp = jax.vjp(fn, *operands)
    return out, vjp(cotangent)


@pytest.mark.parametrize("strong", [True, False], ids=["strong_decay", "weak_decay"])
@pytest.mark.parametrize("L", [96, 100], ids=["chunk_multiple", "ragged"])
def test_chunked_scan_is_the_recurrence(L, strong):
    """Forward and the gradients of all five inputs, chunks of 32 (three, or four with a
    padded tail).  Tolerance 5e-5 of a leaf's largest entry: float32, sums in another
    order (the gradient of ``A`` sums terms of either sign over every token; the other
    leaves agree to 5e-6)."""
    operands, w = _ssd_operands(L, 4, 8, 2, 16, strong)
    want = jax.jit(lambda: _value_and_vjp(ssd.ssd_recurrent, operands, w))()
    got = jax.jit(lambda: _value_and_vjp(lambda *a: ssd.ssd_chunked(*a, chunk=32),
                                         operands, w))()
    _assert_close(want, got, 5e-5)


@pytest.mark.parametrize("L", [300, 2200], ids=["one_run", "three_runs_of_8"])
def test_kernels_are_the_chunked_scan(L):
    """``ssd_fwd`` / ``ssd_bwd`` in interpret mode at lane-width shapes (two groups of two
    heads of 64, a state of 128): 300 tokens are one run of 3 chunks, 2,200 three runs of
    8 (the backward carries ``dS`` across runs).  Tolerance 1e-4 of a leaf's largest entry
    (``A``'s gradient sums every token's; the others agree to 3e-7)."""
    operands, w = _ssd_operands(L, 4, 64, 2, 128, True)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda: _value_and_vjp(ssd.ssd_chunked, operands, w))()
    got = jax.jit(lambda: _value_and_vjp(lambda *a: ssd.ssd_pallas(*a, interpret=True),
                                         operands, w))()
    _assert_close(want, got, 1e-4)


def test_the_references_chunked_scan_is_its_per_token_one():
    operands, w = _ssd_operands(150, 4, 8, 2, 16, True)
    want = jax.jit(lambda: _value_and_vjp(ref.ssd_per_token, operands, w))()
    got = jax.jit(lambda: _value_and_vjp(ref.ssd_by_chunks, operands, w))()
    _assert_close(want, got, 5e-5)


def _kernel_calls(jaxpr, counts=None):
    """``pallas_call`` equations by kernel name, sub-jaxprs included."""
    counts = collections.Counter() if counts is None else counts
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            counts[eqn.params["name"]] += 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _kernel_calls(sub, counts)
    return counts


# -- (b) the mixer and the expert layer ----------------------------------------------------

def _cfg(model, **changes):
    return nemotron_h.NemotronHConfig.from_dict(dict(model, **changes))


def test_mixer_is_the_references(model):
    """The Mamba-2 mixer over 300 tokens (three of the program's chunks, five of the
    reference's), with a seeded ``D``, norm scale and conv bias, values and gradients."""
    cfg = _cfg(model)
    mixer = nemotron_h.Mamba2Mixer(cfg)
    h = jax.random.normal(jax.random.PRNGKey(4), (2, 300, model["hidden_size"]))
    w = jax.jit(mixer.init)(jax.random.PRNGKey(5), h)["params"]
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    w = dict(w, D=1.0 + 0.5 * jax.random.normal(ks[0], w["D"].shape),
             norm=1.0 + 0.1 * jax.random.normal(ks[1], w["norm"].shape),
             conv_b=0.1 * jax.random.normal(ks[2], w["conv_b"].shape))
    assert set(w) == {"in_proj", "conv_w", "conv_b", "dt_bias", "A_log", "D", "norm", "out_proj"}

    def grads(fn):
        return jax.jit(jax.value_and_grad(lambda p, x: jnp.sum(jnp.sin(fn(p, x))), (0, 1)))(w, h)

    got = grads(lambda p, x: mixer.apply({"params": p}, x))
    want = grads(lambda p, x: ref.mamba_mixer(x, p, model, "highest"))
    _assert_close(want, got, 2e-5)


def test_mixer_init_follows_the_assumed_rules(model):
    cfg = _cfg(model)
    h = jnp.zeros((1, 8, model["hidden_size"]))
    w = jax.jit(nemotron_h.Mamba2Mixer(cfg).init)(jax.random.PRNGKey(0), h)["params"]
    assert np.all((jnp.exp(w["A_log"]) >= 1.0) & (jnp.exp(w["A_log"]) <= 16.0))
    dt = jax.nn.softplus(w["dt_bias"])
    assert np.all((dt >= 1e-3 * 0.999) & (dt <= 1e-1 * 1.001))
    assert np.all(w["D"] == 1.0) and np.all(w["conv_b"] == 0.0)


def _program_layer(model, held, kind_index):
    cfg = _cfg(model, experts_held=list(held), n_routed_experts=held[1] - held[0])
    return nemotron_h.Block(cfg, kind_index)


def test_shares_of_a_deployment_add_up_to_the_uncut_layer(model):
    """The sixteen shares [0, 2) ... [30, 32) of one expert layer (32 router outputs, as 8
    of 128 is a sixteenth), the shared expert and the residual counted once, against the
    reference's layer with all 32 experts."""
    whole = dict(model, experts_held=[0, 32], n_routed_experts=32)
    e = model["hybrid_override_pattern"].index("E")
    w = ref.make_weights(whole, 9)["layers"][e]
    x = jax.random.normal(jax.random.PRNGKey(10), (2, 64, model["hidden_size"]))
    want = ref.block(x, w, "E", whole, "highest")
    h = ref.rms_norm(x, w["norm"], model["norm_eps"])
    shared = ref._mlp(h, w["mixer"]["shared"]["w_up"], w["mixer"]["shared"]["w_down"], "highest",
                      lambda a: jnp.square(jax.nn.relu(a)))
    total = x + shared
    for lo in range(0, 32, 2):
        mixer = dict(w["mixer"], **{n: w["mixer"][n][lo:lo + 2] for n in ("e_up", "e_down")})
        total = total + _program_layer(model, (lo, lo + 2), e).apply(
            {"params": dict(w, mixer=mixer)}, x) - x - shared
    np.testing.assert_allclose(total, want, atol=2e-5)


# -- (c) the whole model ---------------------------------------------------------------------

@pytest.fixture(scope="module")
def built(model):
    """(module, the seed's weights as the reference lays them out, tokens)."""
    import fedml_tpu
    from fedml_tpu.arguments import Arguments

    args = Arguments.from_dict({"model_args": {"model": "nemotron_h", "model_config": TINY}})
    module = fedml_tpu.models.create(args.validate(for_training=False), model["vocab_size"])
    ids = np.random.default_rng(0).integers(0, model["vocab_size"], (2, 200))
    return module, ref.make_weights(model, 11), jnp.asarray(ids, jnp.int32)


def test_model_has_the_references_tree(built, model):
    module, weights, tokens = built
    init = jax.jit(lambda k: module.init(k, tokens[:1], train=False))(jax.random.PRNGKey(0))
    program = sim_kimi_linear.to_program(weights)
    assert list(init) == ["params"]
    assert (jax.tree_util.tree_map(jnp.shape, init["params"])
            == jax.tree_util.tree_map(jnp.shape, program["params"]))
    assert module.round_counters == expert_lm.COUNTERS + ("ssm.positions",)


@pytest.fixture(scope="module")
def losses_and_grads(built, model):
    """The program's and the reference's loss-and-gradient on the next tokens, each jitted
    once with the row mask as an argument."""
    module, _, tokens = built
    targets = jnp.roll(tokens, -1, axis=1)
    loss_fn = engine.build_loss_fn(module, True, "ce", module.round_counters)
    program = jax.jit(jax.value_and_grad(
        lambda v, mask: loss_fn(v["params"], {}, tokens, targets, mask, jax.random.PRNGKey(1)),
        has_aux=True))
    reference = jax.jit(jax.value_and_grad(
        lambda w, mask: ref.loss_fn(w, tokens, targets, mask, model, "highest")))
    return program, reference


@pytest.mark.parametrize("mask", [(1.0, 1.0), (1.0, 0.0)], ids=["full_batch", "half_empty_batch"])
def test_loss_and_every_gradient_are_the_references(built, model, losses_and_grads, mask):
    """Tolerances: the loss to 2e-6 relative; every leaf's gradient to 1e-4 of its largest
    entry (the remat's second forward and the grouped products' sums form FMAs elsewhere
    than the reference's)."""
    module, weights, tokens = built
    program_fn, reference_fn = losses_and_grads
    mask = jnp.asarray(mask)
    program = sim_kimi_linear.to_program(weights)
    (total, (_, sums)), grads = program_fn(program, mask)
    want = reference_fn(weights, mask)
    assert abs(float(total) - float(want[0])) < 2e-6 * float(want[0])
    _assert_close(want[1], sim_kimi_linear.from_program(grads), 1e-4)
    layers = model["hybrid_override_pattern"].count("M")
    assert float(sums["ssm.positions"]) == layers * tokens.size
    assert float(sums["moe.assignments_dropped"]) == 0.0


def test_the_remats_second_forward_calls_no_scan_kernel(built, model, monkeypatch):
    """With the kernels in (interpret mode; the preset's Mamba-2 widths at the kernels'
    lanes: two heads of 64 over one group, a state of 128), a training step's gradient
    calls ``ssd_fwd`` once a Mamba-2 layer and ``ssd_bwd`` once: ``KEPT`` keeps
    ``ssd_fwd.y`` and ``ssd_fwd.states``, so the blocks' recomputation finds the forward
    call dead."""
    _, _, tokens = built
    lanes = dict(model, mamba_num_heads=2, mamba_head_dim=64, n_groups=1, ssm_state_size=128)
    module = nemotron_h.NemotronHLM(nemotron_h.NemotronHConfig.from_dict(lanes))
    monkeypatch.setattr(ssd, "ssd", lambda *a: ssd.ssd_pallas(*a, interpret=True))
    loss_fn = engine.build_loss_fn(module, True, "ce", module.round_counters)
    program = sim_kimi_linear.to_program(jax.eval_shape(lambda: ref.make_weights(lanes, 0)))
    jaxpr = jax.make_jaxpr(jax.grad(lambda v: loss_fn(
        v["params"], {}, tokens, tokens, jnp.ones(2), jax.random.PRNGKey(1))[0]))(program).jaxpr
    layers = model["hybrid_override_pattern"].count("M")
    calls = _kernel_calls(jaxpr)
    assert calls["ssd_fwd"] == layers and calls["ssd_bwd"] == layers, calls


def test_the_round_holds_the_scopes(model):
    from benchmark import run
    from benchmark.tests.test_compile_v5e_glm47_flash import lowered_round

    named = lowered_round(jax.devices(), model, run.load_traffic("tiny.fedavg.nemotron"),
                          sim_nemotron_h, ref.make_weights, sim_kimi_linear.to_program,
                          "cpu").as_text(debug_info=True)
    for scope in ("lm.ssm", "lm.attn.global", "lm.moe.route", "lm.moe.experts", "lm.moe.shared",
                  "lm.norm", "lm.head", "lm.embed", "fed.loss", "fed.local_step"):
        assert scope in named, scope
    assert "lm.mtp" not in named and "lm.kda" not in named


# -- (d) the round through the runner --------------------------------------------------------

def test_packed_round_through_the_runner_is_the_references_round(model, monkeypatch):
    """``fedml_tpu.init`` -> ``models.create`` -> ``FedMLRunner.run()`` -> ``XLASimulator``
    (packed), one round of 8 ragged clients on one device, against the reference's FedAvg
    round; the round's ``ssm.positions`` counts every token of every Mamba-2 layer, and a
    round whose count is off fails its unit."""
    from benchmark import run
    from jax.sharding import Mesh

    traffic = run.load_traffic("tiny.fedavg.nemotron")
    # the cell's one device (tests/conftest.py gives the process eight)
    monkeypatch.setattr("fedml_tpu.simulation.xla.fed_sim.create_fl_mesh",
                        lambda: Mesh(np.asarray(jax.devices()[:1]), ("client",)))
    driver = sim_nemotron_h.Driver(model, traffic, 2147483700, 1, "cpu")
    driver.setup()
    driver.first_units()
    record = driver.sim.round_log[-1]
    steps, length = sum(traffic["shard_sequences"]), traffic["sequence_length"]  # batch 1
    layers = model["hybrid_override_pattern"].count("M")
    assert record["ssm.positions"] == layers * steps * length
    assert record["moe.assignments_dropped"] == 0.0
    unit = driver.run_unit()
    assert not unit["failed"]
    program = driver.program
    monkeypatch.setattr(sim_kimi_linear.Driver, "run_unit", lambda self: dict(unit))
    driver.sim.round_log[-1]["ssm.positions"] = float(layers * steps * length - 1)
    assert driver.run_unit()["failed"]
    driver.release()
    correct, table = compare.judge(compare.numbers(program, driver.reference_readings()),
                                   traffic["limits"])
    assert correct, table


# -- (e) validation ------------------------------------------------------------------------

def test_model_config_is_validated(model):
    cfg = _cfg(model)
    assert cfg.experts_held == (0, 2) and cfg.n_routed_experts == 32 and not cfg.moe_gated
    with open(os.path.join(CONFIGS, "nemotron-3-nano-30b-a3b-sim.json")) as f:
        published = json.load(f)
    cell = nemotron_h.NemotronHConfig.from_dict(published)
    assert (cell.hidden_size, cell.mamba_num_heads * cell.mamba_head_dim, cell.n_groups,
            cell.ssm_state_size, cell.num_attention_heads, cell.num_key_value_heads,
            cell.head_dim, cell.moe_intermediate_size, cell.shared_expert_intermediate_size,
            cell.n_routed_experts, cell.num_experts_per_token) == (
                2688, 4096, 8, 128, 32, 2, 128, 1856, 3712, 128, 6)
    assert cell.experts_held == (0, 8) and cell.layer_kinds == "MEMEM*EME"
    for key in ("no RoPE in attention", "d_inner"):
        assert key in published["assumed"]


@pytest.mark.parametrize("key,value,error,says", [
    ("hybrid_override_pattern", "MEM-E", NotImplementedError, "hybrid_override_pattern"),
    ("n_group", 2, NotImplementedError, "n_group"),
    ("topk_group", 2, NotImplementedError, "topk_group"),
    ("mamba_proj_bias", True, NotImplementedError, "mamba_proj_bias"),
    ("time_step_limit", [0.0, 0.1], NotImplementedError, "time_step_limit"),
    ("attention_bias", True, NotImplementedError, "attention_bias"),
    ("tie_word_embeddings", True, NotImplementedError, "tie_word_embeddings"),
    ("mlp_hidden_act", "silu", NotImplementedError, "mlp_hidden_act"),
    ("mamba_hidden_act", "gelu", NotImplementedError, "mamba_hidden_act"),
    ("hybrid_override_pattern", "MEM*", ValueError, "hybrid_override_pattern"),
    ("chunk_size", 256, ValueError, "chunk_size"),
    ("experts_held", [0, 4], ValueError, "counts the experts held"),
    ("num_key_value_heads", 3, ValueError, "key/value heads"),
    ("moe_router_activation_func", "softmax", ValueError, "unknown keys")])
def test_model_config_refuses(model, key, value, error, says):
    with pytest.raises(error, match=says):
        nemotron_h.NemotronHConfig.from_dict(dict(model, **{key: value}))


# -- (f) what the other decoders share -----------------------------------------------------

# sha256 of ``str(jax.make_jaxpr(jax.grad(loss)))`` of each sparse tiny preset (the engine's
# loss over [2, 32] tokens; function addresses cut) on the tree before this model was added
# (commit 4ef8465, jax 0.9.0): the non-gated form, the shared expert's width, the moved
# convolution and the names added to ``KEPT`` leave the gated expert layer and ``kimi_linear``
# as they were
SPARSE_JAXPR_BEFORE = {
    ("kimi_linear", "tiny-kimi-linear"):
        "5ff752ef8c88e2d78d434b801a05144b02247bff5b1437db2e1d45187e280560",
    ("smallthinker", "tiny-smallthinker"):
        "63e883725b869f0d461f824f6fa2bccd446dff3e17512447494de551857dc0c0",
    ("glm4_moe_lite", "tiny-glm47-flash"):
        "29d1b86151b5bd6ee418620a70996082983ea1fb76bbf3d1949a48410ba7deb4",
    ("sdar_moe", "tiny-sdar"):
        "004e2172c48c70a541e498cc3390dc6dc80a26c8518c3c226eefe1c8cec884a1",
}


@pytest.mark.parametrize("name,preset", list(SPARSE_JAXPR_BEFORE), ids=lambda x: str(x))
def test_the_gated_expert_layer_lowers_as_it_did(name, preset):
    import fedml_tpu
    from fedml_tpu.arguments import Arguments

    path = os.path.join(CONFIGS, preset + ".json")
    with open(path) as f:
        vocab = json.load(f)["vocab_size"]
    args = Arguments.from_dict({"model_args": {"model": name, "model_config": path}})
    module = fedml_tpu.models.create(args.validate(for_training=False), vocab)
    tokens = jnp.zeros((2, 32), jnp.int32)
    variables = jax.eval_shape(lambda: module.init(jax.random.PRNGKey(0), tokens, train=False))
    loss_fn = engine.build_loss_fn(module, True, "ce", tuple(getattr(module, "round_counters", ())))
    text = str(jax.make_jaxpr(jax.grad(lambda p: loss_fn(
        p, {}, tokens, tokens, jnp.ones(2), jax.random.PRNGKey(1))[0]))(variables["params"]))
    text = re.sub(r" at 0x[0-9a-f]+", "", text)
    assert hashlib.sha256(text.encode()).hexdigest() == SPARSE_JAXPR_BEFORE[(name, preset)]


def test_gauges_say_which_form_ran(built, model):
    from fedml_tpu.core import obs

    module, weights, tokens = built
    jax.make_jaxpr(lambda v: module.apply(v, tokens, train=False))(
        sim_kimi_linear.to_program(weights))
    gauges = {r["metric"]: r["value"] for r in obs.registry().export()
              if r["kind"] == "gauge" and r["metric"] in ("moe.gated", "ssd.kernel", "ssd.chunk")}
    assert gauges == {"moe.gated": 0, "ssd.kernel": 0, "ssd.chunk": ssd.CHUNK}
