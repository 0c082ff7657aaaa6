"""The ``kimi_linear`` decoder against its plain reference, on the CPU in
float32 at tiny widths with the published ratios (``benchmark/configs/
tiny-kimi-linear.json``): the whole model's loss and gradients, the validation of
``model_config``, and one packed FedAvg round through ``FedMLRunner`` against the
reference's round.  ``tests/test_kimi_linear_layers.py`` holds its layers."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import compare, reference_kimi_linear as ref
from benchmark.drivers import sim_kimi_linear
from fedml_tpu.models import kimi_linear as kl
from tests.test_kimi_linear_layers import _assert_close

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = os.path.join(ROOT, "benchmark", "configs", "tiny-kimi-linear.json")


@pytest.fixture(scope="module")
def model():
    with open(TINY) as f:
        return json.load(f)


def test_model_loss_and_gradients_are_the_references(model):
    import fedml_tpu
    from fedml_tpu.arguments import Arguments

    args = Arguments.from_dict({"model_args": {"model": "kimi_linear", "model_config": TINY}})
    module = fedml_tpu.models.create(args.validate(for_training=False), model["vocab_size"])
    weights = ref.make_weights(model, 9)
    rng = np.random.default_rng(0)
    tokens, targets = (jnp.asarray(rng.integers(0, model["vocab_size"], (2, 80)), jnp.int32)
                       for _ in range(2))
    init = jax.eval_shape(lambda k: module.init(k, tokens[:1, :8], train=False),
                          jax.random.PRNGKey(0))
    program = sim_kimi_linear.to_program(weights)
    assert list(init) == ["params"]  # no counters among the model's state
    assert (jax.tree_util.tree_map(jnp.shape, init["params"])
            == jax.tree_util.tree_map(jnp.shape, program["params"]))

    def program_loss(variables):
        logp = jax.nn.log_softmax(module.apply(variables, tokens, train=True), -1)
        return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], -1))

    got = jax.jit(jax.value_and_grad(program_loss))(program)
    want = jax.jit(jax.value_and_grad(
        lambda w: ref.loss_fn(w, tokens, targets, jnp.ones(2), model, "highest")))(weights)
    assert abs(float(got[0]) - float(want[0])) < 2e-6 * float(want[0])
    _assert_close(want[1], sim_kimi_linear.from_program(got[1]), 1e-4)


def test_model_config_is_validated():
    from fedml_tpu.arguments import Arguments
    from fedml_tpu import models

    with pytest.raises(ValueError, match="model_config"):
        Arguments.from_dict({"model_config": 3}).validate(for_training=False)
    with pytest.raises(ValueError, match="names no file"):
        Arguments.from_dict({"model_config": "/no/such.json"}).validate(for_training=False)
    with pytest.raises(ValueError, match="model_config"):
        models.create(Arguments.from_dict({"model": "kimi_linear"}), 10)
    with open(TINY) as f:
        config = json.load(f)
    with pytest.raises(NotImplementedError, match="q_lora_rank"):
        kl.KimiLinearConfig.from_dict(dict(config, q_lora_rank=64))
    with pytest.raises(ValueError, match="experts_held"):
        kl.KimiLinearConfig.from_dict(dict(config, experts_held=[4, 20]))
    assert kl.KimiLinearConfig.from_dict(config).experts_held == (0, 4)


def test_packed_round_through_the_runner_is_the_references_round(model):
    """``fedml_tpu.init`` -> ``models.create`` -> ``FedMLRunner.run()`` ->
    ``XLASimulator`` (packed), one round of 8 ragged clients, against the
    reference's FedAvg round; the round's counters come out of the program."""
    from benchmark import run
    from fedml_tpu.core import obs

    traffic = run.load_traffic("tiny.fedavg.kimi-linear")
    driver = sim_kimi_linear.Driver(model, traffic, 2147483700, len(jax.devices()), "cpu")
    driver.setup()
    driver.first_units()
    record = driver.sim.round_log[-1]
    steps, layers = sum(traffic["shard_sequences"]), 4  # batch 1; expert layers 2-5
    per_step = traffic["sequence_length"] * model["num_experts_per_token"] * layers
    assert record["moe.assignments_total"] == steps * per_step
    assert 0 < record["moe.assignments_local"] < record["moe.assignments_total"]
    assert record["moe.assignments_dropped"] == 0.0
    assert record["moe.expert_load_max"] >= record["moe.expert_load_mean"] > 0
    gauges = {r["metric"]: r["value"] for r in obs.registry().export() if r["kind"] == "gauge"}
    assert gauges["moe.experts_held"] == 4 and gauges["moe.experts_total"] == 16
    assert gauges["kda.chunk"] == 64 and gauges["kda.kernel"] == 0  # d 8, and no TPU
    program = driver.program
    driver.release()
    correct, table = compare.judge(compare.numbers(program, driver.reference_readings()),
                                   traffic["limits"])
    assert correct, table
