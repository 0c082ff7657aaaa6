"""What a recomputing caller keeps of the token mixers' kernels (``ops/kept.py``,
``models/expert_lm.py``'s ``KEPT``): under a policy that saves the names, the
backward pass's second forward holds no forward kernel; the sparse decoders'
loss and gradients are what the policy-less remat gave; a caller without the
policy does not see the names.  On the CPU, the kernels in interpret mode."""

import collections
import importlib
import json
import os
import re

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.models import expert_lm
from fedml_tpu.models.transformer import TransformerConfig, TransformerLM
from fedml_tpu.ops import kda, kept

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
fa = importlib.import_module("fedml_tpu.ops.flash_attention")
POLICY = jax.checkpoint_policies.save_only_these_names(*expert_lm.KEPT)


def _flash(q, k, v, causal=True, window=None):
    return fa.flash_attention(q, k, v, causal, None, None, True, window)


def _kda(*args):
    return kda.kda_pallas(*args, interpret=True)


def _flash_layer(x, w):  # [1, 256, 256] -> the same: 2 heads of 128
    q, k, v = (jnp.einsum("bld,dhk->blhk", x, w[i].reshape(256, 2, 128)) for i in range(3))
    return x + _flash(q, k, v).reshape(x.shape)


def _kda_layer(x, w):  # [1, 128, 256] -> the same: 2 heads of 128
    q, k, v, g = (jnp.einsum("bld,dhk->blhk", x, w[i].reshape(256, 2, 128)) for i in range(4))
    beta = jax.nn.sigmoid(jnp.sum(g, -1))
    return x + _kda(q, k, v, -jax.nn.softplus(g), beta).reshape(x.shape)


def _kernel_calls(jaxpr, counts=None):
    """``pallas_call`` equations by kernel name, sub-jaxprs included."""
    counts = collections.Counter() if counts is None else counts
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            counts[eqn.params["name"]] += 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _kernel_calls(sub, counts)
    return counts


def _gauges(name):
    from fedml_tpu.core import obs

    return {r["labels"]["kernel"]: r["value"] for r in obs.registry().export()
            if r["kind"] == "gauge" and r["metric"] == name}


@pytest.mark.parametrize("layer,L,forward,backward,kept_bytes", [
    # out [1, 256, 2, 128] + lse [2, 1, 256]; o [1, 128, 256] + states [1, 2, 1, 128, 128]
    (_flash_layer, 256, "flash_fwd", ("flash_bwd_dq", "flash_bwd_dkv"), 4 * (65536 + 512)),
    (_kda_layer, 128, "kda_fwd", ("kda_bwd",), 4 * (32768 + 32768))], ids=["flash", "kda"])
def test_the_second_forward_calls_no_kernel_under_the_policy(layer, L, forward, backward,
                                                             kept_bytes):
    from fedml_tpu.core import obs

    x, w = jnp.ones((1, L, 256)), jnp.ones((4, 256, 256)) / 256
    obs.gauge_set("remat.kept_mib", -1, {"kernel": forward})

    def calls(**checkpoint):
        step = jax.checkpoint(layer, **checkpoint)
        loss = lambda x, w: jnp.sum(step(step(x, w), w))
        return _kernel_calls(jax.make_jaxpr(jax.grad(loss, (0, 1)))(x, w).jaxpr)

    kept_calls, plain_calls = calls(policy=POLICY), calls()
    assert kept_calls[forward] == 2 and plain_calls[forward] == 4  # a layer: one, two
    assert all(kept_calls[name] == plain_calls[name] == 2 for name in backward)
    # the gauge a traced call leaves, per kernel name: the MiB of what it named
    assert _gauges("remat.kept_mib")[forward] == kept_bytes / 2**20


def _preset(name):
    import fedml_tpu
    from fedml_tpu.arguments import Arguments

    path = os.path.join(ROOT, "benchmark", "configs", f"tiny-{name.replace('_', '-')}.json")
    with open(path) as f:
        vocab = json.load(f)["vocab_size"]
    args = Arguments.from_dict({"model_args": {"model": name, "model_config": path}})
    return fedml_tpu.models.create(args.validate(for_training=False), vocab), vocab


# the same arithmetic on the same values in another program: ``smallthinker``'s
# gradients come out to the bit; ``kimi_linear``'s two programs are fused
# differently by XLA's CPU backend and part by 7e-6 of a leaf's largest entry,
# half of what the policy-less remat and no remat at all part by (1.4e-5)
@pytest.mark.parametrize("name,kernel_of_layer,tol", [
    ("kimi_linear", ["kda_fwd", "kda_fwd", "kda_fwd", "flash_fwd", "kda_fwd"], 3e-5),
    ("smallthinker", ["flash_fwd"] * 4, 0.0)])
def test_sparse_decoders_keep_the_kernels_results_and_their_gradients(
        name, kernel_of_layer, tol, monkeypatch, capsys):
    """The tiny presets (``remat: true``) with the mixers on the kernels."""
    monkeypatch.setattr(fa, "attention", _flash)
    monkeypatch.setattr(kda, "kda", _kda)
    module, vocab = _preset(name)
    cfg = module.cfg
    assert cfg.remat and len(kernel_of_layer) == cfg.num_hidden_layers
    rng = np.random.default_rng(0)
    tokens, targets = (jnp.asarray(rng.integers(0, vocab, (1, 80)), jnp.int32) for _ in range(2))
    variables = jax.jit(lambda key: module.init(key, tokens, train=False))(jax.random.PRNGKey(0))

    def loss(variables):
        logp = jax.nn.log_softmax(module.apply(variables, tokens, train=True), -1)
        return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], -1))

    def value_and_grads():
        return jax.tree_util.tree_leaves(jax.jit(jax.value_and_grad(loss))(variables))

    got = value_and_grads()
    remat = nn.remat
    with monkeypatch.context() as plain:  # the remat as it was: no policy
        plain.setattr(expert_lm.nn, "remat", lambda cls, policy, **kw: remat(cls, **kw))
        want = value_and_grads()
    assert len(got) == len(want) > 10 and float(got[0]) == float(want[0])  # the loss
    for a, b in zip(got, want):
        assert float(jnp.max(jnp.abs(a - b))) <= tol * float(jnp.max(jnp.abs(b)))

    # what a block saves for its backward: its arguments and the kernel's two
    # results (jax puts a reduce_precision behind a residual that the forward
    # reads too, which hides that one's name), no q / k / v / rows
    x = jnp.ones((1, 80, cfg.hidden_size), cfg.dtype)
    for i, kernel in enumerate(kernel_of_layer):
        block = nn.remat(module.block_cls, static_argnums=(2,), policy=POLICY)(cfg, i)
        params = jax.jit(lambda key: block.init(key, x, False))(jax.random.PRNGKey(i))
        capsys.readouterr()
        jax.ad_checkpoint.print_saved_residuals(lambda p, x: block.apply(p, x, True), params, x)
        made = [line for line in capsys.readouterr().out.splitlines()
                if "from the argument" not in line and "from a constant" not in line]
        names = [n for n in expert_lm.KEPT if any(f"named '{n}'" in line for line in made)]
        assert len(made) == 2 and names and all(n.startswith(kernel) for n in names), made
        assert all("named" in line or "output of reduce_precision" in line for line in made)


@pytest.mark.parametrize("remat", [False, True], ids=["no_remat", "policy_less_remat"])
def test_a_caller_without_the_policy_does_not_see_the_names(remat, monkeypatch):
    """``TransformerLM``'s gradient lowers to the same text with the tags in
    place and with ``checkpoint_name`` the identity."""
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=2, n_layers=2, d_ff=64,
                            remat=remat)
    module = TransformerLM(cfg, attention_fn=_flash)
    tokens = jnp.zeros((1, 128), jnp.int32)
    variables = module.init(jax.random.PRNGKey(0), tokens)

    def lowered():  # jax numbers its private functions as it lowers: the numbers are cut
        loss = lambda v: jnp.sum(module.apply(v, tokens, train=True))
        return re.sub(r"@(\w+?)_\d+\b", r"@\1",
                      jax.jit(jax.grad(loss)).lower(variables).as_text())

    tagged = lowered()
    seen = []
    monkeypatch.setattr(kept, "checkpoint_name", lambda x, name: seen.append(name) or x)
    assert lowered() == tagged
    assert set(seen) == {"flash_fwd.out", "flash_fwd.lse"}
