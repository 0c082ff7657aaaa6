"""Model zoo shape tests (every hub key initializes and produces logits)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.models import hub

pytestmark = pytest.mark.heavy  # long XLA compiles; see pytest.ini


class _Args:
    def __init__(self, model, dataset="cifar10"):
        self.model = model
        self.dataset = dataset


IMAGE_CASES = [
    ("lr", 10, (2, 28, 28, 1)),
    ("cnn", 62, (2, 28, 28, 1)),
    ("cnn_web", 10, (2, 28, 28, 1)),
    ("resnet20", 10, (2, 32, 32, 3)),
    ("resnet56", 10, (2, 32, 32, 3)),
    ("resnet18_gn", 100, (2, 32, 32, 3)),
    ("mobilenet", 10, (2, 32, 32, 3)),
    ("mobilenet_v3", 10, (2, 32, 32, 3)),
    ("vgg11", 10, (2, 32, 32, 3)),
]


@pytest.mark.parametrize("name,classes,shape", IMAGE_CASES)
def test_image_model_forward(name, classes, shape):
    m = hub.create(_Args(name), classes)
    x = jnp.zeros(shape, jnp.float32)
    variables = jax.jit(lambda key: m.init(key, x, train=False))(jax.random.PRNGKey(0))
    out = jax.jit(lambda v: m.apply(v, x, train=False))(variables)
    assert out.shape == (shape[0], classes)


SEQ_CASES = [
    ("rnn", 90, (2, 16)),
    ("rnn_stackoverflow", 1004, (2, 16)),
]


@pytest.mark.parametrize("name,vocab,shape", SEQ_CASES)
def test_seq_model_forward(name, vocab, shape):
    m = hub.create(_Args(name, dataset="shakespeare"), vocab)
    x = jnp.zeros(shape, jnp.int32)
    variables = jax.jit(lambda key: m.init(key, x, train=False))(jax.random.PRNGKey(0))
    out = jax.jit(lambda v: m.apply(v, x, train=False))(variables)
    assert out.shape[0] == shape[0] and out.shape[-1] >= vocab


def test_transformer_forward():
    from fedml_tpu.models.transformer import TransformerConfig, TransformerLM

    cfg = TransformerConfig(vocab_size=128, d_model=64, n_heads=4, n_layers=2, d_ff=128)
    m = TransformerLM(cfg)
    x = jnp.zeros((2, 16), jnp.int32)
    variables = jax.jit(lambda key: m.init(key, x, train=False))(jax.random.PRNGKey(0))
    out = jax.jit(lambda v: m.apply(v, x, train=False))(variables)
    assert out.shape == (2, 16, 128)


def test_transformer_causality():
    """Changing a future token must not change past logits."""
    from fedml_tpu.models.transformer import TransformerConfig, TransformerLM

    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=2, n_layers=1, d_ff=64)
    m = TransformerLM(cfg)
    x1 = jnp.zeros((1, 8), jnp.int32)
    x2 = x1.at[0, 7].set(5)
    v = jax.jit(lambda key: m.init(key, x1, train=False))(jax.random.PRNGKey(0))
    forward = jax.jit(lambda v, x: m.apply(v, x, train=False))
    o1, o2 = forward(v, x1), forward(v, x2)
    np.testing.assert_allclose(o1[0, :7], o2[0, :7], atol=1e-5)
    assert not np.allclose(o1[0, 7], o2[0, 7])


def test_gan_pair():
    from fedml_tpu.models.gan import MNISTDiscriminator, MNISTGenerator

    g, d = MNISTGenerator(), MNISTDiscriminator()
    z = jnp.zeros((2, 100))
    gv = jax.jit(lambda key: g.init(key, z, train=False))(jax.random.PRNGKey(0))
    img = jax.jit(lambda v: g.apply(v, z, train=False))(gv)
    assert img.shape == (2, 28, 28, 1)
    dv = jax.jit(lambda key: d.init(key, img, train=False))(jax.random.PRNGKey(1))
    out = jax.jit(lambda v: d.apply(v, img, train=False))(dv)
    assert out.shape == (2, 1)


def test_unknown_model_raises():
    with pytest.raises(ValueError):
        hub.create(_Args("nope"), 10)
