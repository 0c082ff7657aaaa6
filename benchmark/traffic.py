"""The one general generator: a traffic file's parameters and ``--seed`` in,
weights and token shards out.  A new traffic mix is a new data file under
``benchmark/traffic/``; nothing here knows a cell by name.

Every seed gives the same multiset of shard sizes in another order (so the
work of a round does not depend on the seed) and other tokens and weights.
Under partial participation (``clients_per_round`` below the number of
shards) the first ``clients_per_round`` sizes of ``shard_sequences`` are the
sampled cohort's and the rest the other clients': each seed orders the two
groups apart, so the cohort that the window trains keeps its multiset.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import reference


def _key(seed: int, stream: int):
    # --seed may exceed 32 signed bits: split it over two folds
    seed = int(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(seed % (2**31)), seed // (2**31))
    return jax.random.fold_in(key, stream)


def make_shards(traffic: dict, vocab_size: int, seed: int) -> list:
    """Per client (x, y): int32 [n, L] tokens and their next tokens, drawn
    from the vocabulary slice with a skew (id = V * u**skew: a few ids take
    most of the mass, as words do).  Rows all differ."""
    rng = np.random.default_rng([int(seed), 1])
    listed = np.asarray(traffic["shard_sequences"], np.int64)
    k = int(traffic.get("clients_per_round", len(listed)))
    # every call of the window is round 0 of a one-round run: one cohort
    cohort = reference.sampled_clients(0, len(listed), k)
    others = np.setdiff1d(np.arange(len(listed)), cohort)
    sizes = np.empty_like(listed)
    sizes[cohort] = rng.permutation(listed[:k])
    if len(others):
        sizes[others] = rng.permutation(listed[k:])
    length = int(traffic["sequence_length"])
    skew = float(traffic.get("token_skew", 1.0))
    shards = []
    for n in sizes:
        u = rng.random((int(n), length + 1))
        ids = np.minimum((vocab_size * u**skew).astype(np.int32), vocab_size - 1)
        shards.append((np.ascontiguousarray(ids[:, :-1]), np.ascontiguousarray(ids[:, 1:])))
    return shards


def weight_shapes(model: dict) -> dict:
    """The reference's own layout (benchmark/reference.py)."""
    d, f, v = model["hidden_size"], model["intermediate_size"], model["vocab_size"]
    h = model["num_attention_heads"]
    k = d // h
    layer = {"attn_norm": (d,), "mlp_norm": (d,), "wq": (d, h, k), "wk": (d, h, k),
             "wv": (d, h, k), "wo": (h, k, d), "w_gate": (d, f), "w_up": (d, f),
             "w_down": (f, d)}
    return {"embed": (v, d), "final_norm": (d,), "head": (d, v),
            "layers": [dict(layer) for _ in range(model["num_hidden_layers"])]}


def _fan_in(name: str, shape: tuple) -> int:
    if name in ("wo",):
        return shape[0] * shape[1]
    if name == "embed":
        return shape[1]
    return shape[0]


@functools.partial(jax.jit, static_argnames=("shapes_key",))
def _make(key, *, shapes_key):
    out = []
    for i, (name, shape) in enumerate(shapes_key):
        if name.endswith("norm"):
            out.append(jnp.ones(shape, jnp.float32))
        else:
            std = 1.0 / np.sqrt(_fan_in(name, shape))
            out.append(std * jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32))
    return out


def make_weights(model: dict, seed: int) -> dict:
    """Float32 weights on the device, one jitted call from the seed: normal
    with variance 1/fan_in (what flax's default initializers give), norm
    scales 1."""
    shapes = weight_shapes(model)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    names = tuple((path[-1].key, shape) for path, shape in flat)
    leaves = _make(_key(seed, 0), shapes_key=names)
    return jax.tree_util.tree_unflatten(treedef, leaves)
