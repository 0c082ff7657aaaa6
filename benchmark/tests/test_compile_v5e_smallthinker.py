"""Rehearsal 3 for ``sim.fedavg.smallthinker.1chip``: its round program compiled at
its real size for a described v5e (``test_compile_v5e.py``'s recipe with the module
from ``fedml_tpu.models.create``; nothing runs), with ``memory_analysis()`` printed.
The numbers in ``configs/smallthinker-21b-a3b-sim.json``'s ``bytes_reckoned`` come
from ``pytest -s`` of this file.  Run by hand, not by tier-1, and in a process of
its own: one process describes a topology at a time."""

from __future__ import annotations

import sys

from benchmark.tests.test_compile_v5e import ROOT, _cell, _report, topo  # noqa: F401

if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def _round(topo, cell):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import fedml_tpu
    from benchmark import reference_smallthinker
    from benchmark.drivers import sim_kimi_linear
    from benchmark.drivers.sim_smallthinker import Driver
    from fedml_tpu.arguments import Arguments
    from fedml_tpu.ops.flash_attention import flash_attention
    from fedml_tpu.simulation.xla.algorithms import create_inmesh_algorithm
    from fedml_tpu.simulation.xla.fed_sim import XLASimulator

    # jax's default backend here is the CPU: say outright what ``attention`` resolves to on ``tpu``
    sys.modules["fedml_tpu.ops.flash_attention"].attention = (
        lambda q, k, v, causal=True, window=None: flash_attention(
            q, k, v, causal=causal, window=window))
    driver = Driver.__new__(Driver)
    driver.model, driver.traffic, driver.seed, driver.device_type = cell.model, cell.traffic, 0, "tpu"
    driver.shards = [None] * len(cell.traffic["shard_sequences"])
    driver.batch, driver.lr = int(cell.traffic["batch_sequences"]), float(cell.traffic["learning_rate"])
    args = Arguments.from_dict(driver.arguments())
    mesh = Mesh(np.array(topo.devices[:1]), ("client",))
    sim = XLASimulator.__new__(XLASimulator)  # the round builder, without the data upload
    sim.args, sim.mesh, sim.n_dev = args, mesh, 1
    sim.module = fedml_tpu.models.create(args, cell.model["vocab_size"])
    sim.clients_per_round = int(cell.traffic["clients_per_round"])
    sim.batch_size, sim.max_client_n = driver.batch, max(cell.traffic["shard_sequences"])
    sim.needs_stack = sim.sharded_state = False
    sim.loss_kind, sim.algo = "ce", create_inmesh_algorithm(args)
    sim._build_packed_round_fn()

    length, n_rows = int(cell.traffic["sequence_length"]), sum(cell.traffic["shard_sequences"])
    steps = sum(-(-n // driver.batch) for n in cell.traffic["shard_sequences"])
    quantum = max(1, -(-sim.s_max // 8))
    bucket = min(-(-steps // quantum) * quantum, sim.s_max)
    repl, split = NamedSharding(mesh, P()), NamedSharding(mesh, P("client"))

    def s(shape, dtype, sharding):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    shapes = jax.eval_shape(lambda: sim_kimi_linear.to_program(
        reference_smallthinker.make_weights(cell.model, 0)))
    variables = jax.tree_util.tree_map(lambda v: s(v.shape, v.dtype, repl), shapes)
    b = driver.batch
    inputs = (variables, (), s((n_rows, length), jnp.int32, repl), s((n_rows, length), jnp.int32, repl),
              s((1, bucket, b), jnp.int32, split), s((1, bucket, b), jnp.float32, split),
              s((1, bucket), jnp.float32, split), s((1, bucket), jnp.float32, split),
              s((1, bucket), jnp.int32, split), s((1,), jnp.int32, split),
              s((1, 2), jnp.uint32, split), s((sim.slots,), jnp.float32, split))
    return sim._round_fn.lower(*inputs).compile()


def test_smallthinker_round_one_chip(topo):  # noqa: F811
    compiled = _round(topo, _cell("sim.fedavg.smallthinker.1chip"))
    _report("sim.fedavg.smallthinker.1chip round program", compiled)
    text = compiled.as_text()
    for kernel in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert kernel in text, f"no {kernel} call in the program"
    assert "ragged-dot" in text or "ragged_dot" in text, "no grouped product in the program"
