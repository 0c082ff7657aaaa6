"""Rehearsal 3 for ``sim.fedavg.glm47-flash.1chip``: its round program compiled at its
real size for a described v5e (``test_compile_v5e_smallthinker.py``'s recipe with this
cell's driver and reference; nothing runs), with ``memory_analysis()`` printed.  The
numbers in ``configs/glm-4.7-flash-sim.json``'s ``bytes_reckoned`` come from ``pytest -s``
of this file.  Run by hand, not by tier-1, and in a process of its own: one process
describes a topology at a time."""

from __future__ import annotations

import re
import sys

from benchmark.tests.test_compile_v5e import ROOT, _cell, _report, topo  # noqa: F401

if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def lowered_round(devices, model, traffic, driver_mod, make_weights, to_program, device_type):
    """The packed round of ``driver_mod.Driver`` (``test_compile_v5e_smallthinker.py``'s
    recipe: the round builder on a bare simulator, no data upload) lowered for one of
    ``devices`` from shapes alone.  ``tests/test_glm4_moe_lite.py`` lowers the tiny presets'
    rounds on the CPU with it."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import fedml_tpu
    from fedml_tpu.arguments import Arguments
    from fedml_tpu.simulation.xla.algorithms import create_inmesh_algorithm
    from fedml_tpu.simulation.xla.fed_sim import XLASimulator

    driver = driver_mod.Driver.__new__(driver_mod.Driver)
    driver.model, driver.traffic, driver.seed, driver.device_type = model, traffic, 0, device_type
    driver.shards = [None] * len(traffic["shard_sequences"])
    driver.batch, driver.lr = int(traffic["batch_sequences"]), float(traffic["learning_rate"])
    args = Arguments.from_dict(driver.arguments())
    mesh = Mesh(np.array(devices[:1]), ("client",))
    sim = XLASimulator.__new__(XLASimulator)  # the round builder, without the data upload
    sim.args, sim.mesh, sim.n_dev = args, mesh, 1
    sim.module = fedml_tpu.models.create(args, model["vocab_size"])
    sim.clients_per_round = int(traffic["clients_per_round"])
    sim.batch_size, sim.max_client_n = driver.batch, max(traffic["shard_sequences"])
    sim.needs_stack = sim.sharded_state = False
    sim.loss_kind, sim.algo = "ce", create_inmesh_algorithm(args)
    sim._build_packed_round_fn()

    length, n_rows = int(traffic["sequence_length"]), sum(traffic["shard_sequences"])
    steps = sum(-(-n // driver.batch) for n in traffic["shard_sequences"])
    quantum = max(1, -(-sim.s_max // 8))
    bucket = min(-(-steps // quantum) * quantum, sim.s_max)
    repl, split = NamedSharding(mesh, P()), NamedSharding(mesh, P("client"))

    def s(shape, dtype, sharding):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    shapes = jax.eval_shape(lambda: to_program(make_weights(model, 0)))
    variables = jax.tree_util.tree_map(lambda v: s(v.shape, v.dtype, repl), shapes)
    b = driver.batch
    inputs = (variables, (), s((n_rows, length), jnp.int32, repl), s((n_rows, length), jnp.int32, repl),
              s((1, bucket, b), jnp.int32, split), s((1, bucket, b), jnp.float32, split),
              s((1, bucket), jnp.float32, split), s((1, bucket), jnp.float32, split),
              s((1, bucket), jnp.int32, split), s((1,), jnp.int32, split),
              s((1, 2), jnp.uint32, split), s((sim.slots,), jnp.float32, split))
    return sim._round_fn.lower(*inputs)


def _round(topo, cell):
    from benchmark import reference_glm47_flash
    from benchmark.drivers import sim_glm47_flash
    from fedml_tpu.ops.flash_attention import flash_attention

    # jax's default backend here is the CPU: say outright what ``attention`` resolves to on ``tpu``
    sys.modules["fedml_tpu.ops.flash_attention"].attention = (
        lambda q, k, v, causal=True, window=None: flash_attention(
            q, k, v, causal=causal, window=window))
    return lowered_round(topo.devices, cell.model, cell.traffic, sim_glm47_flash,
                         reference_glm47_flash.make_weights, sim_glm47_flash.to_program,
                         "tpu").compile()


def test_glm47_flash_round_one_chip(topo):  # noqa: F811
    compiled = _round(topo, _cell("sim.fedavg.glm47-flash.1chip"))
    _report("sim.fedavg.glm47-flash.1chip round program", compiled)
    text = compiled.as_text()
    calls = {k: len(set(re.findall(r"%(" + k + r"(?:\.\d+)?) = ", text)))
             for k in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")}
    print(f"kernel calls by name: {calls}")
    # five layers and the prediction module's block, each kernel once a step: the
    # blocks' remat keeps the forward's results
    assert calls == {"flash_fwd": 6, "flash_bwd_dq": 6, "flash_bwd_dkv": 6}, calls
    assert "ragged-dot" in text or "ragged_dot" in text, "no grouped product in the program"
    assert "lm.mtp" in text, "the prediction module is not in the program"
