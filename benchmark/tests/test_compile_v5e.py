"""Rehearsal 3: the timed programs of every cell of BENCHMARK.json compiled at
their real sizes for a described v5e:2x2 (the TPU's compiler is installed
here; nothing runs), with ``memory_analysis()`` printed.  The numbers in the
configuration files' ``bytes_reckoned`` come from ``pytest -s`` of this file.

Run by hand (``python -m pytest benchmark/tests -s``), not by tier-1.  The
topology is described inside a fixture and everything built from it inside
the tests; these tests stay in this one file (on-chip-measurement guide, 2).
"""

from __future__ import annotations

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

GIB = 1024**3


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        t = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever keeps the compiler from describing it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the cache and cannot be read back
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    return t


def _cell(name):
    from benchmark import run

    return run.load_cell(os.path.join(ROOT, "BENCHMARK.json"), name)


def _module(model):
    """The program's TransformerLM with the attention its default resolves to
    on ``tpu`` (here jax's default backend is the CPU, so say it outright)."""
    import jax.numpy as jnp

    from fedml_tpu.models.transformer import TransformerConfig, TransformerLM
    from fedml_tpu.ops.flash_attention import flash_attention

    cfg = TransformerConfig(
        vocab_size=model["vocab_size"], d_model=model["hidden_size"],
        n_heads=model["num_attention_heads"], n_layers=model["num_hidden_layers"],
        d_ff=model["intermediate_size"], max_seq_len=model["max_position_embeddings"],
        dtype=jnp.bfloat16)
    return TransformerLM(cfg, attention_fn=lambda q, k, v: flash_attention(q, k, v, causal=True))


def _variables_shape(model):
    import jax

    from benchmark import traffic
    from benchmark.drivers import flax_lm

    shapes = jax.eval_shape(lambda: traffic.make_weights(model, 0))
    return jax.eval_shape(flax_lm.to_program, shapes)


def _report(name, compiled):
    m = compiled.memory_analysis()
    args, outs, temps = (m.argument_size_in_bytes, m.output_size_in_bytes, m.temp_size_in_bytes)
    total = (args + outs + temps - m.alias_size_in_bytes) / GIB
    print(f"\n{name}: arguments {args / GIB:.2f} + outputs {outs / GIB:.2f} + temporaries "
          f"{temps / GIB:.2f} - aliased {m.alias_size_in_bytes / GIB:.2f} = {total:.2f} GiB a device")
    assert "tpu_custom_call" in compiled.as_text(), "the flash kernel is not in the program"
    assert total < 15.75, "does not fit a v5e's 16 GiB"
    return total


def _sim_round(topo, cell, n_dev):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from benchmark.drivers.sim import Driver
    from fedml_tpu.arguments import Arguments
    from fedml_tpu.simulation.xla.algorithms import create_inmesh_algorithm
    from fedml_tpu.simulation.xla.fed_sim import XLASimulator

    driver = Driver.__new__(Driver)
    driver.model, driver.traffic, driver.seed, driver.device_type = cell.model, cell.traffic, 0, "tpu"
    driver.shards = [None] * len(cell.traffic["shard_sequences"])
    driver.batch, driver.lr = int(cell.traffic["batch_sequences"]), float(cell.traffic["learning_rate"])
    args = Arguments.from_dict(driver.arguments())
    mesh = Mesh(np.array(topo.devices[:n_dev]), ("client",))
    sim = XLASimulator.__new__(XLASimulator)  # the round builder, without the data upload
    sim.args, sim.module, sim.mesh, sim.n_dev = args, _module(cell.model), mesh, n_dev
    sim.clients_per_round = int(cell.traffic["clients_per_round"])
    sim.batch_size, sim.max_client_n = driver.batch, max(cell.traffic["shard_sequences"])
    sim.needs_stack = sim.sharded_state = False
    sim.loss_kind, sim.algo = "ce", create_inmesh_algorithm(args)
    sim._build_packed_round_fn()

    length, n_rows = int(cell.traffic["sequence_length"]), sum(cell.traffic["shard_sequences"])
    steps = -(-sum(-(-n // driver.batch) for n in cell.traffic["shard_sequences"]) // n_dev)
    quantum = max(1, -(-sim.s_max // 8))
    bucket = min(-(-steps // quantum) * quantum, sim.s_max)
    repl, split = NamedSharding(mesh, P()), NamedSharding(mesh, P("client"))

    def s(shape, dtype, sharding):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    variables = jax.tree_util.tree_map(
        lambda v: s(v.shape, v.dtype, repl), _variables_shape(cell.model))
    b = driver.batch
    inputs = (variables, (), s((n_rows, length), jnp.int32, repl), s((n_rows, length), jnp.int32, repl),
              s((n_dev, bucket, b), jnp.int32, split), s((n_dev, bucket, b), jnp.float32, split),
              s((n_dev, bucket), jnp.float32, split), s((n_dev, bucket), jnp.float32, split),
              s((n_dev, bucket), jnp.int32, split), s((n_dev,), jnp.int32, split),
              s((n_dev, 2), jnp.uint32, split), s((n_dev * sim.slots,), jnp.float32, split))
    return sim._round_fn.lower(*inputs).compile()


def test_sim_round_one_chip(topo):
    _report("sim.fedavg.1chip round program", _sim_round(topo, _cell("sim.fedavg.1chip"), 1))


def test_sim_round_four_chips(topo):
    compiled = _sim_round(topo, _cell("sim.fedavg.4chip"), 4)
    _report("sim.fedavg.4chip round program", compiled)
    assert "all-reduce" in compiled.as_text(), "no psum over the client axis in the program"
