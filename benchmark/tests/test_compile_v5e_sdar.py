"""Rehearsal 3 for ``sim.fedavg.sdar.1chip``: its round program compiled at its real size
for a described v5e (``test_compile_v5e_glm47_flash.py``'s recipe with this cell's driver
and reference; nothing runs), with ``memory_analysis()`` printed — the memory reckoning
the configuration's ``bytes_reckoned`` quotes.  Run by hand, not by tier-1, and in a
process of its own: one process describes a topology at a time."""

from __future__ import annotations

import re
import sys

from benchmark.tests.test_compile_v5e import ROOT, _cell, _report, topo  # noqa: F401
from benchmark.tests.test_compile_v5e_glm47_flash import lowered_round

if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def _round(topo, cell):
    from benchmark import reference_sdar
    from benchmark.drivers import sim_kimi_linear, sim_sdar
    from fedml_tpu.ops import flash_attention as _  # noqa: F401
    fa = sys.modules["fedml_tpu.ops.flash_attention"]

    # jax's default backend here is the CPU: say outright what ``attention`` resolves to on ``tpu``
    fa.attention = lambda q, k, v, causal=True, window=None, block_diffusion=None: (
        fa.bd_flash_attention(q, k, v, block_diffusion) if block_diffusion is not None
        else fa.flash_attention(q, k, v, causal=causal, window=window))
    return lowered_round(topo.devices, cell.model, cell.traffic, sim_sdar,
                         reference_sdar.make_weights, sim_kimi_linear.to_program,
                         "tpu").compile()


def test_sdar_round_one_chip(topo):  # noqa: F811
    compiled = _round(topo, _cell("sim.fedavg.sdar.1chip"))
    _report("sim.fedavg.sdar.1chip round program", compiled)
    text = compiled.as_text()
    calls = {k: len(set(re.findall(r"%(" + k + r"(?:\.\d+)?) = ", text)))
             for k in ("bd_flash_fwd", "bd_flash_bwd_dq", "bd_flash_bwd_dkv", "flash_fwd")}
    print(f"kernel calls by name: {calls}")
    # six layers, each kernel once a step: the blocks' remat keeps the forward's results
    assert calls == {"bd_flash_fwd": 6, "bd_flash_bwd_dq": 6, "bd_flash_bwd_dkv": 6,
                     "flash_fwd": 0}, calls
    assert "ragged-dot" in text or "ragged_dot" in text, "no grouped product in the program"
