"""Rehearsal 3 for ``sim.fedavg.nemotron-nano.1chip``: its round program compiled at its
real size for a described v5e (``test_compile_v5e_glm47_flash.py``'s recipe with this
cell's driver and reference; nothing runs), with ``memory_analysis()`` printed — the
memory reckoning the configuration's ``bytes_reckoned`` quotes.  Run by hand, not by
tier-1, and in a process of its own: one process describes a topology at a time."""

from __future__ import annotations

import re
import sys

from benchmark.tests.test_compile_v5e import ROOT, _cell, _report, topo  # noqa: F401
from benchmark.tests.test_compile_v5e_glm47_flash import lowered_round

if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def _round(topo, cell):
    from benchmark import reference_nemotron_h
    from benchmark.drivers import sim_kimi_linear, sim_nemotron_h
    from fedml_tpu.ops import flash_attention as _, ssd  # noqa: F401
    fa = sys.modules["fedml_tpu.ops.flash_attention"]

    # jax's default backend here is the CPU: say outright what the dispatchers resolve to on
    # ``tpu``
    fa.attention = lambda q, k, v, causal=True, window=None: fa.flash_attention(
        q, k, v, causal=causal, window=window)
    ssd.ssd = ssd.ssd_pallas
    return lowered_round(topo.devices, cell.model, cell.traffic, sim_nemotron_h,
                         reference_nemotron_h.make_weights, sim_kimi_linear.to_program,
                         "tpu").compile()


def test_nemotron_h_round_one_chip(topo):  # noqa: F811
    compiled = _round(topo, _cell("sim.fedavg.nemotron-nano.1chip"))
    _report("sim.fedavg.nemotron-nano.1chip round program", compiled)
    text = compiled.as_text()
    calls = {k: len(set(re.findall(r"%(\w*" + k + r"\w*(?:\.\d+)?) = ", text)))
             for k in ("ssd_fwd", "ssd_bwd", "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")}
    print(f"kernel calls by name: {calls}")
    # four Mamba-2 layers and one attention layer, each kernel once a step: the blocks'
    # remat keeps the forward's results
    assert calls == {"ssd_fwd": 4, "ssd_bwd": 4, "flash_fwd": 1, "flash_bwd_dq": 1,
                     "flash_bwd_dkv": 1}, calls
    assert "ragged-dot" in text or "ragged_dot" in text, "no grouped product in the program"
