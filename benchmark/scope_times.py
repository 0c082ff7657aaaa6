"""Device time by the program's ``jax.named_scope``s, and kernels by name.

An op-line event of a TPU trace is named by its HLO instruction's text
(``%fusion.12 = ...``) and carries no scope.  The compiled program's text does:
every instruction has ``metadata={op_name="jit(..)/../lm.kda/..."}``, the name
stack jax had when it traced the op (a fusion has its root's).  So the round
program is lowered and compiled once more from the shapes its first call had
(``drivers/sim_kimi_linear.py`` keeps them; with the persistent compilation
cache on, the compile is a cache hit), its text read for {instruction name:
op_name}, and each event's SELF time added to the first of the asked-for
scopes its op_name holds.  Forward, recomputed forward and backward all carry
the scope (``transpose(jvp(lm.kda))`` holds ``lm.kda``).  What XLA adds without
metadata (copies, some of the loop's own bookkeeping) stays unattributed.
"""

from __future__ import annotations

import re
from collections import defaultdict

_INSTRUCTION = re.compile(r'^\s*(?:ROOT\s+)?(%?[\w.\-]+) = .*?metadata=\{[^}]*?op_name="([^"]*)"')


def op_names(hlo_text: str) -> dict:
    """{instruction name without '%': op_name} of a compiled module's text."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m:
            name, op_name = m.group(1).lstrip("%"), m.group(2)
            # XLA's own grouped-product kernels carry their kernel's name in place
            # of jax's name stack; in this program only the expert layers call them
            out[name] = "lm.moe.experts/" + op_name if op_name.startswith("ragged-dot") else op_name
    return out


def round_op_names(driver) -> dict | None:
    """Of the driver's round program; None where the driver kept no shapes."""
    if getattr(driver, "round_op_names", None) is None:
        fn, shapes = getattr(driver, "round_fn", None), getattr(driver, "round_shapes", None)
        if fn is None or shapes is None:
            return None
        driver.round_op_names = op_names(fn.lower(*shapes).compile().as_text())
    return driver.round_op_names


def scope_seconds(trace, names: dict, scopes: tuple) -> dict:
    """{scope: device seconds, mean over devices} of the events whose
    instruction's op_name holds the scope (the first of ``scopes`` that it
    holds); ``"unattributed"``: events of instructions without an op_name."""
    total = defaultdict(float)
    for events in trace.ops.values():
        for e in events:
            op_name = names.get(e.name.partition(" = ")[0].strip().lstrip("%"))
            if op_name is None:
                total["unattributed"] += e.self_ns / 1e9
                continue
            for scope in scopes:
                if scope in op_name:
                    total[scope] += e.self_ns / 1e9
                    break
    n = max(len(trace.ops), 1)
    return {k: v / n for k, v in total.items()}


def device_ms_per_round(ctx, scope: str) -> float | None:
    """Device milliseconds a round under ``scope`` (a substring of op_name)."""
    if ctx.trace is None or not ctx.trace.ops or not ctx.units:
        return None
    names = round_op_names(ctx.driver)
    if not names:
        return None
    seconds = scope_seconds(ctx.trace, names, (scope,)).get(scope, 0.0)
    return 1000.0 * seconds / len(ctx.units) if seconds > 0.0 else None


def kernel_seconds_by_name(trace, prefix: str) -> float:
    """Summed self time of the Pallas custom calls whose instruction is named
    ``prefix`` (XLA names it after the kernel's ``name=``: ``%flash_fwd.3``),
    mean over devices; 0.0 where nothing matches."""
    pattern = re.compile(r"^%?" + re.escape(prefix) + r"(\.\d+)? = ")
    per = [sum(e.self_ns for e in events
               if pattern.match(e.name) and 'custom_call_target="tpu_custom_call"' in e.name) / 1e9
           for events in trace.ops.values()]
    return sum(per) / max(len(per), 1)
