"""Device time by the program's ``jax.named_scope``s.

An op-line event of a device trace (the ``XLA Ops`` line of an xplane) is named
by its HLO instruction's text (``%fusion.12 = ...``) and carries no scope.  The
compiled program's text does: every instruction jax traced has
``metadata={op_name="jit(..)/../fed.local_step/jvp(lm.attn)/..."}``, the name
stack at the op (a fusion has ONE of its instructions': a matmul fusion its
matmul's, whatever is fused in behind it; forward, recomputed forward and
backward all hold the scope: ``transpose(jvp(lm.attn))``).  So the join is a
table {instruction name: op_name} made from the compiled text
(:func:`program_scopes`; ``XLASimulator.round_scopes()`` hands out its round's)
and a lookup of each event's instruction in it (:func:`scope_seconds`).

Time is an event's SELF time (its duration less its direct children's: a
``while`` spans its body's events and is not counted on top of them), and an
event counts once, under the FIRST of the asked-for scopes its op_name holds.
``SCOPES`` is the program's vocabulary in the one order in which the round's
disjoint table (:func:`round_table`) is cut.
"""

from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, Iterable, Optional, Tuple

# the vocabulary, most specific first: the prediction module's block counts as
# the module's (its ``lm.mla`` / ``lm.moe.*`` nest inside ``lm.mtp``), a
# windowed, global or block-diffusion mixer before the plain ``lm.attn`` its
# name contains,
# every model scope before the engine's and the round's
SCOPES: Tuple[str, ...] = (
    "lm.mtp", "lm.kda", "lm.ssm", "lm.mla", "lm.moe.", "lm.attn.window", "lm.attn.global",
    "lm.attn.bd", "lm.attn", "lm.bd.noise", "lm.mlp", "lm.embed", "lm.head", "lm.norm",
    "fed.loss", "fed.sgd", "fed.gather", "fed.flush", "fed.exchange", "fed.server_step")
# what the table's last rows are called
STEP_ALONE = "unscoped (fed.local_step alone)"  # inside the step, under no scope of its own
NO_METADATA = "unscoped (no metadata)"  # instructions XLA added: no op_name at all
OUTSIDE = "outside every scope"  # named, outside the step and every scope: the loops' own

_INSTRUCTION = re.compile(r'^\s*(?:ROOT\s+)?(%?[\w.\-]+) = .*?metadata=\{[^}]*?op_name="([^"]*)"')


def program_scopes(hlo_text: str) -> Dict[str, str]:
    """{instruction name without '%': op_name} of a compiled module's text
    (``jitted.lower(...).compile().as_text()``)."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m:
            name, op_name = m.group(1).lstrip("%"), m.group(2)
            # XLA's own grouped-product kernels carry their kernel's name in place
            # of jax's name stack; in this program only the expert layers call them
            out[name] = "lm.moe.experts/" + op_name if op_name.startswith("ragged-dot") else op_name
    return out


def instruction_name(event_name: str) -> str:
    """``%fusion.12 = bf16[..] fusion(..)`` -> ``fusion.12``."""
    return event_name.partition(" = ")[0].strip().lstrip("%")


def self_seconds(events: Iterable[Tuple[str, int, int]]) -> list:
    """[(name, self seconds)] of ONE device's op-line events, each given as
    (name, start_ns, duration_ns): by the nesting of their intervals, an
    event's time less its direct children's."""
    spans = sorted(((int(s), int(s) + int(d), n) for n, s, d in events),
                   key=lambda e: (e[0], e[0] - e[1]))
    own = [end - start for start, end, _ in spans]
    stack: list = []  # indices of the events that are open
    for i, (start, end, _) in enumerate(spans):
        while stack and spans[stack[-1]][1] <= start:
            stack.pop()
        if stack and end <= spans[stack[-1]][1]:
            own[stack[-1]] -= end - start
        stack.append(i)
    return [(name, max(ns, 0) / 1e9) for (_, _, name), ns in zip(spans, own)]


def op_name_seconds(events: Iterable[Tuple[str, int, int]], table: Dict[str, str]) -> dict:
    """{op_name: self seconds} of one device's op-line events; the key ``None``
    holds the events whose instruction the table does not name."""
    total: dict = defaultdict(float)
    for name, seconds in self_seconds(events):
        total[table.get(instruction_name(name))] += seconds
    return dict(total)


def split_by_scope(by_op_name: dict, scopes: Iterable[str]) -> dict:
    """{scope: seconds} of an ``op_name_seconds`` result, each op_name counted
    once under the first of ``scopes`` it holds; what holds none of them under
    ``OUTSIDE``, what has no op_name under ``NO_METADATA``."""
    scopes = tuple(scopes)
    total: dict = defaultdict(float)
    for op_name, seconds in by_op_name.items():
        if op_name is None:
            total[NO_METADATA] += seconds
        else:
            total[next((s for s in scopes if s in op_name), OUTSIDE)] += seconds
    return dict(total)


def scope_seconds(events: Iterable[Tuple[str, int, int]], table: Dict[str, str],
                  scopes: Iterable[str] = SCOPES) -> dict:
    """{scope: device seconds} of one device's op-line events (name, start_ns,
    duration_ns) against a :func:`program_scopes` table: SELF time, first match
    in ``scopes`` wins, plus the rows ``OUTSIDE`` and ``NO_METADATA``."""
    return split_by_scope(op_name_seconds(events, table), scopes)


ROWS: Tuple[str, ...] = SCOPES + (STEP_ALONE, OUTSIDE, NO_METADATA)


def table_row(op_name: Optional[str]) -> str:
    """The one row of the round's disjoint table an op_name is filed under."""
    if op_name is None:
        return NO_METADATA
    rest = STEP_ALONE if "fed.local_step" in op_name else OUTSIDE
    return next((s for s in SCOPES if s in op_name), rest)


def round_table(by_op_name: dict) -> list:
    """The round's disjoint table, [(row, seconds)] in ``ROWS``' order (``SCOPES``', then
    ``STEP_ALONE``, ``OUTSIDE``, ``NO_METADATA``): every second of the op line is in
    exactly one row, so the rows sum to its busy time."""
    total: dict = defaultdict(float)
    for op_name, seconds in by_op_name.items():
        total[table_row(op_name)] += seconds
    return [(row, total.get(row, 0.0)) for row in ROWS]


def unscoped_seconds(by_op_name: dict) -> float:
    """Seconds of the step that no scope of the vocabulary names: no metadata,
    or ``fed.local_step`` alone."""
    rows = dict(round_table(by_op_name))
    return rows[NO_METADATA] + rows[STEP_ALONE]


def largest(by_op_name: dict, row: str, k: int = 8) -> list:
    """The ``k`` op_names with most time among those the table files under ``row``
    (for a remainder that is larger than it should be)."""
    hits = [(n, s) for n, s in by_op_name.items() if n is not None and table_row(n) == row]
    return sorted(hits, key=lambda kv: -kv[1])[:k]


def table_json(table: Optional[Dict[str, str]]) -> dict:
    """What ``round_scopes.json`` holds: the table and the order its scopes
    are matched in."""
    return {"scopes": list(SCOPES), "instructions": table or {}}
