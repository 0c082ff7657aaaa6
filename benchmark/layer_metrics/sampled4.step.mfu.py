"""The whole step's share of the chip's bf16 peak under a sampled, odd-sized cohort on
the four-device client mesh: ``step.mfu.py``'s reading (operations the forward and
backward passes require for the sequences really trained in the window,
``benchmark/flops.py``, over window seconds x chips x the published peak) in a cell of
one client a device, 3-6 steps a device, whose slowest device sets the round and whose
last batch of a client is half empty: the empty slot is computed and trains nothing,
and a device that has finished waits, so both lower this share."""

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "step_mfu", os.path.join(os.path.dirname(__file__), "step.mfu.py"))
_accepted = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_accepted)


def read(ctx):
    return _accepted.read(ctx)
