"""Share of the traced window in which no operation ran on the device, under training of
the nemotron_h cell (one chip, 16 steps a round, rounds back to back):
``device.idle_share.py``'s reading."""

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "device_idle_share", os.path.join(os.path.dirname(__file__), "device.idle_share.py"))
_accepted = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_accepted)


def read(ctx):
    return _accepted.read(ctx)
