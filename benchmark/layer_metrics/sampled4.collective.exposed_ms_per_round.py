"""Milliseconds a round spends in collective instructions on the worst device, under a
sampled, odd-sized cohort on the four-device client mesh: ``collective.exposed_ms_per_round.py``'s
reading in a cell whose devices train 3-6 steps each, so the lighter three reach the
``psum`` over the client axis first and the heaviest device's last step is what the
exchange waits on."""

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "collective_exposed", os.path.join(os.path.dirname(__file__),
                                       "collective.exposed_ms_per_round.py"))
_accepted = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_accepted)


def read(ctx):
    return _accepted.read(ctx)
