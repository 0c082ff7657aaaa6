"""The fullest held expert's load over the mean held expert's, over the window
(``moe.expert_load_max_over_mean.py``'s reading of the compiled round's own sums) in
this configuration's cell."""

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "moe_expert_load_max_over_mean", os.path.join(os.path.dirname(__file__), "moe.expert_load_max_over_mean.py"))
_accepted = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_accepted)


def read(ctx):
    return _accepted.read(ctx)
