"""Of the window's expert assignments, the share that landed on the experts held here
(``moe.local_assignment_share.py``'s reading of the compiled round's own sums) in this
configuration's cell: 8 of 128 experts under even routing give 6.25 %."""

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "moe_local_assignment_share", os.path.join(os.path.dirname(__file__), "moe.local_assignment_share.py"))
_accepted = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_accepted)


def read(ctx):
    return _accepted.read(ctx)
