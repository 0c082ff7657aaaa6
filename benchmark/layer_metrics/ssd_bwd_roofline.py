"""The SSD backward kernel's (``ssd_bwd``) share of its roofline; see
``ssd_fwd_roofline.py``.  The backward's forward pass over each run, which the kernel
makes again in VMEM, is not counted."""

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "ssd_fwd_roofline", os.path.join(os.path.dirname(__file__), "ssd_fwd_roofline.py"))
_fwd = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_fwd)


def read(ctx):
    return _fwd.read(ctx, kernel="ssd_bwd", backward=True)
