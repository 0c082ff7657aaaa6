"""The block-diffusion backward kernels' (``bd_flash_bwd_dq`` and ``bd_flash_bwd_dkv``
together) share of their roofline; see ``bd_flash_fwd_roofline.py``.  The recomputation
of the scores that a flash backward makes is not counted."""

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "bd_flash_fwd_roofline", os.path.join(os.path.dirname(__file__), "bd_flash_fwd_roofline.py"))
_fwd = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_fwd)


def read(ctx):
    return _fwd.read(ctx, kernels=("bd_flash_bwd_dq", "bd_flash_bwd_dkv"), backward=True)
