"""The flash-attention backward kernels' (dQ pass and dK/dV pass together)
share of their roofline; see ``flash_fwd_roofline.py``.  The recomputation of
the scores that a flash backward makes is not counted as required work."""

import importlib.util
import os

# q, k, v, dO and two rows of statistics in; ``_flash_bwd_dq_kernel`` gives dq,
# ``_flash_bwd_dkv_kernel`` gives dk and dv
KERNELS = ((6, 1), (6, 2))

_spec = importlib.util.spec_from_file_location(
    "flash_fwd_roofline", os.path.join(os.path.dirname(__file__), "flash_fwd_roofline.py"))
_fwd = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_fwd)


def read(ctx):
    return _fwd.read(ctx, kernels=KERNELS, backward=True)
