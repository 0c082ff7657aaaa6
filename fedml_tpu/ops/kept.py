"""Names on what a kernel's forward rule returns, for a recomputing caller.

A block under ``jax.checkpoint`` runs its forward again in the backward pass,
kernels included.  A kernel's result is the one thing in a block that is cheap
to keep and dear to rebuild, so the ``fwd`` rule of a kernel's
``jax.custom_vjp`` passes its output and the residuals it made through
:func:`tag`.  A caller whose checkpoint policy saves those names
(``models/expert_lm.py``'s ``KEPT``) then finds the kernel's call dead in its
second forward; for every other caller ``checkpoint_name`` is the identity.
"""

from __future__ import annotations

from jax.ad_checkpoint import checkpoint_name


def tag(kernel: str, **arrays):
    """``arrays`` in order, each named ``"<kernel>.<key>"``.  Leaves the gauge
    ``remat.kept_mib`` per kernel name: the MiB of this call's named arrays
    (trace time: Python, from shapes), what a policy that saves them keeps
    alive from a call's forward to its backward."""
    from ..core import obs

    obs.gauge_set("remat.kept_mib", sum(a.size * a.dtype.itemsize for a in arrays.values())
                  / 2**20, {"kernel": kernel})
    return tuple(checkpoint_name(a, f"{kernel}.{key}") for key, a in arrays.items())
