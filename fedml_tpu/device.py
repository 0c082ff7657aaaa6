"""Device discovery (reference ``device/device.py`` + ``get_jax_device``).

On TPU the interesting object is not a single device but the mesh; this
returns the default jax device for eager host work and exposes mesh helpers
via fedml_tpu.parallel.
"""

from __future__ import annotations

import logging

import jax

logger = logging.getLogger(__name__)


def get_device(args=None):
    """The default jax device.  ``device_args.device_type`` (``cpu`` |
    ``gpu`` | ``tpu``), when the config sets it, must name the backend jax
    selected: a run that asks for a TPU on a host without one raises here
    instead of training on the CPU unannounced."""
    backend = jax.default_backend()
    wanted = str(getattr(args, "device_type", "") or "").lower()
    if wanted and wanted != backend:
        raise RuntimeError(
            f"device_args.device_type is {wanted!r} but jax selected the "
            f"{backend!r} backend ({jax.devices()[0]}); run on a host with "
            f"that device or say device_type: {backend}")
    devices = jax.devices()
    dev = devices[0]
    logger.info("jax devices: %d x %s (using %s)", len(devices), dev.platform, dev)
    return dev
