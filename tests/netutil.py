"""Shared test helpers for multi-process/networked tests."""

import socket


def free_port() -> int:
    """An ephemeral localhost port (bind 0, read, release)."""
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def force_child_cpu() -> None:
    """Keep a SPAWNED child on the CPU backend.  Spawned children don't run
    conftest, so without this a child on a chip host would pick the chip —
    which its parent (or a sibling) already holds.  Call FIRST in every
    spawn target."""
    import os

    os.environ["JAX_PLATFORMS"] = "cpu"
    from fedml_tpu.utils.platform import force_cpu_backend

    force_cpu_backend()
