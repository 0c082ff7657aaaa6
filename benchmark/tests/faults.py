"""Faults planted under the timed path, for ``test_end_to_end.py``: each takes
the driver after its set-up and before its first units, and breaks the
program underneath.  ``correct`` has to come out false for every one."""

from __future__ import annotations


def sim_state_unchanged(driver):
    """The round returns the global model it was given."""
    sim, real = driver.sim, driver.sim._round_fn

    def broken(variables, server_state, *rest):
        _, state, loss, outs = real(variables, server_state, *rest)
        return variables, state, loss, outs

    sim._round_fn = broken


def sim_half_batch(driver):
    """Half of every batch left out, the mean taken over the rest."""
    sim, real = driver.sim, driver.sim._packed_inputs

    def broken(ids, counts, round_idx):
        idx, mask, *rest = real(ids, counts, round_idx)
        return (idx, mask.at[..., mask.shape[-1] // 2:].set(0.0), *rest)

    sim._packed_inputs = broken


def sim_no_exchange(driver):
    """The psum over the client axis left out: every device keeps its own sum."""
    import jax

    jax.lax.psum = lambda x, axis_name, **kw: x  # this process ends with the run


FAULTS = {f.__name__: f for f in (sim_state_unchanged, sim_half_batch, sim_no_exchange)}
