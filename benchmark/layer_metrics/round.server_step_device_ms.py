"""Device milliseconds a round spends after its ``while`` has ended, collective
instructions left out (they are ``collective.exposed_ms_per_round``'s): the server
step (``fed.server_step``) and what is fused with it, by self time, mean over the
cell's devices.  Told by structure (``benchmark/round_phases.py``); fails the run
where the simulator is packed and nothing is found."""

from benchmark import round_phases


def read(ctx):
    return round_phases.device_ms_per_round(ctx, "server_step_s", "server step")
