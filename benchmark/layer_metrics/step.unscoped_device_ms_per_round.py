"""Device milliseconds a round that no scope of the program's vocabulary names: self time of the op events whose
instruction has no ``op_name`` (what XLA adds: copies, the loops' own) or whose ``op_name`` holds ``fed.local_step`` and no
``lm.*`` / ``fed.loss`` / ``fed.sgd`` scope, mean over the cell's devices (``benchmark/program_scopes.py``; the table is
``XLASimulator.round_scopes()``).  With a trace it also prints the cell's disjoint table to stderr: every scope in the one
first-match order of ``fedml_tpu.core.obs.scopes.SCOPES``, whose rows sum to the op line's busy time.  Silent without a
trace and on a program that hands out no table."""

from benchmark import program_scopes


def read(ctx):
    return program_scopes.unscoped_ms_per_round(ctx)
