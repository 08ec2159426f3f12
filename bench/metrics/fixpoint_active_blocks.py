"""Family blocks the solve kernel ran, summed over its sweeps, per query
of the window: the program's ``solve.active_blocks`` counter."""
from metrics.program_spans import observe, per_query  # noqa: F401


def read(ctx):
    return per_query(ctx, "fixpoint_active_blocks", "solve.active_blocks")
