"""Host milliseconds a query of the window spends finding which family
blocks share flat slots (the block adjacency of the active-set sweeps,
part of preparing the solve): the program's ``solve.adjacency`` span."""
from metrics.program_spans import observe, span_ms  # noqa: F401


def read(ctx):
    return span_ms(ctx, "solve_adjacency_ms", "solve.adjacency")
