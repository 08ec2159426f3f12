"""Host milliseconds a query of the window spends preparing the solve
(block adjacency, initial vector, puts to the device, the kernel's
dispatch): the program's ``solve.prepare`` span."""
from metrics.program_spans import observe, span_ms  # noqa: F401


def read(ctx):
    return span_ms(ctx, "solve_prep_ms", "solve.prepare")
