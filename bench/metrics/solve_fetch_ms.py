"""Host milliseconds a query of the window spends copying the solve's
result from the device: the program's ``solve.fetch`` span."""
from metrics.program_spans import observe, span_ms  # noqa: F401


def read(ctx):
    return span_ms(ctx, "solve_fetch_ms", "solve.fetch")
