"""Host milliseconds a query of the window spends building the workload
(specs to per-device traces): the program's ``fleet.build`` span."""
from metrics.program_spans import observe, span_ms  # noqa: F401


def read(ctx):
    return span_ms(ctx, "build_ms", "fleet.build")
