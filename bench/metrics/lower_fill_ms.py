"""Host milliseconds a query of the window spends filling the fleet's
family blocks in lowering: the program's ``lower.assemble`` span."""
from metrics.program_spans import observe, span_ms  # noqa: F401


def read(ctx):
    return span_ms(ctx, "lower_fill_ms", "lower.assemble")
