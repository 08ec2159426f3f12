"""Host milliseconds a query of the window spends unpacking the flat
solve into per-device results: the program's ``fleet.unpack`` span."""
from metrics.program_spans import observe, span_ms  # noqa: F401


def read(ctx):
    return span_ms(ctx, "unpack_ms", "fleet.unpack")
