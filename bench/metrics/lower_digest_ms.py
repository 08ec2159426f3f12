"""Host milliseconds a query of the window spends on the traces' content
digests in lowering: the program's ``lower.digest`` span."""
from metrics.program_spans import observe, span_ms  # noqa: F401


def read(ctx):
    return span_ms(ctx, "lower_digest_ms", "lower.digest")
