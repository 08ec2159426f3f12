"""Host milliseconds a query of the window spends on the experiments'
extraction and checks: the program's ``experiments.evaluate`` span."""
from metrics.program_spans import observe, span_ms  # noqa: F401


def read(ctx):
    return span_ms(ctx, "evaluate_ms", "experiments.evaluate")
