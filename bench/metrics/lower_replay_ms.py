"""Host milliseconds a query of the window spends on the greedy pool
replay in lowering (both rebuilds): the program's ``lower.replay``
span."""
from metrics.program_spans import observe, span_ms  # noqa: F401


def read(ctx):
    return span_ms(ctx, "lower_replay_ms", "lower.replay")
