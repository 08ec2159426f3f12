"""Host milliseconds a query of the window spends replaying the rack's
FIFO pop orders in its refinement and comparing them with the last:
the program's ``cluster.replay`` span."""
from metrics.program_spans import observe, span_ms  # noqa: F401


def read(ctx):
    return span_ms(ctx, "cluster_replay_ms", "cluster.replay")
