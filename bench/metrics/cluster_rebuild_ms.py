"""Host milliseconds a query of the window spends building the rack's
chain programs in its refinement: the program's ``cluster.rebuild``
span."""
from metrics.program_spans import observe, span_ms  # noqa: F401


def read(ctx):
    return span_ms(ctx, "cluster_rebuild_ms", "cluster.rebuild")
