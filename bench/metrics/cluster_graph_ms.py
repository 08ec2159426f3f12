"""Host milliseconds a query of the window spends building the rack's
event graphs (placement, shard plans, the servers' logs and their
zone allocation): the program's ``cluster.graph`` span."""
from metrics.program_spans import observe, span_ms  # noqa: F401


def read(ctx):
    return span_ms(ctx, "cluster_graph_ms", "cluster.graph")
