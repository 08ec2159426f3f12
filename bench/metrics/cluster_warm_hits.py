"""Ladder rungs whose warm start from the previous rung held, per query
of the window: the program's ``cluster.warm_hits`` counter."""
from metrics.program_spans import observe, per_query  # noqa: F401


def read(ctx):
    return per_query(ctx, "cluster_warm_hits", "cluster.warm_hits")
