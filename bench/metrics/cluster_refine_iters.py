"""Refinement solves of the rack's FIFO pop orders, summed over a
query's programs, per query of the window: the program's
``cluster.refine_iters`` counter."""
from metrics.program_spans import observe, per_query  # noqa: F401


def read(ctx):
    return per_query(ctx, "cluster_refine_iters", "cluster.refine_iters")
