"""Device time of the solve kernel per query: the durations of the
``zns_fixpoint_xla`` executable's events in the trace, summed over the
traced window and divided by its queries."""

KERNEL = "zns_fixpoint_xla"


def read(ctx):
    s = ctx.trace["kernels"][KERNEL]
    return s / ctx.queries * 1e3 if s > 0 else None
