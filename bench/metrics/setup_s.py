"""Seconds from the harness's start to the first timed query: imports,
workload build, the warm-up query of every variant and compile-cache
loads (host clock)."""


def read(ctx):
    return ctx.setup_s
