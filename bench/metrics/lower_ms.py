"""Lowering time of a query: ``last_compile_stats().lowering_ms`` after
each query of the traced window, averaged over those queries."""


def read(ctx):
    return sum(ctx.lower_ms) / len(ctx.lower_ms)
