"""Simulated requests of every query completed in the window, per
second of the window's wall time (host clock)."""


def read(ctx):
    return ctx.events / ctx.window_s
