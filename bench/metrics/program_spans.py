"""What the readers of the program's own spans and counters share.

The program keeps one table of named spans and counters
(``repro.core.spans``).  Each reader snapshots it in set-up, after the
warm-up queries and after the readers listed before it in
``BENCHMARK.json`` (``fixpoint_roofline`` lowers a program there), and
reads the table's growth since the last of those snapshots, per query of
the window.  A program without the table gives no snapshot, and the
reader no number.
"""


def snapshot():
    """A copy of the program's table, or ``None`` without one."""
    try:
        from repro.core import spans
    except ImportError:
        return None
    return spans.snapshot()


def observe(cell, variant, res):
    return snapshot()


def per_query(ctx, metric, name):
    """Growth of entry ``name``'s total from the end of set-up (the last
    snapshot ``metric`` observed) to now, per query of the window;
    ``None`` where the program has no table or never took ``name``."""
    then = ctx.observed[metric][-1]
    now = snapshot()
    if then is None or now is None or name not in now:
        return None
    return (now[name]["total"] - then.get(name, {"total": 0})["total"]) \
        / ctx.queries


def span_ms(ctx, metric, name):
    """Milliseconds a query spends in span ``name``."""
    ns = per_query(ctx, metric, name)
    return None if ns is None else ns / 1e6
