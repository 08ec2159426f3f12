"""The program's span-and-counter table (``repro.core.spans``).

* spans nest and add their wall time, counters add their counts, and
  ``snapshot``/``reset`` copy and clear the table;
* the program cache's counters live in the table, and
  ``program_cache_info()`` keeps its shape;
* a small fleet query with the ``"xla"`` driver, plus the observation
  runner's ``evaluate``, opens every span in ``spans.NAMES`` but the
  cluster path's (``tests/test_cluster_spans.py``), the block
  adjacency's inside the solve's preparation;
* under the JAX profiler, the program's spans land in the trace on the
  same host clock as an enclosing ``TraceAnnotation``.
"""
import glob
import os
import time

import jax
import pytest

from repro.core import (
    DeviceFleet, KiB, WorkloadSpec, ZnsDevice, clear_program_cache,
    compile_program, last_compile_stats, last_solve_stats,
    program_cache_info, spans,
)
from repro.experiments import ExperimentRunner


@pytest.fixture(autouse=True)
def empty_table():
    spans.reset()
    yield
    spans.reset()


def _pool_fleet_query():
    """One jitter-free 2-drive query whose saturated append pool goes
    through the greedy replay, solved by the XLA driver."""
    wl = WorkloadSpec()
    for t in range(4):
        wl = wl.appends(n=60, size=8 * KiB, qd=2, zone=t * 4, nzones=4)
    return DeviceFleet.homogeneous(2).run(
        [wl, wl], backend="vectorized", jitter=False, fixpoint="xla")


def test_spans_nest_and_add_their_time():
    with spans.span("outer") as outer:
        with spans.span("inner") as inner:
            time.sleep(0.002)
        with spans.span("inner"):
            pass
    snap = spans.snapshot()
    assert snap["outer"] == {"calls": 1, "total": outer.ns}
    assert snap["inner"]["calls"] == 2
    assert outer.ns >= snap["inner"]["total"] >= inner.ns >= 2_000_000


def test_span_records_a_block_that_raises():
    with pytest.raises(KeyError):
        with spans.span("failing"):
            raise KeyError("x")
    assert spans.snapshot()["failing"]["calls"] == 1


def test_counters_snapshot_and_reset():
    spans.count("c", 3)
    spans.count("c")
    spans.count("d")
    snap = spans.snapshot()
    assert snap["c"] == {"calls": 2, "total": 4}
    snap["c"]["total"] = 0                      # a copy, not the table
    assert spans.snapshot()["c"]["total"] == 4
    spans.reset("c")
    assert set(spans.snapshot()) == {"d"}
    spans.reset()
    assert spans.snapshot() == {}


def test_program_cache_counters_live_in_the_table():
    clear_program_cache()
    dev = ZnsDevice()
    tr = WorkloadSpec().writes(n=50, qd=2).build()
    compile_program(tr, dev.spec, dev.lat)
    compile_program(tr, dev.spec, dev.lat)
    info = program_cache_info()
    assert info == {"hits": 1, "misses": 1, "disk_hits": 0, "size": 1,
                    "maxsize": info["maxsize"]}
    snap = spans.snapshot()
    assert snap["program_cache.hits"]["total"] == 1
    assert snap["program_cache.misses"]["total"] == 1
    clear_program_cache()
    assert program_cache_info() == {"hits": 0, "misses": 0, "disk_hits": 0,
                                    "size": 0, "maxsize": info["maxsize"]}


def test_lowering_ms_is_the_lower_span():
    dev = ZnsDevice()
    tr = WorkloadSpec().writes(n=80, qd=4).build()
    compile_program(tr, dev.spec, dev.lat, cache=False)
    lower = spans.snapshot()["lower"]
    assert lower["calls"] == 1
    assert last_compile_stats().lowering_ms == lower["total"] / 1e6 > 0


def test_a_fleet_query_opens_every_span():
    clear_program_cache()
    res = _pool_fleet_query()
    assert res.converged
    runner = ExperimentRunner(["obs06"])
    assert all(r.passed for r in runner.evaluate(
        runner.simulate(fixpoint="xla")))
    snap = spans.snapshot()
    fleet = {n for n in spans.NAMES if not n.startswith("cluster.")}
    assert fleet <= set(snap)
    assert set(snap) <= fleet | set(spans.COUNTERS)
    # the runner's query is the last solve: its sweeps and active blocks
    # are the last entries the counters took
    st = last_solve_stats()
    assert st.driver == "xla"
    assert snap["solve.sweeps"]["calls"] == 2
    assert snap["solve.sweeps"]["total"] >= st.sweeps >= 1
    assert snap["solve.active_blocks"]["total"] >= sum(st.active_blocks) > 0
    # a span is timed whole: the fleet call holds its direct children
    children = ("fleet.build", "lower", "solve.prepare", "solve.wait",
                "solve.fetch", "fleet.unpack")
    assert snap["fleet.run"]["total"] >= sum(snap[k]["total"]
                                             for k in children)
    assert snap["lower"]["total"] >= sum(
        snap[k]["total"] for k in ("lower.digest", "lower.devices",
                                   "lower.replay", "lower.assemble"))
    # the XLA driver computes the block adjacency inside its preparation
    assert "solve.adjacency" in snap
    assert snap["solve.prepare"]["total"] >= snap["solve.adjacency"]["total"]


def _host_events(path, names):
    data = jax.profiler.ProfileData.from_file(path)
    found = {}
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in names:
                    found.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns))
    return found


def test_program_spans_lie_inside_an_annotation_in_a_profiler_trace(
        tmp_path):
    _pool_fleet_query()                 # compile outside the trace
    clear_program_cache()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("outer query"):
            _pool_fleet_query()
    finally:
        jax.profiler.stop_trace()
    paths = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                      recursive=True)
    assert len(paths) == 1
    found = _host_events(paths[0], {"outer query", "lower", "solve.fetch"})
    [(lo, hi)] = found["outer query"]
    for name in ("lower", "solve.fetch"):
        [(a, b)] = found[name]
        assert lo <= a <= b <= hi, name
    assert found["lower"][0][1] <= found["solve.fetch"][0][0]
