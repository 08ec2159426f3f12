"""The cluster path's spans and counters (``repro.core.spans``).

One small ``plan_capacity`` query, cold and with the warm-started
ladder: each ``cluster.*`` span and counter fires once per call of the
code it names, and the whole-call span holds its children.  The same
query with ``fixpoint="xla"``: ``cluster.confirm`` fires once per
compiled graph, and the answers stay the host's.
"""
import importlib

import numpy as np
import pytest

from repro.cluster import (ClusterConfig, ClusterSpec, ClusterWorkload,
                           erasure, plan_capacity)
from repro.core import MiB, platform, solve_program, spans

CLUSTER = ("cluster.plan", "cluster.graph", "cluster.replay",
           "cluster.rebuild", "cluster.confirm", "cluster.slice")
COUNTERS = ("cluster.refine_iters", "cluster.warm_hits",
            "cluster.warm_attempts")
RUNGS = (3000.0, 6000.0, 12000.0)


def _plan(warm, fixpoint):
    return plan_capacity(
        [ClusterConfig(erasure(2, 1), "round-robin")], [4],
        base_spec=ClusterSpec(n_gateways=1, n_servers=4,
                              scheme=erasure(2, 1),
                              durability="write-through"),
        workload=ClusterWorkload(n_users=4, ops_per_user=6,
                                 object_bytes=2 * MiB, seed=5),
        rate_ladder=RUNGS, degraded=True, fixpoint=fixpoint,
        warm_ladder=warm)


@pytest.fixture(scope="module", params=[False, True], ids=["cold", "warm"])
def query(request):
    """The span table after one query, and what the query called."""
    compiler = importlib.import_module("repro.cluster.compiler")
    capacity = importlib.import_module("repro.cluster.capacity")
    facade = importlib.import_module("repro.cluster.cluster")
    calls = {"graphs": 0, "programs": 0, "compiled": []}

    def counting(fn, key):
        def wrapper(*a, **kw):
            out = fn(*a, **kw)
            if key == "compiled":
                calls[key].append(out)
            else:
                calls[key] += 1
            return out
        return wrapper

    spans.reset()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(compiler, "_build_graph",
                   counting(compiler._build_graph, "graphs"))
        mp.setattr(compiler, "build_program",
                   counting(compiler.build_program, "programs"))
        for mod in (capacity, facade):
            mp.setattr(mod, "compile_graph",
                       counting(compiler.compile_graph, "compiled"))
        report = _plan(request.param, "loop")
    snap = spans.snapshot()
    spans.reset()
    return request.param, report, snap, calls


def test_every_cluster_name_is_declared():
    assert set(CLUSTER) <= set(spans.NAMES)
    assert set(COUNTERS) <= set(spans.COUNTERS)


def test_plan_and_slice_fire_once_per_query(query):
    _, report, snap, _ = query
    assert report.converged and len(report.curves) == 2
    assert snap["cluster.plan"]["calls"] == snap["cluster.slice"]["calls"] \
        == 1


def test_graph_fires_once_per_graph_built(query):
    warm, _, snap, calls = query
    # a warm rate ladder builds one graph a mode and re-stamps the rest
    assert calls["graphs"] == (2 if warm else 2 * len(RUNGS))
    assert snap["cluster.graph"]["calls"] == calls["graphs"]


def test_rebuild_fires_once_per_program_built(query):
    _, _, snap, calls = query
    refined = sum(c.program.refine_used for c in calls["compiled"])
    assert len(calls["compiled"]) == 2 * len(RUNGS)
    # the bootstrap program and one per refinement solve
    assert calls["programs"] == len(calls["compiled"]) + refined
    assert snap["cluster.rebuild"]["calls"] == calls["programs"]


def test_replay_fires_once_per_refinement_pass(query):
    _, _, snap, calls = query
    # each refinement solve replays once, and the pass that finds the
    # orders stable replays once more
    assert all(c.program.order_stable for c in calls["compiled"])
    assert snap["cluster.replay"]["calls"] == sum(
        c.program.refine_used + 1 for c in calls["compiled"])


def test_refine_iters_counts_every_compile(query):
    _, _, snap, calls = query
    assert snap["cluster.refine_iters"] == {
        "calls": len(calls["compiled"]),
        "total": sum(c.program.refine_used for c in calls["compiled"])}
    assert snap["cluster.refine_iters"]["total"] > 0


def test_warm_counters_fire_once_per_query(query):
    warm, report, snap, _ = query
    assert snap["cluster.warm_hits"] == {"calls": 1,
                                         "total": report.warm_hits}
    assert snap["cluster.warm_attempts"] == {"calls": 1,
                                             "total": report.warm_attempts}
    # every rung after each mode's first tries the warm start
    assert report.warm_attempts == (2 * (len(RUNGS) - 1) if warm else 0)


def test_plan_holds_its_children_and_the_solves(query):
    _, _, snap, _ = query
    children = ("cluster.graph", "cluster.replay", "cluster.rebuild",
                "cluster.slice")
    assert snap["cluster.plan"]["total"] >= sum(snap[k]["total"]
                                                for k in children)
    assert snap["solve.sweeps"]["calls"] >= snap["cluster.rebuild"]["calls"]


def test_confirm_never_fires_on_the_host(query):
    _, _, snap, _ = query
    assert "cluster.confirm" not in snap


@pytest.fixture(scope="module", params=[False, True], ids=["cold", "warm"])
def offhost(request):
    """The query again with ``fixpoint="xla"``, its solves computed by
    the host loop."""
    compiler = importlib.import_module("repro.cluster.compiler")
    capacity = importlib.import_module("repro.cluster.capacity")
    facade = importlib.import_module("repro.cluster.cluster")
    compiled, asked = [], []

    def accelerator(program, svc, *, fixpoint="auto", **kw):
        asked.append(fixpoint)
        return solve_program(program, svc, fixpoint="loop", **kw)

    def counting(*a, **kw):
        compiled.append(compiler.compile_graph(*a, **kw))
        return compiled[-1]

    spans.reset()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(compiler, "solve_program", accelerator)
        mp.setattr(capacity, "solve_program", accelerator)
        for mod in (capacity, facade):
            mp.setattr(mod, "compile_graph", counting)
        report = _plan(request.param, "xla")
    snap = spans.snapshot()
    spans.reset()
    return report, _plan(request.param, "loop"), snap, compiled, asked


def test_confirm_fires_once_per_graph_off_the_host(offhost):
    report, _, snap, compiled, asked = offhost
    assert report.converged and len(compiled) == 2 * len(RUNGS)
    assert all(c.program.order_stable for c in compiled)
    assert snap["cluster.confirm"]["calls"] == len(compiled)
    # the bootstrap and the last program, solved again on the host
    assert asked.count("loop") == 2 * len(compiled)


def test_off_the_host_answers_are_the_hosts(offhost):
    report, host, *_ = offhost
    assert report.n_events == host.n_events
    for a, b in zip(report.curves, host.curves):
        for p, q in zip(a.points, b.points):
            np.testing.assert_allclose(p.latencies, q.latencies,
                                       rtol=1e-12)


def test_auto_settles_pop_orders_on_the_host(monkeypatch):
    """On an accelerator, ``auto`` solves the refinement on the host and
    leaves the one fleet solve to the platform."""
    compiler = importlib.import_module("repro.cluster.compiler")
    capacity = importlib.import_module("repro.cluster.capacity")
    asked = {"compiler": [], "capacity": []}

    def recording(where):
        def solve(program, svc, *, fixpoint="auto", **kw):
            asked[where].append(fixpoint)
            return solve_program(program, svc, fixpoint="loop", **kw)
        return solve

    monkeypatch.setattr(platform, "single_chip_driver", lambda: "xla")
    monkeypatch.setattr(compiler, "solve_program", recording("compiler"))
    monkeypatch.setattr(capacity, "solve_program", recording("capacity"))
    spans.reset()
    report = _plan(False, "auto")
    assert report.converged
    assert asked["compiler"] and set(asked["compiler"]) == {"loop"}
    assert asked["capacity"] == ["auto"]
    assert platform.fixpoint_driver(1) == "xla"
    assert "cluster.confirm" not in spans.snapshot()
    spans.reset()
