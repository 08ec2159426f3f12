"""Self-checks of the ``solve_adjacency_ms`` reader.

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/test_solve_adjacency_ms.py

* it reads the program's ``solve.adjacency`` span as the window's growth
  per query, in ms, and gives no number where the program has no table
  or never took the span (a program from before the span);
* its ``per_layer`` entry follows ``fixpoint_roofline``'s and shares the
  solve dispatch layer with ``solve_prep_ms``, whose span holds it.
"""
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(ROOT / "src"), str(BENCH)]

from metrics import program_spans, solve_adjacency_ms  # noqa: E402
from repro.core import spans  # noqa: E402

METRIC, SPAN = "solve_adjacency_ms", "solve.adjacency"


def _ctx(then, queries):
    return SimpleNamespace(queries=queries, observed={METRIC: [then]})


def test_reader_gives_the_span_per_query(monkeypatch):
    assert SPAN in spans.NAMES
    assert solve_adjacency_ms.observe is program_spans.observe
    now = {SPAN: {"calls": 9, "total": 70_000_000}}
    monkeypatch.setattr(program_spans, "snapshot", lambda: now)
    then = {SPAN: {"calls": 2, "total": 10_000_000}}
    assert solve_adjacency_ms.read(_ctx(then, 5)) == pytest.approx(12.0)
    assert solve_adjacency_ms.read(_ctx({}, 5)) == pytest.approx(14.0)


def test_reader_is_silent_without_the_span(monkeypatch):
    monkeypatch.setattr(program_spans, "snapshot", lambda: None)
    assert solve_adjacency_ms.read(_ctx(None, 3)) is None
    before = {"solve.prepare": {"calls": 1, "total": 1}}
    monkeypatch.setattr(program_spans, "snapshot", lambda: before)
    assert solve_adjacency_ms.read(_ctx(before, 3)) is None


def test_entry_follows_fixpoint_roofline_in_the_solve_dispatch_layer():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entries = {m["name"]: (i, m) for i, m in enumerate(bench["per_layer"])}
    at, entry = entries[METRIC]
    assert at > entries["fixpoint_roofline"][0]
    prep = entries["solve_prep_ms"][1]
    for key in ("layer", "moves", "source", "workloads"):
        assert entry[key] == prep[key]
