"""Self-checks of the readers of the rack compiler's spans and counters.

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/test_cluster_metrics.py

* each reader turns two snapshots of the program's table (the end of
  set-up and now) into the window's growth per query, in ms for a span;
* a reader gives no number where the program has no table, or never
  took the entry it reads (a program from before the rack's spans);
* their ``per_layer`` entries come after every earlier one, list the
  rack cell alone, move ``events_per_s`` and read names the program
  declares.
"""
import importlib
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(ROOT / "src"), str(BENCH)]

from metrics import program_spans  # noqa: E402
from repro.core import spans  # noqa: E402

#: Each reader and the entry of the program's table it reads.
READS = {
    "cluster_graph_ms": "cluster.graph",
    "cluster_replay_ms": "cluster.replay",
    "cluster_rebuild_ms": "cluster.rebuild",
    "cluster_refine_iters": "cluster.refine_iters",
    "cluster_warm_hits": "cluster.warm_hits",
}


def _ctx(metric, then, queries):
    # one observation per variant; the last is the end of set-up
    return SimpleNamespace(queries=queries,
                           observed={metric: [{"unrelated": None}, then]})


@pytest.mark.parametrize("metric", sorted(READS))
def test_reader_gives_the_window_per_query(metric, monkeypatch):
    name = READS[metric]
    reader = importlib.import_module(f"metrics.{metric}")
    then = {name: {"calls": 12, "total": 30_000_000},
            "solve.sweeps": {"calls": 3, "total": 9}}
    now = {name: {"calls": 36, "total": 96_000_000},
           "solve.sweeps": {"calls": 99, "total": 10**9}}
    monkeypatch.setattr(program_spans, "snapshot", lambda: now)
    got = reader.read(_ctx(metric, then, queries=3))
    per_query = (96_000_000 - 30_000_000) / 3
    want = per_query if name in spans.COUNTERS else per_query / 1e6
    assert got == pytest.approx(want)
    # an entry first taken in the window counts from zero
    got = reader.read(_ctx(metric, {}, queries=3))
    assert got == pytest.approx(want * 96 / 66)


@pytest.mark.parametrize("metric", sorted(READS))
def test_reader_is_silent_without_the_table_or_the_entry(metric,
                                                         monkeypatch):
    reader = importlib.import_module(f"metrics.{metric}")
    assert reader.observe is program_spans.observe
    monkeypatch.setattr(program_spans, "snapshot", lambda: None)
    assert reader.read(_ctx(metric, None, queries=2)) is None
    fleet = {"fleet.run": {"calls": 1, "total": 1}}
    monkeypatch.setattr(program_spans, "snapshot", lambda: fleet)
    assert reader.read(_ctx(metric, fleet, queries=2)) is None


def test_entries_list_the_rack_cell_and_read_declared_names():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    names = [m["name"] for m in bench["per_layer"]]
    assert names[-len(READS):] == list(READS)
    assert set(READS.values()) <= set(spans.NAMES) | set(spans.COUNTERS)
    for m in bench["per_layer"][-len(READS):]:
        assert m["workloads"] == ["ec42-rack.rate-ladder"]
        assert (m["layer"], m["moves"]) == ("rack compiler", "events_per_s")
        assert m["source"] == ("program_counter"
                               if READS[m["name"]] in spans.COUNTERS
                               else "program_span")
        assert m["unit"] == ("count" if READS[m["name"]] in spans.COUNTERS
                             else "ms")
