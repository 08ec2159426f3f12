"""Self-checks of the readers of the program's spans and counters.

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/test_program_metrics.py

* each reader turns two snapshots of the program's table (the end of
  set-up and now) into the window's growth per query, in ms for a span;
* a reader gives no number where the program has no table, or never
  took the entry it reads;
* their ``per_layer`` entries follow ``fixpoint_roofline``'s, whose
  ``observe`` lowers a program in set-up that the snapshot must hold;
* each reads a span or counter the program declares.
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

#: Each new reader and the entry of the program's table it reads.
READS = {
    "build_ms": "fleet.build",
    "lower_digest_ms": "lower.digest",
    "lower_replay_ms": "lower.replay",
    "lower_fill_ms": "lower.assemble",
    "solve_prep_ms": "solve.prepare",
    "solve_fetch_ms": "solve.fetch",
    "unpack_ms": "fleet.unpack",
    "evaluate_ms": "experiments.evaluate",
    "fixpoint_active_blocks": "solve.active_blocks",
}


def _ctx(metric, then, queries):
    # the harness keeps one observation per variant; the last is the
    # end of set-up
    return SimpleNamespace(queries=queries,
                           observed={metric: [{"unrelated": None}, then]})


@pytest.mark.parametrize("metric", sorted(READS))
def test_reader_gives_the_window_per_query(metric, monkeypatch):
    name = READS[metric]
    reader = importlib.import_module(f"metrics.{metric}")
    then = {name: {"calls": 2, "total": 5_000_000}, "other": {
        "calls": 1, "total": 7}}
    now = {name: {"calls": 10, "total": 45_000_000}, "other": {
        "calls": 9, "total": 10**12}}
    monkeypatch.setattr(program_spans, "snapshot", lambda: now)
    got = reader.read(_ctx(metric, then, queries=4))
    per_query = (45_000_000 - 5_000_000) / 4
    want = per_query if name in spans.COUNTERS else per_query / 1e6
    assert got == pytest.approx(want)
    # an entry first taken in the window counts from zero
    got = reader.read(_ctx(metric, {"other": then["other"]}, queries=4))
    assert got == pytest.approx(want * 45 / 40)


@pytest.mark.parametrize("metric", sorted(READS))
def test_reader_is_silent_without_the_table_or_the_entry(metric,
                                                         monkeypatch):
    reader = importlib.import_module(f"metrics.{metric}")
    assert reader.observe is program_spans.observe
    # a program without repro.core.spans: no snapshot at all
    monkeypatch.setattr(program_spans, "snapshot", lambda: None)
    assert reader.read(_ctx(metric, None, queries=3)) is None
    # the table, but the cell never took this entry
    monkeypatch.setattr(program_spans, "snapshot",
                        lambda: {"other": {"calls": 1, "total": 1}})
    assert reader.read(_ctx(metric, {}, queries=3)) is None


def test_observe_snapshots_the_program_table():
    spans.reset("bench.test")
    spans.count("bench.test", 5)
    snap = program_spans.observe(None, None, None)
    assert snap["bench.test"] == {"calls": 1, "total": 5}
    spans.reset("bench.test")


def test_new_entries_follow_fixpoint_roofline_and_read_declared_names():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    names = [m["name"] for m in bench["per_layer"]]
    roofline = names.index("fixpoint_roofline")
    assert all(names.index(m) > roofline for m in READS)
    assert set(READS.values()) <= set(spans.NAMES) | set(spans.COUNTERS)
    cells = {c["name"] for c in bench["workloads"]}
    for m in bench["per_layer"]:
        if m["name"] in READS:
            assert m["workloads"] and set(m["workloads"]) <= cells
            assert m["source"] == ("program_counter"
                                   if READS[m["name"]] in spans.COUNTERS
                                   else "program_span")
