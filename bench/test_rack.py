"""Self-checks of the rack cell (``ec42-rack.rate-ladder``), on the CPU
at its rehearsal size.

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/test_rack.py

* the plain reference ``configs/ec42-rack.py`` imports nothing of
  ``src/``;
* every point of a query, healthy and with a server down, write-through
  and writeback, with the warm-started ladder and without, matches the
  reference and the program's own event-engine oracle
  (``repro.cluster.simulate_graph``) within the cell's limit;
* the reference in float32 misses that limit;
* solves rounded as an accelerator's float64 rounds (1e-7 us here)
  leave every pop order, and so every point, as the reference has it;
* ``shrink`` keeps every shape, and ``keep`` reads the top rung in both
  modes and one more point drawn from the seed, of the first query only.
"""
import ast
import dataclasses
import json
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(ROOT / "src"), str(BENCH)]

import run  # noqa: E402
from entries import common, rack  # noqa: E402

CELL = "ec42-rack.rate-ladder"
SEED = 2**31 + 29


def _cell(**config):
    _, _, cfg, traffic = run.load_cell(CELL)
    return rack.Cell(rack.shrink(traffic), dict(cfg, **config), SEED)


def test_reference_imports_nothing_of_src():
    tree = ast.parse((BENCH / "configs" / "ec42-rack.py").read_text())
    names = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            names.add((node.module or "").split(".")[0])
    assert names <= {"__future__", "heapq", "json", "pathlib", "numpy"}


def _oracle(cell, wl, degraded, rate):
    from repro.cluster import build_graph, oracle_op_latencies
    from repro.core import PoissonArrivals

    wl = dataclasses.replace(
        wl, arrival=PoissonArrivals(rate_per_s=rate, seed=wl.seed),
        qd=max(wl.qd, wl.ops_per_user))
    down = cell.config["down_server"] if degraded else None
    return oracle_op_latencies(build_graph(
        cell.spec, wl.build(cell.spec.n_gateways), qd=wl.qd, down=down,
        seed=wl.seed))


@pytest.mark.parametrize("warm", [True, False], ids=["warm", "cold"])
@pytest.mark.parametrize("durability", ["write-through", "writeback"])
def test_every_point_matches_reference_and_oracle(durability, warm):
    cell = _cell(durability=durability)
    cell.traffic = dict(cell.traffic, warm_ladder=warm)
    wl = cell.variants[0]
    res = cell.run(wl)
    assert not cell.failed(res)
    assert res.n_programs == 12 and (res.warm_hits > 0) == warm
    limit = cell.traffic["limits"]["max_rel_err"]
    for curve in res.curves:
        assert len(curve.points) == len(cell.rungs)
        for p in curve.points:
            key = (wl.seed, curve.degraded, p.offered_rate)
            got = p.latencies
            assert len(got) == wl.n_users * wl.ops_per_user
            assert common.max_rel_err(got, cell.expected(key)) <= limit
            assert common.max_rel_err(
                got, _oracle(cell, wl, curve.degraded, p.offered_rate)) \
                <= limit


def _rounded(comp, amp):
    """``comp`` off by up to ``amp`` us, the same for equal values and
    independent for values that differ in any bit, as sums rounded in
    another order are."""
    bits = np.ascontiguousarray(comp, dtype=np.float64).view(np.uint64)
    h = (bits * np.uint64(0x9E3779B97F4A7C15)) >> np.uint64(11)
    return comp + amp * (h.astype(np.float64) / 2.0**53 - 0.5)


@pytest.mark.parametrize("seed", [SEED, 3_000_000_019])
def test_accelerator_rounding_keeps_the_reference_order(seed, monkeypatch):
    """The query with ``fixpoint="xla"``, its solves coming back rounded
    by up to 1e-7 us, as the chip's float64 rounds: each settled pop
    order is solved again on the host, and every point still matches
    the reference."""
    from repro.cluster import capacity, compiler, plan_capacity
    from repro.core import solve_program, spans

    def accelerator(program, svc, *, fixpoint="auto", **kw):
        comp, used, converged = solve_program(program, svc, fixpoint="loop",
                                              **kw)
        return (comp if fixpoint == "loop" else _rounded(comp, 1e-7),
                used, converged)

    monkeypatch.setattr(compiler, "solve_program", accelerator)
    monkeypatch.setattr(capacity, "solve_program", accelerator)
    _, _, cfg, traffic = run.load_cell(CELL)
    cell = rack.Cell(dict(traffic, users=16, ops_per_user=16), cfg, seed)
    wl = cell.variants[0]
    spans.reset("cluster.confirm")
    res = plan_capacity(
        cell.rack, [wl.n_users], base_spec=cell.spec, workload=wl,
        rate_ladder=cell.rungs, degraded=True,
        down_server=cfg["down_server"], warm_ladder=True, fixpoint="xla")
    assert not cell.failed(res)
    limit = cell.traffic["limits"]["max_rel_err"]
    for curve in res.curves:
        for p in curve.points:
            key = (wl.seed, curve.degraded, p.offered_rate)
            assert common.max_rel_err(p.latencies, cell.expected(key)) \
                <= limit
    assert spans.snapshot()["cluster.confirm"]["calls"] == res.n_programs


def test_float32_reference_misses_the_limit():
    cell = _cell()
    wl = cell.variants[0]
    res = cell.run(wl)
    samples = cell.keep(0, wl, res, np.random.default_rng(1))
    limit = cell.traffic["limits"]["max_rel_err"]
    assert common.compare(cell, samples)["max_rel_err"] <= limit
    control = common.compare(cell, samples, np.float32)["max_rel_err"]
    assert limit < control < 1e-3


def test_shrink_keeps_every_shape():
    _, _, cfg, traffic = run.load_cell(CELL)
    small = rack.shrink(traffic)
    assert (small["users"], small["ops_per_user"]) == (4, 16)
    assert {k: v for k, v in small.items()
            if k not in ("users", "ops_per_user")} == \
        {k: v for k, v in traffic.items()
         if k not in ("users", "ops_per_user")}
    assert (traffic["users"], traffic["ops_per_user"]) == (32, 32)
    assert cfg["ops_per_rung"] == traffic["users"] * traffic["ops_per_user"]
    assert cfg["scheme"] == {"kind": "ec", "k": 4, "m": 2}
    assert cfg["durability"] == "write-through"
    drive = json.loads((BENCH / "configs" / "zn540.json").read_text())
    cell = _cell()
    assert cell.spec.device_spec.num_zones == drive["spec"]["num_zones"]
    assert np.array_equal(cell.spec.device_params.io_svc_us,
                          drive["latency"]["io_svc_us"])


def test_keep_reads_the_top_rung_both_ways_and_one_more():
    cell = _cell()
    wl = cell.variants[1]
    res = cell.run(wl)
    top = max(cell.rungs)
    drawn = set()
    for s in range(8):
        keys = [k for k, _ in cell.keep(0, wl, res, np.random.default_rng(s))]
        assert keys[:2] == [(wl.seed, False, top), (wl.seed, True, top)]
        assert len(set(keys)) == 3
        drawn.add(keys[2])
    assert len(drawn) > 1
    assert cell.keep(1, wl, res, np.random.default_rng(0)) == []
