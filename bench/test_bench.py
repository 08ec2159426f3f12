"""Self-checks of the benchmark, on the CPU at small sizes.

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/test_bench.py

* the trace reduction, on a hand-made trace and on a small trace
  recorded on a TPU v5e (``testdata/small_trace.json``);
* the least-bytes count behind ``fixpoint_roofline``;
* the lower-precision control (the reference in float32) fails the
  comparison, at a small size;
* the harness after its look for a chip, with the timed path broken
  underneath in each way a cell can break, reports ``correct`` false,
  and true when nothing is broken; a broken solver function is bound
  under every name a ``repro`` module gives it;
* each entry's rehearsal size: its own ``shrink``, one query of at
  most 250,000 events.
"""
import functools
import importlib
import json
import subprocess
import sys
import types
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(ROOT / "src"), str(BENCH)]

import rehearse  # noqa: E402
import trace_reduce  # noqa: E402
from entries import common  # noqa: E402

CELLS = [c["name"] for c in
         json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]


def test_reduce_hand_made_trace():
    dev = "/device:TPU:0"
    events = {
        "host": [["query", 0, 100], ["entry", 5, 80], ["keep", 88, 5],
                 ["query", 100, 100], ["entry", 105, 90]],
        "device": [
            [dev, "XLA Ops", "fusion.1", 10, 20],
            [dev, "XLA Ops", "fusion.2", 20, 20],   # overlaps fusion.1
            [dev, "XLA Ops", "fusion.1", 150, 10],
            [dev, "XLA Ops", "copy", 195, 30],      # runs past the window
            [dev, "XLA Modules", "jit_zns_fixpoint_xla(7)", 10, 30],
            [dev, "XLA Modules", "jit_other(1)", 150, 10],
            [dev, "Steps", "0", 0, 400],
        ]}
    r = trace_reduce.reduce(events, ["zns_fixpoint_xla"])
    assert r["window_s"] == pytest.approx(200e-9)
    # busy: [10, 40) + [150, 160) + [195, 200) = 45 ns
    assert r["busy_s"] == pytest.approx(45e-9)
    assert r["kernels"] == {"zns_fixpoint_xla": pytest.approx(30e-9)}
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(30e-9)]
    # gaps, named by the innermost span around their middle: [0,10)
    # entry, [40,150) query (keep ends at 93), [160,195) entry
    gaps = dict((round(s * 1e9), name) for name, s in r["idle_gaps"])
    assert gaps == {10: "entry", 110: "query", 35: "entry"}


def test_reduce_recorded_trace():
    rec = json.loads((BENCH / "testdata" / "small_trace.json").read_text())
    r = trace_reduce.reduce(rec, ["zns_fixpoint_xla"])
    planes = {p for p, *_ in rec["device"]}
    assert r["chips"] == len(planes) == 1
    assert 0 < r["kernels"]["zns_fixpoint_xla"] <= r["busy_s"] < r["window_s"]
    ops = [(s, s + d) for p, line, n, s, d in rec["device"]
           if line == trace_reduce.OPS_LINE]
    # the union never exceeds the plain sum of op durations
    assert r["busy_s"] <= sum(b - a for a, b in ops) / 1e9 + 1e-12
    spans = {n for n, *_ in rec["host"]} | {"between spans"}
    assert {name for name, _ in r["idle_gaps"]} <= spans


def _tiny_program(traces):
    from repro.core import ZNSDeviceSpec, ZnsDevice, compile_fleet_program

    spec = ZNSDeviceSpec()
    lat = ZnsDevice(spec).lat
    return compile_fleet_program(traces, [spec] * len(traces),
                                 [lat] * len(traces), cache=False)


def test_least_bytes_counts_chain_edges():
    from metrics.fixpoint_roofline import BYTES_PER_EDGE, least_bytes
    from repro.core import KiB, WorkloadSpec

    a = (WorkloadSpec().writes(n=10, size=4 * KiB, qd=2, nzones=3)
         .reads(n=7, size=4 * KiB, qd=3)).build()
    # thread 0: 10 writes on 2 lag chains -> 8 edges; zone writes: 10
    # writes over 3 zones -> 7 edges; thread 1: 7 reads on 3 chains -> 4
    assert least_bytes(_tiny_program([a])) == BYTES_PER_EDGE * (8 + 7 + 4)
    b = WorkloadSpec().reads(n=500, size=4 * KiB, qd=5).build()
    # padding a fleet adds pads, not edges: the fleet is the sum
    assert least_bytes(_tiny_program([a, b])) == \
        least_bytes(_tiny_program([a])) + least_bytes(_tiny_program([b]))


def _cell(name):
    import run

    bench, cell, config, traffic = run.load_cell(name)
    traffic = rehearse.shrink(traffic)
    entry = __import__(f"entries.{traffic['entry']}",
                       fromlist=["Cell"])
    return entry.Cell(traffic, config, 2**31 + 3), traffic


@pytest.mark.parametrize("name", CELLS)
def test_control_fails(name):
    work, traffic = _cell(name)
    rng = np.random.default_rng(0)
    samples = []
    for q, v in enumerate(work.variants):
        samples += work.keep(q, v, work.run(v), rng)
    sound = common.compare(work, samples)["max_rel_err"]
    control = common.compare(
        work, [(k, work.expected(k, np.float32)) for k, _ in samples])
    limit = traffic["limits"]["max_rel_err"]
    assert sound <= limit < control["max_rel_err"]


def test_fleet_keep_reads_every_mix():
    """At the fleet cell's own size the comparison reads
    ``check_devices`` distinct devices, one of each mix among them."""
    import run
    from entries import fleet

    _, _, config, traffic = run.load_cell("zn540.fleet64-randrw4k")
    three = dict(traffic, mixes=traffic["mixes"] * 3)
    for t in (traffic, three):
        work = fleet.Cell(t, config, 2**31 + 5)
        v = work.variants[0]
        fake = [type("R", (), {"sim": type("S", (), {"complete": np.zeros(1)})})
                for _ in v["deal"]]
        keys = [k for k, _ in work.keep(0, v, fake, np.random.default_rng(9))]
        assert len({d for _, _, d in keys}) == t["check_devices"] == 8
        assert {m for _, m, _ in keys} == set(range(len(t["mixes"])))
        assert work.keep(1, v, fake, np.random.default_rng(9)) == []


def _unchanged(solve):
    def broken(program, svc_flat, **kw):
        return program.issue_flat + svc_flat, 1, True
    return broken


def _half(solve):
    def broken(program, svc_flat, **kw):
        comp, used, conv = solve(program, svc_flat, **kw)
        comp = np.array(comp)
        half = len(comp) // 2
        comp[half:] = program.issue_flat[half:] + svc_flat[half:]
        return comp, used, conv
    return broken


def _altered(unpack):
    def broken(*a, **kw):
        out = unpack(*a, **kw)
        for r in out:
            if len(r.complete):
                r.complete[-1] += 1.0
        return out
    return broken


def _kept_altered(keep, calls):
    """``Cell.keep`` with the last value of each kept answer raised by
    1.0; ``calls`` grows by one each time it alters an answer."""
    def broken(self, *a, **kw):
        out = []
        for key, got in keep(self, *a, **kw):
            got = np.array(got, dtype=np.float64)
            if got.size:
                got.flat[-1] += 1.0
                calls.append(type(self).__module__)
            out.append((key, got))
        return out
    return broken


# fault -> (function of repro.core.chain_program it breaks, breaker);
# ``kept_answer_altered`` breaks the entry's own ``Cell.keep`` instead
SOLVER_FAULTS = {"unchanged": ("solve_program", _unchanged),
                 "half_left_out": ("solve_program", _half),
                 "answer_altered": ("unpack_results", _altered)}
FAULTS = [*SOLVER_FAULTS, "kept_answer_altered"]
# the packages whose modules may bind a solver function by name: every
# one is imported before a fault is planted, so none binds it later
SOLVER_USERS = ("repro.core", "repro.experiments", "repro.cluster")


def _entry(name):
    import run

    return run.load_cell(name)[3]["entry"]


def _must_reach(fault, entry):
    """Every cell's timed path solves and keeps answers; only the fleet
    and experiments entries unpack with ``unpack_results`` (the cluster
    path unpacks with ``op_latencies``)."""
    return fault != "answer_altered" or entry in ("fleet", "experiments")


def _bind_everywhere(monkeypatch, original, replacement):
    """Bind ``replacement`` under every name a loaded ``repro`` module
    gives ``original``; the names bound."""
    names = []
    for mod_name, mod in list(sys.modules.items()):
        if mod_name != "repro" and not mod_name.startswith("repro."):
            continue
        for attr, value in list(vars(mod).items()):
            if value is original:
                monkeypatch.setattr(mod, attr, replacement)
                names.append(f"{mod_name}.{attr}")
    return names


def _plant(fault, entry, monkeypatch):
    """Break the timed path by ``fault``; a list that grows by the
    calling module's name each time the broken code runs."""
    calls = []
    if fault == "kept_answer_altered":
        cell = importlib.import_module(f"entries.{entry}").Cell
        monkeypatch.setattr(cell, "keep", _kept_altered(cell.keep, calls))
        return calls
    for package in SOLVER_USERS:
        importlib.import_module(package)
    from repro.core import chain_program

    attr, breaker = SOLVER_FAULTS[fault]
    original = getattr(chain_program, attr)
    broken = breaker(original)

    @functools.wraps(original)
    def counted(*a, **kw):
        calls.append(sys._getframe(1).f_globals.get("__name__"))
        return broken(*a, **kw)

    names = _bind_everywhere(monkeypatch, original, counted)
    assert f"repro.core.chain_program.{attr}" in names, names
    return calls


@pytest.mark.parametrize("fault", [None, *FAULTS])
@pytest.mark.parametrize("name", CELLS)
def test_harness_sees_broken_timed_path(name, fault, monkeypatch):
    entry = _entry(name)
    calls = _plant(fault, entry, monkeypatch) if fault else []
    out, numbers, limits = rehearse.rehearse(name)
    if fault is None:
        assert out["correct"] is True, numbers
    elif calls:
        assert out["correct"] is False, (name, fault, numbers)
    else:
        assert not _must_reach(fault, entry), \
            f"the fault {fault} never reached the timed path of {name}"
        # a fault the path does not reach leaves the run sound
        assert out["correct"] is True, (name, fault, numbers)


def test_solver_fault_reaches_cluster_binding(monkeypatch):
    """``repro.cluster.compiler`` binds ``solve_program`` by name at
    import; a planted fault reaches that binding too."""
    from repro.cluster import Cluster, ClusterSpec, ClusterWorkload, erasure

    rack = Cluster(ClusterSpec(n_gateways=1, n_servers=4,
                               scheme=erasure(2, 1)))
    workload = ClusterWorkload(n_users=4, ops_per_user=4)
    sound = np.asarray(rack.compile(workload).comp)
    calls = _plant("unchanged", None, monkeypatch)
    broken = np.asarray(rack.compile(workload).comp)
    assert "repro.cluster.compiler" in calls, calls
    assert broken.shape == sound.shape and not np.array_equal(broken, sound)


def test_fleet_shrink_keeps_the_rule():
    import run
    from entries import fleet

    traffic = run.load_cell("zn540.fleet64-randrw4k")[3]
    assert fleet.shrink(traffic) == {
        "entry": "fleet", "devices": 4, "jitter": False, "variants": 2,
        "check_devices": 4, "limits": {"max_rel_err": 1e-09},
        "mixes": [[
            {"op": "WRITE", "n": 1000, "size": 4096, "qd": 4, "nzones": 64},
            {"op": "READ", "n": 1000, "size": 4096, "qd": 16,
             "nzones": 64}]]}
    assert traffic["devices"] == 64
    assert traffic["mixes"][0][0]["n"] == 50000


def test_experiments_shrink_is_identity():
    import run
    from entries import experiments

    traffic = run.load_cell("zn540.paper-matrix")[3]
    assert experiments.shrink(traffic) == traffic
    assert rehearse.shrink(traffic) == traffic


def test_rehearse_refuses_entry_without_shrink(monkeypatch):
    entry = types.ModuleType("entries.noshrink")
    entry.Cell = type("Cell", (), {})
    monkeypatch.setitem(sys.modules, "entries.noshrink", entry)
    with pytest.raises(ValueError, match="noshrink"):
        rehearse.shrink({"entry": "noshrink", "n": 10**9})


@pytest.mark.parametrize("name", CELLS)
def test_rehearsal_query_is_small(name):
    """No cell puts a full-size query into the CPU self-checks."""
    work, _ = _cell(name)
    assert 0 < work.events(work.run(work.variants[0])) <= 250_000


def test_run_refuses_without_tpu():
    proc = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1"], cwd=ROOT, capture_output=True,
        text=True, env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert proc.returncode == 2 and proc.stdout == ""
