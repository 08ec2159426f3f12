"""Bring-up smoke test: the simulator's main path on a TPU.

    python chip_smoke.py             # three phases on one chip
    python chip_smoke.py --chips 4   # only the mesh executor, on 4 chips

Everything runs in this one process.  Each phase drives a user entry
point with ``fixpoint="auto"``, which must pick the device form
(``repro.core.platform``), then runs the same call pinned to the host's
float64 ``"loop"`` driver and compares the completions at the exactness
matrix's tolerances (``benchmarks/exactness_matrix.py``: rtol 1e-9
jitter-free, 1e-8 jittered, atol 1e-6 us).

Phases on one chip:

* ``observations`` — the whole experiment registry (the 13 paper
  observations and two traffic scenarios) through
  :class:`repro.experiments.ExperimentRunner`, as ``python -m
  repro.experiments run --all`` runs it (one ``DeviceFleet`` call);
  every experiment's ``check()`` must pass.
* ``fleet`` — 64 full-spec ZN540 devices (904 zones x 1077 MiB), each
  with 50k 4 KiB writes at qd 4 and 50k 4 KiB reads at qd 16 over 64
  zones, jittered, seed ``i`` on device ``i``: 6.4M requests.
* ``rack`` — ``Cluster(ClusterSpec(scheme=erasure(4, 2)))`` running the
  default ``ClusterWorkload`` (its pop-order refinement runs on the
  host under ``auto``; the final solve on the chip).

With ``--chips 4``: the mega-fleet benchmark's straggler fleet solved by
the ``shard_map`` mesh executor across the four chips, compared with
the single-chip device solve at rel 1e-12; the shards must land on all
four devices.

Each phase prints one line: the driver chosen, events, sweeps,
convergence, the largest relative and absolute error, ``compile_s`` (JAX
trace + lower + compile time in the first device call), ``solve_s`` (a
second, warm device call) and ``host_s`` (the pinned host call).  These
are bring-up timings, not benchmark results.  Observation artifacts and
a ``phases.json`` go to ``chiprun_out/chip_smoke/``.  The last line is
one JSON object naming the device.  The exit code is non-zero when no
TPU is found, when ``auto`` picks anything but the device form, or when
any phase fails.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
OUT_DIR = ROOT / "chiprun_out" / "chip_smoke"

#: Exactness-matrix tolerances (``benchmarks/exactness_matrix.py``).
TOL_JITTER_FREE = 1e-9
TOL_JITTERED = 1e-8
ATOL_US = 1e-6
#: Mesh executor vs the single-chip device solve.
MESH_RTOL = 1e-12

#: Full-width fleet phase: devices x (writes + reads) per device.
FLEET_DEVICES = 64
FLEET_OPS = 50_000
#: Mesh phase: replicated devices beside the straggler rack.
MESH_FLEET_DEVICES = 256

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


class CompileClock:
    """Sums JAX's compile-duration events (seconds)."""

    def __init__(self):
        self.total = 0.0

    def __call__(self, event, duration, **_):
        if event in _COMPILE_EVENTS:
            self.total += duration


def compare(got, want, rtol):
    """``(max_rel, max_abs, ok)``; ok iff |got - want| <= atol + rtol |want|
    everywhere."""
    import numpy as np

    got, want = np.asarray(got), np.asarray(want)
    err = np.abs(got - want)
    if not len(err):
        return 0.0, 0.0, True
    rel = float(np.max(err / np.maximum(np.abs(want), 1e-300)))
    ok = bool(got.shape == want.shape
              and np.all(err <= ATOL_US + rtol * np.abs(want)))
    return rel, float(err.max()), ok


def run_phase(name, call, completions, rtol, clock, expect_driver,
              expect_devices):
    """Run ``call(fixpoint)`` pinned to the host loop, then twice with
    ``auto`` (cold, warm); compare the cold device result with the
    host's.  ``completions(result)`` flattens a result to one array.
    Returns ``(record, device_result)``."""
    from repro.core import last_solve_stats

    t0 = time.perf_counter()
    host = call("loop")
    host_s = time.perf_counter() - t0
    before = clock.total
    dev = call("auto")
    stats = last_solve_stats()
    compile_s = clock.total - before
    t0 = time.perf_counter()
    call("auto")
    solve_s = time.perf_counter() - t0
    want = completions(host)
    rel, abs_err, close = compare(completions(dev), want, rtol)
    rec = {"phase": name, "driver": stats.driver,
           "devices": list(stats.devices), "events": int(len(want)),
           "sweeps": stats.sweeps, "converged": bool(stats.converged),
           "max_rel_err": rel, "max_abs_err_us": abs_err, "rtol": rtol,
           "compile_s": compile_s, "solve_s": solve_s, "host_s": host_s}
    problems = []
    if stats.driver != expect_driver:
        problems.append(f"auto picked {stats.driver!r}, not "
                        f"{expect_driver!r}")
    if tuple(stats.devices) != expect_devices:
        problems.append(f"solved on {stats.devices}, not {expect_devices}")
    if not stats.converged:
        problems.append("device solve did not converge")
    if not close:
        problems.append(f"device vs host beyond rtol {rtol:g} / atol "
                        f"{ATOL_US:g} us")
    rec["problems"] = problems
    return rec, dev


def observations_phase(clock, expect):
    import numpy as np

    from repro.experiments import ExperimentRunner

    runner = ExperimentRunner()
    rec, fres = run_phase(
        "observations", lambda fp: runner.simulate(fixpoint=fp),
        lambda r: np.concatenate([x.sim.complete for x in r]),
        TOL_JITTER_FREE, clock, *expect)
    results = runner.evaluate(fres)
    runner.write_artifacts(results, out_dir=str(OUT_DIR / "experiments"))
    failed = [r.name for r in results if not (r.passed and r.converged)]
    rec["experiments_passed"] = f"{len(results) - len(failed)}/" \
                                f"{len(results)}"
    if failed:
        rec["problems"].append(f"experiment checks failed: {failed}")
    return rec


def fleet_phase(clock, expect):
    import numpy as np

    from repro.core import DeviceFleet, KiB, WorkloadSpec, ZNSDeviceSpec

    spec = ZNSDeviceSpec()
    wl = (WorkloadSpec()
          .writes(n=FLEET_OPS, size=4 * KiB, qd=4, nzones=64)
          .reads(n=FLEET_OPS, size=4 * KiB, qd=16, nzones=64))
    fleet = DeviceFleet.homogeneous(FLEET_DEVICES, spec)
    rec, _ = run_phase(
        "fleet",
        lambda fp: fleet.run(wl, policy="replicate", jitter=True,
                             backend="vectorized", fixpoint=fp),
        lambda r: np.concatenate([x.sim.complete for x in r]),
        TOL_JITTERED, clock, *expect)
    rec["zones_x_zone_mib"] = \
        f"{spec.num_zones}x{spec.zone_cap_bytes // 2**20}"
    return rec


def rack_phase(clock, expect):
    from repro.cluster import Cluster, ClusterSpec, ClusterWorkload, erasure

    cluster = Cluster(ClusterSpec(scheme=erasure(4, 2)))
    wl = ClusterWorkload()
    rec, res = run_phase("rack", lambda fp: cluster.run(wl, fixpoint=fp),
                         lambda r: r.comp, TOL_JITTER_FREE, clock, *expect)
    rec["objects"] = res.n_ops
    rec["refine_iters"] = res.compiled.program.refine_used
    rec["order_stable"] = bool(res.compiled.program.order_stable)
    # Recompile warm-started from the device's result, its refinement
    # solved on the chip (``auto`` refines on the host).  The compiler
    # keeps a warm seed only when its tightness check (rtol 1e-12)
    # accepts the solve, so a device answer that is off by more than
    # float64 rounding shows up here as a rejection the host does not make.
    accepted = {who: cluster.compile(wl, fixpoint=fp,
                                     comp0=res.comp).warm_start_used
                for who, fp in (("host", "loop"), ("device", "xla"))}
    rec["warm_seed_accepted"] = accepted
    if accepted["device"] != accepted["host"]:
        rec["problems"].append(f"warm-start verification differs: "
                               f"{accepted}")
    return rec


def mesh_phase(clock, devices):
    """Mesh executor across every chip vs the single-chip device solve."""
    from repro.core import last_solve_stats, solve_program, \
        solve_program_sharded

    from benchmarks.mega_fleet import _fleet, _straggler_rack

    prog, svc, *_ = _fleet(MESH_FLEET_DEVICES, _straggler_rack())
    t0 = time.perf_counter()
    ref, _, ref_conv = solve_program(prog, svc, sweeps=1024,
                                     fixpoint="xla", warn=False)
    one_chip_s = time.perf_counter() - t0
    before = clock.total
    t0 = time.perf_counter()
    got, used, conv = solve_program_sharded(prog, svc, sweeps=1024,
                                            executor="mesh", warn=False)
    mesh_cold_s = time.perf_counter() - t0
    stats = last_solve_stats()
    compile_s = clock.total - before
    t0 = time.perf_counter()
    solve_program_sharded(prog, svc, sweeps=1024, executor="mesh",
                          warn=False)
    solve_s = time.perf_counter() - t0
    rel, abs_err, close = compare(got, ref, MESH_RTOL)
    want = tuple(sorted(str(d) for d in devices))
    rec = {"phase": "mesh", "driver": stats.driver,
           "devices": list(stats.devices), "events": int(prog.n_flat),
           "entries": int(prog.n_devices), "sweeps": used,
           "converged": bool(conv and ref_conv), "max_rel_err": rel,
           "max_abs_err_us": abs_err, "rtol": MESH_RTOL,
           "compile_s": compile_s, "mesh_cold_s": mesh_cold_s,
           "solve_s": solve_s, "one_chip_s": one_chip_s}
    problems = []
    if tuple(stats.devices) != want:
        problems.append(f"shards on {stats.devices}, not on all of {want}")
    if not (conv and ref_conv):
        problems.append("a solve did not converge")
    if not close:
        problems.append(f"mesh vs one chip beyond rtol {MESH_RTOL:g}")
    rec["problems"] = problems
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: no TPU found (jax platform is {platform!r})",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, jax sees {len(devices)}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from repro.core import platform as selection

    selection.probe()
    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    print(f"device: {devices[0].device_kind} x{len(devices)}; compile "
          f"cache: {jax.config.jax_compilation_cache_dir}", flush=True)
    if args.chips == 4:
        phases = [lambda: mesh_phase(clock, devices)]
    else:
        expect = ("xla", (str(devices[0]),))
        phases = [lambda: observations_phase(clock, expect),
                  lambda: fleet_phase(clock, expect),
                  lambda: rack_phase(clock, expect)]
    records = []
    for phase in phases:
        rec = phase()
        records.append(rec)
        print(json.dumps(rec), flush=True)
    OUT_DIR.mkdir(parents=True, exist_ok=True)
    (OUT_DIR / "phases.json").write_text(json.dumps(records, indent=1))
    failed = [r["phase"] for r in records if r["problems"]]
    if failed:
        print(f"chip_smoke: failed phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
