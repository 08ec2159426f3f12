"""One run of one benchmark cell on the accelerator.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``; its
configuration is ``bench/configs/<config>.json`` (with the plain
reference ``<config>.py`` beside it), its traffic
``bench/traffic/<traffic>.json``, whose ``entry`` names the module of
``bench/entries/`` that drives the program's user entry point.  Each
metric is read by ``bench/metrics/<name>.py``.

A query is one call of the entry point, from the declared workload to
the unpacked result.  Set-up builds the cell's seeded variants and runs
each once, so every program the window uses is compiled (or loaded from
JAX's persistent cache) before it.  The window then runs queries back
to back, one at a time, until ``--seconds`` have passed, clearing the
program's compile cache before each, so each query pays its lowering.
With ``--trace 1`` the window is traced by the JAX profiler and the
per-layer metrics are read from the trace; otherwise the end-to-end
metrics are reported.  After the window, the answers kept from it are
compared with the plain reference, which decides ``correct``.

The last line of standard output is one JSON object; the numbers the
comparison used, each beside its limit, are the last lines of standard
error and the last key of that object.  Without a TPU, with fewer chips
than the cell asks for, or on a device missing from ``bench/peaks.json``
the run prints no result and exits with 2.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import resource  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
SPANS = ("query", "clear_program_cache", "entry", "keep", "release")
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"


class CompileClock:
    """Counts and sums XLA backend compilations, persistent-cache loads
    included, and counts the persistent cache's hits and misses
    (jax.monitoring)."""

    def __init__(self):
        self.count = 0
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0

    def __call__(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.count += 1
            self.seconds += duration

    def event(self, event, **_):
        self.hits += event == CACHE_HIT_EVENT
        self.misses += event == CACHE_MISS_EVENT


def heap_only():
    """Have glibc's malloc serve every block from its heap, none from a
    mapping of its own (``M_MMAP_MAX`` 0).  By default a large block is
    mapped apart and unmapped when freed, under a threshold that malloc
    moves as the process runs; a fleet query allocates gigabytes, and
    whether its arrays were mapped afresh each time was decided by what
    came before the window, so some runs went ~15% slower throughout.
    Without glibc nothing is set."""
    import ctypes

    try:
        libc = ctypes.CDLL("libc.so.6")
    except OSError:
        return
    libc.mallopt(-4, 0)  # M_MMAP_MAX


def fail(msg):
    print(f"bench/run.py: {msg}", file=sys.stderr)
    return 2


def load_cell(name):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {c["name"]: c for c in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{sorted(cells)}")
    cell = cells[name]
    config = json.loads(
        (BENCH / "configs" / f"{cell['config']}.json").read_text())
    traffic = json.loads(
        (BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
    return bench, cell, config, traffic


def metric_specs(bench, cell, trace):
    group = bench["per_layer" if trace else "end_to_end"]
    return [m for m in group
            if cell["name"] in m.get("workloads", [cell["name"]])]


def device_peak(devices):
    """The device allocator's peak so far, on the fullest chip."""
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)


def window(work, seconds, rng, annotate, clear, peak):
    """Run queries back to back until ``seconds`` have passed; each
    query's wall seconds and the device peak after it are kept for the
    diagnostic lines on standard error."""
    w = SimpleNamespace(queries=0, events=0, failed=0, lower_ms=[],
                        query_s=[], query_peak=[], query_variants=[],
                        samples=[])
    t0 = time.perf_counter()
    while True:
        q0 = time.perf_counter()
        k = w.queries % len(work.variants)
        v = work.variants[k]
        with annotate("query"):
            with annotate("clear_program_cache"):
                clear()
            with annotate("entry"):
                res = work.run(v)
            w.events += work.events(res)
            w.failed += bool(work.failed(res))
            w.lower_ms.append(work.lower_ms(res))
            with annotate("keep"):
                w.samples += work.keep(w.queries, v, res, rng)
        with annotate("release"):
            del res
        w.query_variants.append(k)
        w.query_s.append(time.perf_counter() - q0)
        w.query_peak.append(peak())
        w.queries += 1
        if time.perf_counter() - t0 >= seconds:
            break
    w.window_s = time.perf_counter() - t0
    return w


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        return fail(f"--seed must be >= 0, got {args.seed}")
    heap_only()
    try:
        bench, cell, config, traffic = load_cell(args.workload)
    except (OSError, KeyError, ValueError) as e:
        return fail(f"cannot load the cell: {e}")
    # a fixed directory inside the checkout, whatever the environment
    # says, so that two checkouts never share compiled programs; the
    # benchmark's own, because where JAX_COMPILATION_CACHE_MAX_SIZE is
    # set jax writes no entry into a directory that holds entries
    # written without it (such as those of the repository's tests)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(BENCH / ".jax_cache")
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        return fail(f"no TPU found (jax platform is "
                    f"{devices[0].platform!r})")
    if len(devices) < cell["chips"]:
        return fail(f"{cell['name']} needs {cell['chips']} chips, jax "
                    f"sees {len(devices)}")
    peaks = json.loads((BENCH / "peaks.json").read_text())
    if devices[0].device_kind not in peaks:
        return fail(f"device kind {devices[0].device_kind!r} is not in "
                    f"bench/peaks.json")
    out, numbers, limits = measure(bench, cell, config, traffic, args,
                                   devices, peaks[devices[0].device_kind])
    for k in limits:
        print(f"check {k}: {numbers[k]!r} (limit {limits[k]!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


def measure(bench, cell, config, traffic, args, devices, peaks):
    """Set up, run the window and compare: ``(result, numbers,
    limits)``.  Everything after the look for a chip."""
    import jax
    import numpy as np

    sys.path[:0] = [str(ROOT / "src"), str(BENCH)]
    from entries import common
    from repro.core import clear_program_cache, set_program_cache_dir

    set_program_cache_dir(None)
    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    jax.monitoring.register_event_listener(clock.event)
    entry = importlib.import_module(f"entries.{traffic['entry']}")
    specs = metric_specs(bench, cell, args.trace)
    readers = {m["name"]: importlib.import_module(f"metrics.{m['name']}")
               for m in specs}

    work = entry.Cell(traffic, config, args.seed)
    observed = {name: [] for name, r in readers.items()
                if hasattr(r, "observe")}
    for v in work.variants:
        clear_program_cache()
        res = work.run(v)
        for name in observed:
            observed[name].append(readers[name].observe(work, v, res))
        del res
    setup_s = time.perf_counter() - T0
    setup_peak = device_peak(devices)

    rng = np.random.default_rng(args.seed)
    annotate = jax.profiler.TraceAnnotation
    peak = lambda: device_peak(devices)  # noqa: E731
    trace = None
    before = clock.count
    if args.trace:
        from trace_reduce import load, reduce

        kernels = [r.KERNEL for r in readers.values() if hasattr(r, "KERNEL")]
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        with tempfile.TemporaryDirectory() as log_dir:
            jax.profiler.start_trace(log_dir, profiler_options=opts)
            try:
                w = window(work, args.seconds, rng, annotate,
                           clear_program_cache, peak)
            finally:
                jax.profiler.stop_trace()
            trace = reduce(load(log_dir, SPANS), kernels)
    else:
        w = window(work, args.seconds, rng, annotate, clear_program_cache,
                   peak)
    window_compiles = clock.count - before
    memory_peak = device_peak(devices)
    rss_peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
    clear_program_cache()
    gc.collect()

    ctx = SimpleNamespace(
        events=w.events, window_s=w.window_s, queries=w.queries,
        lower_ms=w.lower_ms, query_variants=w.query_variants,
        setup_s=setup_s, memory_peak_bytes=memory_peak,
        rss_peak_bytes=rss_peak, window_compiles=window_compiles,
        trace=trace, observed=observed, peaks=peaks)
    metrics = {}
    for m in specs:
        value = readers[m["name"]].read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    numbers = common.compare(work, w.samples)
    numbers["failed_queries"] = w.failed
    limits = dict(traffic["limits"], failed_queries=0)
    correct = bool(w.samples) and all(
        math.isfinite(numbers[k]) and numbers[k] <= limits[k]
        for k in limits)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": memory_peak}
    out = {"correct": correct, "attempted": w.queries, "failed": w.failed,
           "metrics": metrics, "device": device}
    if trace is not None:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        out["breakdown"] = {"device_ops": trace["device_ops"],
                            "idle_gaps": trace["idle_gaps"]}
    out["checks"] = {k: {"value": numbers[k], "limit": limits[k]}
                     for k in limits}
    print("query seconds: " + " ".join(f"{q:.4f}" for q in w.query_s),
          file=sys.stderr)
    print(f"device peak bytes: set-up {setup_peak}, after each query "
          + " ".join(str(b) for b in w.query_peak), file=sys.stderr)
    print(f"window: {w.queries} queries, {w.events} events, "
          f"{w.window_s:.3f} s, {window_compiles} compiles; set-up "
          f"{setup_s:.3f} s ({clock.count} compiles in all, "
          f"{clock.seconds:.3f} s; persistent cache {clock.hits} hits, "
          f"{clock.misses} misses)",
          file=sys.stderr)
    return out, numbers, limits


if __name__ == "__main__":
    sys.exit(main())
