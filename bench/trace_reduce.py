"""Reduction of a profiler trace to the benchmark's device numbers.

``load`` reads the ``.xplane.pb`` a ``jax.profiler`` session wrote and
keeps what the reduction needs, as plain data:

* ``device``: ``[plane, line, name, start_ns, dur_ns]`` of every event on
  an accelerator plane (``/device:...``);
* ``host``: ``[name, start_ns, dur_ns]`` of the harness's own spans.

``reduce`` turns that into the window, the device's busy time (the union
of the ``XLA Ops`` intervals, averaged over the chips), the time of named
executables (the ``XLA Modules`` events whose name holds one), the device
operations that took most time, and the longest idle gaps, each named by
the innermost harness span around its middle.  ``test_bench.py`` beside
this file checks it on a small recorded trace.
"""
from __future__ import annotations

import glob
import os

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "query"


def load(log_dir, spans):
    """Device events and the named host spans of the one trace under
    ``log_dir``."""
    import jax

    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {log_dir}, "
                           f"found {len(paths)}")
    data = jax.profiler.ProfileData.from_file(paths[0])
    device, host = [], []
    for plane in data.planes:
        on_device = plane.name.startswith("/device:")
        for line in plane.lines:
            for ev in line.events:
                if on_device:
                    # an op's name is its whole HLO instruction; keep
                    # the part before " = " ("%fusion.12")
                    device.append([plane.name, line.name,
                                   ev.name.split(" = ")[0], ev.start_ns,
                                   ev.duration_ns])
                elif ev.name in spans:
                    host.append([ev.name, ev.start_ns, ev.duration_ns])
    return {"device": device, "host": host}


def _union(intervals):
    total, end = 0.0, float("-inf")
    for a, b in sorted(intervals):
        if b <= end:
            continue
        total += b - max(a, end)
        end = b
    return total


def _gaps(busy, lo, hi):
    out, cur = [], lo
    for a, b in sorted(busy):
        if a > cur:
            out.append((cur, min(a, hi)))
        cur = max(cur, b)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(a, b) for a, b in out if b > a]


def _span_at(host, t):
    inside = [(dur, name) for name, start, dur in host
              if start <= t <= start + dur]
    return min(inside)[1] if inside else "between spans"


def reduce(events, kernels, top=10):
    """Window and busy seconds, the seconds of each executable named in
    ``kernels``, the top device ops and the longest idle gaps."""
    host = events["host"]
    queries = [(s, s + d) for name, s, d in host if name == WINDOW_SPAN]
    if not queries:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    lo = min(a for a, _ in queries)
    hi = max(b for _, b in queries)
    planes = sorted({p for p, *_ in events["device"]})
    busy_ns, kernel_ns, per_op, gaps = 0.0, dict.fromkeys(kernels, 0.0), \
        {}, []
    for plane in planes:
        ops = [(max(s, lo), min(s + d, hi))
               for p, line, name, s, d in events["device"]
               if p == plane and line == OPS_LINE and s < hi and s + d > lo]
        busy_ns += _union(ops)
        for p, line, name, s, d in events["device"]:
            if p != plane or not lo <= s < hi:
                continue
            if line == OPS_LINE:
                per_op[name] = per_op.get(name, 0.0) + d
            elif line == MODULES_LINE:
                for k in kernel_ns:
                    if k in name:
                        kernel_ns[k] += d
        gaps += [(b - a, _span_at(host, (a + b) / 2))
                 for a, b in _gaps(ops, lo, hi)]
    n = max(len(planes), 1)
    ops_top = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    gaps_top = sorted(gaps, key=lambda g: -g[0])[:top]
    return {"window_s": (hi - lo) / 1e9, "busy_s": busy_ns / n / 1e9,
            "kernels": {k: ns / n / 1e9 for k, ns in kernel_ns.items()},
            "chips": len(planes),
            "device_ops": [[name, ns / n / 1e9] for name, ns in ops_top],
            "idle_gaps": [[name, ns / 1e9] for ns, name in gaps_top]}
