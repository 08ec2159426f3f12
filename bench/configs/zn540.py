"""Plain reference of the ZN540 device model, independent of ``src/``.

It follows the model's published description (arXiv 2310.19094 sec. III,
as the repository calibrates it) step by step and shares no code with
the program under test:

1. ``lower`` turns declared streams (plain dicts) into a request list.
2. ``service_times`` gives each request its calibrated service time,
   with the seeded lognormal jitter drawn in the model's order: resets,
   finishes, then data I/O.
3. ``simulate`` is a discrete-event simulation in ready-time order:
   closed-loop threads gate request ``p`` on the completion of request
   ``p - qd``; one write in flight per zone; READ/WRITE/APPEND share the
   flash servers, APPEND also takes one of the append servers;
   RESET/FINISH go to the metadata engine (or the flash servers where
   ``reset_on_io_path`` is set); OPEN/CLOSE to two management servers.

``dtype`` selects the arithmetic of the simulation: float64 is the
reference, float32 the lower-precision control.
"""
from __future__ import annotations

import heapq

import numpy as np

READ, WRITE, APPEND, RESET, FINISH, OPEN, CLOSE = range(7)
OPS = {"READ": READ, "WRITE": WRITE, "APPEND": APPEND, "RESET": RESET,
       "FINISH": FINISH, "OPEN": OPEN, "CLOSE": CLOSE}
LBA_512 = 0
KIB = 1024


def _issue_times(arrival, n, start_us, size):
    kind = arrival["kind"]
    if kind == "DeterministicRate":
        if arrival.get("every_us") is not None:
            pace = float(arrival["every_us"])
        elif arrival.get("rate_per_s") is not None:
            pace = 1e6 / float(arrival["rate_per_s"])
        else:
            pace = float(size) / float(arrival["rate_bytes_per_s"]) * 1e6
        return start_us + np.arange(n, dtype=np.float64) * pace
    if kind == "PoissonArrivals":
        rng = np.random.default_rng(arrival["seed"])
        return start_us + np.cumsum(
            rng.exponential(1e6 / float(arrival["rate_per_s"]), n))
    if kind == "TraceReplay" and arrival.get("path") is None:
        return start_us + np.sort(np.asarray(arrival["times_us"],
                                             dtype=np.float64))[:n]
    raise ValueError(f"reference has no arrival process {kind!r}")


def _stream_arrival(s):
    if s.get("arrival") is not None:
        return s["arrival"]
    if s.get("every_us") is not None:
        return {"kind": "DeterministicRate", "every_us": s["every_us"]} \
            if s["every_us"] > 0.0 else None
    if s.get("rate_bytes_per_s") is not None:
        return {"kind": "DeterministicRate",
                "rate_bytes_per_s": s["rate_bytes_per_s"]}
    return None


def _lower_stream(s, thread):
    op = OPS[s["op"]] if isinstance(s["op"], str) else int(s["op"])
    n = int(s["n"])
    start = float(s.get("start_us", 0.0))
    nzones = max(int(s.get("nzones", 1)), 1)
    rows = []
    if op in (READ, WRITE, APPEND):
        arrival = _stream_arrival(s)
        issue = _issue_times(arrival, n, start, s.get("size", 0)) \
            if arrival is not None else np.full(n, start)
        for i in range(n):
            rows.append((op, int(s.get("zone", 0)) + i % nzones,
                         int(s.get("size", 0)), float(issue[i]), 0.0,
                         False, -1))
    else:
        levels = s.get("occupancies") or (s.get("occupancy", 0.0),)
        per = int(s.get("n_per_level", 1)) if s.get("occupancies") else n
        pause = float(s.get("pause_us", 0.0))
        base = _issue_times(s["arrival"], len(levels) * per, start, 0) \
            if s.get("arrival") is not None else None
        ctx = int(s.get("io_ctx", -1))
        t, slot = start, 0
        for occ in levels:
            for _ in range(per):
                t = float(base[slot]) + pause if base is not None \
                    else t + pause
                slot += 1
                if op == RESET and s.get("finish_first") and 0.0 < occ < 1.0:
                    rows.append((FINISH, 0, 0, t, occ, False, ctx))
                    t += 1.0
                    rows.append((RESET, 0, 0, t, occ, True, ctx))
                else:
                    rows.append((op, 0, 0, t, occ,
                                 bool(s.get("was_finished", False)), ctx))
                if base is None and s.get("every_us") is not None:
                    t += float(s["every_us"])
        rows = [(r[0], int(s.get("zone", 0)) + i % nzones) + r[2:]
                for i, r in enumerate(rows)]
    qd = int(s.get("qd", 1))
    qd = qd if qd > 0 else max(len(rows), 1)
    return [r + (thread, qd) for r in rows]


def lower(streams):
    """Request list of declared streams: a dict of numpy columns ``op,
    zone, size, issue, occupancy, was_finished, io_ctx, thread, qd`` in
    stream order.  Streams without a pinned ``thread`` take the lowest
    free thread ids in order."""
    pinned = {s["thread"] for s in streams if s.get("thread") is not None}
    free = iter(t for t in range(len(streams) + len(pinned))
                if t not in pinned)
    rows = []
    for s in streams:
        thread = s["thread"] if s.get("thread") is not None else next(free)
        rows += _lower_stream(s, thread)
    cols = ("op", "zone", "size", "issue", "occupancy", "was_finished",
            "io_ctx", "thread", "qd")
    types = (np.int64, np.int64, np.int64, np.float64, np.float64, bool,
             np.int64, np.int64, np.int64)
    return {c: np.array([r[i] for r in rows], dtype=t)
            for i, (c, t) in enumerate(zip(cols, types))}


def _io_us(lat, op, size, stack, fmt):
    keys = np.asarray(lat["size_anchors"], dtype=np.float64)
    row = np.asarray(lat["io_svc_us"], dtype=np.float64)[op]
    if size > keys[-1]:
        base = row[-1] * (size / keys[-1])
    else:
        base = float(np.interp(size, keys, row))
    if fmt == LBA_512:
        pen = lat["lba512_penalty"][op]
        decay = min(max(32 * KIB / max(size, 4 * KIB), 0.25), 1.0)
        base *= 1.0 + (pen - 1.0) * decay
    return base + lat["stack_overhead_us"][stack]


def service_times(req, lat, *, stack=0, fmt=1, seed=0, jitter=True):
    """Calibrated service time (us) of every request."""
    rng = np.random.default_rng(seed)
    op = req["op"]
    svc = np.zeros(len(op))
    io = np.flatnonzero(op <= APPEND)
    cost = {}
    for i in io:
        key = (int(op[i]), float(req["size"][i]))
        if key not in cost:
            cost[key] = _io_us(lat, *key, stack, fmt)
        svc[i] = cost[key]
    sigma = float(lat["reset_tail_sigma"])
    resets = np.flatnonzero(op == RESET)
    for i in resets:
        occ = min(max(float(req["occupancy"][i]), 0.0), 1.0)
        us = float(np.interp(occ, lat["reset_occ"], lat["reset_us_table"]))
        if req["was_finished"][i]:
            us *= float(lat["reset_finished_discount"])
        svc[i] = us
    if jitter and len(resets):
        svc[resets] *= np.exp(sigma * rng.standard_normal(len(resets))
                              - sigma ** 2 / 2)
    for i in resets:
        ctx = int(req["io_ctx"][i])
        if 0 <= ctx <= APPEND:
            svc[i] *= float(lat["reset_inflation"][ctx])
    finishes = np.flatnonzero(op == FINISH)
    for i in finishes:
        occ = min(max(float(req["occupancy"][i]), 0.0), 1.0)
        svc[i] = float(lat["finish_floor_us"]) \
            + float(lat["finish_span_us"]) * (1.0 - occ)
    if jitter and len(finishes):
        svc[finishes] *= np.exp(sigma * rng.standard_normal(len(finishes))
                                - sigma ** 2 / 2)
    svc[op == OPEN] = float(lat["open_cost_us"])
    svc[op == CLOSE] = float(lat["close_cost_us"])
    if jitter and len(io):
        sig = np.asarray(lat["io_jitter_sigma"], dtype=np.float64)[op[io]]
        svc[io] *= np.exp(sig * rng.standard_normal(len(io)) - sig ** 2 / 2)
    return svc


def simulate(req, svc, spec, lat, *, dtype=np.float64):
    """``(start, complete)`` of every request (us), in request order."""
    f = float if np.dtype(dtype) == np.float64 else np.dtype(dtype).type
    n = len(svc)
    svc = [f(v) for v in svc]
    issue = [f(v) for v in req["issue"]]
    zero = f(0.0)
    flash = [zero] * int(spec["read_parallelism"])
    append = [zero] * int(spec["append_parallelism"])
    meta = [zero] * max(int(spec["reset_parallelism"]), 1)
    mgmt = [zero] * 2
    meta_on_io = float(lat["reset_on_io_path"]) != 0.0
    zone_ready = {}
    threads = {}
    for idx in np.argsort(req["issue"], kind="stable"):
        threads.setdefault(int(req["thread"][idx]), []).append(int(idx))
    done = {t: [] for t in threads}
    pos = {t: 0 for t in threads}
    start = [zero] * n
    complete = [zero] * n
    heap = []

    def push(t):
        p = pos[t]
        if p < len(threads[t]):
            i = threads[t][p]
            q = max(int(req["qd"][i]), 1)
            gate = done[t][p - q] if p >= q else zero
            heapq.heappush(heap, (max(issue[i], gate), issue[i], i, t))

    for t in threads:
        push(t)
    while heap:
        ready, _, i, t = heapq.heappop(heap)
        pos[t] += 1
        op = int(req["op"][i])
        z = int(req["zone"][i])
        if op == WRITE and z >= 0:
            ready = max(ready, zone_ready.get(z, zero))
        if op <= APPEND or (op in (RESET, FINISH) and meta_on_io):
            begin = max(ready, heapq.heappop(flash))
            if op == APPEND:
                begin = max(begin, heapq.heappop(append))
                heapq.heappush(append, begin + svc[i])
            heapq.heappush(flash, begin + svc[i])
        else:
            pool = meta if op in (RESET, FINISH) else mgmt
            begin = max(ready, heapq.heappop(pool))
            heapq.heappush(pool, begin + svc[i])
        end = begin + svc[i]
        if op == WRITE and z >= 0:
            zone_ready[z] = end
        start[i], complete[i] = begin, end
        done[t].append(end)
        push(t)
    return (np.asarray(start, dtype=np.float64),
            np.asarray(complete, dtype=np.float64))


def run(streams, spec, lat, *, stack=0, fmt=1, seed=0, jitter=True,
        dtype=np.float64):
    """Lower, draw service times and simulate: ``(request list, start,
    complete)``."""
    req = lower(streams)
    svc = service_times(req, lat, stack=stack, fmt=fmt, seed=seed,
                        jitter=jitter)
    start, complete = simulate(req, svc, spec, lat, dtype=dtype)
    return req, start, complete
