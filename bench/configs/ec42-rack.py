"""Plain reference of the erasure-coded object rack, independent of
``src/``.

It reads the seeded op list as plain dicts (``seq``, ``client``,
``gateway``, ``kind``, ``obj``, ``nbytes``, ``issue``), the rack's
configuration file and the drive's (``configs/<drive>.json``), builds
its own event model of every object op and runs it as a ready-time-
ordered discrete-event simulation with FIFO servers.  It returns each
op's latency: the op's acknowledgement less the instant the client was
free to issue it.  The rules follow the rack's documented semantics
(the cluster compiler's and the storage server's docstrings):

1. **Placement and shards.**  Slot ``s`` of object ``o`` lives on
   server ``(o + s) % n_servers`` (round-robin).  An object of ``B``
   bytes is cut into ``k`` data shards of ``ceil(B / k)`` bytes, and
   ``m`` parity shards of that size; every shard is padded to it on the
   wire and on flash.
2. **Gateway.**  Each op takes one of the gateway's CPU cores; a PUT
   then encodes (``encode_us_per_mib`` per object MiB).  A PUT writes
   every slot whose server is up.  A GET reads the ``k`` data slots;
   when one of them is on the down server it reads every surviving slot
   instead (``k - 1`` data and ``m`` parity: the rack's conservative
   full-stripe reconstruction, of which the decode needs ``k``) and
   decodes at the gateway (``decode_us_per_mib``) once all have come
   back.  A DELETE sends an empty write to every live slot.
3. **Hops.**  Each shard request crosses the gateway's transmit lane,
   the fabric (a pure delay), the server's receive lane, one of the
   server's CPU cores, the server's transmit lane, the fabric and the
   gateway's receive lane.  A lane serialises ``bytes x wire_overhead``
   at its line rate; a write carries its shard out and a control
   message back, a read the reverse.  The op is acknowledged when the
   last of its shards is back (plus the decode).
4. **Writeback log.**  A written shard is appended to its server's log
   when the server's CPU takes it; an insert that would overflow the
   ``writeback_bytes`` buffer waits for the flush that frees enough
   room.  The log goes to flash in ``flush_chunk`` appends, in log
   order, at most ``flush_qd`` outstanding and through the drive's
   ``append_parallelism`` append servers; with write-through
   durability a chunk may be flushed as soon as its first byte is in
   (every insert asks for durability), with writeback once it is full
   (or holds the log's tail).  **Write-through**: a shard's
   acknowledgement leaves the server only after the flush covering its
   last byte has completed.
5. **Reads.**  A shard whose covering chunk could already be flushed
   when the GET was planned is read from flash, after that flush, on
   one of the drive's ``read_parallelism`` read servers; otherwise it
   is served from the buffer in RAM.
6. **Drive.**  Service times are the drive's calibrated QD-1 times,
   jitter-free, on the SPDK stack with the configuration's LBA format:
   interpolated between the size anchors, and proportional to size
   beyond the last anchor.
7. **Clients.**  A client prepares its ops in order (op ``i``'s gateway
   CPU follows op ``i - 1``'s) and, at closed-loop depth ``qd``, op
   ``i`` waits for the acknowledgement of op ``i - qd``.
8. **Order.**  Events become ready at the latest of their issue time
   and their predecessors' completions; each FIFO pool serves them in
   ready order on its least-loaded server.  Ties go to the earlier
   issue time, then to the event made first: ops in ``seq`` order, each
   as gateway CPU, encode, acknowledgement, then its shards' hops in
   slot order; the flushes after every op, server by server.  Ready
   times are compared on a 1e-6 us grid, as the rack's documentation
   sets, so that sums taken in another order tie alike.

``dtype`` selects the arithmetic of the simulation: float64 is the
reference, float32 the lower-precision control.
"""
from __future__ import annotations

import heapq
import json
from pathlib import Path

import numpy as np

PUT, GET, DELETE = 0, 1, 2
READ, APPEND = 0, 2
LBA_512 = 0
KIB = 1024
MIB = 1024 * KIB
QUANTUM_US = 1e-6
CONFIGS = Path(__file__).resolve().parent


def drive(config):
    """The drive's configuration file named by the rack's."""
    return json.loads((CONFIGS / f"{config['drive']}.json").read_text())


def _wire_us(nbytes, gbps, overhead):
    return float(nbytes) * overhead * 8.0e-3 / float(gbps)


def _io_us(lat, op, size, fmt):
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
    return float(base) + float(lat["stack_overhead_us"][0])


class _Events:
    """Events in the order they are made: service time, issue time, FIFO
    pool (or none) and predecessors."""

    def __init__(self):
        self.svc, self.issue, self.pool, self.preds = [], [], [], []

    def new(self, svc, *, issue=0.0, pool=None, after=()):
        self.svc.append(float(svc))
        self.issue.append(float(issue))
        self.pool.append(pool)
        self.preds.append(list(after))
        return len(self.svc) - 1

    def path(self, *evs):
        """Each event after the one before it."""
        for a, b in zip(evs[:-1], evs[1:]):
            self.preds[b].append(a)


class _Log:
    """One server's writeback log: inserts and shard ranges."""

    def __init__(self):
        self.cum = 0
        self.inserts = []          # (cum after, insert event)
        self.ranges = {}           # (obj, slot) -> (lo, hi)


def _slots(config, kind, row, down):
    """``(slots, decode)`` an op touches."""
    k, m = config["scheme"]["k"], config["scheme"]["m"]
    live = [s for s in range(k + m) if down is None or row[s] != down]
    if kind in (PUT, DELETE):
        return live, False
    if down is None or all(row[s] != down for s in range(k)):
        return list(range(k)), False
    return live, True


def build(ops, config, *, down=None, qd=1):
    """The event model of ``ops``: ``(events, op heads, op acks)``."""
    dr = drive(config)
    spec, lat = dr["spec"], dr["latency"]
    fmt = int(spec["lba_format"])
    net, gw, srv = config["network"], config["gateway"], config["server"]
    k, m = config["scheme"]["k"], config["scheme"]["m"]
    n_srv = int(config["n_servers"])
    chunk, wb = int(srv["flush_chunk"]), int(srv["writeback_bytes"])
    through = config["durability"] == "write-through"
    req = int(net["req_bytes"])
    ov = float(net["wire_overhead"])

    def gw_wire(n):
        return _wire_us(n, net["gw_nic_gbps"], ov)

    def srv_wire(n):
        return _wire_us(n, net["srv_nic_gbps"], ov)

    ev = _Events()
    logs = [_Log() for _ in range(n_srv)]
    room, acks, reads = [], [], []        # (server, event, hi)
    heads, tails = {}, {}
    for op in sorted(ops, key=lambda o: o["seq"]):
        g, kind, nbytes = op["gateway"], op["kind"], int(op["nbytes"])
        row = [(op["obj"] + s) % n_srv for s in range(k + m)]
        sb = -(-nbytes // k) if nbytes > 0 else 0
        slots, decode = _slots(config, kind, row, down)
        head = ev.new(gw["cpu_us"], issue=op["issue"],
                      pool=("gw_cpu", g, gw["cpu_cores"]))
        src = head
        encode = gw["encode_us_per_mib"] * nbytes / MIB \
            if kind == PUT and config["scheme"]["kind"] == "ec" and m > 0 \
            else 0.0
        if encode > 0.0:
            src = ev.new(encode, after=[head])
        ack = ev.new(gw["decode_us_per_mib"] * nbytes / MIB if decode
                     else 0.0)
        heads[op["seq"]], tails[op["seq"]] = head, ack
        for slot in slots:
            r = row[slot]
            log = logs[r]
            if kind in (PUT, DELETE):
                shard = sb if kind == PUT else 0
                out = [ev.new(gw_wire(shard + req), pool=("gw_tx", g, 1)),
                       ev.new(net["one_way_us"]),
                       ev.new(srv_wire(shard + req), pool=("srv_rx", r, 1)),
                       ev.new(srv["cpu_us"],
                              pool=("srv_cpu", r, srv["cpu_cores"])),
                       ev.new(srv_wire(req), pool=("srv_tx", r, 1))]
                if shard > 0:
                    if shard > wb - chunk:
                        raise ValueError("a shard does not fit the buffer "
                                         "beside one flush chunk")
                    lo = log.cum
                    log.cum += shard
                    log.ranges[(op["obj"], slot)] = (lo, log.cum)
                    log.inserts.append((log.cum, out[3]))
                    if log.cum > wb:
                        room.append((r, out[3], log.cum))
                    if through:
                        acks.append((r, out[4], log.cum))
                back = [ev.new(net["one_way_us"]),
                        ev.new(gw_wire(req), pool=("gw_rx", g, 1))]
            else:
                lo, hi = log.ranges[(op["obj"], slot)]
                out = [ev.new(gw_wire(req), pool=("gw_tx", g, 1)),
                       ev.new(net["one_way_us"]),
                       ev.new(srv_wire(req), pool=("srv_rx", r, 1)),
                       ev.new(srv["cpu_us"],
                              pool=("srv_cpu", r, srv["cpu_cores"]))]
                first = (hi - 1) // chunk
                on_flash = log.cum > first * chunk if through \
                    else log.cum >= (first + 1) * chunk
                if on_flash:
                    out.append(ev.new(
                        _io_us(lat, READ, sb, fmt),
                        pool=("dev_read", r, spec["read_parallelism"])))
                    reads.append((r, out[-1], hi))
                out.append(ev.new(srv_wire(sb + req),
                                  pool=("srv_tx", r, 1)))
                back = [ev.new(net["one_way_us"]),
                        ev.new(gw_wire(sb + req), pool=("gw_rx", g, 1))]
            ev.path(src, *out, *back, ack)

    by_client = {}
    for op in sorted(ops, key=lambda o: o["seq"]):
        by_client.setdefault(op["client"], []).append(op["seq"])
    for seqs in by_client.values():
        for i in range(1, len(seqs)):
            ev.preds[heads[seqs[i]]].append(heads[seqs[i - 1]])
        for i in range(qd, len(seqs)):
            ev.preds[heads[seqs[i]]].append(tails[seqs[i - qd]])

    append_us = _io_us(lat, APPEND, chunk, fmt)
    flushes = []
    for log in logs:
        n = -(-log.cum // chunk)
        cums = [c for c, _ in log.inserts]
        fl = []
        for f in range(n):
            # the insert that makes chunk f flushable
            if through:
                i = int(np.searchsorted(cums, f * chunk, side="right"))
            else:
                i = int(np.searchsorted(
                    cums, min((f + 1) * chunk, log.cum), side="left"))
            lag = [fl[f - d] for d in (int(srv["flush_qd"]),
                                       int(spec["append_parallelism"]))
                   if f >= d]
            fl.append(ev.new(append_us, after=[log.inserts[i][1], *lag]))
        flushes.append(fl)
    for r, e, hi in room:
        ev.preds[e].append(flushes[r][-(-(hi - wb) // chunk) - 1])
    for r, e, hi in acks + reads:
        ev.preds[e].append(flushes[r][(hi - 1) // chunk])
    return ev, heads, tails


def simulate(ev, *, dtype=np.float64):
    """``(ready, complete)`` of every event (us)."""
    f = float if np.dtype(dtype) == np.float64 else np.dtype(dtype).type
    n = len(ev.svc)
    svc = [f(v) for v in ev.svc]
    issue = [f(v) for v in ev.issue]
    succs = [[] for _ in range(n)]
    waiting = [0] * n
    for b, preds in enumerate(ev.preds):
        for a in set(preds):
            succs[a].append(b)
            waiting[b] += 1
    pools = {}
    ready = list(issue)
    complete = [f(0.0)] * n

    def key(e):
        return (round(float(ready[e]) / QUANTUM_US) * QUANTUM_US,
                float(issue[e]), e)

    heap = [key(e) for e in range(n) if not waiting[e]]
    heapq.heapify(heap)
    done = 0
    while heap:
        e = heapq.heappop(heap)[2]
        begin = ready[e]
        pool = ev.pool[e]
        if pool is not None:
            free = pools.setdefault(pool, [f(0.0)] * int(pool[2]))
            begin = max(begin, heapq.heappop(free))
        end = begin + svc[e]
        if pool is not None:
            heapq.heappush(free, end)
        complete[e] = end
        done += 1
        for s in succs[e]:
            ready[s] = max(ready[s], end)
            waiting[s] -= 1
            if not waiting[s]:
                heapq.heappush(heap, key(s))
    if done != n:
        raise ValueError(f"the rack's events wait on each other: {done} of "
                         f"{n} completed")
    return (np.asarray(ready, dtype=np.float64),
            np.asarray(complete, dtype=np.float64))


def run(ops, config, *, down=None, qd=1, dtype=np.float64):
    """Each op's latency (us), in ``seq`` order."""
    ev, heads, tails = build(ops, config, down=down, qd=qd)
    ready, complete = simulate(ev, dtype=dtype)
    seqs = sorted(heads)
    return np.asarray([float(complete[tails[s]]) - float(ready[heads[s]])
                       for s in seqs], dtype=np.float64)
