"""Entry-axis sharding of compiled chain programs.

A fleet-wide :class:`~repro.core.chain_program.ChainProgram` is
block-diagonal over its entries: chains never cross devices (the fleet
compiler) or cluster entries (``concat_programs``), so the fused
Gauss-Seidel fixpoint decomposes into independent sub-fixpoints.  This
module exploits that two ways:

* **host executor** — partition the entries into *signature groups*
  (entries with identical chain structure: replicas, or one
  heterogeneity tier of a mixed fleet) and solve each group with the
  float64 numpy driver under its own convergence budget.  A single
  whole-fleet solve pays ``max_s sweeps(s)`` sweeps of fleet-wide
  gathers and edge checks; the grouped solve pays
  ``sum_s sweeps(s) * |group_s|`` — on fleets mixing easy
  (read-dominated, ~2 sweeps) and hard (saturated qd-2 write pools,
  ``threads + 1`` sweeps) devices that is a multiple-x win on one chip,
  before any parallel hardware enters the picture.
* **mesh executor** — balance the entries across every local jax
  device with a 1-D :class:`jax.sharding.Mesh` + ``shard_map``
  (``repro.kernels.zns_fixpoint.zns_fixpoint_sharded``): stacked,
  padded per-shard block tensors, one early-exiting float64
  ``while_loop`` per shard, completion buffers donated across sweeps.

Partitioning is safe by construction: entries are the connected
components of the chain/device incidence graph (a union-find pass), so
a family added by ``extend_program`` that couples two devices simply
fuses them into one shard.  ``solve_program(fixpoint="auto")`` routes
here only on multi-chip accelerator hosts (:mod:`repro.core.platform`);
on CPU the single-chip numpy driver stays the default.  A 1-shard plan
falls back to the single-chip solve, bit-identically.
"""
from __future__ import annotations

import dataclasses
import warnings
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

import hashlib

from . import platform
from .chain_program import (ChainProgram, SolveStats, _blocks_from_chains,
                            _solve_numpy, block_adjacency, last_solve_stats,
                            program_chains, solve_program)

#: The host executor merges the smallest signature groups until at most
#: this many shards remain — each shard is one numpy sub-solve, and
#: Python dispatch per sweep makes many tiny solves slower than one
#: fused solve.
HOST_MAX_SHARDS = 16


@dataclasses.dataclass(frozen=True)
class Shard:
    """One independent sub-fixpoint of a sharded program.

    ``devices`` are base-program device ids (ascending); ``perm`` maps
    the shard's flat event order back to base flat indices
    (``base_comp[perm] = shard_comp``); ``program`` is the extracted
    sub-program (device metadata collapsed to one flat pseudo-device —
    results are always scattered back through ``perm``, never unpacked
    from the sub-program).
    """

    devices: Tuple[int, ...]
    program: ChainProgram
    perm: np.ndarray

    @property
    def n_events(self) -> int:
        return self.program.n_flat


@dataclasses.dataclass
class ShardedProgram:
    """A partition of a chain program's entry axis into shards."""

    base: ChainProgram
    shards: Tuple[Shard, ...]
    #: per-device-count stacked mesh tensors, built lazily
    _mesh_cache: Dict[int, dict] = dataclasses.field(
        default_factory=dict, repr=False)

    @property
    def n_shards(self) -> int:
        return len(self.shards)

    def __repr__(self) -> str:
        sizes = [s.n_events for s in self.shards]
        return (f"ShardedProgram(shards={len(sizes)}, "
                f"events={sizes})")


def _entry_components(program: ChainProgram):
    """Union-find connected components of the chain/device graph.

    Returns ``(bounds, comp_list, recs)``: per-device flat bounds,
    components as ascending device-id lists, and one record ``(label,
    chain, component_index)`` per chain.
    """
    D = program.n_devices
    bounds = np.append(np.asarray(program.offsets, dtype=np.int64),
                       program.n_flat)
    parent = list(range(D))

    def find(x: int) -> int:
        while parent[x] != x:
            parent[x] = parent[parent[x]]
            x = parent[x]
        return x

    def union(a: int, b: int) -> None:
        ra, rb = find(a), find(b)
        if ra != rb:
            parent[max(ra, rb)] = min(ra, rb)

    raw = []                    # (label, chain, device)
    for label, chs in program_chains(program).items():
        for c in chs:
            cmin = int(c.min())
            d0 = int(np.searchsorted(bounds, cmin, side="right") - 1)
            if int(c.max()) >= bounds[d0 + 1]:
                # cross-entry chain (extend_program coupling): fuse
                # every touched device into one component
                ds = np.unique(np.searchsorted(bounds, c,
                                               side="right") - 1)
                for d in ds[1:]:
                    union(int(ds[0]), int(d))
                d0 = int(ds[0])
            raw.append((label, c, d0))
    comps: "OrderedDict[int, list]" = OrderedDict()
    for d in range(D):
        comps.setdefault(find(d), []).append(d)
    pos = {root: i for i, root in enumerate(comps)}
    recs = [(label, c, pos[find(d)]) for label, c, d in raw]
    return bounds, list(comps.values()), recs


def _signatures(n_comps: int, recs) -> List[tuple]:
    """Chain-structure signature per component: sorted ``(label,
    n_chains, total_len)`` triples.  Replicated entries and the members
    of one heterogeneity tier share a signature."""
    acc: List[dict] = [OrderedDict() for _ in range(n_comps)]
    for label, c, i in recs:
        st = acc[i].setdefault(label, [0, 0])
        st[0] += 1
        st[1] += len(c)
    return [tuple(sorted((lab, st[0], st[1]) for lab, st in a.items()))
            for a in acc]


def _lpt(weights: Sequence[int], k: int) -> List[List[int]]:
    """Longest-processing-time balanced partition into ``k`` bins."""
    k = max(min(k, len(weights)), 1)
    bins: List[List[int]] = [[] for _ in range(k)]
    loads = [0] * k
    for i in sorted(range(len(weights)), key=lambda i: -weights[i]):
        j = min(range(k), key=loads.__getitem__)
        bins[j].append(i)
        loads[j] += weights[i]
    return [sorted(b) for b in bins if b]


def shard_program(program: ChainProgram, *,
                  n_shards: Optional[int] = None) -> ShardedProgram:
    """Partition a program's entry axis into independent shards.

    With ``n_shards=None`` (host executor) entries group by chain
    *signature* — replicas and same-tier devices solve together, each
    group under its own convergence budget — merged down to at most
    :data:`HOST_MAX_SHARDS` groups.  With ``n_shards=k`` (mesh
    executor) entries are LPT-balanced into ``<= k`` event-weighted
    bins.  Entries are connected components of the chain/device graph,
    so cross-entry families from ``extend_program`` are never split —
    and neither are a refined pool's greedy-replay coupling chains,
    which always live inside one device's component.  Sub-programs
    inherit the parent's exactness contract verbatim (``exact``,
    ``order_stable``, ``unstable_pools``, ``svc_seeds``), so the
    sharded solve claims exactly what the single-chip solve would.
    """
    if program.n_devices == 0 or program.n_flat == 0:
        return ShardedProgram(base=program, shards=())
    bounds, comp_list, recs = _entry_components(program)
    weights = [int(sum(bounds[d + 1] - bounds[d] for d in devs))
               for devs in comp_list]
    if n_shards is None:
        by_sig: "OrderedDict[tuple, list]" = OrderedDict()
        for i, sig in enumerate(_signatures(len(comp_list), recs)):
            by_sig.setdefault(sig, []).append(i)
        groups = list(by_sig.values())
        while len(groups) > HOST_MAX_SHARDS:
            groups.sort(key=lambda g: sum(weights[i] for i in g))
            a, b = groups[0], groups[1]
            groups = [sorted(a + b)] + groups[2:]
    else:
        groups = _lpt(weights, int(n_shards))

    group_of = np.empty(len(comp_list), dtype=np.int64)
    for g, comps in enumerate(groups):
        for i in comps:
            group_of[i] = g

    # global -> shard-local index map (shards partition the flat axis)
    loc = np.empty(program.n_flat, dtype=np.int64)
    perms: List[np.ndarray] = []
    dev_lists: List[Tuple[int, ...]] = []
    for comps in groups:
        devs = sorted(d for i in comps for d in comp_list[i])
        perm = np.concatenate([np.arange(bounds[d], bounds[d + 1])
                               for d in devs]) if devs else \
            np.zeros(0, dtype=np.int64)
        loc[perm] = np.arange(len(perm))
        perms.append(perm)
        dev_lists.append(tuple(devs))

    chain_maps: List["OrderedDict[str, list]"] = \
        [OrderedDict() for _ in groups]
    for label, c, i in recs:
        chain_maps[group_of[i]].setdefault(label, []).append(loc[c])

    shards = []
    for g, perm in enumerate(perms):
        n = len(perm)
        order = np.arange(n, dtype=np.int64)
        sub = ChainProgram(
            n_flat=n, offsets=(0,), orders=(order,), invs=(order,),
            issue_flat=program.issue_flat[perm],
            svc0_flat=program.svc0_flat[perm],
            families=_blocks_from_chains(chain_maps[g], n),
            exact=program.exact,
            multiclass_pools=program.multiclass_pools,
            refine_used=program.refine_used,
            order_stable=program.order_stable,
            unstable_pools=program.unstable_pools,
            svc_seeds=program.svc_seeds)
        shards.append(Shard(devices=dev_lists[g], program=sub, perm=perm))
    return ShardedProgram(base=program, shards=tuple(shards))


# ---------------------------------------------------------------------------
# Plan cache: program object identity fast path + content-digest
# fallback (mirrors the lowering cache), so rebuilding an identical
# program — e.g. across capacity-ladder rungs — still hits.
# ---------------------------------------------------------------------------
_PLAN_CACHE: "OrderedDict[tuple, tuple]" = OrderedDict()
_PLAN_CACHE_MAX = 4


def _program_digest(program: ChainProgram) -> bytes:
    """Content digest of a compiled program's solve-relevant structure
    (flat size, entry offsets, family tensors), memoized on the program
    object — same trick as the trace digest memo."""
    cached = getattr(program, "_shard_digest_memo", None)
    if cached is not None:
        return cached
    h = hashlib.sha1()
    h.update(np.int64(program.n_flat).tobytes())
    h.update(np.asarray(program.offsets, dtype=np.int64).tobytes())
    for blk in program.families:
        h.update(blk.label.encode())
        h.update(blk.layout.encode())
        h.update(np.ascontiguousarray(blk.gidx).tobytes())
        h.update(np.ascontiguousarray(blk.heads).tobytes())
    d = h.digest()
    try:
        object.__setattr__(program, "_shard_digest_memo", d)
    except Exception:        # pragma: no cover - slotted subclass
        pass
    return d


def _plan(program: ChainProgram,
          n_shards: Optional[int]) -> ShardedProgram:
    ikey = ("id", id(program), n_shards)
    hit = _PLAN_CACHE.get(ikey)
    if hit is not None and hit[0] is program:
        _PLAN_CACHE.move_to_end(ikey)
        return hit[1]
    dkey = ("sha", _program_digest(program), n_shards)
    hit = _PLAN_CACHE.get(dkey)
    if hit is not None:
        sp = hit[1]
        _PLAN_CACHE.move_to_end(dkey)
    else:
        sp = shard_program(program, n_shards=n_shards)
        _PLAN_CACHE[dkey] = (None, sp)
    # (re)bind the identity fast path for this object; the digest entry
    # keeps serving identical rebuilds after this object dies.
    _PLAN_CACHE[ikey] = (program, sp)
    _PLAN_CACHE.move_to_end(ikey)
    while len(_PLAN_CACHE) > 2 * _PLAN_CACHE_MAX:
        _PLAN_CACHE.popitem(last=False)
    return sp


def clear_shard_plans() -> None:
    _PLAN_CACHE.clear()


# ---------------------------------------------------------------------------
# Executors
# ---------------------------------------------------------------------------
def _solve_host(program: ChainProgram, svc: np.ndarray, *, sweeps: int,
                scan_backend: str, comp0: Optional[np.ndarray]
                ) -> Tuple[np.ndarray, int, bool]:
    plan = _plan(program, None)
    if len(plan.shards) <= 1:
        if program.n_flat >= WINDOW_AUTO_MIN:
            # homogeneous mega-entry: the entry axis gives no
            # parallelism, but the request axis still pipelines into
            # issue-time windows with bounded per-window memory
            return solve_program_windowed(
                program, svc, sweeps=sweeps, scan_backend=scan_backend,
                comp0=comp0, warn=False)
        # one signature group: the grouped solve IS the base solve
        return _solve_numpy(program, svc, sweeps=sweeps,
                            scan_backend=scan_backend, comp0=comp0)
    comp = np.empty(program.n_flat, dtype=np.float64)
    used, conv = 0, True
    for sh in plan.shards:
        c, u, k = _solve_numpy(
            sh.program, svc[sh.perm], sweeps=sweeps,
            scan_backend=scan_backend,
            comp0=None if comp0 is None else comp0[sh.perm])
        comp[sh.perm] = c
        used = max(used, u)
        conv = conv and k
    return comp, used, conv


def _mesh_static(plan: ShardedProgram, ndev: int) -> dict:
    """Stacked padded block tensors for the mesh kernel (cached per
    plan + device count).  Family slot ``f`` stacks every shard's
    ``f``-th block at that slot's max (R, L); shards with fewer
    families pad with all-dead blocks; the shard count pads up to a
    multiple of ``ndev`` with empty shards."""
    cached = plan._mesh_cache.get(ndev)
    if cached is not None:
        return cached
    shards = plan.shards
    S = -(-len(shards) // ndev) * ndev
    n_max = max(sh.program.n_flat for sh in shards)
    views = [[blk.rows_view() for blk in sh.program.families]
             for sh in shards]
    F = max(len(v) for v in views)
    blocks = []
    for f in range(F):
        shapes = [v[f][0].shape for v in views if f < len(v)]
        R = max(s[0] for s in shapes)
        L = max(s[1] for s in shapes)
        gidx = np.full((S, R, L), n_max, dtype=np.int32)
        heads = np.ones((S, R, L), dtype=bool)
        for s, v in enumerate(views):
            if f < len(v):
                g, h = v[f]
                g = np.where(g == shards[s].program.n_flat, n_max, g)
                gidx[s, :g.shape[0], :g.shape[1]] = g
                heads[s, :h.shape[0], :h.shape[1]] = h
        blocks.append((gidx, heads))
    # per-shard block adjacency for the in-kernel active-set mask,
    # padded to the stacked family-slot count (padding slots gather
    # only the dead index, so they are adjacent to nothing)
    adjS = np.zeros((S, F, F), dtype=bool)
    for s, sh in enumerate(shards):
        a = block_adjacency(sh.program)
        adjS[s, :a.shape[0], :a.shape[1]] = a
    cached = {"S": S, "n_max": n_max, "blocks": tuple(blocks),
              "adj": adjS}
    plan._mesh_cache[ndev] = cached
    return cached


def _solve_mesh(program: ChainProgram, svc: np.ndarray, *, sweeps: int,
                scan_backend: str, comp0: Optional[np.ndarray]
                ) -> Tuple[np.ndarray, int, bool, Tuple[str, ...]]:
    """Shards across every local jax device; also returns the devices
    the shards' results came from."""
    import jax

    from repro.kernels.zns_fixpoint import zns_fixpoint_sharded

    devices = platform.probe()[1]
    plan = _plan(program, len(devices))
    if len(plan.shards) <= 1:
        comp, used, conv = solve_program(
            program, svc, sweeps=sweeps, scan_backend=scan_backend,
            fixpoint=platform.single_chip_driver(), warn=False, comp0=comp0)
        return comp, used, conv, last_solve_stats().devices
    st = _mesh_static(plan, len(devices))
    S, n_max = st["S"], st["n_max"]
    init = np.full((S, n_max + 1), -np.inf, dtype=np.float64)
    svcS = np.zeros((S, n_max + 1), dtype=np.float64)
    for s, sh in enumerate(plan.shards):
        v = svc[sh.perm]
        c0 = program.issue_flat[sh.perm] + v
        if comp0 is not None:
            c0 = np.maximum(c0, comp0[sh.perm])
        init[s, :len(v)] = c0
        svcS[s, :len(v)] = v
    with jax.enable_x64(True):
        comp_s, used_s, conv_s = zns_fixpoint_sharded(
            init, svcS, st["blocks"], sweeps=sweeps, devices=devices,
            adj=st["adj"])
        used_on = tuple(sorted({str(sh.device)
                                for sh in comp_s.addressable_shards}))
        comp_s = np.asarray(comp_s, dtype=np.float64)
        used_s = np.asarray(used_s)
        conv_s = np.asarray(conv_s)
    comp = np.empty(program.n_flat, dtype=np.float64)
    for s, sh in enumerate(plan.shards):
        comp[sh.perm] = comp_s[s, :len(sh.perm)]
    n = len(plan.shards)
    return comp, int(used_s[:n].max()), bool(conv_s[:n].all()), used_on


def solve_program_sharded(program: ChainProgram, svc_flat, *,
                          sweeps: int = 8, scan_backend: str = "auto",
                          comp0: Optional[np.ndarray] = None,
                          executor: str = "auto", warn: bool = True
                          ) -> Tuple[np.ndarray, int, bool]:
    """Sharded drop-in for :func:`repro.core.solve_program`.

    Partitions the program's entry axis (plan cached per program
    object) and solves each shard independently — the fixpoint is
    block-diagonal over entries, so the result equals the single-chip
    solve to float64 fixpoint tolerance (~1e-12 relative; a 1-shard
    plan falls back to the single-chip driver bit-identically).
    ``executor`` = ``"host"`` (signature-grouped numpy sub-solves),
    ``"mesh"`` (``shard_map`` across local jax devices), or ``"auto"``
    (:func:`repro.core.platform.shard_executor`: mesh on multi-chip
    accelerator hosts, host otherwise).
    """
    svc = np.asarray(svc_flat, dtype=np.float64)
    if program.n_flat == 0:
        return np.zeros(0, dtype=np.float64), 0, True
    if len(svc) != program.n_flat:
        raise ValueError(f"service vector has {len(svc)} entries for a "
                         f"{program.n_flat}-request program")
    if comp0 is not None and len(comp0) != program.n_flat:
        raise ValueError(f"comp0 has {len(comp0)} entries for a "
                         f"{program.n_flat}-request program")
    if executor not in ("auto", "host", "mesh"):
        raise ValueError(f"unknown shard executor {executor!r}; "
                         f"expected auto | host | mesh")
    if executor == "auto":
        executor = platform.shard_executor()
    devices: Tuple[str, ...] = ()
    if executor == "host" or program.n_devices <= 1:
        comp, used, conv = _solve_host(program, svc, sweeps=sweeps,
                                       scan_backend=scan_backend,
                                       comp0=comp0)
    else:
        comp, used, conv, devices = _solve_mesh(
            program, svc, sweeps=sweeps, scan_backend=scan_backend,
            comp0=comp0)
    import repro.core.chain_program as _cp
    _cp._LAST_SOLVE_STATS = SolveStats(
        driver=f"sharded/{executor}", sweeps=used, converged=conv,
        n_blocks=len(program.families), devices=devices)
    if not conv and warn:
        warnings.warn(
            f"sharded chain-program fixpoint exhausted its sweep budget "
            f"({sweeps}) while still moving; completions are a lower "
            f"bound.", RuntimeWarning, stacklevel=2)
    return comp, used, conv


# ---------------------------------------------------------------------------
# Intra-entry time-window sharding
# ---------------------------------------------------------------------------
#: Default issue-time window size (events) when ``n_windows`` is not
#: given: large enough that per-window solver overhead vanishes, small
#: enough that the per-window float64 scratch stays ~tens of MB.
WINDOW_TARGET_EVENTS = 1 << 18

#: ``solve_program_sharded`` auto-windows a degenerate 1-shard plan
#: only above this event count — smaller programs keep the documented
#: bit-identical numpy fallback.
WINDOW_AUTO_MIN = 2_000_000


@dataclasses.dataclass(frozen=True)
class Window:
    """One issue-time window of a windowed program.

    ``perm`` maps the window's flat event order back to base flat
    indices; ``bnd_local``/``bnd_pred`` are the pipeline boundary: the
    window-local index of each chain-segment head whose predecessor
    completed in an earlier window, and that predecessor's base flat
    index.  The boundary condition ``comp0[head] >= comp[pred] +
    svc[head]`` re-creates the cut chain edge exactly (the fixpoint is
    monotone from below, so a lower bound installed at init holds
    permanently)."""

    program: ChainProgram
    perm: np.ndarray
    bnd_local: np.ndarray
    bnd_pred: np.ndarray


@dataclasses.dataclass
class WindowedProgram:
    """A partition of one program's request axis into issue-time
    windows, solved as a pipelined sequence (earlier windows feed later
    ones their completion frontier)."""

    base: ChainProgram
    windows: Tuple[Window, ...]

    @property
    def n_windows(self) -> int:
        return len(self.windows)

    def __repr__(self) -> str:
        return (f"WindowedProgram(windows={len(self.windows)}, "
                f"events={[len(w.perm) for w in self.windows]})")


def window_program(program: ChainProgram, *,
                   n_windows: Optional[int] = None,
                   window_events: Optional[int] = None
                   ) -> WindowedProgram:
    """Partition a program's request axis into issue-time windows.

    Events are bucketed by issue-time rank into ``n_windows`` (default
    ``ceil(n_flat / window_events)``) near-equal windows, then the
    window index is repaired to be non-decreasing along every chain of
    every family (a running max per chain, iterated across families to
    a fixpoint) — so every cross-window chain edge points forward and
    the pipelined solve is exact.  Each window becomes a sub-program
    over its own events plus a boundary list of (segment head,
    upstream predecessor) pairs.  Results are memoized on the program
    per window count.
    """
    n = program.n_flat
    if n_windows is None:
        we = int(window_events) if window_events else WINDOW_TARGET_EVENTS
        n_windows = -(-n // we) if n else 1
    k = max(min(int(n_windows), n if n else 1), 1)
    memo = getattr(program, "_window_memo", None)
    if memo is not None and k in memo:
        return memo[k]

    w = np.empty(n, dtype=np.int64)
    order = np.argsort(program.issue_flat, kind="stable")
    w[order] = (np.arange(n, dtype=np.int64) * k) // max(n, 1)
    chains_by_label = program_chains(program)
    all_chains = [c for chs in chains_by_label.values() for c in chs]
    # monotone repair: raising an event's window can break another
    # chain through that event, so iterate to a fixpoint (bounded by
    # k passes; in practice 1-2)
    changed = True
    while changed:
        changed = False
        for c in all_chains:
            wc = w[c]
            acc = np.maximum.accumulate(wc)
            if (acc != wc).any():
                w[c] = acc
                changed = True

    perms = [np.nonzero(w == j)[0] for j in range(k)]
    loc = np.empty(n, dtype=np.int64)
    for p in perms:
        loc[p] = np.arange(len(p))
    chain_maps: List["OrderedDict[str, list]"] = \
        [OrderedDict() for _ in range(k)]
    bnds: List[Tuple[list, list]] = [([], []) for _ in range(k)]
    for label, chs in chains_by_label.items():
        for c in chs:
            wc = w[c]
            cut = np.nonzero(np.diff(wc))[0] + 1
            starts = np.concatenate(([0], cut))
            ends = np.concatenate((cut, [len(c)]))
            for a, b in zip(starts, ends):
                j = int(wc[a])
                chain_maps[j].setdefault(label, []).append(loc[c[a:b]])
                if a > 0:
                    bnds[j][0].append(int(loc[c[a]]))
                    bnds[j][1].append(int(c[a - 1]))

    windows = []
    for j in range(k):
        p = perms[j]
        m = len(p)
        oj = np.arange(m, dtype=np.int64)
        sub = ChainProgram(
            n_flat=m, offsets=(0,), orders=(oj,), invs=(oj,),
            issue_flat=program.issue_flat[p],
            svc0_flat=program.svc0_flat[p],
            families=_blocks_from_chains(chain_maps[j], m),
            exact=program.exact,
            multiclass_pools=program.multiclass_pools,
            refine_used=program.refine_used,
            order_stable=program.order_stable,
            unstable_pools=program.unstable_pools,
            svc_seeds=program.svc_seeds)
        windows.append(Window(
            program=sub, perm=p,
            bnd_local=np.asarray(bnds[j][0], dtype=np.int64),
            bnd_pred=np.asarray(bnds[j][1], dtype=np.int64)))
    wp = WindowedProgram(base=program, windows=tuple(windows))
    if memo is None:
        memo = {}
        try:
            object.__setattr__(program, "_window_memo", memo)
        except Exception:    # pragma: no cover - slotted subclass
            pass
    memo[k] = wp
    return wp


def solve_program_windowed(program: ChainProgram, svc_flat, *,
                           sweeps: int = 8, scan_backend: str = "auto",
                           comp0: Optional[np.ndarray] = None,
                           n_windows: Optional[int] = None,
                           window_events: Optional[int] = None,
                           warn: bool = True
                           ) -> Tuple[np.ndarray, int, bool]:
    """Solve one program as a pipeline of issue-time windows.

    Window ``j+1`` starts from window ``j``'s completion frontier: each
    cut chain edge becomes a ``comp0`` lower bound ``comp[pred] +
    svc[head]`` on its downstream head, which the monotone fixpoint
    enforces permanently — so the pipelined result equals the full
    solve (and hence the event oracle, when ``program.exact``) to
    float64 fixpoint tolerance, while the solver's per-sweep scratch
    (gathers + the per-family float64 service matrices) is bounded by
    the largest window instead of the whole program.  ``sweeps`` is a
    per-window budget; ``sweeps_used`` reports the hungriest window.
    """
    svc = np.asarray(svc_flat, dtype=np.float64)
    if program.n_flat == 0:
        return np.zeros(0, dtype=np.float64), 0, True
    if len(svc) != program.n_flat:
        raise ValueError(f"service vector has {len(svc)} entries for a "
                         f"{program.n_flat}-request program")
    if comp0 is not None and len(comp0) != program.n_flat:
        raise ValueError(f"comp0 has {len(comp0)} entries for a "
                         f"{program.n_flat}-request program")
    wp = window_program(program, n_windows=n_windows,
                        window_events=window_events)
    if wp.n_windows <= 1:
        return _solve_numpy(program, svc, sweeps=sweeps,
                            scan_backend=scan_backend, comp0=comp0)
    comp = np.empty(program.n_flat, dtype=np.float64)
    used, conv = 0, True
    for win in wp.windows:
        p = win.perm
        if not len(p):
            continue
        svc_w = svc[p]
        lb = None
        if comp0 is not None:
            lb = np.asarray(comp0, dtype=np.float64)[p].copy()
        if len(win.bnd_local):
            if lb is None:
                lb = np.full(len(p), -np.inf)
            np.maximum.at(lb, win.bnd_local,
                          comp[win.bnd_pred] + svc_w[win.bnd_local])
        c, u, ok = _solve_numpy(win.program, svc_w, sweeps=sweeps,
                                scan_backend=scan_backend, comp0=lb)
        comp[p] = c
        used = max(used, u)
        conv = conv and ok
    import repro.core.chain_program as _cp
    _cp._LAST_SOLVE_STATS = SolveStats(
        driver="windowed", sweeps=used, converged=conv,
        n_blocks=len(program.families))
    if not conv and warn:
        warnings.warn(
            f"windowed chain-program fixpoint exhausted its per-window "
            f"sweep budget ({sweeps}) while still moving; completions "
            f"are a lower bound.", RuntimeWarning, stacklevel=2)
    return comp, used, conv
