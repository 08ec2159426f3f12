"""Fused chain-program fixpoint — all sweeps × families in one kernel.

The trace-compilation layer (:mod:`repro.core.chain_program`) lowers a
fleet of traces into family blocks: padded ``(R, L)`` gather-index +
segment-head tensors addressing one flat completion vector (dead slot at
index ``n``).  One Gauss–Seidel sweep applies, per block, a segmented
max-plus scan to the gathered completions and scatter-maxes the result
back; sweeps repeat until an early-exit ``moved`` reduction clears.

The blocks carry *all* of the compiler's chain families through one
uniform metadata shape — per-thread closed-loop lag chains, per-zone
write chains, the metadata engine, and the greedy-replay server-pool
coupling chains (per-server pop sequences, multi-class and jittered
alike).  Nothing pool-specific reaches this layer: exactness is decided
entirely at compile time (``ChainProgram.exact``), and the kernels just
run whatever segmented scans they are handed — which is what lets the
fused solver replace the event engine everywhere outside tests.

This module runs that whole fixpoint as one compiled artifact instead of
``sweeps × families`` host dispatches:

* :func:`zns_fixpoint_xla` — a jitted ``lax.while_loop`` whose body
  unrolls the (static) family blocks; the per-block scan is the same
  Hillis–Steele doubling ladder as ``zns_event_scan``, vectorized over
  rows, and the scatter is ``comp.at[gidx].max(...)`` (duplicate dead
  indices max-reduce harmlessly).
* :func:`zns_fixpoint` — the Pallas form: the fixpoint core runs inside
  a single ``pallas_call`` with the flat completion vector resident in
  kernel memory.  It has no grid, so the whole vector and every gather
  block would sit in VMEM, and the TPU compiler refuses it (its bool
  output cannot be lowered); it runs in interpret mode on the CPU and is
  reachable only by name.

Both keep the caller's dtype.  The TPU path is :func:`zns_fixpoint_xla`
in float64 (the caller scopes ``jax.enable_x64``): float32 cannot hold
the exactness contract's 1e-6 us on a trace that spans seconds.  The
semantic ground truth is ``repro.kernels.ref.zns_fixpoint_ref``
(sequential per-row scans); CPU solves use the float64 numpy driver in
:func:`repro.core.chain_program.solve_program`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

NEG_INF = -1e30
#: Progress thresholds of the early-exit ``moved`` reduction (float32:
#: looser than the numpy driver's 1e-12/1e-9).
MOVED_RTOL = 1e-5
MOVED_ATOL = 1e-3


def _pad_value(dtype):
    """Padding sentinel: the historical finite ``NEG_INF`` for float32,
    true ``-inf`` for float64 — with ``-inf``, all-padding lanes can
    never satisfy the progress test (``-inf < -inf`` is false), which
    matches the numpy driver's ``-np.inf`` semantics exactly."""
    if dtype == jnp.float64:
        return jnp.array(-jnp.inf, dtype)
    return jnp.array(NEG_INF, dtype)


def _moved_tol(dtype):
    """Early-exit progress tolerances: the float64 path mirrors the
    numpy driver's rel 1e-12 / abs 1e-9; float32 keeps the looser
    kernel thresholds."""
    if dtype == jnp.float64:
        return 1e-12, 1e-9
    return MOVED_RTOL, MOVED_ATOL


def _rows_maxplus(start, svc, heads):
    """Segmented max-plus scan over the rows of (R, L) matrices.

    Same affine-map composition as ``zns_event_scan`` — ``a = svc``
    (``-inf`` at segment heads), ``b = start + svc`` — as a doubling
    ladder of ``log2(L)`` shifted composes, vectorized over rows.
    dtype-generic: float32 keeps the finite ``NEG_INF`` sentinel,
    float64 uses true ``-inf``.
    """
    r, n = start.shape
    dt = start.dtype
    ninf = _pad_value(dt)
    a = jnp.where(heads, ninf, svc)
    b = start + svc
    k = 1
    while k < n:
        a_prev = jnp.concatenate(
            [jnp.zeros((r, k), dt), a[:, :-k]], axis=1)
        b_prev = jnp.concatenate(
            [jnp.full((r, k), ninf, dt), b[:, :-k]], axis=1)
        # compose earlier (shifted) map, then current: (a_p,b_p) . (a,b)
        a, b = a_prev + a, jnp.maximum(b_prev + a, b)
        k *= 2
    return b


def blocks_adjacency(gidxs, n: int) -> np.ndarray:
    """Symmetric ``(F, F)`` bool block adjacency from raw gather-index
    matrices: ``adj[i, j]`` iff blocks ``i`` and ``j`` address a common
    flat slot (padding at ``n`` excluded).  Diagonal False — a block is
    at its own fixpoint right after its scan.  Host-side numpy; the
    kernels consume the result as a traced bool array.

    One linear pass per word of up to 64 blocks: each block of the word
    stamps its bit into a per-slot word of the narrowest unsigned type
    that holds the word's blocks (the padding slot cleared after), then
    every block OR-reduces the stamps it gathers.  The cost is
    ``ceil(F / 64)`` times the lanes of all blocks, whatever ``n``."""
    nf = len(gidxs)
    adj = np.zeros((nf, nf), dtype=bool)
    if nf < 2:
        return adj
    flats = [np.asarray(g).ravel() for g in gidxs]
    for lo in range(0, nf, 64):
        hi = min(lo + 64, nf)
        word = np.min_scalar_type((1 << (hi - lo)) - 1).type
        bits = np.zeros(n + 1, dtype=word)
        for k in range(hi - lo):
            bits[flats[lo + k]] |= word(1 << k)
        bits[n] = 0
        for f, flat in enumerate(flats):
            m = int(np.bitwise_or.reduce(bits[flat]))
            adj[f, lo:hi] = [(m >> k) & 1 for k in range(hi - lo)]
    np.fill_diagonal(adj, False)
    return adj


def _fixpoint_core(comp_ext, svc_ext, blocks, sweeps: int, adj=None):
    """``lax.while_loop`` fixpoint shared by the XLA and Pallas forms.

    ``comp_ext``/``svc_ext``: flat ``(n + 1,)`` vectors (dead slot
    last); ``blocks``: static tuple of ``(gidx, heads)`` pairs; ``adj``
    the ``(F, F)`` bool block adjacency driving the active-set mask (a
    converged block costs one predicate evaluation instead of a full
    gather + scan until a neighbour's scatter re-activates it; ``None``
    keeps every block active every sweep).  Returns ``(comp_ext,
    sweeps_used, moved, active_counts)`` where ``moved`` means "blocks
    still active at exit" — its negation is the convergence flag — and
    ``active_counts[s]`` (int32, length ``sweeps``) the blocks active
    when their turn came in sweep ``s`` (0 past ``sweeps_used``).
    """

    dead = comp_ext.shape[0] - 1
    dt = comp_ext.dtype
    ninf = _pad_value(dt)
    rtol, atol = _moved_tol(dt)
    nf = len(blocks)
    if adj is None:
        adj = jnp.zeros((nf, nf), dtype=bool) if nf == 0 \
            else jnp.ones((nf, nf), bool) & ~jnp.eye(nf, dtype=bool)
    later_f = [jnp.arange(nf) > f for f in range(nf)]

    def body(carry):
        comp, s, active, counts = carry
        act_now = active
        act_next = jnp.zeros_like(active)
        n_active = jnp.int32(0)
        for f, (gidx, heads) in enumerate(blocks):

            def run(comp, gidx=gidx, heads=heads):
                svc_m = svc_ext[gidx]
                cur = comp[gidx]
                out = _rows_maxplus(cur - svc_m, svc_m, heads)
                # padding gathers the sentinel, which would trivially
                # satisfy the relative-progress test — mask it out
                mv = jnp.any((out > cur * (1.0 + rtol) + atol)
                             & (gidx < dead))
                comp = comp.at[gidx].max(jnp.maximum(cur, out))
                comp = comp.at[-1].set(ninf)
                return comp, mv

            n_active = n_active + act_now[f].astype(jnp.int32)
            comp, mv = jax.lax.cond(
                act_now[f], run, lambda c: (c, jnp.bool_(False)), comp)
            # a moving block re-activates neighbours: later blocks see
            # the scatter within this sweep (Gauss–Seidel order),
            # earlier ones on the next sweep
            nbr = adj[f] & mv
            act_now = act_now | (nbr & later_f[f])
            act_next = act_next | (nbr & ~later_f[f])
        return comp, s + 1, act_next, counts.at[s].set(n_active)

    comp, used, active, counts = jax.lax.while_loop(
        lambda c: (c[1] < sweeps) & jnp.any(c[2]),
        body, (comp_ext, jnp.int32(0), jnp.ones((max(nf, 1),), bool),
               jnp.zeros((sweeps,), jnp.int32)))
    return comp, used, jnp.any(active), counts


def _extend(comp0, svc):
    """Append the dead slot: the dtype's padding sentinel to the
    completions, a zero service time."""
    dt = comp0.dtype
    return (jnp.append(comp0, _pad_value(dt)),
            jnp.append(svc.astype(dt), jnp.zeros((), dt)))


@functools.partial(jax.jit, static_argnames=("sweeps",))
def zns_fixpoint_xla(comp0, svc, blocks, adj=None, *, sweeps: int = 8):
    """Fused fixpoint as a jitted ``lax.while_loop`` (no Pallas).

    ``comp0``: (n,) initial completions (``issue + svc``); ``svc``: (n,)
    service times; ``blocks``: tuple of ``(gidx int32 (R, L), heads
    bool (R, L))`` with padding indexed at ``n``; ``adj``: optional
    ``(F, F)`` bool block adjacency for the active-set mask.  Computes
    in the dtype of ``comp0``.  Returns ``(comp (n,), sweeps_used,
    converged, active_blocks (sweeps,))``, the last the blocks active in
    each sweep run.
    """
    comp_ext, svc_ext = _extend(comp0, svc)
    comp, used, moved, counts = _fixpoint_core(comp_ext, svc_ext, blocks,
                                               sweeps, adj)
    return comp[:-1], used, ~moved, counts


def _kernel(comp_ref, svc_ref, adj_ref, *rest, sweeps: int):
    """Single-program Pallas kernel: the whole fixpoint in-kernel.

    ``rest`` interleaves the per-block ``gidx``/``heads`` refs and ends
    with the three output refs (completions, sweeps_used, converged).
    """
    n_out = 3
    block_refs, out_refs = rest[:-n_out], rest[-n_out:]
    blocks = tuple((block_refs[i][...], block_refs[i + 1][...])
                   for i in range(0, len(block_refs), 2))
    comp, used, moved, _ = _fixpoint_core(
        comp_ref[...], svc_ref[...], blocks, sweeps, adj_ref[...])
    out_refs[0][...] = comp
    out_refs[1][...] = used[None]
    out_refs[2][...] = (~moved)[None]


@functools.partial(jax.jit, static_argnames=("sweeps", "interpret"))
def zns_fixpoint(comp0, svc, blocks, adj=None, *, sweeps: int = 8,
                 interpret: bool = False):
    """Pallas form of :func:`zns_fixpoint_xla` (one ``pallas_call``).

    The flat completion vector stays resident across all sweeps ×
    family blocks; sweep iteration, the active-set block mask, and the
    early-exit ``moved`` reduction run in-kernel.
    """
    n = comp0.shape[0]
    nf = len(blocks)
    comp_ext, svc_ext = _extend(comp0, svc)
    if adj is None:
        adj = jnp.ones((nf, nf), bool) & ~jnp.eye(nf, dtype=bool)
    ins = [comp_ext, svc_ext, jnp.asarray(adj, dtype=bool)]
    for gidx, heads in blocks:
        ins += [gidx.astype(jnp.int32), heads.astype(bool)]
    comp, used, conv = pl.pallas_call(
        functools.partial(_kernel, sweeps=max(int(sweeps), 1)),
        out_shape=(
            jax.ShapeDtypeStruct((n + 1,), comp_ext.dtype),
            jax.ShapeDtypeStruct((1,), jnp.int32),
            jax.ShapeDtypeStruct((1,), jnp.bool_),
        ),
        interpret=interpret,
    )(*ins)
    return comp[:-1], used[0], conv[0]


# ---------------------------------------------------------------------------
# Mesh-sharded form: independent per-shard fixpoints across local chips
# ---------------------------------------------------------------------------
def _stack_solve(comp0, svc, adj, *flat_blocks, sweeps: int):
    """Solve a stack of independent shard fixpoints (leading axis).

    ``comp0``/``svc``: ``(s, n_max + 1)``; ``adj``: ``(s, F, F)``
    per-shard block adjacency; ``flat_blocks`` interleaves ``gidx
    (s, R_f, L_f)`` / ``heads (s, R_f, L_f)`` per family slot.
    ``lax.map`` runs one ``while_loop`` per shard, so every shard keeps
    its own trip count (early convergence on one shard never pays for a
    slower sibling's sweeps).
    """

    def one(args):
        c, v, a, *bl = args
        blocks = tuple((bl[i], bl[i + 1]) for i in range(0, len(bl), 2))
        comp, used, moved, _ = _fixpoint_core(c, v, blocks, sweeps, a)
        return comp, used, ~moved

    return jax.lax.map(one, (comp0, svc, adj) + tuple(flat_blocks))


@functools.lru_cache(maxsize=8)
def _sharded_fn(devices, n_arrays: int, sweeps: int):
    """Build (and cache) the jitted ``shard_map`` solver for a device
    tuple.  ``check_vma=False`` is required: the per-shard
    ``lax.while_loop`` trip count is data-dependent, which the
    varying-axes checker cannot track."""
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.asarray(devices), ("shard",))
    fn = jax.shard_map(
        functools.partial(_stack_solve, sweeps=sweeps),
        mesh=mesh,
        in_specs=(P("shard"),) * n_arrays,
        out_specs=(P("shard"), P("shard"), P("shard")),
        check_vma=False)
    # donate the completion buffer: it is overwritten every sweep and
    # the stacked (s, n_max + 1) float64 arrays are the footprint.
    # (CPU backends don't implement donation and warn; skip there.)
    donate = tuple(
        () if all(d.platform == "cpu" for d in devices) else (0,))
    return jax.jit(fn, donate_argnums=donate)


def zns_fixpoint_sharded(comp0, svc, blocks, *, sweeps: int = 8,
                         devices=None, adj=None):
    """Shard independent fixpoints across every local chip.

    ``comp0``/``svc``: ``(S, n_max + 1)`` stacked extended vectors (one
    row per shard, dead slot last, rows beyond a shard's real length
    padded with the dtype sentinel / 0); ``blocks``: tuple of
    ``(gidx (S, R_f, L_f), heads (S, R_f, L_f))`` stacked family slots
    with padding indexed at ``n_max``.  ``S`` must be a multiple of
    ``len(devices)`` (pad with empty shards).  The shard axis is
    embarrassingly parallel — shards share no chains — so ``shard_map``
    over a 1-D :class:`jax.sharding.Mesh` places ``S / n_dev`` shards
    per chip and each runs its own early-exiting ``while_loop``.
    Returns ``(comp (S, n_max + 1), sweeps_used (S,), converged (S,))``.
    """
    if devices is None:
        devices = tuple(jax.local_devices())
    else:
        devices = tuple(devices)
    if comp0.shape[0] % len(devices):
        raise ValueError(f"shard count {comp0.shape[0]} not a multiple "
                         f"of device count {len(devices)}")
    flat = []
    for gidx, heads in blocks:
        flat += [gidx, heads]
    if adj is None:
        n_max = comp0.shape[1] - 1
        adj = np.stack([
            blocks_adjacency([np.asarray(g)[s] for g, _ in blocks], n_max)
            for s in range(comp0.shape[0])]) if blocks else \
            np.zeros((comp0.shape[0], 0, 0), dtype=bool)
    fn = _sharded_fn(devices, 3 + len(flat), max(int(sweeps), 1))
    return fn(comp0, svc, np.asarray(adj, dtype=bool), *flat)
