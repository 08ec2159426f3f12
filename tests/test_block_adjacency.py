"""The block adjacency of the active-set sweeps.

``adj[i, j]`` holds iff family blocks ``i`` and ``j`` gather a common
flat slot, the dead padding slot ``n`` excluded.  One implementation,
``repro.kernels.zns_fixpoint.blocks_adjacency``, stamps one bit per
block into a word per slot; ``repro.core.chain_program.block_adjacency``
runs it on a program's blocks and memoizes the result.

* on programs of 1 to 70 blocks, which cross the 8-, 16- and 64-block
  words, it equals a brute-force pairwise intersection, has a False
  diagonal, is symmetric and never counts padding as shared;
* on a small fleet of the benchmark's mixed 4 KiB job it equals the
  sort-based construction it replaced.
"""
import numpy as np
import pytest

from repro.core import (
    DeviceFleet, KiB, WorkloadSpec, block_adjacency, build_program,
    compile_fleet_program,
)
from repro.kernels.zns_fixpoint import blocks_adjacency


def _brute_force(gidxs, n):
    slots = [set(np.asarray(g).ravel().tolist()) - {n} for g in gidxs]
    return np.array([[i != j and bool(a & b) for j, b in enumerate(slots)]
                     for i, a in enumerate(slots)], dtype=bool)


def _sorted_runs(gidxs, n):
    """The construction ``blocks_adjacency`` replaced: a stable argsort
    of every real slot, then shifted compares within runs of equal
    slot."""
    nf = len(gidxs)
    adj = np.zeros((nf, nf), dtype=bool)
    flats = [np.asarray(g).ravel() for g in gidxs]
    flats = [fl[fl != n] for fl in flats]
    idx = np.concatenate(flats)
    own = np.concatenate([np.full(len(fl), f) for f, fl in enumerate(flats)])
    order = np.argsort(idx, kind="stable")
    idx, own = idx[order], own[order]
    for k in range(1, nf):
        same = idx[k:] == idx[:-k]
        adj[own[k:][same], own[:-k][same]] = True
        adj[own[:-k][same], own[k:][same]] = True
    np.fill_diagonal(adj, False)
    return adj


def _program(nf, seed):
    """``nf`` families, each two chains of 3 and 2 events (one padded
    block), drawn from a pool of ``3 nf + 8`` events.  From 3 families
    on, family 1 keeps to events of its own, so a pair of padded blocks
    shares nothing; the last family takes an event of family 0, across
    the 64-block word at 70 families."""
    rng = np.random.default_rng(seed)
    pool = 3 * nf + 8
    events = [rng.choice(pool, 5, replace=False) for _ in range(nf)]
    if nf > 2:
        events[1] = pool + np.arange(5)
    if nf > 1 and events[0][0] not in events[-1]:
        events[-1][0] = events[0][0]
    n = pool + 5
    fams = [(f"fam{f:03d}", [ev[:3], ev[3:]]) for f, ev in enumerate(events)]
    return build_program(np.zeros(n), np.ones(n), fams)


@pytest.mark.parametrize("nf", [1, 2, 3, 9, 17, 70])
def test_adjacency_is_the_pairwise_intersection(nf):
    prog = _program(nf, seed=nf)
    n = prog.n_flat
    gidxs = [blk.gidx for blk in prog.families]
    assert len(gidxs) == nf
    assert all((g == n).any() for g in gidxs)      # every block is padded
    adj = block_adjacency(prog)
    want = _brute_force(gidxs, n)
    assert adj.dtype == bool and adj.shape == (nf, nf)
    np.testing.assert_array_equal(adj, want)
    assert not adj.diagonal().any()
    np.testing.assert_array_equal(adj, adj.T)
    np.testing.assert_array_equal(
        adj, blocks_adjacency([blk.rows_view()[0] for blk in prog.families],
                              n))
    assert block_adjacency(prog) is adj             # memoized
    if nf > 1:
        assert adj[0, nf - 1] and adj[nf - 1, 0]
    if nf > 2:
        # the private family shares nothing, padding included
        assert not adj[1].any() and not adj[:, 1].any()


def test_adjacency_of_a_fleet_program_equals_the_sorted_runs():
    wl = WorkloadSpec() \
        .writes(n=50_000, size=4 * KiB, qd=4, nzones=64) \
        .reads(n=50_000, size=4 * KiB, qd=16, nzones=64)
    fleet = DeviceFleet.homogeneous(4)
    prog = compile_fleet_program(
        [wl.build()] * 4, [d.spec for d in fleet.devices],
        [d.lat for d in fleet.devices], cache=False)
    gidxs = [blk.gidx for blk in prog.families]
    assert {blk.layout for blk in prog.families} == {"rows", "cols"}
    adj = block_adjacency(prog)
    np.testing.assert_array_equal(adj, _sorted_runs(gidxs, prog.n_flat))
    assert adj.any()
