"""The ``"xla"`` fixpoint driver's per-sweep active blocks.

``SolveStats.active_blocks`` of the XLA driver comes from the kernel's
own count (``zns_fixpoint_xla``'s fourth output).  It is checked against
the host loop driver's trajectory on the programs of
``test_active_set.py`` and on a heterogeneous fleet program.  Both
drivers run the same active-set sweeps, so the trajectories agree sweep
for sweep, with one difference: when the last sweep that moved
re-activates no block, the loop driver runs one more sweep that finds
nothing dirty (0 active blocks), which the kernel's loop condition
skips.
"""
import numpy as np
import pytest

from repro.core import (
    LatencyModel, WorkloadSpec, compile_fleet_program, force_layout,
    last_solve_stats, solve_program, spans,
)
from strategies import fleet_members, mixed_workload
from test_active_set import _compile, _jacobi_reference, _pool_wl


def _fleet_program():
    members = fleet_members(4)
    traces = [mixed_workload(10 + 5 * i).build() for i in range(4)]
    prog = compile_fleet_program(
        traces, [s for s, _ in members],
        [LatencyModel(s, p) for s, p in members], cache=False)
    return prog, prog.svc0_flat


PROGRAMS = {
    "pool": lambda: _compile(_pool_wl()),
    "pool6": lambda: _compile(_pool_wl(threads=6, n=80)),
    "writes": lambda: _compile(WorkloadSpec().writes(n=300, qd=4, zone=3)),
    "writes_reads": lambda: _compile(
        WorkloadSpec().writes(n=200, qd=4, zone=3).reads(n=200, qd=16)),
    "fleet": _fleet_program,
}


def _solve(prog, svc, fixpoint, comp0):
    got, used, conv = solve_program(prog, svc, sweeps=512,
                                    fixpoint=fixpoint, comp0=comp0)
    assert conv
    return got, used, last_solve_stats()


@pytest.mark.parametrize("layout", ["rows", "cols"])
@pytest.mark.parametrize("warm", [False, True], ids=["cold", "warm"])
@pytest.mark.parametrize("name", sorted(PROGRAMS))
def test_xla_active_blocks_follow_the_loop_driver(name, warm, layout):
    prog, svc = PROGRAMS[name]()
    prog = force_layout(prog, layout)
    ref = _jacobi_reference(prog, svc)
    comp0 = None
    if warm:
        comp0 = np.full(prog.n_flat, -np.inf)
        comp0[: prog.n_flat // 2] = ref[: prog.n_flat // 2]
    _, _, loop = _solve(prog, svc, "loop", comp0)
    spans.reset("solve.sweeps", "solve.active_blocks")
    got, used, st = _solve(prog, svc, "xla", comp0)
    np.testing.assert_allclose(got, ref, rtol=1e-12, atol=1e-9)
    assert st.driver == "xla" and st.residuals == ()
    assert len(st.active_blocks) == st.sweeps == used
    assert st.active_blocks[0] == st.n_blocks == len(prog.families)
    # the kernel never runs a sweep in which no block is active
    assert all(0 < a <= st.n_blocks for a in st.active_blocks)
    traj = tuple(st.active_blocks)
    assert tuple(loop.active_blocks) in (traj, traj + (0,))
    snap = spans.snapshot()
    assert snap["solve.sweeps"]["total"] == used
    assert snap["solve.active_blocks"]["total"] == sum(traj)
