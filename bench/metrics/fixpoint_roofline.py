"""Share of the HBM roofline the solve kernel reaches, in %.

The least bytes of a solve are those of one verifying pass over the
program's real chain edges: every lane of a family block that is
neither padding nor a chain head reads two int32 indices (its own slot
and its predecessor's) and three float64 values (the predecessor's
completion, its own service time, its own completion written back),
32 bytes.  The count depends neither on the sweeps the solver spends
nor on padding or layout, so no change to those can push the share past
100%.  float64 is emulated on the TPU and has no published peak, so the
share is against HBM bandwidth alone: least bytes over (peak bytes/s x
the kernel's device time).
"""
import numpy as np

KERNEL = "zns_fixpoint_xla"
BYTES_PER_EDGE = 2 * 4 + 3 * 8


def least_bytes(program):
    """Least bytes one verifying pass over ``program``'s chain edges
    moves."""
    dead = program.n_flat
    edges = 0
    for blk in program.families:
        gidx, heads = blk.gidx, blk.heads
        edges += int(np.count_nonzero((gidx != dead) & ~heads))
    return BYTES_PER_EDGE * edges


def observe(cell, variant, res):
    return least_bytes(cell.program(variant, res))


def read(ctx):
    s = ctx.trace["kernels"][KERNEL]
    if s <= 0:
        return None
    moved = sum(ctx.observed["fixpoint_roofline"][k]
                for k in ctx.query_variants)
    return 100.0 * moved / (ctx.peaks["hbm_bytes_per_s"] * s)
