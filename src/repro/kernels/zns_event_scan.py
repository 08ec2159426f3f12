"""Max-plus segmented scan — the ZNS device model's hot loop, as a
TPU Pallas kernel.

The per-zone sequential-write completion recurrence
``c_i = max(c_{i-1}, s_i) + v_i`` (engine.py) is linear in the max-plus
semiring: with ``a_i = v_i`` and ``b_i = s_i + v_i``,
``c_i = max(c_{i-1} + a_i, b_i)``.  Composition of two such maps is
``(a1, b1) . (a2, b2) = (a1 + a2, max(b1 + a2, b2))`` — associative, so the
recurrence parallelizes as a scan.  Segment heads (first request of each
zone) set ``a_i = -inf``, which resets the carry exactly like the
sequential oracle.

TPU adaptation (vs. a GPU warp-shuffle scan): requests are tiled into
VMEM blocks of ``block`` elements laid out as (8, block//8) vregs; the
intra-block scan is a Hillis–Steele ladder of ``log2(block)`` vector
shifts (lane/sublane rolls on the VPU), and the inter-block carry is a
scalar in SMEM threaded through the sequential grid dimension.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _block_scan(s, v, head, carry_ref):
    """Intra-block max-plus ladder + inter-block carry (shared by the 1-D
    and batched kernels; the carry lives in SMEM and is updated in place).
    """
    n = s.shape[0]
    # Elementwise affine maps in the max-plus semiring.
    a = jnp.where(head, jnp.float32(NEG_INF), v)   # segment heads drop carry
    b = s + v

    # Hillis–Steele inclusive scan over the block (log2(n) ladder steps).
    # shift-by-k via iota select: positions < k keep the composition
    # identity (a=0, b=-inf): f(c) = max(c + 0, -inf) = c.
    idx = jax.lax.iota(jnp.int32, n)
    k = 1
    while k < n:
        a_shift = jnp.where(idx >= k, jnp.roll(a, k), jnp.float32(0.0))
        b_shift = jnp.where(idx >= k, jnp.roll(b, k), jnp.float32(NEG_INF))
        # compose earlier (shifted) then current: (a_s,b_s) . (a,b)
        a, b = a_shift + a, jnp.maximum(b_shift + a, b)
        k *= 2

    # Apply the inter-block carry: c_i = max(carry + A_i, B_i).
    c = jnp.maximum(carry_ref[0] + a, b)
    carry_ref[0] = c[n - 1]
    return c


def _kernel(issue_ref, svc_ref, head_ref, out_ref, carry_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        carry_ref[0] = jnp.float32(NEG_INF)

    out_ref[...] = _block_scan(issue_ref[...].astype(jnp.float32),
                               svc_ref[...].astype(jnp.float32),
                               head_ref[...], carry_ref)


def _kernel_batched(issue_ref, svc_ref, head_ref, out_ref, carry_ref):
    # Grid is (batch, blocks); the block axis is minor (sequential on TPU),
    # so the SMEM carry threads through one device row at a time and is
    # re-initialized at each row's first block.
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        carry_ref[0] = jnp.float32(NEG_INF)

    out_ref[0, :] = _block_scan(issue_ref[0, :].astype(jnp.float32),
                                svc_ref[0, :].astype(jnp.float32),
                                head_ref[0, :], carry_ref)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def zns_event_scan_batched(issue, svc, seg_start, *, block: int = 1024,
                           interpret: bool = False):
    """Batched completion times over a device axis: (B, N) inputs.

    The device-fleet counterpart of :func:`zns_event_scan` — one kernel
    launch scans every device's serialized chains by adding a leading
    batch grid dimension (rows are independent: each row's carry starts
    fresh, exactly like ``jax.vmap`` of the 1-D scan).
    """
    bsz, n = issue.shape
    npad = max((n + block - 1) // block * block, block)
    pad = npad - n
    issue_p = jnp.pad(issue.astype(jnp.float32), ((0, 0), (0, pad)))
    svc_p = jnp.pad(svc.astype(jnp.float32), ((0, 0), (0, pad)))
    head_p = jnp.pad(seg_start.astype(bool), ((0, 0), (0, pad)),
                     constant_values=True)   # padded tail = its own segment

    grid = (bsz, npad // block)
    spec = pl.BlockSpec((1, block), lambda b, i: (b, i))
    out = pl.pallas_call(
        _kernel_batched,
        grid=grid,
        in_specs=[spec, spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((bsz, npad), jnp.float32),
        scratch_shapes=[pltpu.SMEM((1,), jnp.float32)],
        interpret=interpret,
    )(issue_p, svc_p, head_p)
    return out[:, :n]


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def zns_event_scan(issue, svc, seg_start, *, block: int = 1024,
                   interpret: bool = False):
    """Completion times for per-zone serialized requests.

    issue/svc: (N,) float32; seg_start: (N,) bool.  N is padded to a
    multiple of ``block`` internally.
    """
    n = issue.shape[0]
    npad = (n + block - 1) // block * block
    pad = npad - n
    issue_p = jnp.pad(issue.astype(jnp.float32), (0, pad))
    svc_p = jnp.pad(svc.astype(jnp.float32), (0, pad))
    head_p = jnp.pad(seg_start.astype(bool), (0, pad),
                     constant_values=True)   # padded tail = its own segment

    grid = npad // block
    out = pl.pallas_call(
        _kernel,
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((block,), lambda i: (i,)),
        ],
        out_specs=pl.BlockSpec((block,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((npad,), jnp.float32),
        scratch_shapes=[pltpu.SMEM((1,), jnp.float32)],
        interpret=interpret,
    )(issue_p, svc_p, head_p)
    return out[:n]
