"""Blocked diagonal linear recurrence (RG-LRU core) Pallas kernel.

Computes h_t = a_t * h_{t-1} + b_t over (B, T, D) with a Hillis–Steele
intra-block scan over time (composition of affine maps (a, b), identity
(1, 0)) and an inter-block carry of the hidden state held in VMEM scratch
across the sequential time-grid dimension.  Time blocks of 256 keep three
(256, D) f32 buffers in VMEM for D ≤ 8192.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(a_ref, b_ref, o_ref, h_ref, *, bt):
    ti = pl.program_id(1)

    @pl.when(ti == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    a = a_ref[0].astype(jnp.float32)       # (bt, D)
    b = b_ref[0].astype(jnp.float32)
    n = a.shape[0]
    idx = jax.lax.broadcasted_iota(jnp.int32, a.shape, 0)
    k = 1
    while k < n:
        a_s = jnp.where(idx >= k, pltpu.roll(a, k, 0), jnp.float32(1.0))
        b_s = jnp.where(idx >= k, pltpu.roll(b, k, 0), jnp.float32(0.0))
        a, b = a_s * a, b_s * a + b
        k *= 2
    h = h_ref[...]                          # (1, D) carry
    out = a * h + b
    o_ref[0] = out.astype(o_ref.dtype)
    h_ref[...] = out[n - 1:n]


@functools.partial(jax.jit, static_argnames=("block_t", "interpret"))
def linear_recurrence(a, b, *, block_t: int = 256, interpret: bool = False):
    """a, b: (B, T, D) -> h: (B, T, D) with h_t = a_t h_{t-1} + b_t."""
    bb, t, d = a.shape
    bt = min(block_t, t)
    t_p = (t + bt - 1) // bt * bt
    # pad with identity maps (a=1, b=0)
    a_p = jnp.pad(a, ((0, 0), (0, t_p - t), (0, 0)), constant_values=1)
    b_p = jnp.pad(b, ((0, 0), (0, t_p - t), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_kernel, bt=bt),
        grid=(bb, t_p // bt),
        in_specs=[
            pl.BlockSpec((1, bt, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, bt, d), lambda i, j: (i, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bt, d), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((bb, t_p, d), b.dtype),
        scratch_shapes=[pltpu.VMEM((1, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(a_p, b_p)
    return out[:, :t, :]
