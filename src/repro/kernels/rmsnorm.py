"""Fused RMSNorm Pallas kernel.

One pass over a (rows, D) view: the row block is normalized in f32 and
scaled by (1 + w) without materializing the intermediate variance tensor
in HBM.  Row blocks of 256 keep (256, D<=16384) f32 within VMEM budget
for every assigned architecture width.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, w_ref, o_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    o_ref[...] = (y * (1.0 + w_ref[...].astype(jnp.float32))).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("eps", "block_rows", "interpret"))
def rmsnorm(x, w, *, eps: float = 1e-6, block_rows: int = 256,
            interpret: bool = False):
    """x: (..., D), w: (D,) -> (..., D)."""
    orig_shape = x.shape
    d = orig_shape[-1]
    rows = int(np.prod(orig_shape[:-1])) if len(orig_shape) > 1 else 1
    x2 = x.reshape(rows, d)
    br = min(block_rows, rows)
    rows_p = (rows + br - 1) // br * br
    x2 = jnp.pad(x2, ((0, rows_p - rows), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_kernel, eps=eps),
        grid=(rows_p // br,),
        in_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows_p, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x2, w)
    return out[:rows].reshape(orig_shape)


import numpy as np  # noqa: E402  (used in jit-static shape math only)
