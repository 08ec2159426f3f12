"""Mamba2 SSD (state-space duality) chunked-scan Pallas kernel.

The SSD recurrence S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T,
y_t = S_t C_t is evaluated chunk-wise (chunk length L): an intra-chunk
quadratic term (C B^T ⊙ decay-masked, like a tiny attention over the
chunk) plus an inter-chunk term that threads the (P, N) state through the
sequential chunk-grid dimension in VMEM scratch.  All three matmuls are
(L×N)·(N×L), (L×L)·(L×P) and (P×L)·(L×N) — MXU-shaped for
L = 128, N = 128, P = 64.

Grid: (batch, heads, chunks); chunks is the sequential carry dimension.
KV groups (G < H) are handled by the B/C index_map (h -> h // rep), as in
the attention kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, sfin_ref, s_ref, *,
            nchunks):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    x = x_ref[0, 0].astype(jnp.float32)          # (L, P)
    dt = dt_ref[0, 0].astype(jnp.float32)        # (L,)
    a = a_ref[0]                                  # scalar A_h (negative)
    bmat = b_ref[0, 0].astype(jnp.float32)       # (L, N)
    cmat = c_ref[0, 0].astype(jnp.float32)       # (L, N)

    da = dt * a                                   # (L,) decay log-increments
    cum = jnp.cumsum(da)                          # (L,) inclusive
    l_len = x.shape[0]

    # Intra-chunk: scores[i, j] = (C_i . B_j) * exp(cum_i - cum_j) * dt_j, j<=i
    scores = jax.lax.dot_general(cmat, bmat, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    ii = jax.lax.broadcasted_iota(jnp.int32, (l_len, l_len), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (l_len, l_len), 1)
    decay = jnp.exp(cum[:, None] - cum[None, :])
    scores = jnp.where(jj <= ii, scores * decay * dt[None, :], 0.0)
    y_intra = jax.lax.dot_general(scores, x, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)

    # Inter-chunk: y_i += (C_i exp(cum_i)) . S_prev^T
    s_prev = s_ref[...]                           # (P, N)
    c_dec = cmat * jnp.exp(cum)[:, None]          # (L, N)
    y_inter = jax.lax.dot_general(c_dec, s_prev, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)

    y_ref[0, 0] = (y_intra + y_inter).astype(y_ref.dtype)

    # State update: S = exp(cum_L) S_prev + sum_j exp(cum_L - cum_j) dt_j x_j B_j^T
    w = jnp.exp(cum[l_len - 1] - cum) * dt        # (L,)
    xw = x * w[:, None]                           # (L, P)
    s_new = s_prev * jnp.exp(cum[l_len - 1]) + jax.lax.dot_general(
        xw, bmat, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    s_ref[...] = s_new

    @pl.when(ci == nchunks - 1)
    def _emit_state():
        sfin_ref[0, 0] = s_new


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_chunk_scan(x, dt, A, B, C, *, chunk: int = 128, interpret: bool = False):
    """x:(Bb,T,H,P) dt:(Bb,T,H) A:(H,) B,C:(Bb,T,G,N) -> y:(Bb,T,H,P), S:(Bb,H,P,N).

    T must be a multiple of ``chunk`` (the model pads sequences).
    """
    bb, t, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    rep = h // g
    assert t % chunk == 0, "pad T to a chunk multiple"
    nchunks = t // chunk
    # head-major layouts
    xh = jnp.moveaxis(x, 2, 1)          # (Bb,H,T,P)
    dth = jnp.moveaxis(dt, 2, 1)        # (Bb,H,T)
    bh = jnp.moveaxis(B, 2, 1)          # (Bb,G,T,N)
    ch = jnp.moveaxis(C, 2, 1)
    y, sfin = pl.pallas_call(
        functools.partial(_kernel, nchunks=nchunks),
        grid=(bb, h, nchunks),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda i, j, c: (i, j, c, 0)),
            pl.BlockSpec((1, 1, chunk), lambda i, j, c: (i, j, c)),
            pl.BlockSpec((1,), lambda i, j, c: (j,),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, chunk, n),
                         lambda i, j, c, rep=rep: (i, j // rep, c, 0)),
            pl.BlockSpec((1, 1, chunk, n),
                         lambda i, j, c, rep=rep: (i, j // rep, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda i, j, c: (i, j, c, 0)),
            pl.BlockSpec((1, 1, p, n), lambda i, j, c: (i, j, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bb, h, t, p), x.dtype),
            jax.ShapeDtypeStruct((bb, h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(xh, dth, A.astype(jnp.float32), bh, ch)
    return jnp.moveaxis(y, 1, 2), sfin
