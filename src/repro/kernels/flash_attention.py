"""Blocked (flash) attention forward kernel for TPU.

Online-softmax attention with causal and local-window masking, tiled for
VMEM: the (bq, D) query block stays resident while (bk, D) key/value
blocks stream through the innermost (sequential) grid dimension, with the
running max/denominator/accumulator held in f32 VMEM scratch.  GQA is
handled without materializing repeated KV heads: the K/V BlockSpec
index_map maps query-head ``h`` to KV head ``h // rep``.

MXU alignment: D is the lane dimension (pad to 128 in the wrapper if
needed); bq/bk default to 128/256.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale, causal, window, bq, bk, tq, tk, nk):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)          # (bq, D)
    k = k_ref[0, 0].astype(jnp.float32)          # (bk, D)
    v = v_ref[0, 0].astype(jnp.float32)          # (bk, D)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    # Global positions; query positions are aligned to the *end* of the KV
    # sequence (decode convention: tq <= tk).
    qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + (tk - tq)
    kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = (qpos < tk) & (kpos < tk)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                          # (bq, 1)
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)                       # (bq, bk)
    corr = jnp.exp(m_prev - m_new)               # (bq, 1)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        l = l_ref[...]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / safe).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "scale", "bq", "bk", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int | None = None,
                    scale: float | None = None, bq: int = 128, bk: int = 256,
                    interpret: bool = False):
    """q: (B, Hq, Tq, D); k/v: (B, Hkv, Tk, D) -> (B, Hq, Tq, D)."""
    b, hq, tq, d = q.shape
    hkv, tk = k.shape[1], k.shape[2]
    rep = hq // hkv
    scale = float(scale) if scale is not None else 1.0 / float(np.sqrt(d))
    bq = min(bq, tq)
    bk = min(bk, tk)
    # pad seq dims to block multiples
    tq_p = (tq + bq - 1) // bq * bq
    tk_p = (tk + bk - 1) // bk * bk
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, tq_p - tq), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, tk_p - tk), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, tk_p - tk), (0, 0)))
    nq, nk = tq_p // bq, tk_p // bk

    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, window=window,
        bq=bq, bk=bk, tq=tq, tk=tk, nk=nk)
    out = pl.pallas_call(
        kernel,
        grid=(b, hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h, qi, ki: (b_, h, qi, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b_, h, qi, ki, rep=rep: (b_, h // rep, ki, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b_, h, qi, ki, rep=rep: (b_, h // rep, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), lambda b_, h, qi, ki: (b_, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, tq_p, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(qp, kp, vp)
    return out[:, :, :tq, :]
