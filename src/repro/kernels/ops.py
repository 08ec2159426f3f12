"""Public jit'd wrappers for the kernel package.

Every op takes ``impl``: 'pallas' runs the Pallas kernel compiled for
TPU, 'interpret' runs the kernel body in interpret mode (CPU
correctness), 'xla' runs the XLA form.  The model kernels' default
'auto' asks :func:`repro.core.platform.kernel_impl` ('pallas' on TPU,
'xla' elsewhere) — the multi-pod dry-run therefore lowers the XLA path,
while kernel tests pin 'interpret' to exercise the kernel bodies.  The
ZNS kernels default to 'xla' everywhere: the TPU compiler refuses their
Pallas forms, which are reachable only by name.
"""
from __future__ import annotations

import jax.numpy as jnp

from . import ref
from .flash_attention import flash_attention as _fa
from .linear_recurrence import linear_recurrence as _lr
from .rmsnorm import rmsnorm as _rms
from .ssd_chunk_scan import ssd_chunk_scan as _ssd
from .zns_event_scan import zns_event_scan as _zns
from .zns_event_scan import zns_event_scan_batched as _zns_batched
from .zns_fixpoint import zns_fixpoint as _zns_fixpoint
from .zns_fixpoint import zns_fixpoint_xla as _zns_fixpoint_xla


def _resolve(impl: str | None) -> str:
    if impl not in (None, "auto"):
        return impl
    from repro.core import platform
    return platform.kernel_impl()


def attention(q, k, v, *, causal=True, window=None, scale=None,
              kv_length=None, impl: str | None = None):
    impl = _resolve(impl)
    if impl == "xla" or kv_length is not None:
        tq, tk = q.shape[2], k.shape[2]
        if kv_length is None and tq * tk > 1024 * 1024:
            # memory-bounded flash-style path (mirrors the Pallas kernel)
            return ref.attention_xla_chunked(q, k, v, causal=causal,
                                             window=window, scale=scale)
        return ref.attention_ref(q, k, v, causal=causal, window=window,
                                 scale=scale, kv_length=kv_length)
    return _fa(q, k, v, causal=causal, window=window, scale=scale,
               interpret=(impl == "interpret"))


def rmsnorm(x, w, *, eps=1e-6, impl: str | None = None):
    impl = _resolve(impl)
    if impl == "xla":
        return ref.rmsnorm_ref(x, w, eps=eps)
    return _rms(x, w, eps=eps, interpret=(impl == "interpret"))


def linear_recurrence(a, b, *, impl: str | None = None):
    impl = _resolve(impl)
    if impl == "xla":
        return ref.linear_recurrence_ref(a, b)
    return _lr(a, b, interpret=(impl == "interpret"))


def ssd_scan(x, dt, A, B, C, *, chunk=128, impl: str | None = None):
    impl = _resolve(impl)
    if impl == "xla":
        return ref.ssd_ref(x, dt, A, B, C)
    return _ssd(x, dt, A, B, C, chunk=chunk, interpret=(impl == "interpret"))


def zns_event_scan(issue, svc, seg_start, *, impl: str = "xla"):
    if impl == "xla":
        return ref.zns_event_scan_ref(issue, svc, seg_start)
    return _zns(issue, svc, seg_start, interpret=(impl == "interpret"))


def zns_event_scan_batched(issue, svc, seg_start, *, impl: str = "xla"):
    """(B, N) device-batched max-plus scan."""
    if impl == "xla":
        return ref.zns_event_scan_batched_ref(issue, svc, seg_start)
    return _zns_batched(issue, svc, seg_start, interpret=(impl == "interpret"))


def zns_fixpoint(comp0, svc, blocks, *, sweeps: int = 8,
                 impl: str = "xla", adj=None):
    """Fused chain-program fixpoint: all sweeps × family blocks in one
    compiled call (the ``ZnsDevice``/``DeviceFleet`` vectorized-backend
    hot loop on TPU).

    ``blocks``: tuple of ``(gidx, heads)`` padded index/head matrices
    from :class:`repro.core.ChainProgram`.  ``adj`` is the symmetric
    block-adjacency matrix (``repro.core.chain_program.block_adjacency``)
    driving the in-kernel active-set mask; computed from the blocks when
    omitted.  Returns ``(completions, sweeps_used, converged)``.
    ``impl='xla'`` runs the jitted ``lax.while_loop`` form,
    ``'pallas'``/``'interpret'`` the Pallas kernel (compiled / interpret
    mode).  Computes in the dtype ``comp0`` has as a jax array: float64
    under ``jax.enable_x64``, float32 otherwise.
    """
    from .zns_fixpoint import blocks_adjacency
    blocks = tuple((jnp.asarray(g, dtype=jnp.int32), jnp.asarray(h, bool))
                   for g, h in blocks)
    comp0 = jnp.asarray(comp0)
    svc = jnp.asarray(svc, dtype=comp0.dtype)
    if adj is None:
        adj = blocks_adjacency([g for g, _ in blocks], comp0.shape[0])
    adj = jnp.asarray(adj, dtype=bool)
    if impl == "xla":
        return _zns_fixpoint_xla(comp0, svc, blocks, adj, sweeps=int(sweeps))
    return _zns_fixpoint(comp0, svc, blocks, adj, sweeps=int(sweeps),
                         interpret=(impl == "interpret"))
