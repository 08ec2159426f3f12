"""Per-kernel shape/dtype sweeps, asserted allclose against the ref.py
pure-jnp oracles (interpret mode on CPU)."""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.kernels import ops, ref

RNG = np.random.default_rng(42)


def _arr(shape, dtype=jnp.float32, scale=1.0):
    return jnp.array(RNG.standard_normal(shape) * scale, dtype)


@pytest.mark.parametrize("b,hq,hkv,tq,tk,d", [
    (1, 4, 4, 128, 128, 64),
    (2, 8, 2, 100, 100, 64),
    (1, 4, 1, 64, 256, 128),
    (1, 2, 2, 1, 128, 64),        # decode-like single query
    (2, 4, 2, 37, 37, 32),        # ragged, non-multiple-of-block
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(b, hq, hkv, tq, tk, d, dtype):
    q, k, v = (_arr((b, hq, tq, d), dtype), _arr((b, hkv, tk, d), dtype),
               _arr((b, hkv, tk, d), dtype))
    out = ops.attention(q, k, v, impl="interpret")
    want = ref.attention_ref(q, k, v)
    tol = 2e-4 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=tol)


@pytest.mark.parametrize("window", [16, 64])
def test_flash_attention_window(window):
    q, k, v = _arr((1, 4, 128, 64)), _arr((1, 2, 128, 64)), _arr((1, 2, 128, 64))
    out = ops.attention(q, k, v, window=window, impl="interpret")
    want = ref.attention_ref(q, k, v, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-4)


def test_chunked_xla_attention_matches_dense():
    q, k, v = _arr((2, 4, 300, 64)), _arr((2, 2, 300, 64)), _arr((2, 2, 300, 64))
    out = ref.attention_xla_chunked(q, k, v, q_chunk=128)
    want = ref.attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("shape", [(4, 17, 256), (2, 128), (1, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_sweep(shape, dtype):
    x = _arr(shape, dtype)
    w = _arr((shape[-1],))
    out = ops.rmsnorm(x, w, impl="interpret")
    want = ref.rmsnorm_ref(x, w)
    tol = 1e-4 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=tol)


@pytest.mark.parametrize("b,t,d", [(2, 64, 32), (1, 300, 16), (3, 1024, 8)])
def test_linear_recurrence_sweep(b, t, d):
    a = jnp.array(RNG.uniform(0.6, 0.999, (b, t, d)), jnp.float32)
    x = _arr((b, t, d))
    out = ops.linear_recurrence(a, x, impl="interpret")
    want = ref.linear_recurrence_ref(a, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-3, rtol=1e-3)


@pytest.mark.parametrize("b,t,h,p,g,n,chunk", [
    (1, 128, 4, 32, 2, 64, 64),
    (2, 256, 2, 16, 1, 32, 128),
    (1, 64, 2, 64, 2, 128, 32),
])
def test_ssd_chunk_scan_sweep(b, t, h, p, g, n, chunk):
    x = _arr((b, t, h, p), scale=0.5)
    dt = jnp.array(RNG.uniform(0.001, 0.1, (b, t, h)), jnp.float32)
    A = jnp.array(-RNG.uniform(0.5, 2.0, h), jnp.float32)
    B = _arr((b, t, g, n), scale=0.3)
    C = _arr((b, t, g, n), scale=0.3)
    y, s = ops.ssd_scan(x, dt, A, B, C, chunk=chunk, impl="interpret")
    yr, sr = ref.ssd_ref(x, dt, A, B, C)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=1e-3)
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), atol=1e-3)


@pytest.mark.parametrize("n,block", [(7, 1024), (1000, 256), (4096, 512)])
def test_zns_event_scan_sweep(n, block):
    issue = jnp.array(np.sort(RNG.uniform(0, 1e5, n)), jnp.float32)
    svc = jnp.array(RNG.uniform(1, 50, n), jnp.float32)
    seg = jnp.array(RNG.uniform(size=n) < 0.05)
    seg = seg.at[0].set(True)
    from repro.kernels.zns_event_scan import zns_event_scan
    out = zns_event_scan(issue, svc, seg, block=block, interpret=True)
    want = ref.zns_event_scan_ref(issue, svc, seg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-2)


def test_zns_event_scan_matches_numpy_engine_path():
    """engine.zone_sequential_completions numpy path == kernel body."""
    from repro.core.engine import zone_sequential_completions
    n = 500
    issue = np.sort(RNG.uniform(0, 1e4, n))
    svc = RNG.uniform(1, 30, n)
    seg = RNG.uniform(size=n) < 0.1
    seg[0] = True
    a = zone_sequential_completions(issue, svc, seg, backend="numpy")
    b = ops.zns_event_scan(jnp.asarray(issue, jnp.float32),
                           jnp.asarray(svc, jnp.float32),
                           jnp.asarray(seg), impl="interpret")
    np.testing.assert_allclose(a, np.asarray(b), rtol=1e-5, atol=1e-2)


@pytest.mark.parametrize("batched", [False, True])
def test_explicit_pallas_scan_backend_raises_off_tpu(batched):
    """``backend="pallas"`` runs the compiled kernel or fails: off a TPU
    Pallas refuses compiled mode, and nothing falls back to numpy."""
    from repro.core.engine import (zone_sequential_completions,
                                   zone_sequential_completions_batched)
    shape = (2, 64) if batched else (64,)
    issue = np.sort(RNG.uniform(0, 1e4, shape), axis=-1)
    svc = RNG.uniform(1, 30, shape)
    seg = np.zeros(shape, dtype=bool)
    seg[..., 0] = True
    fn = zone_sequential_completions_batched if batched \
        else zone_sequential_completions
    with pytest.raises(ValueError, match="interpret mode"):
        fn(issue, svc, seg, backend="pallas")


@pytest.mark.parametrize("bsz,n,block", [(1, 7, 256), (3, 1000, 256),
                                         (5, 2048, 512)])
def test_zns_event_scan_batched_sweep(bsz, n, block):
    """Batch grid dimension == vmap of the 1-D oracle, per device row."""
    issue = jnp.array(np.sort(RNG.uniform(0, 1e5, (bsz, n)), axis=1),
                      jnp.float32)
    svc = jnp.array(RNG.uniform(1, 50, (bsz, n)), jnp.float32)
    seg = jnp.array(RNG.uniform(size=(bsz, n)) < 0.05)
    seg = seg.at[:, 0].set(True)
    out = ops.zns_event_scan_batched(issue, svc, seg, impl="interpret")
    want = ref.zns_event_scan_batched_ref(issue, svc, seg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-2)
    # rows independent: each row equals its own 1-D kernel run
    for b in range(bsz):
        row = ops.zns_event_scan(issue[b], svc[b], seg[b], impl="interpret")
        np.testing.assert_allclose(np.asarray(out[b]), np.asarray(row),
                                   rtol=1e-5, atol=1e-2)


def test_zns_event_scan_batched_engine_dispatch():
    """engine.zone_sequential_completions_batched numpy == kernel body."""
    from repro.core.engine import zone_sequential_completions_batched
    bsz, n = 4, 600
    issue = np.sort(RNG.uniform(0, 1e4, (bsz, n)), axis=1)
    svc = RNG.uniform(1, 30, (bsz, n))
    seg = RNG.uniform(size=(bsz, n)) < 0.1
    seg[:, 0] = True
    a = zone_sequential_completions_batched(issue, svc, seg, backend="numpy")
    b = ops.zns_event_scan_batched(jnp.asarray(issue, jnp.float32),
                                   jnp.asarray(svc, jnp.float32),
                                   jnp.asarray(seg), impl="interpret")
    np.testing.assert_allclose(a, np.asarray(b), rtol=1e-5, atol=1e-2)
