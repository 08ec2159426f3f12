"""Device selection (``repro.core.platform``): one decision from the
platform and the program, no silent fallback, a fixed compile cache."""
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core import (KiB, WorkloadSpec, ZnsDevice, last_solve_stats,
                        platform)

SRC = os.path.join(os.path.dirname(__file__), os.pardir, "src")


@pytest.fixture
def fresh_probe():
    """Let a test re-run the once-per-process probe, then restore it."""
    platform.probe.cache_clear()
    yield
    platform.probe.cache_clear()


def test_probe_raises_when_backend_query_fails(monkeypatch, fresh_probe):
    def broken():
        raise RuntimeError("backend query failed")

    monkeypatch.setattr(jax, "default_backend", broken)
    with pytest.raises(RuntimeError, match="backend query failed"):
        platform.probe()
    with pytest.raises(RuntimeError, match="backend query failed"):
        platform.fixpoint_driver(4)


@pytest.mark.parametrize("backend,n_dev,n_entries,driver,executor,impl", [
    ("cpu", 1, 1, "loop", "host", "xla"),
    ("cpu", 4, 8, "loop", "host", "xla"),      # virtual CPU devices
    ("tpu", 1, 1, "xla", "host", "pallas"),
    ("tpu", 1, 64, "xla", "host", "pallas"),
    ("tpu", 4, 1, "xla", "mesh", "pallas"),
    ("tpu", 4, 2, "sharded", "mesh", "pallas"),
])
def test_choice_follows_platform_and_program(monkeypatch, backend, n_dev,
                                             n_entries, driver, executor,
                                             impl):
    monkeypatch.setattr(platform, "probe",
                        lambda: (backend, tuple(range(n_dev))))
    assert platform.fixpoint_driver(n_entries) == driver
    assert platform.shard_executor() == executor
    assert platform.kernel_impl() == impl


def test_auto_solves_with_the_float64_loop_on_cpu():
    """On the CPU ``auto`` is the numpy loop: results are bit-identical
    to pinning it."""
    dev = ZnsDevice()
    wl = (WorkloadSpec()
          .appends(n=60, size=8 * KiB, qd=2, zone=0, nzones=4)
          .appends(n=60, size=8 * KiB, qd=2, zone=4, nzones=4))
    auto = dev.run(wl, backend="vectorized", jitter=False)
    assert last_solve_stats().driver == "loop"
    assert last_solve_stats().devices == ()
    loop = dev.run(wl, backend="vectorized", jitter=False, fixpoint="loop")
    np.testing.assert_array_equal(auto.sim.complete, loop.sim.complete)


def test_xla_driver_solves_in_float64_on_a_jax_device():
    dev = ZnsDevice()
    wl = (WorkloadSpec()
          .appends(n=60, size=8 * KiB, qd=2, zone=0, nzones=4)
          .appends(n=60, size=8 * KiB, qd=2, zone=4, nzones=4))
    loop = dev.run(wl, backend="vectorized", jitter=True, fixpoint="loop")
    got = dev.run(wl, backend="vectorized", jitter=True, fixpoint="xla")
    st = last_solve_stats()
    assert st.driver == "xla" and st.converged
    assert st.devices == (str(jax.devices()[0]),)
    # float32 would miss by ~1e-7 relative at these magnitudes
    np.testing.assert_allclose(got.sim.complete, loop.sim.complete,
                               rtol=1e-12, atol=1e-9)


CACHE_SCRIPT = (
    "import jax\n"
    "from repro.core import platform\n"
    "print(jax.config.jax_compilation_cache_dir)\n")


@pytest.mark.parametrize("env_dir", [None, "set"])
def test_compile_cache_directory(tmp_path, env_dir):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [SRC] + env.get("PYTHONPATH", "").split(os.pathsep))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    want = str(platform.CACHE_DIR)
    if env_dir:
        want = env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    proc = subprocess.run([sys.executable, "-c", CACHE_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.strip().splitlines()[-1] == want
    assert platform.CACHE_DIR == \
        Path(SRC).resolve().parent / ".jax_cache"
