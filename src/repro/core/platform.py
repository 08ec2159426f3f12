"""Device selection: the one place that decides where a fixpoint runs.

Every solver entry point asks this module which driver to use instead of
probing jax itself.  The answer comes from the platform and the program:

* on a TPU the fixpoint runs on the chip as the float64 XLA
  ``lax.while_loop`` (``fixpoint="xla"``, :mod:`repro.kernels.zns_fixpoint`);
* on a host with more than one accelerator device, a program with more
  than one entry is split across them (``fixpoint="sharded"``, mesh
  executor, :mod:`repro.core.shard`);
* on the CPU the float64 numpy ``"loop"`` driver solves it.

jax's backend and local devices are read once per process and nothing
here catches an error: a jax that cannot say which devices it has stops
the solve instead of quietly running it on the host.

Importing this module also places jax's persistent compile cache, before
anything compiles.  Where ``JAX_COMPILATION_CACHE_DIR`` is set jax uses
that directory and this module sets none; otherwise the cache lives at
:data:`CACHE_DIR`, a fixed path in the checkout.
"""
from __future__ import annotations

import functools
import os
from pathlib import Path
from typing import Tuple

import jax

#: Compile-cache directory used when ``JAX_COMPILATION_CACHE_DIR`` is unset.
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"

if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))


@functools.cache
def probe() -> Tuple[str, tuple]:
    """``(jax.default_backend(), jax.local_devices())``, read once per
    process.  Errors from jax propagate."""
    return jax.default_backend(), tuple(jax.local_devices())


def single_chip_driver() -> str:
    """Driver of a solve that stays on one device: ``"xla"`` on a TPU,
    ``"loop"`` elsewhere."""
    return "xla" if probe()[0] == "tpu" else "loop"


def multi_chip() -> bool:
    """True on a host with more than one accelerator device."""
    backend, devices = probe()
    return backend != "cpu" and len(devices) > 1


def fixpoint_driver(n_entries: int) -> str:
    """What ``solve_program(fixpoint="auto")`` runs for a program with
    ``n_entries`` independent entries (devices or cluster programs)."""
    if n_entries > 1 and multi_chip():
        return "sharded"
    return single_chip_driver()


def shard_executor() -> str:
    """What ``solve_program_sharded(executor="auto")`` runs: the
    ``shard_map`` mesh across local accelerators, else the host
    executor's signature-grouped numpy solves."""
    return "mesh" if multi_chip() else "host"


def kernel_impl() -> str:
    """Default ``impl`` of the model kernels in :mod:`repro.kernels.ops`:
    the Pallas kernel on a TPU, the pure-jnp reference elsewhere."""
    return "pallas" if probe()[0] == "tpu" else "xla"
