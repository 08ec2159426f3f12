"""Compile rehearsal for the TPU v5e: the solver's device programs at
real widths, compiled for a described (not attached) ``v5e:2x2`` host.

Nothing runs, so this says nothing about results or times; it catches
what the chip's compiler would refuse and programs that do not fit the
chip's 16 GiB.  The topology is described inside a fixture, never at
import: only one process may load the TPU library, and every xdist
worker imports this file.

No Pallas kernel is on a path ``auto`` can reach (the compiler refuses
the ZNS Pallas kernels; see ``repro.kernels.zns_fixpoint``), so only the
XLA forms are compiled here.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.kernels import zns_fixpoint as zf

HBM_BYTES = 16 * 2**30

#: Family-block shapes ``(rows, chain length)`` of a DeviceFleet program
#: of 10 full-spec devices, each with 50k 4 KiB writes at qd 4 and 50k
#: reads at qd 16 over 64 zones: 1M events (chip_smoke's fleet phase at
#: 10/64 of its width).
FLEET_DEVICES = 10
BLOCKS = ((4 * FLEET_DEVICES, 12500), (16 * FLEET_DEVICES, 3125),
          (64 * FLEET_DEVICES, 782))
N_EVENTS = 100_000 * FLEET_DEVICES
SWEEPS = 64


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep these out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    cc.reset_cache()


def _fits(compiled):
    m = compiled.memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert 0 < used < HBM_BYTES, m
    return used


def test_float64_fixpoint_compiles_for_one_v5e(topo):
    one = SingleDeviceSharding(topo.devices[0])

    def spec(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one)

    nf = len(BLOCKS)
    with jax.enable_x64(True):
        compiled = zf.zns_fixpoint_xla.lower(
            spec((N_EVENTS,), jnp.float64), spec((N_EVENTS,), jnp.float64),
            tuple((spec(s, jnp.int32), spec(s, jnp.bool_)) for s in BLOCKS),
            spec((nf, nf), jnp.bool_), sweeps=SWEEPS).compile()
    # the completion vector really is float64 on the chip
    assert "f64[" in compiled.as_text()
    assert _fits(compiled) > N_EVENTS * 8


def test_mesh_solver_compiles_across_four_v5e(topo):
    devices = tuple(topo.devices)
    assert len(devices) == 4
    mesh = Mesh(np.asarray(devices), ("shard",))
    sharded = NamedSharding(mesh, P("shard"))
    n_shards = 8
    n_max = N_EVENTS // n_shards

    def spec(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharded)

    nf = len(BLOCKS)
    args = [spec((n_shards, n_max + 1), jnp.float64),
            spec((n_shards, n_max + 1), jnp.float64),
            spec((n_shards, nf, nf), jnp.bool_)]
    for rows, length in BLOCKS:
        shape = (n_shards, rows // n_shards, length)
        args += [spec(shape, jnp.int32), spec(shape, jnp.bool_)]
    with jax.enable_x64(True):
        fn = zf._sharded_fn(devices, len(args), SWEEPS)
        compiled = fn.lower(*args).compile()
    # one shard stack per chip, every chip used
    out = compiled.output_shardings[0]
    assert len(out.device_set) == 4
    assert out.shard_shape((n_shards, n_max + 1)) == \
        (n_shards // 4, n_max + 1)
    _fits(compiled)
