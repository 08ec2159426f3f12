"""``ClusterSpec.device_spec`` and ``device_params`` reach every storage
server's drive.

* by default a server times its flush appends and shard reads with the
  calibrated ZN540 coefficients, as before the field existed;
* other coefficients change those times, and only through the field;
* a full-size drive spec lands in the server's volume.
"""
import dataclasses

import numpy as np

from repro.cluster import (CLUSTER_DEVICE_SPEC, ClusterSpec, ClusterWorkload,
                           StorageServer, build_graph, erasure)
from repro.core import (DEFAULT_LATENCY_PARAMS, MiB, OpType, Trace,
                        ZNSDeviceSpec, compute_service_times)


def _calibrated(op, nbytes, params=None):
    tr = Trace.build(op=[int(op)], zone=[0], size=[nbytes], issue=[0.0])
    return float(compute_service_times(tr, params, jitter=False)[0])


def _slower(params, factor):
    return dataclasses.replace(params, io_svc_us=params.io_svc_us * factor)


def test_default_drive_is_the_calibrated_zn540():
    spec = ClusterSpec()
    assert spec.device_params is DEFAULT_LATENCY_PARAMS
    assert spec.device_spec is CLUSTER_DEVICE_SPEC
    srv = StorageServer(0, spec)
    assert srv.volume.device.params is DEFAULT_LATENCY_PARAMS
    assert srv.append_svc() == _calibrated(OpType.APPEND, 1 * MiB) == 888.0
    assert srv.read_svc(1 * MiB) == _calibrated(OpType.READ, 1 * MiB)


def test_device_params_set_the_servers_service_times():
    slow = _slower(DEFAULT_LATENCY_PARAMS, 1.5)
    srv = StorageServer(3, ClusterSpec(device_params=slow))
    assert srv.volume.device.params is slow
    assert srv.append_svc() == _calibrated(OpType.APPEND, 1 * MiB, slow) \
        == 1.5 * StorageServer(3, ClusterSpec()).append_svc()
    assert srv.read_svc(1 * MiB) == 1.5 * _calibrated(OpType.READ, 1 * MiB)
    # the graph's flush events carry them
    spec = ClusterSpec(n_gateways=1, n_servers=4, scheme=erasure(2, 1),
                       durability="write-through")
    ops = ClusterWorkload(n_users=2, ops_per_user=4).build(1)
    flush = {}
    for params in (DEFAULT_LATENCY_PARAMS, slow):
        g = build_graph(dataclasses.replace(spec, device_params=params), ops)
        flush[params is slow] = g.svc[np.asarray(g.labels) == "flush"]
    assert len(flush[False]) and np.allclose(flush[True], 1.5 * flush[False])


def test_full_size_drive_reaches_the_volume():
    full = ZNSDeviceSpec()
    srv = StorageServer(0, ClusterSpec(device_spec=full))
    assert srv.volume.spec is full and srv.volume.spec.num_zones == 904
    for _ in range(3):
        srv.insert_shard(obj=0, slot=0, nbytes=1 * MiB)
    assert srv.finalize() == 3
    assert srv.volume.allocator.open_count == 1
