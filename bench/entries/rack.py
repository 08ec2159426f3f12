"""Entry ``rack``: one query is one ``plan_capacity`` call on the
configuration's rack.

The query sweeps the traffic's open-loop rate ladder, every rung healthy
and with the configuration's ``down_server`` down, with the warm-started
ladder, and solves all its programs in one fleet call.  The
configuration gives the rack (scheme, placement, gateways, servers,
network, gateway and server stages, durability) and names the drive's
configuration file, whose spec and latency every server uses.  The
traffic file gives the users, their ops a rung, the object size and op
mix, the rungs (objects/s), whether the ladder is warm-started, the
number of seeded variants and the limits.  Variant ``k`` of seed ``s``
draws its op mix and its arrival clocks from workload seed
``1000 s + 100 k``.

A query fails when a point did not converge or a refinement ran out of
its budget.  The comparison reads the per-op latencies of three of the
window's first query's programs: the top rung healthy, the top rung
degraded and one more drawn from the seed; the reference runs each cold
on the same op list, so the warm ladder is held to the cold answer.
"""
from __future__ import annotations

import dataclasses
import json

import numpy as np

from entries import common


def shrink(traffic):
    """4 users x 16 ops a rung; rungs, modes, ladder and mix kept."""
    return dict(traffic, users=4, ops_per_user=16)


class Cell:
    def __init__(self, traffic, config, seed):
        from repro.cluster import (ClusterConfig, ClusterSpec,
                                   ClusterWorkload, GatewaySpec, NetworkSpec,
                                   RedundancyScheme, ServerSpec)

        self.traffic = traffic
        self.config = config
        self.reference = common.reference(config["name"])
        drive = json.loads((common.BENCH / "configs"
                            / f"{config['drive']}.json").read_text())
        dspec, params = common.device(drive)
        self.spec = ClusterSpec(
            n_gateways=config["n_gateways"], n_servers=config["n_servers"],
            scheme=RedundancyScheme(**config["scheme"]),
            placement=config["placement"],
            network=NetworkSpec(**config["network"]),
            gateway=GatewaySpec(**config["gateway"]),
            server=ServerSpec(**config["server"]),
            device_spec=dspec, device_params=params,
            durability=config["durability"])
        self.rack = [ClusterConfig(self.spec.scheme, self.spec.placement)]
        self.rungs = [float(r) for r in traffic["rungs"]]
        self.variants = [ClusterWorkload(
            n_users=traffic["users"], ops_per_user=traffic["ops_per_user"],
            object_bytes=traffic["object_bytes"],
            get_fraction=traffic["get_fraction"],
            delete_fraction=traffic["delete_fraction"],
            seed=1000 * seed + 100 * k) for k in range(traffic["variants"])]

    def run(self, wl):
        from repro.cluster import plan_capacity

        return plan_capacity(
            self.rack, [wl.n_users], base_spec=self.spec, workload=wl,
            rate_ladder=self.rungs, degraded=True,
            down_server=self.config["down_server"],
            warm_ladder=self.traffic["warm_ladder"], fixpoint="auto")

    def events(self, res):
        return res.n_events

    def failed(self, res):
        return not res.converged or bool(res.order_unstable) or not all(
            p.converged for c in res.curves for p in c.points)

    def lower_ms(self, res):
        return 0.0   # the rack keeps no lowering time; no metric reads it

    def keep(self, query, wl, res, rng):
        """The top rung healthy and degraded, and one more point drawn
        from the seed, of the window's first query."""
        if query:
            return []
        points = {(c.degraded, p.offered_rate): p
                  for c in res.curves for p in c.points}
        top = max(self.rungs)
        picks = [(False, top), (True, top)]
        rest = sorted(set(points) - set(picks))
        picks.append(rest[int(rng.integers(len(rest)))])
        return [((wl.seed, degraded, rate),
                 np.array(points[(degraded, rate)].latencies))
                for degraded, rate in picks]

    def ops(self, seed, rate):
        """The op list of the rung at ``rate`` of variant ``seed``, as
        plain dicts, and the clients' depth the planner gives it."""
        from repro.core import PoissonArrivals

        wl = next(v for v in self.variants if v.seed == seed)
        wl = dataclasses.replace(
            wl, arrival=PoissonArrivals(rate_per_s=rate, seed=wl.seed),
            qd=max(wl.qd, wl.ops_per_user))
        return ([dataclasses.asdict(op) for op in
                 wl.build(self.spec.n_gateways)], wl.qd)

    def expected(self, key, dtype=np.float64):
        seed, degraded, rate = key
        ops, qd = self.ops(seed, rate)
        return self.reference.run(
            ops, self.config, qd=qd, dtype=dtype,
            down=self.config["down_server"] if degraded else None)
