"""Entry ``fleet``: one query is one ``DeviceFleet.run`` call.

The traffic file gives the fleet size, whether service times are
jittered, the device mixes (each a list of streams), how many seeded
variants the window cycles through, how many devices to check, and the
limits of the comparison.  Every variant holds each mix on the same
number of devices; the seed only deals them out: variant ``k`` of seed
``s`` draws the deal from ``s`` and ``k`` and gives device ``i`` the
jitter seed ``1000 s + 100 k + i``.  Without jitter the seed changes
no answer, only which devices the comparison reads.
"""
from __future__ import annotations

import numpy as np

from entries import common


def shrink(traffic):
    """4 devices, all of them checked, and at most 1,000 requests a
    stream."""
    t = dict(traffic, devices=4, check_devices=4)
    t["mixes"] = [[dict(s, n=min(s["n"], 1000)) for s in mix]
                  for mix in traffic["mixes"]]
    return t


class Cell:
    def __init__(self, traffic, config, seed):
        from repro.core import DeviceFleet, WorkloadSpec

        self.traffic = traffic
        self.config = config
        self.reference = common.reference(config["name"])
        self._expected = {}
        spec, params = common.device(config)
        n = traffic["devices"]
        self.fleet = DeviceFleet.homogeneous(n, spec, params)
        mixes = []
        for streams in traffic["mixes"]:
            wl = WorkloadSpec()
            for s in streams:
                wl = common.add_stream(wl, s)
            mixes.append(wl)
        self.variants = []
        for k in range(traffic["variants"]):
            deal = np.random.default_rng([seed, k]).permutation(n) \
                % len(mixes)
            self.variants.append({
                "base": 1000 * seed + 100 * k, "deal": deal.tolist(),
                "workloads": [mixes[m] for m in deal]})

    def run(self, v):
        return self.fleet.run(v["workloads"], jitter=self.traffic["jitter"],
                              backend="vectorized", fixpoint="auto",
                              seeds=[v["base"] + i
                                     for i in range(len(v["deal"]))])

    def events(self, res):
        return sum(len(r) for r in res)

    def failed(self, res):
        return not res.converged

    def lower_ms(self, res):
        return res.compile_stats.lowering_ms

    def keep(self, query, v, res, rng):
        """``check_devices`` devices of the window's first query: one of
        each mix, then others drawn from the seed."""
        if query:
            return []
        deal = np.asarray(v["deal"])
        picks = [int(rng.choice(np.flatnonzero(deal == m)))
                 for m in np.unique(deal)]
        rest = rng.permutation(np.setdiff1d(np.arange(len(deal)), picks))
        picks += rest[:self.traffic["check_devices"] - len(picks)].tolist()
        return [((v["base"], v["deal"][d], d), res[d].sim.complete.copy())
                for d in picks]

    def expected(self, key, dtype=np.float64):
        base, mix, d = key
        seed = base + d if self.traffic["jitter"] else 0
        memo = (mix, seed, np.dtype(dtype).name)
        if memo not in self._expected:
            _, _, self._expected[memo] = self.reference.run(
                self.traffic["mixes"][mix], self.config["spec"],
                self.config["latency"], seed=seed,
                jitter=self.traffic["jitter"], dtype=dtype)
        return self._expected[memo]

    def program(self, v, res):
        from repro.core import compile_fleet_program
        from repro.core.chain_program import DEFAULT_REFINE

        return compile_fleet_program(
            [r.trace for r in res], list(self.fleet.specs),
            [d.lat for d in self.fleet.devices], refine=DEFAULT_REFINE,
            jitter=self.traffic["jitter"],
            seeds=[v["base"] + i for i in range(len(res))])
