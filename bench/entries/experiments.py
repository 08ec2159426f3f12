"""Entry ``experiments``: one query is ``ExperimentRunner.simulate``
plus ``evaluate`` over the observation registry, as ``python -m
repro.experiments run --all`` runs it.

A query fails when any experiment's ``check()`` fails or its fixpoint
did not converge.  The traffic file gives the selection (``null`` for
the whole registry), whether service times are jittered, the number of
seeded variants, how many of the window's first queries to keep for the
comparison (every entry of each), and the limits.  Variant ``k`` of
seed ``s`` is the runner seed ``1000 s + 100 k``.

The registry declares its own devices and workloads; the reference
reads those declarations back as plain data (streams, spec fields,
latency parameters) and lowers and simulates them itself.
"""
from __future__ import annotations

import numpy as np

from entries import common


def shrink(traffic):
    """Unchanged: the whole registry already runs in about a second."""
    return traffic


class Cell:
    def __init__(self, traffic, config, seed):
        from repro.experiments import ExperimentRunner

        self.traffic = traffic
        self.config = config
        self.reference = common.reference(config["name"])
        self.variants = [
            ExperimentRunner(traffic["select"], jitter=traffic["jitter"],
                             seed=1000 * seed + 100 * k)
            for k in range(traffic["variants"])]
        self.points = [pt for exp in self.variants[0].experiments
                       for pt in exp.points]

    def run(self, runner):
        fres = runner.simulate(fixpoint="auto")
        return fres, runner.evaluate(fres)

    def events(self, res):
        return sum(len(r) for r in res[0])

    def failed(self, res):
        return not all(r.passed and r.converged for r in res[1])

    def lower_ms(self, res):
        return res[0].compile_stats.lowering_ms

    def keep(self, query, runner, res, rng):
        if query >= self.traffic["check_queries"]:
            return []
        return [((runner.seed, i), r.sim.complete.copy())
                for i, r in enumerate(res[0])]

    def expected(self, key, dtype=np.float64):
        seed, i = key
        pt = self.points[i]
        lat = common.params_dict(pt.params) if pt.params is not None \
            else self.config["latency"]
        _, _, complete = self.reference.run(
            [common.stream_dict(s) for s in pt.workload.streams],
            common.spec_dict(pt.spec), lat, stack=int(pt.workload.stack),
            fmt=int(pt.workload.fmt), seed=seed + pt.seed,
            jitter=self.traffic["jitter"], dtype=dtype)
        return complete

    def program(self, runner, res):
        from repro.core import DEFAULT_LATENCY_PARAMS, compile_fleet_program
        from repro.core.chain_program import DEFAULT_REFINE

        return compile_fleet_program(
            [r.trace for r in res[0]], [pt.spec for pt in self.points],
            [DEFAULT_LATENCY_PARAMS if pt.params is None else pt.params
             for pt in self.points],
            refine=DEFAULT_REFINE, jitter=self.traffic["jitter"],
            seeds=[runner.seed + pt.seed for pt in self.points])
