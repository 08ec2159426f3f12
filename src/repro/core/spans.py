"""Named spans and counters of the program's own phases.

``span(name)`` times a phase on the host clock (``perf_counter_ns``)
and, where jax has been imported, opens a
``jax.profiler.TraceAnnotation`` of the same name, so a profiler trace
shows the phase on the host timeline beside the device's ops.
``count(name, n)`` adds ``n`` to a counter.  Both land in one
process-wide table keyed by name, each entry ``calls`` and ``total``
(nanoseconds for a span, the summed ``n`` for a counter);
``snapshot()`` copies it and ``reset()`` clears it.

Nothing switches the table on or off.  With no profiler running the
annotation records nothing, and a span costs two clock reads and one
table update.  ``NAMES`` lists every span the program opens and
``COUNTERS`` every counter, so a reader of a trace or of the table need
not guess.

    >>> from repro.core import spans
    >>> with spans.span("lower"):
    ...     pass
    >>> spans.snapshot()["lower"]["calls"] >= 1
    True
"""
from __future__ import annotations

import sys
import threading
import time
from typing import Dict

#: Every span the program opens, each under the one it nests in.
NAMES = (
    "fleet.run",                # DeviceFleet.run, the whole call
    "fleet.build",              # workload specs -> per-device traces
    "lower",                    # compile_fleet_program
    "lower.digest",             # trace content digests
    "lower.devices",            # per-device lowering of unique traces
    "lower.replay",             # greedy pool replay (both rebuilds)
    "lower.assemble",           # family lists + fleet block fill
    "solve.prepare",            # adjacency, initial vector, device puts
    "solve.adjacency",          # block adjacency (memo misses only)
    "solve.wait",               # until the device's result is ready
    "solve.fetch",              # device -> host copy of the result
    "fleet.unpack",             # flat solve -> per-device results
    "experiments.evaluate",     # ExperimentRunner.evaluate
)

#: Every counter the program keeps.
COUNTERS = (
    "program_cache.hits",       # in-memory program-cache hits
    "program_cache.misses",     # in-memory misses
    "program_cache.disk_hits",  # programs loaded from the disk cache
    "solve.sweeps",             # fixpoint sweeps run
    "solve.active_blocks",      # family blocks active, summed over sweeps
)

_TABLE: Dict[str, list] = {}
_LOCK = threading.Lock()


def _add(name: str, total: int) -> None:
    with _LOCK:
        entry = _TABLE.get(name)
        if entry is None:
            _TABLE[name] = [1, total]
        else:
            entry[0] += 1
            entry[1] += total


class span:
    """Time the ``with`` block as span ``name``; ``ns`` holds its wall
    nanoseconds once the block has exited."""

    __slots__ = ("name", "ns", "_t0", "_note")

    def __init__(self, name: str):
        self.name = name
        self.ns = 0

    def __enter__(self) -> "span":
        jax = sys.modules.get("jax")
        profiler = getattr(jax, "profiler", None)
        self._note = None if profiler is None \
            else profiler.TraceAnnotation(self.name)
        if self._note is not None:
            self._note.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        self.ns = time.perf_counter_ns() - self._t0
        if self._note is not None:
            self._note.__exit__(*exc)
        _add(self.name, self.ns)


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to counter ``name``."""
    _add(name, int(n))


def snapshot() -> Dict[str, Dict[str, int]]:
    """A copy of the table: ``{name: {"calls": ..., "total": ...}}``."""
    with _LOCK:
        return {k: {"calls": c, "total": t} for k, (c, t) in _TABLE.items()}


def reset(*names: str) -> None:
    """Clear the named entries, or the whole table when none is named."""
    with _LOCK:
        if not names:
            _TABLE.clear()
        for k in names:
            _TABLE.pop(k, None)
