"""CPU rehearsal of every cell at a tiny size.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py [--trace 1]

Shrinks each cell's traffic by its entry's own rule, runs the harness's
set-up, window and comparison on the CPU for half a second, and prints
one line per cell: whether it came out correct, the queries and events
it ran, and the numbers compared.  It reports no metric: a CPU run says
nothing about the chip, and ``run.py`` never shrinks.

The entry contract.  A traffic file's ``entry`` names a module of
``bench/entries/`` that defines

* ``Cell(traffic, config, seed)``: the cell's seeded work, built from
  the plain traffic and configuration dicts;
* ``Cell.variants``: the seeded variants the window cycles through;
  set-up runs each once;
* ``Cell.run(v)``: one query of variant ``v`` through the program's
  user entry point, returning its result;
* ``Cell.events(res)``, ``Cell.failed(res)``, ``Cell.lower_ms(res)``:
  the events a result answered, whether its query failed, and the
  lowering time it reports;
* ``Cell.keep(query, v, res, rng)``: the ``(key, answer)`` pairs of
  the window's ``query``-th query kept for the comparison (``rng``
  drawn from the seed), each answer a float array;
* ``Cell.expected(key, dtype)``: the plain reference's answer for
  ``key``, computed in ``dtype``;
* ``shrink(traffic)``, at module level: the same traffic at a size the
  CPU runs in about a second, every shape kept.  An entry without it
  cannot be rehearsed: :func:`shrink` raises rather than rehearse a
  cell at full size.
"""
import argparse
import importlib
import json
import sys
from pathlib import Path
from types import SimpleNamespace

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import run  # noqa: E402


def shrink(traffic):
    """The traffic at a size the CPU runs in a second, by the rule of
    the entry it names."""
    name = traffic["entry"]
    entry = importlib.import_module(f"entries.{name}")
    if not callable(getattr(entry, "shrink", None)):
        raise ValueError(f"entry {name!r} defines no shrink(traffic): "
                         f"its cells cannot be rehearsed")
    return entry.shrink(json.loads(json.dumps(traffic)))


def rehearse(name, seed=2**31 + 11, trace=0, seconds=0.5):
    import jax

    bench, cell, config, traffic = run.load_cell(name)
    args = SimpleNamespace(seed=seed, seconds=seconds, trace=trace)
    peaks = json.loads((BENCH / "peaks.json").read_text())["TPU v5 lite"]
    return run.measure(bench, cell, config, shrink(traffic), args,
                       jax.devices(), peaks)


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    ok = True
    for cell in bench["workloads"]:
        out, _, _ = rehearse(cell["name"], trace=args.trace)
        ok &= out["correct"]
        print(json.dumps({"cell": cell["name"], "correct": out["correct"],
                          "queries": out["attempted"],
                          "failed": out["failed"],
                          "checks": out["checks"]}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
