"""CPU rehearsal of every cell at a tiny size.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py [--trace 1]

Shrinks each cell's traffic (a fleet keeps 4 devices and 1,000 requests
a stream), runs the harness's set-up, window and comparison on the CPU
for half a second, and prints one line per cell: whether it came out
correct, the queries and events it ran, and the numbers compared.  It
reports no metric: a CPU run says nothing about the chip.
"""
import argparse
import json
import sys
from pathlib import Path
from types import SimpleNamespace

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import run  # noqa: E402


def shrink(traffic):
    """The traffic at a size the CPU runs in a second."""
    t = json.loads(json.dumps(traffic))
    if t["entry"] == "fleet":
        t["devices"] = 4
        t["check_devices"] = t["devices"]
        for mix in t["mixes"]:
            for s in mix:
                s["n"] = min(s["n"], 1000)
    return t


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
