"""What the entries share: building the program's inputs from plain
data, reading the program's declared workloads back into plain data for
the reference, and the comparison that decides ``correct``."""
from __future__ import annotations

import dataclasses
import importlib.util
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent.parent


def reference(config_name):
    """The plain reference beside the configuration file
    (``configs/<name>.py``)."""
    path = BENCH / "configs" / f"{config_name}.py"
    spec = importlib.util.spec_from_file_location(
        f"reference_{config_name.replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def device(config):
    """The program's device spec and latency parameters, built from the
    configuration file's numbers."""
    from repro.core import LatencyParams, LBAFormat, ZNSDeviceSpec

    sp = dict(config["spec"], lba_format=LBAFormat(config["spec"]
                                                   ["lba_format"]))
    params = LatencyParams(**{k: np.asarray(v, dtype=np.float64)
                              for k, v in config["latency"].items()})
    return ZNSDeviceSpec(**sp), params


def add_stream(workload, s):
    """``workload`` with the stream described by the plain dict ``s``."""
    import repro.core as core

    kw = {k: v for k, v in s.items() if k != "op"}
    if kw.get("arrival") is not None:
        a = dict(kw["arrival"])
        kw["arrival"] = getattr(core, a.pop("kind"))(**a)
    return workload.stream(core.OpType[s["op"]], **kw)


def stream_dict(stream):
    """A declared ``StreamSpec`` as the plain dict the reference reads."""
    out = {f.name: getattr(stream, f.name)
           for f in dataclasses.fields(stream)}
    out["op"] = int(out["op"])
    a = out.get("arrival")
    if a is not None:
        out["arrival"] = dict(dataclasses.asdict(a), kind=type(a).__name__)
    return out


def spec_dict(spec):
    out = {f.name: getattr(spec, f.name) for f in dataclasses.fields(spec)}
    out["lba_format"] = int(out["lba_format"])
    return out


def params_dict(params):
    return {k: np.asarray(v).tolist() for k, v in params.fields()}


def max_rel_err(got, want):
    """Largest ``|got - want| / |want|``; infinite when the shapes
    differ or a value is not finite."""
    got = np.asarray(got, dtype=np.float64)
    want = np.asarray(want, dtype=np.float64)
    if got.shape != want.shape:
        return float("inf")
    if not len(want):
        return 0.0
    err = np.abs(got - want) / np.maximum(np.abs(want), 1e-300)
    return float(np.max(err)) if np.all(np.isfinite(err)) else float("inf")


def compare(cell, samples, dtype=np.float64):
    """``max_rel_err`` of the kept answers against the reference
    computed in ``dtype``."""
    worst = 0.0
    for key, got in samples:
        worst = max(worst, max_rel_err(got, cell.expected(key, dtype)))
    return {"max_rel_err": worst}
