"""Arithmetic that the metric readers (``metrics/<name>.py``) share."""
from __future__ import annotations

import importlib.util
import os

import numpy as np

import reduce_trace

HERE = os.path.dirname(os.path.abspath(__file__))


def idle_share(run) -> float | None:
    """1 - (union of device operation intervals) / traced window, %."""
    if run.events is None:
        return None
    lo, hi = run.trace_window
    return 100.0 * (1.0 - reduce_trace.busy_ns(run.events, lo, hi)
                    / (hi - lo))


def kernel_seconds(run, kernel: str) -> tuple[float, int]:
    """(device seconds, calls) of a kernel inside the traced window."""
    lo, hi = run.trace_window
    return (reduce_trace.op_seconds(run.events, lo, hi, kernel),
            reduce_trace.kernel_calls(run.events, lo, hi, kernel))


def roofline_share(run, flops: float, nbytes: float, seconds: float
                   ) -> float:
    """Least time the chip allows for the work over the time taken, %."""
    bound = max(flops / run.peaks["flops_per_s"],
                nbytes / run.peaks["hbm_bytes_per_s"])
    return 100.0 * bound / seconds


def loader(config: dict) -> dict:
    """The parameters of a configuration's ``synthetic_tomo_loader``: the
    scan's sizes."""
    for e in config["process_list"]["plugins"]:
        if e["plugin"] == "synthetic_tomo_loader":
            return e["params"]
    raise KeyError("the configuration has no synthetic_tomo_loader")


def load(kind: str, name: str):
    """The module ``<kind>/<name>.py`` under ``bench/`` (a metric's
    reader or a kernel's work count), found by its name."""
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}".replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def percentile(values, q: float) -> float | None:
    return float(np.percentile(values, q)) if len(values) else None
