#!/usr/bin/env python3
"""Readings that a cell's limits are set from, on the chip.

    python bench/calibrate.py --workload <cell> --seed <n> --requests <k>

In one process: drives the cell as a run does (``harness.drive_cell``),
with ``k`` requests of its traffic in place of a timed window (each
with its own seed drawn from ``--seed``), and after the service is
stopped compares every delivered volume with the plain reference at the
image rows a run with ``--seed`` checks (``harness.check_rows``).  Then
the control and the other lower-precision variants of the reference
(stage outputs rounded to bfloat16, ``reference.py``) are compared with
the float32 reference on the same seeds.  Prints one JSON
object: per request the program's readings, and per variant its
readings; the lower reading of a limit is the largest of the
program's, the upper the smallest of the control's.

The benchmark's runs do not run this.  It also reports whether the
reference regenerates the program's raw scan bit for bit.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

#: reference variants: stage outputs rounded to bfloat16 (a stage the
#: chain does not run is not rounded)
VARIANTS = {"control_fbp_input": ("fbp_input",),
            "correction": ("dark_flat_correction",),
            "paganin_filter": ("paganin_filter",),
            "all_stages": ("dark_flat_correction", "paganin_filter",
                           "ring_removal", "sinogram_filter", "fbp_input")}


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--requests", type=int, default=12)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    # libtpu would log under /tmp, outside the checkout
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [HERE, os.path.join(ROOT, "src")]
    import numpy as np

    import harness
    import reference
    from repro.tomo.geometry import ParallelGeometry
    from repro.tomo.phantom import simulate_phantom_scan

    run = harness.drive_cell(harness.load_benchmark(), args.workload,
                             seed=args.seed, count=args.requests,
                             t_start=time.perf_counter())
    config, phantom = run.config, run.config["phantom"]
    lp = reference.chain_params(config["process_list"])[
        "synthetic_tomo_loader"]
    rows = harness.check_rows(config, args.seed)
    out: dict = {"workload": args.workload, "seed": args.seed,
                 "program": [], "variants": {k: [] for k in VARIANTS},
                 "raw_identical": []}
    for i, r in enumerate(run.requests):
        if r.error:
            out["program"].append({"seed": r.seed, "error": r.error})
            continue
        t = time.perf_counter()
        sinos, mu = reference.filtered_sinograms(r.spec, phantom, r.seed)
        want = reference.volume_rows(r.spec, sinos, mu, rows)
        del sinos
        ref_s = time.perf_counter() - t
        out["program"].append({"seed": r.seed, "reference_s": ref_s,
                               **reference.compare(r.volume[:, rows, :],
                                                   want)})
        r.volume = None
        for name, stages in VARIANTS.items():
            low, _ = reference.filtered_sinograms(
                r.spec, phantom, r.seed, round_to=frozenset(stages))
            got = reference.volume_rows(r.spec, low, mu, rows)
            del low
            out["variants"][name].append(
                {"seed": r.seed, **reference.compare(got, want)})
        if i < 3:
            ours = reference.raw_scan(lp["n_angles"], lp["n_rows"],
                                      lp["n_det"], r.seed, phantom)
            theirs = simulate_phantom_scan(
                ParallelGeometry(lp["n_angles"], lp["n_det"], lp["n_rows"]),
                seed=r.seed)
            same = np.asarray(ours["data"]) == theirs["data"]
            out["raw_identical"].append(
                {"seed": r.seed, "differing_pixels": int(same.size
                                                         - same.sum())})
            del ours, theirs
        print(json.dumps(out["program"][-1]), file=sys.stderr, flush=True)
    for key in ("max_err", "rms_err"):
        prog = [p[key] for p in out["program"] if key in p]
        ctrl = [v[key] for v in out["variants"]["control_fbp_input"]]
        out[key] = {"lower": max(prog, default=None),
                    "upper": min(ctrl, default=None)}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
