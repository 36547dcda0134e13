"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (plus the Fig-9 profile chart).

CPU-only (JAX_PLATFORMS=cpu) until ROADMAP S1's on-chip benchmark replaces it.
"""
from __future__ import annotations

import sys


def main() -> None:
    rows = []

    def report(name: str, us_per_call: float, derived: str = "") -> None:
        rows.append((name, us_per_call, derived))
        print(f"{name},{us_per_call:.1f},{derived}", flush=True)

    print("name,us_per_call,derived")
    from . import (bench_checkpoint, bench_chunking, bench_lm,
                   bench_profile, bench_recon, bench_scaling, bench_service)
    for mod in (bench_chunking, bench_profile, bench_recon, bench_scaling,
                bench_service, bench_checkpoint, bench_lm):
        try:
            mod.run(report)
        except Exception as e:  # keep the harness going
            print(f"{mod.__name__},-1,FAILED: {type(e).__name__}: {e}",
                  file=sys.stderr, flush=True)
    print(f"# {len(rows)} rows", file=sys.stderr)


if __name__ == "__main__":
    main()
