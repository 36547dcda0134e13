#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the chips of this machine.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json`` at the root
of the checkout.  The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer ones),
``device`` (platform, kind, count, peak memory; with ``--trace 1`` also
the device's busy seconds and the traced window), with ``--trace 1``
``breakdown``, and last ``check``: each number compared with its limit.
The check's lines also end standard error.

It exits 1 with no result when JAX finds no TPU or fewer chips than the
cell needs, and 2 when the program or the benchmark's files are not in
the checkout.  JAX's compilation cache is kept in ``.jax_cache`` at the
root of the checkout.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    # libtpu would log under /tmp, outside the checkout
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [HERE, os.path.join(ROOT, "src")]
    try:
        import repro.service  # noqa: F401
        import harness
        bench = harness.load_benchmark()
        harness.cell_parts(bench, args.workload)
    except (ImportError, OSError, KeyError) as e:
        print(f"bench: cannot set up {args.workload!r}: {e}",
              file=sys.stderr)
        return 2
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    cell = {w["name"]: w for w in bench["workloads"]}[args.workload]
    t_imported = time.perf_counter()
    try:
        harness.device_info(jax.devices(), cell["chips"], "tpu")
    except RuntimeError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    print(f"start: {t_imported - T_START:.3f} s of imports, "
          f"{time.perf_counter() - t_imported:.3f} s for JAX to find the "
          f"chips", file=sys.stderr, flush=True)
    result = harness.run_cell(bench, args.workload, seed=args.seed,
                              seconds=args.seconds, trace=bool(args.trace),
                              t_start=T_START)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
