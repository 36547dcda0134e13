"""Compile every step of the standard chain for a described TPU, without
a chip, and print each step's memory analysis.

The TPU compiler is installed with jaxlib, so a step that Mosaic or XLA
would refuse on the chip (tiling, VMEM, HBM) is refused here too.  Run
with the CPU as the default backend; the kernels' backend check is
steered to "tpu" for the duration of the compiles::

    JAX_PLATFORMS=cpu PYTHONPATH=src \\
        python -m repro.launch.rehearse_chain --n-rows 32 --paganin
    JAX_PLATFORMS=cpu PYTHONPATH=src \\
        python -m repro.launch.rehearse_chain --n-rows 128 --paganin --chips 4

A compile that passes is not a chip run: it gives no times and checks
no results.
"""
from __future__ import annotations

import argparse
import os
import time
from typing import Iterator
from unittest import mock

import numpy as np

import jax
from jax.experimental import topologies
from jax.sharding import Mesh

from ..core import BasePlugin, PluginRunner, ProcessList, ShardedTransport
from ..tomo import standard_chain

GiB = 2**30


def compile_steps(chain: ProcessList, mesh: Mesh
                  ) -> Iterator[tuple[BasePlugin, jax.stages.Compiled]]:
    """Compile each step of ``chain`` for ``mesh`` (devices of a
    described topology), in order; yields (plugin, compiled program)."""
    runner = PluginRunner(chain, ShardedTransport(mesh))
    runner.prepare()
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        while (group := runner.begin_step()) is not None:
            for p in group:
                with mesh:
                    yield p, runner.transport.compile_plugin(
                        p, lower_only=True).compile()
            runner.complete_step()


def rehearse(n_det: int, n_angles: int, n_rows: int, *, paganin: bool,
             chips: int, use_pallas: bool = True) -> list[dict]:
    """Compile each step; returns one dict of byte counts per step."""
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = Mesh(np.asarray(topo.devices[:chips]), ("data",))
    chain = standard_chain(n_det=n_det, n_angles=n_angles, n_rows=n_rows,
                           paganin=paganin, use_pallas=use_pallas)
    rows = []
    t0 = time.perf_counter()
    for p, compiled in compile_steps(chain, mesh):
        ma = compiled.memory_analysis()
        rows.append({
            "step": p.name,
            "compile_s": time.perf_counter() - t0,
            "argument": ma.argument_size_in_bytes,
            "output": ma.output_size_in_bytes,
            "temp": ma.temp_size_in_bytes,
            "peak": (ma.argument_size_in_bytes
                     + ma.output_size_in_bytes
                     + ma.temp_size_in_bytes
                     - ma.alias_size_in_bytes),
            "tpu_custom_calls": compiled.as_text().count("tpu_custom_call"),
        })
        t0 = time.perf_counter()
    return rows


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--n-det", type=int, default=2048)
    ap.add_argument("--n-angles", type=int, default=3072)
    ap.add_argument("--n-rows", type=int, default=32)
    ap.add_argument("--paganin", action="store_true")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--reference", action="store_true",
                    help="compile the use_pallas=False chain instead")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    rows = rehearse(args.n_det, args.n_angles, args.n_rows,
                    paganin=args.paganin, chips=args.chips,
                    use_pallas=not args.reference)
    print(f"chain {args.n_angles} x {args.n_rows} x {args.n_det}, "
          f"{args.chips} v5e chip(s); bytes per device")
    for r in rows:
        print(f"  {r['step']:22s} peak {r['peak'] / GiB:7.3f} GiB  "
              f"(arg {r['argument'] / GiB:.3f}, out {r['output'] / GiB:.3f},"
              f" temp {r['temp'] / GiB:.3f})  custom calls "
              f"{r['tpu_custom_calls']}  compile {r['compile_s']:.1f} s")
    print(f"max peak {max(r['peak'] for r in rows) / GiB:.3f} GiB")


if __name__ == "__main__":
    main()
