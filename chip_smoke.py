#!/usr/bin/env python3
"""Bring-up check of the served tomography path on a TPU.

Serves ``PipelineService`` (scheduler mode, ``ShardedTransport`` on a
one-device mesh) on localhost, submits two scans of the standard chain
with Paganin phase retrieval at beamline width (3072 angles × R rows ×
2048 detector columns, raw uint16; ROADMAP deployment D1) through
``PipelineClient``, fetches each volume over ``GET /jobs/{id}/result``
and compares it with the same chain run on the pure-jnp reference
kernels (``use_pallas=False``).  It prints each job's wall time and
error, the compile-cache counts (the second scan must compile nothing)
and the number of Mosaic kernels (``tpu_custom_call``) in each kernel
step's compiled program.  The last line is one JSON object::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Run from the root of the repository::

    python chip_smoke.py              # one chip: two served scans
    python chip_smoke.py --chips 4    # only the chain on a 4-chip data mesh

It exits nonzero, with no result line, when JAX finds no TPU or any
phase fails.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

N_ANGLES = 3072            # ROADMAP D1: the paper's ~3k angles
N_DET = 2048               # ROADMAP D1: 2k detector columns
#: detector rows.  D1 has 2048, but ShardedTransport holds each dataset
#: whole on the device (ROADMAP R1), so the scan is cut to 32 rows: the
#: largest compiled step of the chain then peaks at ~7 GiB of the chip's
#: 16 GB (repro.launch.rehearse_chain).
N_ROWS = 32
#: max |volume − reference| allowed, as a fraction of max |reference|.
#: On v5e the kernel chain has read up to 4.6e-6 on one chip and 3.5e-5
#: on four; PERF.md records which bf16 faults this limit catches.
TOLERANCE = 2e-4
#: plugin steps that must run a Mosaic kernel on a TPU
KERNEL_STEPS = ("dark_flat_correction", "sinogram_filter", "fbp_recon")


def _step_kernels(cache) -> dict[str, int]:
    """``tpu_custom_call`` count of each compiled plugin step in the
    service's compile cache, keyed by plugin name."""
    from repro.tomo import plugins as tomo_plugins
    by_class = {f"{c.__module__}.{c.__qualname__}": c.name
                for c in vars(tomo_plugins).values()
                if isinstance(c, type) and hasattr(c, "name")}
    counts: dict[str, int] = {}
    for key, program in cache.items():
        if key[0] != "plugin":
            continue
        name = by_class.get(key[1][0], key[1][0])
        counts[name] = program.as_text().count("tpu_custom_call")
    return counts


def serve_and_check(*, n_devices: int, n_rows: int, seeds: tuple[int, ...],
                    platform: str = "tpu", n_det: int = N_DET,
                    n_angles: int = N_ANGLES, tolerance: float = TOLERANCE,
                    log=print) -> dict:
    """Serve ``len(seeds)`` scans on an ``n_devices`` mesh, check each
    against the reference chain on one device; raise on any failure.

    ``platform`` is what ``jax.devices()`` must report.  On a TPU every
    kernel step must hold at least one Mosaic kernel.  Returns per-seed
    ``{"wall_s", "new_compiles", "max_abs_diff", "max_abs_ref"}`` plus
    ``"kernels"`` (custom calls per step)."""
    import jax
    from jax.sharding import Mesh

    from repro.core import PluginRunner, ShardedTransport
    from repro.service import CompileCache, PipelineClient, PipelineService
    from repro.tomo import standard_chain

    devices = jax.devices()
    if devices[0].platform != platform:
        raise RuntimeError(f"expected {platform} devices, JAX found "
                           f"{devices[0].platform}")
    if len(devices) < n_devices:
        raise RuntimeError(f"need {n_devices} devices, JAX found "
                           f"{len(devices)}")
    mesh = Mesh(np.asarray(devices[:n_devices]), ("data",))
    log(f"device: {devices[0].device_kind} x {len(devices)} "
        f"({platform}); mesh of {n_devices}")
    log(f"scan: {n_angles} angles x {n_rows} rows x {n_det} columns, "
        f"uint16; chain: standard_chain(paganin=True)")

    def chain(seed: int, use_pallas: bool):
        return standard_chain(n_det=n_det, n_angles=n_angles,
                              n_rows=n_rows, paganin=True, seed=seed,
                              use_pallas=use_pallas)

    cache = CompileCache()
    service = PipelineService(
        transport_factory=lambda job: ShardedTransport(
            mesh, compile_cache=cache),
        n_workers=1, compile_cache=cache)
    host, port = service.serve(host="127.0.0.1", port=0)
    report: dict = {}
    try:
        client = PipelineClient(f"http://{host}:{port}")
        volumes = {}
        for i, seed in enumerate(seeds):
            misses = cache.stats()["misses"]
            t0 = time.perf_counter()
            jid = client.submit(chain(seed, True), job_id=f"scan-{seed}")
            snap = client.wait(jid, timeout=1200)
            if snap["state"] != "done":
                raise RuntimeError(f"job {jid} {snap['state']}: "
                                   f"{snap.get('error')}")
            volumes[seed] = client.result(jid)
            wall = time.perf_counter() - t0
            new = cache.stats()["misses"] - misses
            report[seed] = {"wall_s": wall, "new_compiles": new}
            log(f"job {jid}: submit -> result {wall:.3f} s "
                f"(server wall {snap['wall']:.3f} s), {new} new compiles, "
                f"volume {volumes[seed].shape} {volumes[seed].dtype}")
            if i > 0 and new:
                raise RuntimeError(f"job {jid} compiled {new} programs; "
                                   f"a repeat scan must compile none")
        log(f"compile cache: {cache.stats()}")
        kernels = _step_kernels(cache)
        log(f"tpu_custom_call per step: {kernels}")
        report["kernels"] = kernels
        if platform == "tpu":
            missing = [s for s in KERNEL_STEPS if kernels.get(s, 0) < 1]
            if missing:
                raise RuntimeError(f"no Mosaic kernel in steps {missing}")
    finally:
        service.stop()
    # the reference steps need the chip's memory: drop the service's
    # jobs (and the device buffers they hold) first
    del service
    gc.collect()

    ref_mesh = Mesh(np.asarray(devices[:1]), ("data",))
    for seed in seeds:
        t0 = time.perf_counter()
        ref = PluginRunner(chain(seed, False),
                           ShardedTransport(ref_mesh)).run()
        want = np.asarray(ref["recon"].materialise())
        del ref
        got = volumes.pop(seed)
        if got.shape != want.shape or not np.all(np.isfinite(got)):
            raise RuntimeError(f"scan {seed}: volume {got.shape} (finite: "
                               f"{bool(np.all(np.isfinite(got)))}) vs "
                               f"reference {want.shape}")
        diff = float(np.max(np.abs(got - want)))
        scale = float(np.max(np.abs(want)))
        report[seed].update(max_abs_diff=diff, max_abs_ref=scale)
        log(f"scan {seed}: max |vol - ref| = {diff:.6g}, max |ref| = "
            f"{scale:.6g}, ratio {diff / scale:.3g} (limit {tolerance}); "
            f"reference chain {time.perf_counter() - t0:.3f} s")
        if not diff <= tolerance * scale:
            raise RuntimeError(f"scan {seed}: max |vol - ref| {diff} > "
                               f"{tolerance} x max |ref| {scale}")
    return report


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the chain on a 4-chip data mesh, "
                         "checked against the one-chip reference")
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(HERE, "src"))
    try:
        from repro.service.compile_cache import setup_compilation_cache
    except ImportError as e:
        print(f"chip_smoke: the repository's package is not next to this "
              f"script ({e})", file=sys.stderr)
        return 2
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU; JAX found {devices[0].platform} "
              f"devices", file=sys.stderr)
        return 1
    setup_compilation_cache()
    if args.chips == 4:
        # rows stay at N_ROWS: the one-chip reference's backprojection
        # peaks at 11.3 GiB there, so 4x the rows would not fit one chip
        serve_and_check(n_devices=4, n_rows=N_ROWS, seeds=(1,))
    else:
        serve_and_check(n_devices=1, n_rows=N_ROWS, seeds=(1, 2))
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
