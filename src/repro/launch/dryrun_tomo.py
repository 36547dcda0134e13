import os
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=512")

"""Dry-run of the paper's OWN workload at production scale: a
full-field scan of (3000 angles × 2048 rows × 2048 det) — the paper's
"typical single scan ≈ 96 GB" scaled to power-of-two dims (25 GB u16
raw, 50 GB fp32 working set) — through the fused
correction → ring-removal → sinogram-filter chain, compiled on the
256-chip production mesh with pattern-driven shardings.

This is the chain Savu runs through parallel HDF5; here the pattern
transition PROJECTION → SINOGRAM lowers to an in-HBM all-to-all and the
whole chain is ONE XLA program (plugin fusion, beyond-paper).

    PYTHONPATH=src python -m repro.launch.dryrun_tomo
"""
import json

import numpy as np

import jax
import jax.numpy as jnp

from ..core.dataset import DataSet
from ..core.patterns import PROJECTION, SINOGRAM
from ..core.plugin import PluginData
from ..core.transport import ShardedTransport
from ..roofline.analysis import analyse
from ..tomo.geometry import ParallelGeometry
from ..tomo.plugins import DarkFlatCorrection, RingRemoval, SinogramFilter
from .mesh import make_production_mesh

N_ANGLES, N_ROWS, N_DET = 3072, 2048, 2048   # paper's ~3k angles,
#   rounded to divide the 16-way data axis


def _dataset(name: str) -> DataSet:
    ds = DataSet(name, (N_ANGLES, N_ROWS, N_DET), np.float32,
                 ("rotation_angle", "detector_y", "detector_x"))
    ds.add_pattern(PROJECTION, core=("detector_y", "detector_x"),
                   slice_=("rotation_angle",))
    ds.add_pattern(SINOGRAM, core=("rotation_angle", "detector_x"),
                   slice_=("detector_y",))
    return ds


def lower_chain(mesh, use_pallas: bool = False) -> dict:
    tr = ShardedTransport(mesh)
    geom = ParallelGeometry(N_ANGLES, N_DET, N_ROWS)
    dark = np.full((N_ROWS, N_DET), 96.0, np.float32)
    flat = np.full((N_ROWS, N_DET), 40000.0, np.float32)

    raw = _dataset("tomo")
    raw.metadata.update({"dark": dark, "flat": flat, "mu": 0.02,
                         "geometry": geom})

    plugins = [
        DarkFlatCorrection(in_datasets=["tomo"], out_datasets=["tomo"],
                           use_pallas=use_pallas),
        RingRemoval(in_datasets=["tomo"], out_datasets=["tomo"]),
        SinogramFilter(in_datasets=["tomo"], out_datasets=["tomo"],
                       use_pallas=use_pallas),
    ]
    cur = raw
    for p in plugins:
        p.in_data = [PluginData(cur)]
        p.out_data = []
        (out,) = p.setup([cur])
        out.name = p.out_dataset_names[0]
        p.out_data = [PluginData(out)]
        p.out_data[0].pattern_name = (p.out_pattern_name
                                      or p.in_data[0].pattern_name)
        p.out_data[0].n_frames = p.in_data[0].n_frames
        if p.out_data[0].pattern_name not in out.patterns:
            out.patterns.update(cur.patterns)
        cur = out

    # XLA's SPMD partitioner REPLICATES fft ops regardless of batch-dim
    # sharding (measured: 198 GiB/dev for a 52 GB dataset).  These
    # plugins' frame math is shard-local (the transform axes are core
    # dims, never sharded), so each runs under shard_map — manual SPMD,
    # per-shard local compute, zero replication; the pattern transition
    # between plugins stays a with_sharding_constraint (all-to-all).
    
    def local_fn(p_):
        pat_in = p_.in_data[0].pattern
        pat_out = p_.out_data[0].pattern

        def f(a):
            frames = pat_in.to_frames(a)
            nf = frames.shape[0]
            res = jax.vmap(
                lambda fr: p_.process_frames([fr[None]]))(frames)
            res = res.reshape((nf,) + res.shape[2:])
            return pat_out.from_frames(res, a.shape).astype(jnp.float32)
        return f

    wrapped, mid_sh = [], []
    for p_ in plugins:
        in_sh_p = tr._sharding(p_.in_data[0].pattern, "data")
        out_sh_p = tr._sharding(p_.out_data[0].pattern, "data")
        mid_sh.append(out_sh_p)
        wrapped.append(jax.shard_map(local_fn(p_), mesh=mesh,
                                     in_specs=(in_sh_p.spec,),
                                     out_specs=in_sh_p.spec,
                                     check_vma=False))

    def chain(x):
        cur = x
        for w, sh in zip(wrapped, mid_sh):
            cur = w(cur)
            cur = jax.lax.with_sharding_constraint(cur, sh)
        return cur

    in_sh = tr._sharding(raw.get_pattern(PROJECTION), "data")
    out_sh = tr._sharding(cur.get_pattern(SINOGRAM), "data")
    spec = jax.ShapeDtypeStruct(raw.shape, jnp.float32, sharding=in_sh)
    with mesh:
        compiled = jax.jit(chain, in_shardings=(in_sh,),
                           out_shardings=out_sh).lower(spec).compile()
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo = compiled.as_text()
    roof = analyse(cost, hlo, n_devices=mesh.size)
    return {
        "tag": f"tomo-fullfield-chain__{N_ANGLES}x{N_ROWS}x{N_DET}",
        "mesh": list(mesh.devices.shape),
        "dataset_gb": N_ANGLES * N_ROWS * N_DET * 4 / 1e9,
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "peak_estimate": mem.argument_size_in_bytes +
            mem.output_size_in_bytes + mem.temp_size_in_bytes -
            mem.alias_size_in_bytes,
        },
        "roofline": roof.to_json(),
    }


def main() -> None:
    mesh = make_production_mesh()
    rec = lower_chain(mesh)
    os.makedirs("experiments/dryrun", exist_ok=True)
    with open("experiments/dryrun/tomo_chain_pod.json", "w") as fh:
        json.dump(rec, fh, indent=1)
    ro = rec["roofline"]
    print(f"{rec['tag']}: {rec['dataset_gb']:.0f} GB fp32 working set, "
          f"peak/dev={rec['memory']['peak_estimate'] / 2**30:.2f} GiB")
    print(f"  compute={ro['compute_s'] * 1e3:.1f}ms "
          f"memory={ro['memory_s'] * 1e3:.1f}ms "
          f"collective={ro['collective_s'] * 1e3:.1f}ms "
          f"-> {ro['bottleneck']}")
    print("  (the PROJECTION->SINOGRAM pattern transition is the "
          "collective term: Savu paid it as a parallel-HDF5 round trip)")


if __name__ == "__main__":
    main()
