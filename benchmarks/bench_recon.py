"""FBP hot-spot benchmark: backprojection kernel (interpret mode) vs
pure-jnp reference, plus the fused correction kernel, with derived
throughput.  CPU only: the kernel runs interpreted, so its wall time
checks the path, not the speed of the compiled TPU kernel."""
from __future__ import annotations

import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.kernels.backproject.ops import backproject
from repro.kernels.backproject.ref import backproject_ref
from repro.kernels.correction.ops import correct


def _time(fn, *args, reps=3):
    fn(*args).block_until_ready()         # compile + warm
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.tree.map(lambda x: x.block_until_ready(), out)
    return (time.perf_counter() - t0) / reps


def run(report):
    A, D, N = 64, 128, 128
    rng = np.random.default_rng(0)
    sino = jnp.asarray(rng.normal(size=(A, D)).astype(np.float32))
    angles = jnp.linspace(0, np.pi, A, endpoint=False)

    t_ref = _time(lambda s: backproject_ref(s, angles, N), sino)
    flops = 2.0 * A * N * N * D            # hat-matmul formulation
    report("fbp_ref_jnp", t_ref * 1e6,
           f"{flops / t_ref / 1e9:.1f} GFLOP/s-equiv (gather form)")

    t_pal = _time(lambda s: backproject(s, angles, N, use_pallas=True,
                                        interpret=True), sino)
    report("fbp_pallas_interpret", t_pal * 1e6,
           "interpret-mode correctness path")

    raw = jnp.asarray(rng.integers(100, 40000, size=(16, 64, 512))
                      .astype(np.uint16))
    dark = jnp.asarray(np.full((64, 512), 96, np.uint16))
    flat = jnp.asarray(np.full((64, 512), 40000, np.uint16))
    t_corr = _time(lambda r: correct(r, dark, flat, use_pallas=False), raw)
    px = raw.size
    report("correction_fused", t_corr * 1e6,
           f"{px / t_corr / 1e6:.0f} Mpixel/s (xla ref)")
