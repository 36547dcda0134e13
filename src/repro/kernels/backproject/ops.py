"""jit'd public wrapper for the backprojection kernel.

Chooses the kernel's blocks within a stated VMEM budget, maps over
leading slice dims, and runs the kernel compiled on a TPU or interpreted
on a CPU (:func:`repro.kernels.pallas_interpret`).  ``use_pallas=False``
selects the pure-jnp reference instead; nothing falls back to it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .. import pallas_interpret
from .kernel import LANES, SUBLANES, backproject_pallas, detector_padding
from .ref import backproject_ref

#: scoped-VMEM budget of one grid step: the double-buffered (ba, L) value
#: and difference row blocks plus the double-buffered output block.  Half
#: of v5e's 16 MiB default scoped VMEM, leaving room for Mosaic's own
#: scratch.
VMEM_BUDGET_BYTES = 8 * 2**20
_MAX_BH = 128        # output block rows
_MAX_BW = 256        # output block columns
_MAX_BA = 64         # angles per grid step


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _pick_blocks(out_size: int, n_angles: int, n_det: int,
                 centre: float | None = None) -> tuple[int, int, int]:
    """(bh, bw, ba) for :func:`backproject_pallas`.

    bh is a multiple of 8 and bw of 128 (the image is padded up to them),
    ba a multiple of 8; ba is halved until the step fits
    ``VMEM_BUDGET_BYTES``.  Raises ValueError when even ba = 8 does not
    fit (a detector row too long for VMEM)."""
    if centre is None:
        centre = (n_det - 1) / 2.0
    bh = min(_MAX_BH, _round_up(out_size, SUBLANES))
    bw = min(_MAX_BW, _round_up(out_size, LANES))
    _, length = detector_padding(out_size, n_det, centre, bh, bw)

    def step_bytes(ba: int) -> int:
        return 4 * (2 * 2 * ba * length + 2 * bh * bw)

    ba = min(_MAX_BA, _round_up(n_angles, SUBLANES))
    while ba > SUBLANES and step_bytes(ba) > VMEM_BUDGET_BYTES:
        ba = _round_up(ba // 2, SUBLANES)
    if step_bytes(ba) > VMEM_BUDGET_BYTES:
        raise ValueError(
            f"backproject: n_det={n_det}, out_size={out_size} needs "
            f"{step_bytes(ba)} B of VMEM per step at the smallest angle "
            f"block ({ba}); the budget is {VMEM_BUDGET_BYTES} B")
    return bh, bw, ba


@functools.partial(jax.jit, static_argnames=("out_size", "centre",
                                             "use_pallas", "interpret"))
def backproject(sino: jnp.ndarray, angles: jnp.ndarray, out_size: int,
                centre: float | None = None, *, use_pallas: bool = True,
                interpret: bool | None = None) -> jnp.ndarray:
    """Filtered-backproject sinogram(s) -> image(s).

    sino: (..., n_angles, n_det); returns (..., out_size, out_size).
    The kernel runs compiled on a TPU and interpreted on a CPU
    (:func:`repro.kernels.pallas_interpret`).
    """
    sino = sino.astype(jnp.float32)
    lead = sino.shape[:-2]
    n_angles, n_det = sino.shape[-2:]
    flat = sino.reshape((-1, n_angles, n_det))

    if use_pallas:
        bh, bw, ba = _pick_blocks(out_size, n_angles, n_det, centre)
        cos_t = jnp.cos(angles).astype(jnp.float32).reshape(-1, 1)
        sin_t = jnp.sin(angles).astype(jnp.float32).reshape(-1, 1)
        mode = pallas_interpret(interpret)
        fn = lambda s: backproject_pallas(
            s, cos_t, sin_t, out_size=out_size, centre=centre,
            bh=bh, bw=bw, ba=ba, interpret=mode)
    else:
        fn = lambda s: backproject_ref(s, angles, out_size, centre)
    out = jax.lax.map(fn, flat)
    return out.reshape(lead + (out_size, out_size))
