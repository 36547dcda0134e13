"""Pure-jnp oracle for parallel-beam filtered backprojection."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def backproject_ref(sino: jnp.ndarray, angles: jnp.ndarray, out_size: int,
                    centre: float | None = None) -> jnp.ndarray:
    """(n_angles, n_det) filtered sinogram -> (out_size, out_size) image.

    out(y, x) = (π / n_angles) · Σ_θ lerp(sino_zeropad[θ], t),
    t = (x - cx)·cosθ + (y - cy)·sinθ + centre.

    Boundary convention: the detector row is zero-padded, so rays whose
    t falls in (-1, 0) or (n_det-1, n_det) taper linearly to zero and
    rays further outside contribute exactly 0 — identical to the
    hat-function semantics of the Pallas kernel.

    The sum runs angle by angle, so memory stays O(out²) per slice.
    Under a vmap over slices (the sharded transport maps every plugin
    over its frames) all slices are summed together: each gather then
    fetches one value per slice, which a TPU does ~100× faster than
    one scalar per call.
    """
    @jax.custom_batching.custom_vmap
    def one(s, a):
        return _backproject_slices(s[None], a, out_size, centre)[0]

    @one.def_vmap
    def _over_slices(axis_size, in_batched, s, a):
        if in_batched[1]:               # per-slice angles: one at a time
            return jax.lax.map(
                lambda sa: _backproject_slices(sa[0][None], sa[1],
                                               out_size, centre)[0],
                (s, a)), True
        if not in_batched[0]:
            s = jnp.broadcast_to(s, (axis_size,) + s.shape)
        return _backproject_slices(s, a, out_size, centre), True

    return one(sino, angles)


def _backproject_slices(sino: jnp.ndarray, angles: jnp.ndarray,
                        out_size: int, centre: float | None
                        ) -> jnp.ndarray:
    """(S, n_angles, n_det) -> (S, out_size, out_size)."""
    n_slices, n_angles, n_det = sino.shape
    if centre is None:
        centre = (n_det - 1) / 2.0
    c = (out_size - 1) / 2.0
    xs = jnp.arange(out_size, dtype=sino.dtype) - c
    ys = jnp.arange(out_size, dtype=sino.dtype) - c
    rows = jnp.pad(jnp.swapaxes(sino, 0, 1), ((0, 0), (0, 0), (1, 1)))

    def one_angle(acc, blk):
        row_p, theta = blk                         # (S, n_det + 2), ()
        ct, st = jnp.cos(theta), jnp.sin(theta)
        t = xs[None, :] * ct + ys[:, None] * st + centre
        tp = t + 1.0                               # into padded coords
        inside = (tp > 0.0) & (tp < n_det + 1.0)
        tp = jnp.clip(tp, 0.0, n_det + 1.0)
        t0 = jnp.floor(tp)
        frac = tp - t0
        i0 = jnp.clip(t0.astype(jnp.int32), 0, n_det)
        i1 = jnp.clip(i0 + 1, 0, n_det + 1)
        val = row_p[:, i0] * (1 - frac) + row_p[:, i1] * frac
        return acc + jnp.where(inside, val, 0.0), None

    acc, _ = jax.lax.scan(
        one_angle, jnp.zeros((n_slices, out_size, out_size), sino.dtype),
        (rows, angles.astype(sino.dtype)))
    return acc * (jnp.pi / n_angles)
