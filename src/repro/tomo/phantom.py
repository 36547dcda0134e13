"""Synthetic data: Shepp–Logan-style phantoms + a differentiable
parallel-beam forward projector (Radon transform).

These are the data-generation oracle for the whole tomography test
suite: phantom → forward project → (simulated dark/flat/noise) → the
Savu chain must reconstruct something close to the phantom.
"""
from __future__ import annotations

import functools
import math

import numpy as np

import jax
import jax.numpy as jnp

from ..obs.trace import traced
from .geometry import ParallelGeometry

# (value, a, b, x0, y0, phi_deg) — standard Shepp-Logan ellipses
# (modified/high-contrast variant so tests have healthy SNR).
_SHEPP_LOGAN = [
    (1.00, 0.69, 0.92, 0.0, 0.0, 0),
    (-0.80, 0.6624, 0.8740, 0.0, -0.0184, 0),
    (-0.20, 0.1100, 0.3100, 0.22, 0.0, -18),
    (-0.20, 0.1600, 0.4100, -0.22, 0.0, 18),
    (0.10, 0.2100, 0.2500, 0.0, 0.35, 0),
    (0.10, 0.0460, 0.0460, 0.0, 0.10, 0),
    (0.10, 0.0460, 0.0460, 0.0, -0.10, 0),
    (0.10, 0.0460, 0.0230, -0.08, -0.605, 0),
    (0.10, 0.0230, 0.0230, 0.0, -0.606, 0),
    (0.10, 0.0230, 0.0460, 0.06, -0.605, 0),
]


def shepp_logan(n: int, dtype=np.float32) -> np.ndarray:
    """n×n modified Shepp–Logan phantom in [0, ~1]."""
    coord = np.linspace(-1.0, 1.0, n)
    img = np.zeros((n, n), dtype=np.float64)
    for val, a, b, x0, y0, phi in _SHEPP_LOGAN:
        th = math.radians(phi)
        c, s = math.cos(th), math.sin(th)
        # rasterise inside the ellipse's bounding box only
        r = max(a, b)
        rows = np.flatnonzero(np.abs(coord - y0) <= r)
        cols = np.flatnonzero(np.abs(coord - x0) <= r)
        if rows.size == 0 or cols.size == 0:
            continue
        ys = coord[rows][:, None] - y0
        xs = coord[cols][None, :] - x0
        xr = xs * c + ys * s
        yr = -xs * s + ys * c
        box = img[rows[0]:rows[-1] + 1, cols[0]:cols[-1] + 1]
        box[(xr / a) ** 2 + (yr / b) ** 2 <= 1.0] += val
    return img.astype(dtype)


def row_scales(n_rows: int) -> np.ndarray:
    """Per-row intensity scale of :func:`phantom_stack`."""
    return 0.5 + 0.5 * (np.arange(n_rows) + 1) / n_rows


def phantom_stack(n: int, n_rows: int, dtype=np.float32) -> np.ndarray:
    """(n_rows, n, n) phantom volume: Shepp–Logan modulated per row, so
    adjacent slices differ (tests catch axis mix-ups)."""
    base = shepp_logan(n, np.float32)
    scales = row_scales(n_rows).astype(np.float32)
    return (base[None] * scales[:, None, None]).astype(dtype, copy=False)


@functools.partial(jax.jit, static_argnames=("n", "geom"))
def shepp_logan_sinogram(n: int, geom: ParallelGeometry) -> jnp.ndarray:
    """(n_angles, n_det) exact line integrals of the n×n phantom's
    ellipses, in pixel units — the continuous counterpart of
    ``forward_project(shepp_logan(n))`` at O(angles·n_det) cost.

    An ellipse of value ρ, semi-axes (a, b) rotated by φ and centred at
    (x0, y0) has, at angle θ and detector offset τ from its centre's
    projection, the chord 2ab·√(s² − τ²)/s² with
    s² = a²cos²(θ−φ) + b²sin²(θ−φ)."""
    h = 2.0 / (n - 1)                    # pixel pitch in phantom units
    theta = jnp.asarray(geom.angles, jnp.float32)[:, None]
    t = (jnp.arange(geom.n_det, dtype=jnp.float32)
         - (geom.n_det - 1) / 2.0)[None, :] * h
    sino = jnp.zeros((geom.n_angles, geom.n_det), jnp.float32)
    for val, a, b, x0, y0, phi in _SHEPP_LOGAN:
        rel = theta - math.radians(phi)
        s2 = (a * jnp.cos(rel)) ** 2 + (b * jnp.sin(rel)) ** 2
        tau = t - (x0 * jnp.cos(theta) + y0 * jnp.sin(theta))
        sino += val * 2 * a * b * jnp.sqrt(jnp.maximum(s2 - tau ** 2, 0)) / s2
    return sino / h


# ----------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("n_angles", "n_det"))
def _project_slice(img: jnp.ndarray, angles: jnp.ndarray, n_angles: int,
                   n_det: int) -> jnp.ndarray:
    """Radon transform of one (H, W) slice -> (n_angles, n_det) sinogram.

    Rotation-based: for each angle rotate the image by -θ with bilinear
    sampling and integrate columns.  Differentiable; matches FBP's
    adjoint conventions (t = x·cosθ + y·sinθ with pixel units)."""
    h, w = img.shape
    cy, cx = (h - 1) / 2.0, (w - 1) / 2.0
    cd = (n_det - 1) / 2.0
    # sample grid in detector coords: t along detector, s along the ray
    n_s = h  # integration samples
    t = jnp.arange(n_det, dtype=img.dtype) - cd
    s = jnp.arange(n_s, dtype=img.dtype) - (n_s - 1) / 2.0

    def one_angle(theta):
        ct, st = jnp.cos(theta), jnp.sin(theta)
        # point = t*(cos,sin) + s*(-sin,cos) in (x, y)
        xs = t[None, :] * ct - s[:, None] * st + cx
        ys = t[None, :] * st + s[:, None] * ct + cy
        x0 = jnp.floor(xs)
        y0 = jnp.floor(ys)
        fx = xs - x0
        fy = ys - y0
        x0i = jnp.clip(x0.astype(jnp.int32), 0, w - 1)
        x1i = jnp.clip(x0i + 1, 0, w - 1)
        y0i = jnp.clip(y0.astype(jnp.int32), 0, h - 1)
        y1i = jnp.clip(y0i + 1, 0, h - 1)
        inside = ((xs >= 0) & (xs <= w - 1) & (ys >= 0) & (ys <= h - 1))
        v = (img[y0i, x0i] * (1 - fx) * (1 - fy) +
             img[y0i, x1i] * fx * (1 - fy) +
             img[y1i, x0i] * (1 - fx) * fy +
             img[y1i, x1i] * fx * fy)
        return jnp.sum(jnp.where(inside, v, 0.0), axis=0)

    return jax.vmap(one_angle)(angles.astype(img.dtype))


def forward_project(volume: np.ndarray, geom: ParallelGeometry
                    ) -> np.ndarray:
    """(rows, H, W) volume -> (n_angles, rows, n_det) projection data
    in the paper's (θ, y, x) layout."""
    vol = jnp.asarray(volume)
    if vol.ndim == 2:
        vol = vol[None]
    angles = jnp.asarray(geom.angles)
    sinos = jax.vmap(lambda s: _project_slice(
        s, angles, geom.n_angles, geom.n_det))(vol)  # (rows, ang, det)
    return np.asarray(jnp.transpose(sinos, (1, 0, 2)))


@jax.jit
def _counts(proj, row_scale, dark, flat, mu):
    """Detector counts dark + (flat − dark)·exp(−μ·path), path =
    proj (θ, 1 | y, x) × row_scale (y,), clipped to the uint16 range."""
    path = proj * row_scale[None, :, None]
    counts = dark[None] + (flat[None] - dark[None]) * jnp.exp(-mu * path)
    return jnp.clip(counts, 0, 65535)


def _raw_scan(proj, row_scale, *, noise: float, seed: int, mu: float,
              i0: float = 40000.0, dark_level: float = 96.0
              ) -> dict[str, np.ndarray]:
    rng = np.random.default_rng(seed)
    shape = (row_scale.shape[0], proj.shape[2])        # (y, x)
    flat = np.full(shape, i0, dtype=np.float64)
    flat += rng.normal(0, i0 * 0.002, size=flat.shape)
    dark = np.full(shape, dark_level, dtype=np.float64)
    counts = _counts(jnp.asarray(proj, jnp.float32),
                     jnp.asarray(row_scale, jnp.float32),
                     jnp.asarray(dark, jnp.float32),
                     jnp.asarray(flat, jnp.float32), mu)
    if noise > 0:
        with traced("transfer.d2h", bytes=counts.nbytes):
            host = np.asarray(counts)
        counts = rng.poisson(host / noise) * noise
        data = np.clip(counts, 0, 65535).astype(np.uint16)
    else:
        counts = counts.astype(jnp.uint16)
        with traced("transfer.d2h", bytes=counts.nbytes):
            data = np.asarray(counts)
    return {
        "data": data,
        "dark": np.clip(dark, 0, 65535).astype(np.uint16),
        "flat": np.clip(flat, 0, 65535).astype(np.uint16),
        "mu": mu,
    }


def simulate_raw_scan(volume: np.ndarray, geom: ParallelGeometry, *,
                      i0: float = 40000.0, dark_level: float = 96.0,
                      noise: float = 0.0, seed: int = 0,
                      mu: float = 0.02) -> dict[str, np.ndarray]:
    """Make a realistic uint16 raw scan from a phantom volume:
    transmission I = dark + (I0-dark)·exp(-μ·path) with optional Poisson
    noise; plus dark/flat fields — i.e. what a loader plugin would see."""
    proj = forward_project(volume, geom)           # path lengths (θ, y, x)
    scan = _raw_scan(proj, np.ones(proj.shape[1]), i0=i0,
                     dark_level=dark_level, noise=noise, seed=seed, mu=mu)
    scan["truth"] = np.asarray(volume, dtype=np.float32)
    return scan


def simulate_phantom_scan(geom: ParallelGeometry, *, noise: float = 0.0,
                          seed: int = 0, mu: float = 0.02
                          ) -> dict[str, np.ndarray]:
    """:func:`simulate_raw_scan` of :func:`phantom_truth`, without the
    truth volume, built from the closed-form sinogram of one slice (rows
    are scaled copies), so a beamline-size scan costs
    O(angles·rows·n_det) on the device instead of a projector pass per
    row.  A caller that compares against the phantom builds it with
    :func:`phantom_truth`."""
    proj = shepp_logan_sinogram(geom.n_det, geom)[:, None, :]  # on device
    return _raw_scan(proj, row_scales(geom.n_rows), noise=noise, seed=seed,
                     mu=mu)


def phantom_truth(geom: ParallelGeometry) -> np.ndarray:
    """The (n_rows, n_det, n_det) volume :func:`simulate_phantom_scan`
    projects, rasterised on the host on demand."""
    with traced("loader.truth"):
        return phantom_stack(geom.n_det, geom.n_rows)
