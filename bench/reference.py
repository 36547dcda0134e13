"""Plain float32 reference of the served tomography chain.

Independent of the code under test: it imports nothing from ``repro``
and takes nothing the program made.  From a configuration's wire spec
(``configs/<config>.json``) and a request's seed it regenerates the raw
scan with its own copy of the phantom generator, then runs each stage
as the straightforward formula, in float32 at the highest matmul
precision:

    correction  -log clip((raw - dark) / max(flat - dark, eps), eps, 10)
    paganin     -log clip(ifft2(fft2(exp(-p)) / (1 + tau (kx^2 + ky^2))), 1e-6)
    ring        s - strength (mean_angles(s) - boxcar_k(edge_pad(mean)))
    filter      irfft(rfft(s, n_fft) * |f| window(f) [f <= cutoff])[:n_det]
    fbp         (pi / A) sum_theta lerp(zero_pad(s_theta), t) / mu,
                t = (x - c) cos(theta) + (y - c) sin(theta) + (n_det - 1) / 2

The backprojection is computed for chosen image rows only (the sample
the benchmark checks), for many slices at once: every slice shares one
gather index per pixel and angle, so a batch of slices costs about what
one does.

``round_to`` names stage outputs rounded to bfloat16 between stages;
``{"fbp_input"}`` is the control: the backprojection reading a bf16
sinogram, the step a matmul form of the kernel would tempt.
"""
from __future__ import annotations

import functools
import math

import numpy as np

import jax
import jax.numpy as jnp

#: the Shepp-Logan ellipses of the synthetic scan:
#: (value, a, b, x0, y0, phi in degrees), modified high-contrast variant
SHEPP_LOGAN = (
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
)

EPS = 1e-6          # correction: denominator and transmission floor
TRANS_MAX = 10.0    # correction: transmission ceiling
PAGANIN_FLOOR = 1e-6


# -- the scan ------------------------------------------------------------
def angles(n_angles: int) -> np.ndarray:
    """[0, pi) in ``n_angles`` equal steps, float64."""
    return np.linspace(0.0, math.pi, n_angles, endpoint=False)


def _sinogram(n: int, n_angles: int):
    """(n_angles, n) closed-form line integrals of the n x n phantom,
    in pixel units (chord 2ab sqrt(s^2 - tau^2) / s^2 per ellipse)."""
    h = 2.0 / (n - 1)
    theta = jnp.asarray(angles(n_angles), jnp.float32)[:, None]
    t = (jnp.arange(n, dtype=jnp.float32) - (n - 1) / 2.0)[None, :] * h
    sino = jnp.zeros((n_angles, n), jnp.float32)
    for val, a, b, x0, y0, phi in SHEPP_LOGAN:
        rel = theta - math.radians(phi)
        s2 = (a * jnp.cos(rel)) ** 2 + (b * jnp.sin(rel)) ** 2
        tau = t - (x0 * jnp.cos(theta) + y0 * jnp.sin(theta))
        sino += val * 2 * a * b * jnp.sqrt(jnp.maximum(s2 - tau ** 2, 0)) / s2
    return sino / h


_sinogram_jit = jax.jit(_sinogram, static_argnums=(0, 1))


@jax.jit
def _counts(proj, row_scale, dark, flat, mu):
    path = proj * row_scale[None, :, None]
    counts = dark[None] + (flat[None] - dark[None]) * jnp.exp(-mu * path)
    return jnp.clip(counts, 0, 65535)


def raw_scan(n_angles: int, n_rows: int, n_det: int, seed: int,
             phantom: dict) -> dict:
    """The uint16 raw scan (angles, rows, n_det) on the device, with its
    dark and flat fields: detector counts dark + (flat - dark)
    exp(-mu path), path the phantom's line integral scaled per row by
    0.5 + 0.5 (row + 1) / rows, and a flat field with 0.2% Gaussian
    noise from ``seed``."""
    if phantom.get("noise", 0.0):
        raise ValueError("the reference regenerates noise-free scans only")
    i0, dark_level, mu = phantom["i0"], phantom["dark_level"], phantom["mu"]
    rng = np.random.default_rng(seed)
    flat = np.full((n_rows, n_det), i0, dtype=np.float64)
    flat += rng.normal(0, i0 * 0.002, size=flat.shape)
    dark = np.full((n_rows, n_det), dark_level, dtype=np.float64)
    scale = 0.5 + 0.5 * (np.arange(n_rows) + 1) / n_rows
    proj = _sinogram_jit(n_det, n_angles)[:, None, :]
    counts = _counts(proj, jnp.asarray(scale, jnp.float32),
                     jnp.asarray(dark, jnp.float32),
                     jnp.asarray(flat, jnp.float32), mu)
    return {"data": counts.astype(jnp.uint16),
            "dark": np.clip(dark, 0, 65535).astype(np.uint16),
            "flat": np.clip(flat, 0, 65535).astype(np.uint16),
            "mu": mu}


# -- the stages ------------------------------------------------------------
@jax.jit
def correction(raw, dark, flat):
    raw = raw.astype(jnp.float32)
    dark = dark.astype(jnp.float32)[None]
    flat = flat.astype(jnp.float32)[None]
    trans = jnp.clip((raw - dark) / jnp.maximum(flat - dark, EPS),
                     EPS, TRANS_MAX)
    return -jnp.log(trans)


def paganin(proj, tau: float):
    """proj: (angles, rows, n_det) -log transmission."""
    _, ny, nx = proj.shape
    ky = np.fft.fftfreq(ny)[:, None]
    kx = np.fft.fftfreq(nx)[None, :]
    denom = jnp.asarray(1.0 / (1.0 + tau * (kx ** 2 + ky ** 2)), jnp.float32)
    return _paganin(proj, denom)


@jax.jit
def _paganin(proj, denom):
    spec = jnp.fft.fft2(jnp.exp(-proj).astype(jnp.complex64), axes=(1, 2))
    filt = jnp.real(jnp.fft.ifft2(spec * denom, axes=(1, 2)))
    return -jnp.log(jnp.maximum(filt, PAGANIN_FLOOR))


def ring_removal(proj, kernel: int, strength: float):
    """Per sinogram: subtract the column mean's deviation from its
    k-point moving average (edge-padded)."""
    return _ring(proj, kernel, jnp.float32(strength))


@functools.partial(jax.jit, static_argnums=1)
def _ring(proj, k, strength):
    mean = jnp.mean(proj, axis=0, keepdims=True)         # (1, rows, det)
    pad = k // 2
    padded = jnp.pad(mean, ((0, 0), (0, 0), (pad, pad)), mode="edge")
    n = mean.shape[-1]
    smooth = sum(padded[..., i:i + n] for i in range(k)) / k
    return proj - strength * (mean - smooth)


def ramp_filter(n_det: int, kind: str, cutoff: float) -> np.ndarray:
    """rfft-bin response |f| x window(f), zero above ``cutoff`` of
    Nyquist, for an FFT of the next power of two >= 2 n_det."""
    n_fft = 1 << (2 * n_det - 1).bit_length()
    f = np.fft.rfftfreq(n_fft)
    window = {"ramlak": np.ones_like(f), "shepp": np.sinc(f),
              "cosine": np.cos(np.pi * f),
              "hann": 0.5 * (1 + np.cos(2 * np.pi * f))}[kind]
    resp = (f * window).astype(np.float32)
    keep = np.linspace(0.0, 1.0, resp.shape[0], dtype=np.float32) <= cutoff
    return (resp * keep).astype(np.float32)


@jax.jit
def sinogram_filter(proj, resp):
    n_det = proj.shape[-1]
    n_fft = 2 * (resp.shape[0] - 1)
    spec = jnp.fft.rfft(proj, n=n_fft, axis=-1) * resp
    return jnp.fft.irfft(spec, n=n_fft, axis=-1)[..., :n_det]


@functools.partial(jax.jit, static_argnums=(3,))
def backproject_rows(sino, theta, rows, out_size: int):
    """sino: (S, angles, n_det) -> (S, len(rows), out_size): the
    linear-interpolation backprojection at the image rows ``rows``,
    times pi / angles.  The detector row is zero-padded by one bin each
    side, so rays within one bin outside taper to 0."""
    _, n_angles, n_det = sino.shape
    c = (out_size - 1) / 2.0
    centre = (n_det - 1) / 2.0
    xs = jnp.arange(out_size, dtype=jnp.float32) - c
    ys = rows.astype(jnp.float32) - c
    # (angles, n_det + 2, S): one gather fetches every slice's value
    padded = jnp.pad(jnp.transpose(sino, (1, 2, 0)),
                     ((0, 0), (1, 1), (0, 0)))

    def one_angle(acc, item):
        row, th = item
        t = xs[None, :] * jnp.cos(th) + ys[:, None] * jnp.sin(th) + centre
        tp = t + 1.0
        inside = (tp > 0.0) & (tp < n_det + 1.0)
        tp = jnp.clip(tp, 0.0, n_det + 1.0)
        j = jnp.floor(tp)
        f = (tp - j)[..., None]
        i0 = jnp.clip(j.astype(jnp.int32), 0, n_det)
        i1 = jnp.minimum(i0 + 1, n_det + 1)
        val = row[i0] * (1 - f) + row[i1] * f
        return acc + jnp.where(inside[..., None], val, 0.0), None

    acc = jnp.zeros((rows.shape[0], out_size, sino.shape[0]), jnp.float32)
    acc, _ = jax.lax.scan(one_angle, acc,
                          (padded, jnp.asarray(theta, jnp.float32)))
    return jnp.transpose(acc, (2, 0, 1)) * (jnp.pi / n_angles)


def _bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


# -- the chain -------------------------------------------------------------
def chain_params(spec: dict) -> dict:
    """Wire name -> params of each plugin in a configuration's spec."""
    return {e["plugin"]: e.get("params", {}) for e in spec["plugins"]}


def filtered_sinograms(spec: dict, phantom: dict, seed: int,
                       round_to: frozenset = frozenset()):
    """The chain from the raw scan up to the backprojection's input:
    (rows, angles, n_det) float32 on the device, and mu."""
    plugins = chain_params(spec)
    known = {"synthetic_tomo_loader", "dark_flat_correction",
             "paganin_filter", "ring_removal", "sinogram_filter",
             "fbp_recon", "hdf5_saver"}
    unknown = set(plugins) - known
    if unknown:
        raise ValueError(f"the reference has no stage for {sorted(unknown)}")
    lp = plugins["synthetic_tomo_loader"]
    scan = raw_scan(lp["n_angles"], lp["n_rows"], lp["n_det"], seed, phantom)
    rnd = lambda name, x: _bf16(x) if name in round_to else x
    with jax.default_matmul_precision("highest"):
        p = rnd("dark_flat_correction",
                correction(scan["data"], scan["dark"], scan["flat"]))
        if "paganin_filter" in plugins:
            p = rnd("paganin_filter",
                    paganin(p, plugins["paganin_filter"]["tau"]))
        if "ring_removal" in plugins:
            rp = plugins["ring_removal"]
            p = rnd("ring_removal",
                    ring_removal(p, rp["kernel"], rp["strength"]))
        fp = plugins["sinogram_filter"]
        resp = jnp.asarray(ramp_filter(lp["n_det"], fp["kind"],
                                       fp["cutoff"]))
        p = sinogram_filter(p, resp)
        p = rnd("sinogram_filter", p)
        p = rnd("fbp_input", p)
    return jnp.transpose(p, (1, 0, 2)), scan["mu"]


def volume_rows(spec: dict, sinos, mu: float, rows: np.ndarray):
    """Backproject (S, angles, n_det) filtered sinograms at image rows
    ``rows``: (S, len(rows), out_size) float32 on the host."""
    plugins = chain_params(spec)
    lp = plugins["synthetic_tomo_loader"]
    out_size = plugins["fbp_recon"].get("out_size") or lp["n_det"]
    with jax.default_matmul_precision("highest"):
        img = backproject_rows(sinos, angles(lp["n_angles"]),
                               jnp.asarray(rows, jnp.int32), out_size)
    return np.asarray(img / mu)


def compare(got: np.ndarray, want: np.ndarray) -> dict:
    """The two numbers compared: the widest gap over the largest
    reference value, and the gap's root mean square over the
    reference's."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    diff = got - want
    return {"max_err": float(np.max(np.abs(diff)) / np.max(np.abs(want))),
            "rms_err": float(np.sqrt(np.mean(diff ** 2))
                             / np.sqrt(np.mean(want ** 2)))}
