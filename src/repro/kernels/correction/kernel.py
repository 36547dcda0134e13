"""Pallas TPU kernel: fused dark/flat correction + −log linearisation.

One VMEM round-trip instead of four elementwise HLOs (sub, sub, div,
log) — the raw uint16 projections are upcast in-register, so the HBM
read stays at 2 bytes/pixel (the paper notes raw data "is immediately
doubled on processing"; fusing the cast into the kernel avoids
materialising the fp32 copy).

Grid: (frames, Y/by); dark/flat blocks are broadcast across the frame
grid dim (index_map drops the frame index).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _to_f32(x: jnp.ndarray) -> jnp.ndarray:
    # Mosaic has no direct uint16 -> float32 cast; widen through int32
    # (exact for every 16-bit value) inside the kernel, so HBM still
    # carries 2 B/pixel
    if jnp.issubdtype(x.dtype, jnp.integer) and x.dtype.itemsize < 4:
        x = x.astype(jnp.int32)
    return x.astype(jnp.float32)


def _corr_kernel(raw_ref, dark_ref, flat_ref, out_ref, *, eps: float,
                 hi: float):
    raw = _to_f32(raw_ref[...])
    dark = _to_f32(dark_ref[...])
    flat = _to_f32(flat_ref[...])
    denom = jnp.maximum(flat - dark, eps)
    trans = jnp.clip((raw - dark) / denom, eps, hi)
    out_ref[...] = -jnp.log(trans)


@functools.partial(jax.jit, static_argnames=("eps", "hi", "by",
                                             "interpret"))
def correct_pallas(raw: jnp.ndarray, dark: jnp.ndarray, flat: jnp.ndarray,
                   *, eps: float = 1e-6, hi: float = 10.0, by: int = 32,
                   interpret: bool) -> jnp.ndarray:
    """raw (F, Y, X) any real dtype; dark/flat (Y, X) -> (F, Y, X) fp32.
    ``by`` (rows per block) must be a multiple of 8."""
    f, y, x = raw.shape
    # a row block is a multiple of 8 sublanes or the whole (short)
    # frame; a last partial block is padded on read and masked on write
    by = y if y <= by else by
    grid = (f, pl.cdiv(y, by))
    kernel = functools.partial(_corr_kernel, eps=eps, hi=hi)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, by, x), lambda i, j: (i, j, 0)),
            pl.BlockSpec((by, x), lambda i, j: (j, 0)),
            pl.BlockSpec((by, x), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((1, by, x), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((f, y, x), jnp.float32),
        interpret=interpret,
    )(raw, dark, flat)
