"""Pallas TPU kernel: fused frequency-domain ramp-filter scale.

The FFT itself stays in XLA (fft is a first-class XLA op with a tuned
TPU implementation); what the kernel fuses is the complex
spectrum × real-filter scale for the whole frame block in one VMEM
pass, operating on the (re, im) planes jointly so the spectrum is read
once.  Complex arrays are carried as two real planes because Mosaic has
no complex register type.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _scale_kernel(re_ref, im_ref, filt_ref, ore_ref, oim_ref):
    f = filt_ref[...]
    ore_ref[...] = re_ref[...] * f
    oim_ref[...] = im_ref[...] * f


@functools.partial(jax.jit, static_argnames=("bf", "interpret"))
def scale_spectrum_pallas(re: jnp.ndarray, im: jnp.ndarray,
                          filt: jnp.ndarray, *, bf: int = 8,
                          interpret: bool):
    """re/im (F, NF) spectrum planes × filt (1, NF) -> scaled planes.
    ``bf`` (frames per block) must be a multiple of 8."""
    f, nf = re.shape
    # a frame block is a multiple of 8 sublanes or all (few) frames; a
    # last partial block is padded on read and masked on write
    bf = f if f <= bf else bf
    grid = (pl.cdiv(f, bf),)
    return pl.pallas_call(
        _scale_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bf, nf), lambda i: (i, 0)),
            pl.BlockSpec((bf, nf), lambda i: (i, 0)),
            pl.BlockSpec((1, nf), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bf, nf), lambda i: (i, 0)),
            pl.BlockSpec((bf, nf), lambda i: (i, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((f, nf), re.dtype),
                   jax.ShapeDtypeStruct((f, nf), im.dtype)],
        interpret=interpret,
    )(re, im, filt)
