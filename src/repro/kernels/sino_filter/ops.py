"""Public wrapper: FFT (XLA) + Pallas spectrum scale + iFFT."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .. import pallas_interpret
from .kernel import scale_spectrum_pallas
from .ref import filter_sino_ref, make_filter  # noqa: F401 (re-export)


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def filter_sino(sino: jnp.ndarray, filt: jnp.ndarray, *,
                use_pallas: bool = True, interpret: bool | None = None
                ) -> jnp.ndarray:
    """Apply a precomputed rfft-domain filter along the detector axis.

    sino: (..., n_det); filt: (n_rfft_bins,).  The spectrum-scale kernel
    runs compiled on a TPU and interpreted on a CPU
    (:func:`repro.kernels.pallas_interpret`).
    """
    if not use_pallas:
        return filter_sino_ref(sino, filt)
    n_det = sino.shape[-1]
    lead = sino.shape[:-1]
    n_fft = 2 * (filt.shape[-1] - 1)
    spec = jnp.fft.rfft(sino.reshape((-1, n_det)), n=n_fft, axis=-1)
    re, im = jnp.real(spec), jnp.imag(spec)
    fre, fim = scale_spectrum_pallas(re, im, filt.reshape(1, -1),
                                     interpret=pallas_interpret(interpret))
    out = jnp.fft.irfft(jax.lax.complex(fre, fim), n=n_fft, axis=-1)
    return out[..., :n_det].reshape(lead + (n_det,)).astype(sino.dtype)
