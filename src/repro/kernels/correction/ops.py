"""Public wrapper for the fused correction kernel."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .. import pallas_interpret
from .kernel import correct_pallas
from .ref import EPS, HI, correct_ref


@functools.partial(jax.jit, static_argnames=("eps", "hi", "use_pallas",
                                             "interpret"))
def correct(raw: jnp.ndarray, dark: jnp.ndarray, flat: jnp.ndarray,
            eps: float = EPS, hi: float = HI, *, use_pallas: bool = True,
            interpret: bool | None = None) -> jnp.ndarray:
    """(..., Y, X) raw + (Y, X) dark/flat -> (..., Y, X) −log corrected.

    The kernel runs compiled on a TPU and interpreted on a CPU
    (:func:`repro.kernels.pallas_interpret`)."""
    lead = raw.shape[:-2]
    y, x = raw.shape[-2:]
    flatr = raw.reshape((-1, y, x))
    if use_pallas:
        out = correct_pallas(flatr, dark, flat, eps=eps, hi=hi,
                             interpret=pallas_interpret(interpret))
    else:
        out = correct_ref(flatr, dark[None], flat[None], eps, hi)
    return out.reshape(lead + (y, x))
