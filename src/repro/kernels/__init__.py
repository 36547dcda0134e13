# Pallas TPU kernels for the perf-critical compute layers.
from __future__ import annotations

import jax


def pallas_interpret(requested: bool | None = None) -> bool:
    """Whether Pallas kernels run interpreted, chosen from the backend.

    TPU compiles them with Mosaic; CPU interprets them (the test path).
    Any other backend has neither, and a request for the mode the
    backend cannot run (interpreted on a TPU, compiled on a CPU) is an
    error rather than a silent switch.
    """
    backend = jax.default_backend()
    if backend == "tpu":
        interpret = False
    elif backend == "cpu":
        interpret = True
    else:
        raise RuntimeError(
            f"Pallas kernels run compiled on 'tpu' or interpreted on 'cpu'; "
            f"the default backend is {backend!r}")
    if requested is not None and requested != interpret:
        raise ValueError(
            f"interpret={requested} requested, but backend {backend!r} "
            f"runs Pallas kernels with interpret={interpret}")
    return interpret
