"""Work of the sinogram filter's spectrum scale, counted from shapes.

The kernel multiplies the real and the imaginary plane of each row's
rfft spectrum by the filter's response: 2 operations per bin and row.
It reads both planes and the response and writes both planes, float32.
"""
from __future__ import annotations

#: the Mosaic kernel's call as the device trace names it (the program's
#: ``pallas_call`` wrapper)
KERNEL = "scale_spectrum_pallas"


def n_bins(n_det: int) -> int:
    """rfft bins of the filter's FFT: the next power of two >= 2 n_det,
    halved, plus one."""
    return (1 << (2 * n_det - 1).bit_length()) // 2 + 1


def work(n_rows: int, n_det: int) -> tuple[float, float]:
    """(operations, bytes) of scaling ``n_rows`` detector rows' spectra
    (one row per slice and angle)."""
    bins = n_bins(n_det)
    flops = 2 * bins * n_rows
    nbytes = 4 * (4 * bins * n_rows + bins)
    return float(flops), float(nbytes)
