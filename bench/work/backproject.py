"""Work of one backprojection, counted from shapes alone.

Every pixel of every slice takes, at every angle, one linearly
interpolated detector value and adds it: v0 + f (v1 - v0), then the
sum, so 4 floating-point operations per pixel, angle and slice.  The
count does not depend on how a kernel computes it (lane gathers today,
a hat-matrix matmul later): a form that spends more operations does
more than the work, not more work.  Bytes are the least a kernel must
move: the float32 sinograms read once and the float32 images written
once.
"""
from __future__ import annotations

#: the Mosaic kernel's call as the device trace names it (the program's
#: ``pallas_call`` wrapper)
KERNEL = "backproject_pallas"
FLOPS_PER_PIXEL_ANGLE = 4


def work(n_slices: int, n_angles: int, n_det: int, out_size: int
         ) -> tuple[float, float]:
    """(operations, bytes) of backprojecting ``n_slices`` sinograms of
    ``n_angles`` x ``n_det`` into ``out_size``^2 images."""
    pixels = out_size * out_size
    flops = FLOPS_PER_PIXEL_ANGLE * pixels * n_angles * n_slices
    nbytes = 4 * (n_angles * n_det + pixels) * n_slices
    return float(flops), float(nbytes)
