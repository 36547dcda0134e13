"""Pallas TPU kernel: parallel-beam backprojection.

For every output pixel p and angle θ the kernel adds the linearly
interpolated sinogram value at the pixel's detector coordinate

    t(p) = (x - cx)·cosθ + (y - cy)·sinθ + centre
    out[p] += sino[θ, j] + f·(sino[θ, j+1] - sino[θ, j])
    j = ⌊t⌋, f = t - j

which is the hat-function sum Σ_d sino[θ, d]·max(0, 1 - |t - d|) of
ref.backproject_ref, with the sinogram zero outside [0, n_det).

GPU codes (including the one Savu wrapped) read sino[θ, j] through a
texture unit.  A TPU has none, but it can permute the 128 lanes of a
vector register by per-lane indices (``take_along_axis`` lowers to
``tpu.dynamic_gather``).  An (8, 128) block of pixels spans less than
3·128 detector bins at any angle (|t| moves ≤ 127 bins along the lanes
and ≤ 7 along the sublanes), so the kernel loads the three 128-bin
windows of the row that cover the block and gathers from each, picking
per pixel the window its bin falls in.  That is O(pixels·angles) work
with no redundant bins, where a dense hat matrix costs a factor n_det
more.

The wrapper zero-pads the detector axis so every pixel of the (padded)
image, at every angle, lands on a valid window: rays outside the
detector then read zeros, exactly the reference's boundary rule.  It
also passes the forward difference of each row, so one gather index
serves both interpolation neighbours.

Grid = (H/bh, W/bw, A/ba); the angle axis is innermost and accumulates
into the output block, which stays resident across it.  VMEM per step:
two (ba, L) fp32 row blocks (values and differences), double-buffered,
plus the (bh, bw) output block — see ops._pick_blocks.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128          # pixels per register row, bins per detector window
SUBLANES = 8         # pixel rows per register
WINDOWS = 3          # 128-bin windows an (8, 128) pixel block can touch


def _bp_kernel(cos_ref, sin_ref, sino_ref, diff_ref, out_ref, *,
               bh: int, bw: int, ba: int, cx: float, cy: float,
               centre: float, left: int):
    h_idx = pl.program_id(0)
    w_idx = pl.program_id(1)
    a_idx = pl.program_id(2)

    @pl.when(a_idx == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    shape = (SUBLANES, LANES)
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1).astype(jnp.float32)
    sub = jax.lax.broadcasted_iota(jnp.int32, shape, 0).astype(jnp.float32)
    # image coordinates (relative to the rotation centre) of this output
    # block's first pixel
    x_blk = (w_idx * bw).astype(jnp.float32) - cx
    y_blk = (h_idx * bh).astype(jnp.float32) - cy

    n_cols = bw // LANES

    def pixel_block(blk, carry):
        # one (8, 128) register of output pixels, summed over this
        # step's ba angles
        i = blk // n_cols
        j = blk % n_cols
        x0 = x_blk + (j * LANES).astype(jnp.float32)
        y0 = y_blk + (i * SUBLANES).astype(jnp.float32)
        xs = lane + x0
        ys = sub + y0

        def angle_group(g, acc):
            # rows are loaded 8 angles at a time (sublane-aligned); each
            # angle then takes its own row of the loaded registers
            a0 = pl.multiple_of(g * SUBLANES, SUBLANES)
            for r in range(SUBLANES):
                c = cos_ref[a_idx * ba + a0 + r]
                s = sin_ref[a_idx * ba + a0 + r]
                # lowest t over the block in the padded row (> 1 there,
                # so trunc = floor), minus a bin of slack for the
                # rounding difference between this and the vector t;
                # ``start`` is its 128-aligned window
                t_lo = (x0 * c + y0 * s + (centre + left)
                        + jnp.minimum(0.0, (LANES - 1) * c)
                        + jnp.minimum(0.0, (SUBLANES - 1) * s))
                start = (t_lo.astype(jnp.int32) - 1) & -LANES
                t = xs * c + ys * s + centre          # as in the reference
                u = t - (start - left).astype(jnp.float32)
                rel = u.astype(jnp.int32)           # u >= 0: trunc = floor
                frac = u - rel.astype(jnp.float32)
                win = rel >> (LANES.bit_length() - 1)  # rel // LANES
                idx = rel & (LANES - 1)
                val = jnp.zeros(shape, jnp.float32)
                dval = jnp.zeros(shape, jnp.float32)
                for q in range(WINDOWS):
                    cols = pl.ds(pl.multiple_of(start + q * LANES, LANES),
                                 LANES)
                    rows = pl.ds(a0, SUBLANES)
                    row = jnp.broadcast_to(sino_ref[rows, cols][r:r + 1],
                                           shape)
                    drow = jnp.broadcast_to(diff_ref[rows, cols][r:r + 1],
                                            shape)
                    hit = win == q
                    val = jnp.where(hit, jnp.take_along_axis(
                        row, idx, axis=1, mode="promise_in_bounds"), val)
                    dval = jnp.where(hit, jnp.take_along_axis(
                        drow, idx, axis=1, mode="promise_in_bounds"), dval)
                acc = acc + val + frac * dval
            return acc

        acc = jax.lax.fori_loop(0, ba // SUBLANES, angle_group,
                                jnp.zeros(shape, jnp.float32))
        rows = pl.ds(pl.multiple_of(i * SUBLANES, SUBLANES), SUBLANES)
        cols = pl.ds(pl.multiple_of(j * LANES, LANES), LANES)
        out_ref[rows, cols] = out_ref[rows, cols] + acc
        return carry

    jax.lax.fori_loop(0, (bh // SUBLANES) * n_cols, pixel_block, 0)


def detector_padding(out_size: int, n_det: int, centre: float, bh: int,
                     bw: int) -> tuple[int, int]:
    """(left pad, padded row length) of the detector axis such that every
    pixel of the (bh, bw)-padded image, at any angle, has all
    ``WINDOWS`` windows of its block inside the row, with at least one
    bin of zeros below the lowest t."""
    c = (out_size - 1) / 2.0
    hp = -(-out_size // bh) * bh
    wp = -(-out_size // bw) * bw
    reach = math.hypot(max(c, wp - 1 - c), max(c, hp - 1 - c)) + 2.0
    left = max(0, math.ceil((reach - centre + 2.0) / LANES)) * LANES
    top = left + max(n_det, centre + reach) + 2.0
    length = (math.ceil(top / LANES) + WINDOWS) * LANES
    return left, length


@functools.partial(jax.jit,
                   static_argnames=("out_size", "centre", "bh", "bw", "ba",
                                    "interpret"))
def backproject_pallas(sino: jnp.ndarray, cos_t: jnp.ndarray,
                       sin_t: jnp.ndarray, *, out_size: int,
                       centre: float | None = None,
                       bh: int, bw: int, ba: int,
                       interpret: bool) -> jnp.ndarray:
    """(A, D) sinogram + angle tables (A, 1) -> (out_size, out_size) fp32.

    ``bh`` must be a multiple of 8, ``bw`` of 128 and ``ba`` of 8; the
    image is padded up to whole (bh, bw) blocks and the angles up to a
    whole number of ba-blocks (zero rows add nothing), then cropped.
    Scaling (π / A) is applied here, matching ref.backproject_ref.
    """
    n_angles, n_det = sino.shape
    if centre is None:
        centre = (n_det - 1) / 2.0
    if bh % SUBLANES or bw % LANES or ba % SUBLANES:
        raise ValueError(
            f"backproject_pallas: blocks (bh={bh}, bw={bw}, ba={ba}) must "
            f"be multiples of ({SUBLANES}, {LANES}, {SUBLANES})")
    hp = -(-out_size // bh) * bh
    wp = -(-out_size // bw) * bw
    ap = -(-n_angles // ba) * ba
    left, length = detector_padding(out_size, n_det, centre, bh, bw)

    rows = jnp.pad(sino.astype(jnp.float32),
                   ((0, ap - n_angles), (left, length - left - n_det)))
    diff = jnp.pad(rows[:, 1:] - rows[:, :-1], ((0, 0), (0, 1)))
    cos_p = jnp.pad(cos_t.astype(jnp.float32).reshape(-1),
                    (0, ap - n_angles))
    sin_p = jnp.pad(sin_t.astype(jnp.float32).reshape(-1),
                    (0, ap - n_angles))

    c = (out_size - 1) / 2.0          # square volume: cx == cy
    kernel = functools.partial(_bp_kernel, bh=bh, bw=bw, ba=ba, cx=c, cy=c,
                               centre=float(centre), left=left)
    # whole angle tables in SMEM (8 B per angle)
    table = pl.BlockSpec((ap,), lambda h, w, a: (0,),
                         memory_space=pltpu.SMEM)
    block = pl.BlockSpec((ba, length), lambda h, w, a: (a, 0))
    out = pl.pallas_call(
        kernel,
        grid=(hp // bh, wp // bw, ap // ba),
        in_specs=[table, table, block, block],
        out_specs=pl.BlockSpec((bh, bw), lambda h, w, a: (h, w)),
        out_shape=jax.ShapeDtypeStruct((hp, wp), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(cos_p, sin_p, rows, diff)
    return out[:out_size, :out_size] * (jnp.pi / n_angles)
