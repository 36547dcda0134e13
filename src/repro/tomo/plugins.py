"""Tomography processing plugins — the paper's standard full-field chain
(§II.A): correction/linearisation → (ring removal | Paganin phase
retrieval) → sinogram filtering → FBP reconstruction.

Every plugin is a thin Savu-style shell over a kernels/ op (Pallas on
TPU, interpret-validated here) or a jnp routine; the framework owns the
slicing/sharding per the declared pattern.
"""
from __future__ import annotations

import math

import numpy as np

import jax
import jax.numpy as jnp

from ..core.dataset import DataSet
from ..core.patterns import PROJECTION, SINOGRAM, TIMESERIES, VOLUME_XZ
from ..core.plugin import BaseFilter, BaseLoader, BasePlugin, BaseRecon, BaseSaver
from ..kernels.backproject.ops import backproject
from ..kernels.correction.ops import correct
from ..kernels.sino_filter.ops import filter_sino
from ..kernels.sino_filter.ref import make_filter
from .geometry import ParallelGeometry
from .phantom import simulate_phantom_scan


# ----------------------------------------------------------------------
class SyntheticTomoLoader(BaseLoader):
    """Creates a raw full-field scan (θ, y, x) from a phantom — the
    nx_tomo_loader analogue, with dark/flat fields in metadata.  A given
    ``scan`` that carries a ``truth`` volume passes it on as metadata;
    the synthetic phantom's truth is :func:`phantom_truth` of the
    ``geometry`` metadata."""

    name = "synthetic_tomo_loader"
    parameters = {"n_det": 64, "n_angles": 64, "n_rows": 4, "noise": 0.0,
                  "seed": 0, "scan": None}
    data_params = ("seed", "scan")      # dataset identity, not pipeline

    def load(self) -> list[DataSet]:
        p = self.params
        scan = p["scan"]
        if scan is None:
            geom = ParallelGeometry(p["n_angles"], p["n_det"], p["n_rows"])
            scan = simulate_phantom_scan(geom, noise=p["noise"],
                                         seed=p["seed"])
        else:
            geom = ParallelGeometry(scan["data"].shape[0],
                                    scan["data"].shape[2],
                                    scan["data"].shape[1])
        data = scan["data"]
        ds = DataSet(self.out_dataset_names[0], data.shape, data.dtype,
                     ("rotation_angle", "detector_y", "detector_x"),
                     backing=lambda: data)      # lazy (paper §III.F.2)
        ds.add_pattern(PROJECTION, core=("detector_y", "detector_x"),
                       slice_=("rotation_angle",))
        ds.add_pattern(SINOGRAM, core=("rotation_angle", "detector_x"),
                       slice_=("detector_y",))
        ds.metadata.update({
            "dark": scan["dark"], "flat": scan["flat"],
            "mu": scan.get("mu", 1.0), "geometry": geom,
        })
        if scan.get("truth") is not None:
            ds.metadata["truth"] = scan["truth"]
        return [ds]


class DarkFlatCorrection(BaseFilter):
    """(raw−dark)/(flat−dark), clip, −log — fused Pallas kernel."""

    name = "dark_flat_correction"
    pattern_name = PROJECTION
    frames = 1
    parameters = {"use_pallas": True}

    def setup(self, in_datasets):
        (din,) = in_datasets
        self._dark = jnp.asarray(din.metadata["dark"].astype(np.float32))
        self._flat = jnp.asarray(din.metadata["flat"].astype(np.float32))
        dout = din.like(self.out_dataset_names[0], dtype=np.float32)
        dout.metadata = dict(din.metadata)
        self.chunk_frames(self.pattern_name, self.frames)
        return [dout]

    def process_frames(self, frames):
        (block,) = frames          # (m, y, x)
        return correct(block, self._dark, self._flat,
                       use_pallas=self.params["use_pallas"])


class PaganinFilter(BaseFilter):
    """Single-distance phase retrieval (Paganin 2002) — the phase-contrast
    method the paper says Savu made routine on I12/I13.  Projection-space
    low-pass:  T = −(1/μ)·ln( F⁻¹[ F[I] / (1 + τ(kx²+ky²)) ] )."""

    name = "paganin_filter"
    pattern_name = PROJECTION
    frames = 1
    parameters = {"tau": 10.0}   # δ·z/μ lumped constant, pixel units
    # tau only shapes self._denom (a jit constant), so it is sweepable:
    # variants with different tau share one compiled program
    tunable_params = ("tau",)

    def setup(self, in_datasets):
        (din,) = in_datasets
        dout = din.like(self.out_dataset_names[0], dtype=np.float32)
        dout.metadata = dict(din.metadata)
        ny, nx = din.shape[1], din.shape[2]
        ky = np.fft.fftfreq(ny)[:, None]
        kx = np.fft.fftfreq(nx)[None, :]
        self._denom = jnp.asarray(
            1.0 / (1.0 + self.params["tau"] * (kx ** 2 + ky ** 2)),
            dtype=jnp.complex64)
        self.chunk_frames(self.pattern_name, self.frames)
        return [dout]

    def process_frames(self, frames):
        (block,) = frames          # (m, y, x) — already −log corrected
        intensity = jnp.exp(-block)            # back to transmission
        spec = jnp.fft.fft2(intensity.astype(jnp.complex64), axes=(1, 2))
        filt = jnp.real(jnp.fft.ifft2(spec * self._denom[None], axes=(1, 2)))
        return -jnp.log(jnp.clip(filt, 1e-6, None))


class RingRemoval(BaseFilter):
    """Sinogram-space stripe suppression: subtract the smoothed column
    mean (a standard mean-filter ring-removal; operates per sinogram)."""

    name = "ring_removal"
    pattern_name = SINOGRAM
    frames = 1
    parameters = {"kernel": 9, "strength": 1.0}
    # strength scales the correction as a float jit constant
    # (self._strength below), so it is sweepable; kernel selects shapes
    # and stays a static trace-time value
    tunable_params = ("strength",)

    def setup(self, in_datasets):
        (din,) = in_datasets
        dout = din.like(self.out_dataset_names[0], dtype=np.float32)
        dout.metadata = dict(din.metadata)
        self._strength = float(self.params["strength"])
        self.chunk_frames(self.pattern_name, self.frames)
        return [dout]

    def process_frames(self, frames):
        (block,) = frames          # (m, angles, x)
        col_mean = jnp.mean(block, axis=1, keepdims=True)   # (m, 1, x)
        k = int(self.params["kernel"])
        pad = k // 2
        padded = jnp.pad(col_mean, ((0, 0), (0, 0), (pad, pad)), mode="edge")
        kern = jnp.ones((k,), block.dtype) / k
        # full f32: a TPU's default precision rounds the operands to
        # bf16, by rules that follow the compiled layout (one chip and a
        # mesh then disagree by ~1e-3 of the volume's range)
        smooth = jax.vmap(lambda r: jnp.convolve(
            r, kern, mode="valid", precision=jax.lax.Precision.HIGHEST))(
            padded[:, 0, :])[:, None, :]
        stripe = col_mean - smooth
        return block - self._strength * stripe


class SinogramFilter(BaseFilter):
    """Frequency-domain ramp filtering of sinogram rows (FBP step 1)."""

    name = "sinogram_filter"
    pattern_name = SINOGRAM
    frames = 1
    # cutoff: fraction of Nyquist above which the response is zeroed —
    # the classic Savu tuning knob.  It only shapes self._filt (a jit
    # constant), so sweep variants share one compiled program.
    parameters = {"kind": "shepp", "use_pallas": True, "cutoff": 1.0}
    tunable_params = ("cutoff",)

    def setup(self, in_datasets):
        (din,) = in_datasets
        dout = din.like(self.out_dataset_names[0], dtype=np.float32)
        dout.metadata = dict(din.metadata)
        n_det = din.shape[din.label_index("detector_x")]
        filt = make_filter(n_det, self.params["kind"])
        cutoff = float(self.params["cutoff"])
        nyq_frac = np.linspace(0.0, 1.0, filt.shape[0], dtype=np.float32)
        filt = (filt * (nyq_frac <= cutoff)).astype(np.float32)
        self._filt = jnp.asarray(filt)
        self.chunk_frames(self.pattern_name, self.frames)
        return [dout]

    def process_frames(self, frames):
        (block,) = frames          # (m, angles, x)
        return filter_sino(block, self._filt,
                           use_pallas=self.params["use_pallas"])


class FBPRecon(BaseRecon):
    """Filtered backprojection — sinogram in, volume slice out (Pallas
    backprojection kernel; the chain's compute hot spot)."""

    name = "fbp_recon"
    n_in_datasets = 1
    n_out_datasets = 1
    out_pattern_name = VOLUME_XZ
    parameters = {"use_pallas": True, "out_size": None}

    def setup(self, in_datasets):
        (din,) = in_datasets
        n_angles = din.shape[din.label_index("rotation_angle")]
        n_det = din.shape[din.label_index("detector_x")]
        n_rows = din.shape[din.label_index("detector_y")]
        out_size = self.params["out_size"] or n_det
        self._out_size = out_size
        geom: ParallelGeometry = din.metadata["geometry"]
        # slice to the input's angle count so a streaming preview (an
        # angle-prefix of the full scan) reconstructs from exactly the
        # acquired angles
        self._angles = jnp.asarray(
            geom.angles.astype(np.float32)[:n_angles])
        self._mu = float(din.metadata.get("mu", 1.0))
        dout = DataSet(self.out_dataset_names[0],
                       (n_rows, out_size, out_size), np.float32,
                       ("voxel_y", "voxel_z", "voxel_x"))
        dout.add_pattern(VOLUME_XZ, core=("voxel_z", "voxel_x"),
                         slice_=("voxel_y",))
        dout.metadata = dict(din.metadata)
        for pd in self.in_data:
            pd.pattern_name = SINOGRAM
            pd.n_frames = 1
        return [dout]

    def process_frames(self, frames):
        (block,) = frames          # (m, angles, x)
        img = backproject(block, self._angles, self._out_size,
                          use_pallas=self.params["use_pallas"])
        return img / self._mu      # linearised path -> attenuation units


class UpstreamLoader(BaseLoader):
    """Workflow stage input (docs/workflows.md): loads another job's
    result volume as this chain's starting dataset.

    In a spec the reference is ``{"data": {"from_job": "<node>",
    "dataset": "<name>"}}`` (or the split ``from_job``/``dataset``
    params).  The service resolves it before execution — the scheduler
    injects the array into ``data``, the broker splices a shared-fs
    ``path``, a remote worker fetches over HTTP — so by ``load()`` time
    exactly one of ``data`` (an array) or ``path`` is materialised.
    All four params are ``data_params``: they select WHICH volume, so
    downstream chains of different workflows share one chain signature
    (and compiled programs) and may gang.
    """

    name = "upstream_loader"
    parameters = {"from_job": None, "dataset": None, "data": None,
                  "path": None}
    data_params = ("from_job", "dataset", "data", "path")

    def load(self) -> list[DataSet]:
        p = self.params
        data = p["data"]
        if isinstance(data, dict):
            raise RuntimeError(
                f"upstream_loader: unresolved upstream reference {data!r} "
                f"— submit through the service (POST /workflows) so it "
                f"is resolved at dispatch time")
        if data is None and p["path"]:
            data = np.load(p["path"])
        if data is None:
            raise RuntimeError(
                "upstream_loader: no input — neither a resolved 'data' "
                "array nor a 'path' was provided")
        arr = np.asarray(data)
        if arr.ndim == 2:
            arr = arr[None]
        if arr.ndim != 3:
            raise RuntimeError(
                f"upstream_loader: expected a (y, z, x) volume, got "
                f"shape {arr.shape}")
        ds = DataSet(self.out_dataset_names[0], arr.shape, arr.dtype,
                     ("voxel_y", "voxel_z", "voxel_x"),
                     backing=lambda: arr)
        ds.add_pattern(VOLUME_XZ, core=("voxel_z", "voxel_x"),
                       slice_=("voxel_y",))
        return [ds]


class Downsample(BaseFilter):
    """Block-mean downsampling of a reconstructed volume's in-plane
    dims — the classic post-recon reduction stage (Ot2Rec-style staged
    campaigns run it between reconstruction and quantification)."""

    name = "downsample"
    pattern_name = VOLUME_XZ
    frames = 1
    parameters = {"factor": 2}

    def setup(self, in_datasets):
        (din,) = in_datasets
        f = int(self.params["factor"])
        if f < 1:
            raise ValueError(f"downsample: factor must be >= 1, got {f}")
        y = din.shape[din.label_index("voxel_y")]
        z = din.shape[din.label_index("voxel_z")]
        x = din.shape[din.label_index("voxel_x")]
        if z % f or x % f:
            raise ValueError(
                f"downsample: factor {f} must divide the in-plane dims "
                f"({z}, {x})")
        dout = DataSet(self.out_dataset_names[0], (y, z // f, x // f),
                       np.float32, ("voxel_y", "voxel_z", "voxel_x"))
        dout.add_pattern(VOLUME_XZ, core=("voxel_z", "voxel_x"),
                         slice_=("voxel_y",))
        dout.metadata = dict(din.metadata)
        self.chunk_frames(self.pattern_name, self.frames)
        return [dout]

    def process_frames(self, frames):
        (block,) = frames          # (m, z, x)
        f = int(self.params["factor"])
        m, z, x = block.shape
        return jnp.mean(
            block.reshape(m, z // f, f, x // f, f).astype(jnp.float32),
            axis=(2, 4))


class Quantify(BaseFilter):
    """Per-slice summary statistics (mean/std/min/max) of a volume —
    the terminal quantification stage of a recon → downsample →
    quantify workflow."""

    name = "quantify"
    n_in_datasets = 1
    n_out_datasets = 1
    out_pattern_name = TIMESERIES
    parameters: dict = {}

    def setup(self, in_datasets):
        (din,) = in_datasets
        y = din.shape[din.label_index("voxel_y")]
        dout = DataSet(self.out_dataset_names[0], (y, 4), np.float32,
                       ("voxel_y", "stat"))
        dout.add_pattern(TIMESERIES, core=("stat",), slice_=("voxel_y",))
        dout.metadata = dict(din.metadata)
        for pd in self.in_data:
            pd.pattern_name = VOLUME_XZ
            pd.n_frames = 1
        return [dout]

    def process_frames(self, frames):
        (block,) = frames          # (m, z, x)
        flat = block.reshape(block.shape[0], -1).astype(jnp.float32)
        return jnp.stack([jnp.mean(flat, axis=1), jnp.std(flat, axis=1),
                          jnp.min(flat, axis=1), jnp.max(flat, axis=1)],
                         axis=-1)


class HDF5LikeSaver(BaseSaver):
    """Terminal saver: flushes chunked files / materialises arrays and
    records the manifest entry (the NeXus-link analogue)."""

    name = "hdf5_saver"

    def save(self, dataset: DataSet) -> None:
        backing = dataset.backing
        if hasattr(backing, "flush"):
            backing.flush()
        dataset.metadata["saved"] = True
