"""Framework behaviour: transports agree, fusion agrees, replacement
semantics, multi-dataset chains (Fig 10), profiler."""
import os
import subprocess
import sys

import numpy as np
import pytest

import jax

from repro.core import (BaseLoader, BasePlugin, BaseSaver, ChunkedFile,
                        ChunkedFileTransport, DataSet, InMemoryTransport,
                        LambdaFilter, PluginRunner, ProcessList,
                        ShardedTransport)
pytest.importorskip("hypothesis")
from hypothesis import given, settings
from hypothesis import strategies as st


class ArrayLoader(BaseLoader):
    name = "array_loader"

    def __init__(self, array=None, labels=("theta", "y", "x"), **kw):
        super().__init__(**kw)
        self.array = array
        self.labels = labels

    def load(self):
        d = DataSet(self.out_dataset_names[0], self.array.shape,
                    self.array.dtype, self.labels, backing=self.array)
        d.add_pattern("PROJECTION", core=self.labels[1:],
                      slice_=self.labels[:1])
        d.add_pattern("SINOGRAM",
                      core=(self.labels[0], self.labels[2]),
                      slice_=(self.labels[1],))
        return [d]


class CaptureSaver(BaseSaver):
    name = "capture_saver"
    captured = {}

    def save(self, ds):
        b = ds.backing
        CaptureSaver.captured[ds.name] = (
            b.read_all() if isinstance(b, ChunkedFile) else np.asarray(b))


def _chain(a, frames=1):
    pl = ProcessList()
    pl.add(ArrayLoader, params={"array": a}, out_datasets=("tomo",))
    pl.add(LambdaFilter,
           params={"fn": lambda b: b * 2.0, "pattern": "PROJECTION",
                   "frames": frames},
           in_datasets=("tomo",), out_datasets=("tomo",))
    pl.add(LambdaFilter,
           params={"fn": lambda b: b + 1.0, "pattern": "SINOGRAM",
                   "frames": frames},
           in_datasets=("tomo",), out_datasets=("tomo",))
    pl.add(CaptureSaver, in_datasets=("tomo",))
    return pl


@pytest.fixture
def data(rng):
    return rng.normal(size=(8, 6, 4)).astype(np.float32)


def test_transports_agree(data):
    """in-memory, chunked-file and sharded transports produce identical
    results for the same chain (the paper's serial-vs-MPI equivalence)."""
    expect = data * 2 + 1
    for transport in (InMemoryTransport(), ChunkedFileTransport()):
        CaptureSaver.captured = {}
        PluginRunner(_chain(data), transport).run()
        np.testing.assert_allclose(CaptureSaver.captured["tomo"], expect,
                                   rtol=1e-6)
    mesh = jax.make_mesh((1,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    CaptureSaver.captured = {}
    PluginRunner(_chain(data), ShardedTransport(mesh)).run()
    np.testing.assert_allclose(CaptureSaver.captured["tomo"], expect,
                               rtol=1e-5)


def test_fusion_matches_unfused(data):
    mesh = jax.make_mesh((1,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    CaptureSaver.captured = {}
    PluginRunner(_chain(data), ShardedTransport(mesh), fuse=True).run()
    fused = CaptureSaver.captured["tomo"]
    np.testing.assert_allclose(fused, data * 2 + 1, rtol=1e-5)


_FOUR_DEVICE_CHAIN = """
import jax
jax.config.update("jax_num_cpu_devices", 4)
import numpy as np
from jax.sharding import Mesh
from repro.core import PluginRunner, ShardedTransport
from test_framework import _chain

mesh = Mesh(np.asarray(jax.devices()), ("data",))
assert mesh.size == 4
# 12 projections and 20 rows divide over 4 devices, but not into whole
# groups of 2 or 4 frames on every device
a = np.random.default_rng(0).normal(size=(12, 20, 4)).astype(np.float32)
for frames in (1, 2, 4):
    out = PluginRunner(_chain(a, frames), ShardedTransport(mesh)).run()
    np.testing.assert_allclose(np.asarray(out["tomo"].materialise()),
                               a * 2 + 1, rtol=1e-6)
try:
    PluginRunner(_chain(a[:6]), ShardedTransport(mesh)).run()
except ValueError as e:
    assert "has 6 slices" in str(e) and "4 devices" in str(e), e
else:
    raise AssertionError("6 projections were laid over 4 devices")
"""


def test_sharded_transport_pads_indivisible_frames():
    """On a 4-device data mesh, frame counts that do not fill whole
    groups of frames on every device still map per device and give the
    single-device result; slice counts that do not divide over the
    devices are refused with the counts."""
    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(os.path.dirname(here), "src"), here,
                    os.environ.get("PYTHONPATH", "")]))
    proc = subprocess.run([sys.executable, "-c", _FOUR_DEVICE_CHAIN],
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode == 0, proc.stderr[-4000:]


def test_multi_frame_processing(data):
    CaptureSaver.captured = {}
    PluginRunner(_chain(data, frames=2), InMemoryTransport()).run()
    np.testing.assert_allclose(CaptureSaver.captured["tomo"],
                               data * 2 + 1, rtol=1e-6)


def test_dataset_replacement_semantics(data):
    """An out_dataset with the same name replaces the in_dataset; a new
    name creates a parallel dataset (paper §III.B)."""
    pl = ProcessList()
    pl.add(ArrayLoader, params={"array": data}, out_datasets=("tomo",))
    pl.add(LambdaFilter, params={"fn": lambda b: b * 2.0},
           in_datasets=("tomo",), out_datasets=("doubled",))
    pl.add(LambdaFilter, params={"fn": lambda b: b + 5.0},
           in_datasets=("tomo",), out_datasets=("tomo",))
    pl.add(CaptureSaver, in_datasets=("doubled",))
    pl.add(CaptureSaver, in_datasets=("tomo",))
    CaptureSaver.captured = {}
    runner = PluginRunner(pl, InMemoryTransport())
    out = runner.run()
    # 'doubled' was computed from the ORIGINAL tomo
    np.testing.assert_allclose(CaptureSaver.captured["doubled"], data * 2)
    np.testing.assert_allclose(CaptureSaver.captured["tomo"], data + 5)
    assert set(out) == {"tomo", "doubled"}


def test_multi_loader_multimodal_chain(rng):
    """Fig 10: multiple loaders, a 2-in plugin combining datasets."""
    absorb = rng.normal(size=(4, 4, 4)).astype(np.float32)
    fluo = rng.normal(size=(4, 4, 4)).astype(np.float32)

    class TwoIn(BasePlugin):
        name = "combine"
        n_in_datasets = 2
        n_out_datasets = 1

        def setup(self, ins):
            dout = ins[1].like(self.out_dataset_names[0])
            self.chunk_frames(self.default_pattern(ins[0]))
            return [dout]

        def process_frames(self, frames):
            a, f = frames
            return f / (1.0 + np.abs(a))

    pl = ProcessList()
    pl.add(ArrayLoader, params={"array": absorb}, out_datasets=("absorb",))
    pl.add(ArrayLoader, params={"array": fluo}, out_datasets=("fluo",))
    pl.add(TwoIn, in_datasets=("absorb", "fluo"),
           out_datasets=("corrected",))
    pl.add(CaptureSaver, in_datasets=("corrected",))
    CaptureSaver.captured = {}
    PluginRunner(pl, InMemoryTransport()).run()
    np.testing.assert_allclose(CaptureSaver.captured["corrected"],
                               fluo / (1 + np.abs(absorb)), rtol=1e-6)


def test_profiler_records_all_plugins(data):
    runner = PluginRunner(_chain(data), InMemoryTransport())
    runner.run()
    totals = runner.profiler.totals()
    assert "lambda_filter" in totals
    report = runner.profiler.report()
    assert "profile" in report and "#" in report


def test_manifest_written(tmp_path, data):
    runner = PluginRunner(_chain(data), InMemoryTransport(),
                          output_dir=str(tmp_path))
    runner.run()
    import json
    man = json.load(open(tmp_path / "savu_manifest.nxs.json"))
    names = [d["name"] for d in man["datasets"]]
    assert names.count("tomo") >= 2       # lineage keeps intermediates


@given(shape=st.tuples(st.integers(2, 9), st.integers(2, 9),
                       st.integers(2, 9)),
       chunks=st.tuples(st.integers(1, 4), st.integers(1, 4),
                        st.integers(1, 4)))
@settings(max_examples=20, deadline=None)
def test_chunked_file_region_io(tmp_path_factory, shape, chunks):
    """Property: ChunkedFile read(write(x)) == x for random regions."""
    import tempfile
    rng = np.random.default_rng(1)
    d = tempfile.mkdtemp()
    cf = ChunkedFile(f"{d}/t.dat", shape, np.float32, chunks,
                     cache_bytes=1024)
    ref = rng.normal(size=shape).astype(np.float32)
    cf.write_all(ref)
    np.testing.assert_array_equal(cf.read_all(), ref)
    # random sub-region
    lo = [rng.integers(0, s) for s in shape]
    hi = [int(rng.integers(l + 1, s + 1)) for l, s in zip(lo, shape)]
    region = tuple(slice(int(l), int(h)) for l, h in zip(lo, hi))
    np.testing.assert_array_equal(cf.read(region), ref[region])
    # partial write
    val = rng.normal(size=tuple(h - l for l, h in zip(lo, hi))
                     ).astype(np.float32)
    cf.write(region, val)
    cf.flush()
    ref[region] = val
    np.testing.assert_array_equal(cf.read_all(), ref)
