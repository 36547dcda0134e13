"""ShardedTransport's host<->device transfer spans and the names of its
step programs, on a one-device CPU mesh; the served loader builds no
phantom truth."""
import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.core import PluginRunner, ProcessList, ShardedTransport
from repro.obs import Trace, use_trace
from repro.tomo import (DarkFlatCorrection, HDF5LikeSaver, RingRemoval,
                        SyntheticTomoLoader)
from repro.tomo import phantom
from repro.tomo.geometry import ParallelGeometry
from repro.tomo.phantom import (phantom_stack, phantom_truth,
                                simulate_phantom_scan)

N_ANGLES, N_ROWS, N_DET = 12, 2, 16


def _chain(scan=None) -> ProcessList:
    """Loader -> correction -> ring removal -> saver: two steps."""
    pl = ProcessList()
    pl.add(SyntheticTomoLoader,
           params={"n_det": N_DET, "n_angles": N_ANGLES, "n_rows": N_ROWS,
                   "scan": scan},
           out_datasets=("tomo",))
    pl.add(DarkFlatCorrection, params={"use_pallas": False},
           in_datasets=("tomo",), out_datasets=("tomo",))
    pl.add(RingRemoval, in_datasets=("tomo",), out_datasets=("tomo",))
    pl.add(HDF5LikeSaver, in_datasets=("tomo",))
    return pl


def _transport() -> ShardedTransport:
    return ShardedTransport(Mesh(np.asarray(jax.devices()[:1]), ("data",)),
                            donate=False)


def _run(pl: ProcessList) -> tuple[Trace, PluginRunner]:
    trace = Trace()
    with use_trace(trace):
        runner = PluginRunner(pl, _transport())
        runner.run()
    return trace, runner


def _spans(trace: Trace, name: str) -> list:
    return [s for s in trace.spans() if s.name == name]


def test_chain_copies_the_raw_scan_to_the_device_once():
    trace, _ = _run(_chain())
    (h2d,) = _spans(trace, "transfer.h2d")
    raw_bytes = N_ANGLES * N_ROWS * N_DET * np.dtype(np.uint16).itemsize
    assert h2d.attrs["bytes"] == raw_bytes
    assert h2d.end is not None
    # the loader made the counts on the device and copied them back
    (d2h,) = _spans(trace, "transfer.d2h")
    assert d2h.attrs["bytes"] == raw_bytes
    # no step reads the phantom's truth, so the served chain builds none
    assert _spans(trace, "loader.truth") == []


def test_served_chain_never_builds_the_phantom_truth(monkeypatch):
    def refuse(*args, **kwargs):
        raise AssertionError("the served chain built the phantom truth")

    monkeypatch.setattr(phantom, "phantom_stack", refuse)
    _, runner = _run(_chain())
    assert runner.transport.read(runner.datasets["tomo"]).shape == (
        N_ANGLES, N_ROWS, N_DET)


def test_loader_metadata_is_the_simulated_scan_s():
    geom = ParallelGeometry(N_ANGLES, N_DET, N_ROWS)
    scan = simulate_phantom_scan(geom)
    (ds,) = SyntheticTomoLoader(n_det=N_DET, n_angles=N_ANGLES,
                                n_rows=N_ROWS, out_datasets=("tomo",)).load()
    assert np.array_equal(ds.materialise(), scan["data"])
    assert np.array_equal(ds.metadata["dark"], scan["dark"])
    assert np.array_equal(ds.metadata["flat"], scan["flat"])
    assert ds.metadata["mu"] == scan["mu"]
    assert ds.metadata["geometry"] == geom
    assert "truth" not in ds.metadata


def test_loader_passes_on_a_given_scan_s_truth():
    scan = simulate_phantom_scan(ParallelGeometry(N_ANGLES, N_DET, N_ROWS))
    scan["truth"] = np.ones((N_ROWS, N_DET, N_DET), np.float32)
    (ds,) = SyntheticTomoLoader(scan=scan, out_datasets=("tomo",)).load()
    assert ds.metadata["truth"] is scan["truth"]


def test_phantom_truth_is_the_phantom_stack_in_one_span():
    geom = ParallelGeometry(N_ANGLES, N_DET, N_ROWS)
    trace = Trace()
    with use_trace(trace):
        truth = phantom_truth(geom)
    assert np.array_equal(truth, phantom_stack(N_DET, N_ROWS))
    assert truth.dtype == np.float32
    (span,) = _spans(trace, "loader.truth")
    assert span.end is not None


def test_input_already_on_the_device_records_no_transfer():
    scan = simulate_phantom_scan(ParallelGeometry(N_ANGLES, N_DET, N_ROWS))
    scan["data"] = jnp.asarray(scan["data"])
    trace, runner = _run(_chain(scan))
    assert _spans(trace, "transfer.h2d") == []
    assert isinstance(runner.datasets["tomo"].backing, jax.Array)


def test_read_records_the_copy_to_the_host():
    _, runner = _run(_chain())
    trace = Trace()
    with use_trace(trace):
        out = runner.transport.read(runner.datasets["tomo"])
    (d2h,) = _spans(trace, "transfer.d2h")
    assert d2h.attrs["bytes"] == out.nbytes == (
        N_ANGLES * N_ROWS * N_DET * np.dtype(np.float32).itemsize)


def test_step_program_is_named_after_its_plugin():
    runner = PluginRunner(_chain(), _transport()).prepare()
    (plugin,) = runner.begin_step()
    text = runner.transport.compile_plugin(
        plugin, consts=plugin.jit_constants()).as_text()
    assert "HloModule jit_dark_flat_correction" in text
    assert "jit_fn" not in text
    assert 'op_name="jit(dark_flat_correction)/dark_flat_correction/' in text
