"""The plain reference agrees with the program's chain at a small size,
and disagrees when one kernel's values are rounded to bfloat16."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

import reference
import rehearse
from repro.core import PluginRunner, ShardedTransport
from repro.service.wire import from_spec
from repro.tomo import plugins
from repro.tomo.geometry import ParallelGeometry
from repro.tomo.phantom import simulate_phantom_scan

SEED = 2**31 + 99
CELLS = ("d1_paganin.batch", "d1_preview.tune")


def _bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _round_output(name):
    def fault(monkeypatch):
        fn = getattr(plugins, name)
        monkeypatch.setattr(plugins, name,
                            lambda *a, **k: _bf16(fn(*a, **k)))
    return fault


def _round_backprojection_input(monkeypatch):
    fn = plugins.backproject
    monkeypatch.setattr(plugins, "backproject",
                        lambda sino, *a, **k: fn(_bf16(sino), *a, **k))


def _served_and_reference(workload):
    config = rehearse.tiny_config(workload)
    spec = config["process_list"]
    for e in spec["plugins"]:
        if e["plugin"] == "synthetic_tomo_loader":
            e["params"]["seed"] = SEED
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    out = PluginRunner(from_spec(spec), ShardedTransport(mesh)).run()
    got = np.asarray(out["recon"].materialise())
    sinos, mu = reference.filtered_sinograms(spec, config["phantom"], SEED)
    rows = np.arange(got.shape[1])
    want = reference.volume_rows(spec, sinos, mu, rows)
    return reference.compare(got, want), config["check"]["limits"]


def test_reference_regenerates_the_program_s_raw_scan():
    lp = rehearse.TINY
    ours = reference.raw_scan(lp["n_angles"], lp["n_rows"], lp["n_det"],
                              SEED, {"i0": 40000.0, "dark_level": 96.0,
                                     "mu": 0.02, "noise": 0.0})
    theirs = simulate_phantom_scan(
        ParallelGeometry(lp["n_angles"], lp["n_det"], lp["n_rows"]),
        seed=SEED)
    assert np.array_equal(np.asarray(ours["data"]), theirs["data"])
    assert np.array_equal(ours["dark"], theirs["dark"])
    assert np.array_equal(ours["flat"], theirs["flat"])


@pytest.mark.parametrize("workload", CELLS)
def test_reference_agrees_with_the_program(workload):
    errs, limits = _served_and_reference(workload)
    for k, v in errs.items():
        assert v <= limits[k], (k, v, limits[k])


@pytest.mark.parametrize("fault", [
    _round_output("correct"), _round_output("filter_sino"),
    _round_backprojection_input],
    ids=["correction", "sino_filter", "backprojection_input"])
@pytest.mark.parametrize("workload", CELLS)
def test_reference_disagrees_with_a_bf16_kernel(workload, fault,
                                                monkeypatch):
    fault(monkeypatch)
    errs, limits = _served_and_reference(workload)
    assert any(v > limits[k] for k, v in errs.items()), errs
