"""End-to-end tomography: phantom → simulated scan → Savu chain → FBP
reconstruction ≈ phantom.  This is the paper's core workload."""
import numpy as np
import pytest

from repro.core import ChunkedFileTransport, InMemoryTransport, PluginRunner
from repro.tomo import (ParallelGeometry, forward_project, phantom_stack,
                        phantom_truth, shepp_logan, simulate_raw_scan,
                        standard_chain)


def _run(chain, transport=None):
    runner = PluginRunner(chain, transport or InMemoryTransport())
    out = runner.run()
    recon = np.asarray(runner.transport.read(out["recon"]))
    loaded = runner.lineage[0].metadata
    truth = loaded.get("truth")
    if truth is None:
        truth = phantom_truth(loaded["geometry"])
    return recon, truth, runner


def _quality(recon, truth):
    sl = slice(8, -8)
    t, x = truth[:, sl, sl], recon[:, sl, sl]
    corr = np.corrcoef(t.ravel(), x.ravel())[0, 1]
    return corr


def test_full_chain_reconstructs_phantom():
    recon, truth, _ = _run(standard_chain(n_det=64, n_angles=96, n_rows=2))
    assert recon.shape == truth.shape
    assert _quality(recon, truth) > 0.85


def test_chain_on_chunked_file_transport():
    recon, truth, runner = _run(
        standard_chain(n_det=64, n_angles=96, n_rows=2),
        ChunkedFileTransport())
    assert _quality(recon, truth) > 0.85
    stats = runner.transport.total_stats()
    assert stats.chunk_reads > 0 and stats.chunk_writes > 0


def test_chain_with_paganin():
    recon, truth, _ = _run(standard_chain(n_det=64, n_angles=96, n_rows=1,
                                          paganin=True, ring=False))
    # Paganin low-passes; correlation threshold relaxed
    assert _quality(recon, truth) > 0.7


def test_chain_survives_noise():
    recon, truth, _ = _run(standard_chain(n_det=64, n_angles=96, n_rows=1,
                                          noise=4.0))
    assert _quality(recon, truth) > 0.75


def test_ref_vs_pallas_chain_agree():
    r1, t1, _ = _run(standard_chain(n_det=64, n_angles=64, n_rows=1,
                                    use_pallas=True))
    r2, t2, _ = _run(standard_chain(n_det=64, n_angles=64, n_rows=1,
                                    use_pallas=False))
    np.testing.assert_allclose(r1, r2, rtol=1e-3, atol=1e-4)


def test_forward_projector_sanity():
    """Radon of a centred disc: projection mass ≈ π r² at every angle."""
    n = 64
    ys, xs = np.mgrid[-1:1:n * 1j, -1:1:n * 1j]
    disc = ((xs ** 2 + ys ** 2) <= 0.5 ** 2).astype(np.float32)
    geom = ParallelGeometry(8, n, 1)
    proj = forward_project(disc[None], geom)      # (angles, 1, det)
    sums = proj.sum(axis=-1)[:, 0]
    # mass conservation across angles
    assert sums.std() / sums.mean() < 0.02
    expected = np.pi * (0.5 * n / 2) ** 2
    assert abs(sums.mean() - expected) / expected < 0.05


def test_simulated_scan_fields():
    geom = ParallelGeometry(16, 32, 2)
    scan = simulate_raw_scan(phantom_stack(32, 2), geom)
    assert scan["data"].shape == (16, 2, 32)
    assert scan["data"].dtype == np.uint16
    assert scan["flat"].mean() > scan["dark"].mean()


def test_phantom_rows_differ():
    v = phantom_stack(32, 3)
    assert not np.allclose(v[0], v[2])
