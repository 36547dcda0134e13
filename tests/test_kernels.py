"""Per-kernel shape/dtype sweeps against the pure-jnp oracles
(interpret=True executes the Pallas kernel bodies on CPU)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels.backproject.kernel import (backproject_pallas,
                                              detector_padding)
from repro.kernels.backproject.ops import (VMEM_BUDGET_BYTES, _pick_blocks,
                                           backproject)
from repro.kernels.backproject.ref import backproject_ref
from repro.kernels.correction.kernel import correct_pallas
from repro.kernels.correction.ref import correct_ref
from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.flash_attention.ref import mha_chunked_ref, mha_ref
from repro.kernels.sino_filter.kernel import scale_spectrum_pallas
from repro.kernels.sino_filter.ref import filter_sino_ref, make_filter
from repro.kernels.sino_filter.ops import filter_sino


# ----------------------------------------------------------------- FBP
@pytest.mark.parametrize("A,D,N,bh,bw,ba", [
    (16, 32, 32, 8, 128, 8),
    (32, 64, 64, 8, 128, 16),
    (24, 48, 48, 16, 128, 8),
    (8, 128, 64, 8, 128, 8),
    (20, 64, 200, 64, 256, 8),     # image wider than the detector
])
def test_backproject_shapes(rng, A, D, N, bh, bw, ba):
    sino = jnp.asarray(rng.normal(size=(A, D)).astype(np.float32))
    angles = jnp.linspace(0, np.pi, A, endpoint=False)
    ref = backproject_ref(sino, angles, N)
    out = backproject_pallas(sino, jnp.cos(angles).reshape(-1, 1),
                             jnp.sin(angles).reshape(-1, 1),
                             out_size=N, bh=bh, bw=bw, ba=ba,
                             interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("N,A,D", [(2048, 3072, 2048), (2000, 1800, 2000),
                                   (32, 16, 32), (130, 33, 100)])
def test_pick_blocks_tiles_within_budget(N, A, D):
    bh, bw, ba = _pick_blocks(N, A, D)
    assert bh % 8 == 0 and bw % 128 == 0 and ba % 8 == 0
    _, length = detector_padding(N, D, (D - 1) / 2.0, bh, bw)
    assert 4 * (2 * 2 * ba * length + 2 * bh * bw) <= VMEM_BUDGET_BYTES


def test_pick_blocks_refuses_rows_past_vmem():
    with pytest.raises(ValueError, match="VMEM"):
        _pick_blocks(64, 64, 200_000)


def test_backproject_ops_batched(rng):
    sino = jnp.asarray(rng.normal(size=(3, 16, 32)).astype(np.float32))
    angles = jnp.linspace(0, np.pi, 16, endpoint=False)
    out = backproject(sino, angles, 32)
    assert out.shape == (3, 32, 32)
    for i in range(3):
        ref = backproject_ref(sino[i], angles, 32)
        np.testing.assert_allclose(np.asarray(out[i]), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)


def test_backproject_centre_offset(rng):
    sino = jnp.asarray(rng.normal(size=(16, 32)).astype(np.float32))
    angles = jnp.linspace(0, np.pi, 16, endpoint=False)
    ref = backproject_ref(sino, angles, 32, centre=17.5)
    out = backproject(sino, angles, 32, centre=17.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


# ----------------------------------------------------------- correction
@pytest.mark.parametrize("dtype", [np.uint16, np.float32])
@pytest.mark.parametrize("shape", [(2, 8, 128), (5, 33, 64), (1, 16, 256)])
def test_correction_sweep(rng, dtype, shape):
    raw = rng.integers(50, 40000, size=shape).astype(dtype)
    dark = rng.integers(80, 120, size=shape[1:]).astype(dtype)
    flat = rng.integers(30000, 42000, size=shape[1:]).astype(dtype)
    out = correct_pallas(jnp.asarray(raw), jnp.asarray(dark),
                         jnp.asarray(flat), interpret=True)
    ref = correct_ref(jnp.asarray(raw), jnp.asarray(dark)[None],
                      jnp.asarray(flat)[None])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)


def test_correction_handles_dead_pixels(rng):
    raw = np.full((1, 8, 128), 0, np.uint16)          # dead detector
    dark = np.full((8, 128), 100, np.uint16)
    flat = np.full((8, 128), 100, np.uint16)           # flat == dark!
    out = correct_pallas(jnp.asarray(raw), jnp.asarray(dark),
                         jnp.asarray(flat), interpret=True)
    assert np.all(np.isfinite(np.asarray(out)))


# ----------------------------------------------------------- sino filter
@pytest.mark.parametrize("kind", ["ramlak", "shepp", "cosine", "hann"])
@pytest.mark.parametrize("F,D", [(6, 64), (3, 100), (16, 32)])
def test_sino_filter_sweep(rng, kind, F, D):
    sino = jnp.asarray(rng.normal(size=(F, D)).astype(np.float32))
    filt = jnp.asarray(make_filter(D, kind))
    a = filter_sino(sino, filt, use_pallas=True, interpret=True)
    b = filter_sino_ref(sino, filt)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-5)


def test_scale_spectrum_kernel_direct(rng):
    re = jnp.asarray(rng.normal(size=(4, 65)).astype(np.float32))
    im = jnp.asarray(rng.normal(size=(4, 65)).astype(np.float32))
    filt = jnp.asarray(rng.normal(size=(1, 65)).astype(np.float32))
    fre, fim = scale_spectrum_pallas(re, im, filt, interpret=True)
    np.testing.assert_allclose(np.asarray(fre), np.asarray(re * filt),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(fim), np.asarray(im * filt),
                               rtol=1e-6)


# ------------------------------------------------------ flash attention
@pytest.mark.parametrize("B,Hq,Hkv,S,D", [
    (2, 4, 2, 64, 16),
    (1, 8, 1, 128, 32),
    (2, 4, 4, 32, 64),
    (1, 6, 2, 96, 16),
])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(rng, B, Hq, Hkv, S, D, causal):
    q = jnp.asarray(rng.normal(size=(B, Hq, S, D)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, Hkv, S, D)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, Hkv, S, D)).astype(np.float32))
    o = flash_attention_pallas(q, k, v, causal=causal, bq=32, bk=32,
                               interpret=True)
    r = mha_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(o), np.asarray(r), rtol=2e-5,
                               atol=2e-5)


def test_flash_attention_bf16(rng):
    q = jnp.asarray(rng.normal(size=(1, 2, 64, 32))).astype(jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(1, 2, 64, 32))).astype(jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(1, 2, 64, 32))).astype(jnp.bfloat16)
    o = flash_attention_pallas(q, k, v, causal=True, bq=32, bk=32,
                               interpret=True)
    r = mha_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(r, np.float32), rtol=5e-2,
                               atol=5e-2)


def test_chunked_attention_matches_ref(rng):
    q = jnp.asarray(rng.normal(size=(2, 4, 128, 16)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(2, 2, 128, 16)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(2, 2, 128, 16)).astype(np.float32))
    for causal in (True, False):
        a = mha_chunked_ref(q, k, v, causal=causal, block_q=32)
        b = mha_ref(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)
