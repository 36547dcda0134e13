"""The tomo kernels compile for a TPU v5e at beamline widths (ROADMAP
D1: 3072 angles × 2048 detector columns), without a chip: the TPU
compiler ships with jaxlib and compiles for a described topology.
Interpret-mode tests cannot see what Mosaic refuses (tiling, casts,
VMEM); these compiles can.  Also: the backend -> interpret choice."""
from unittest import mock

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels import pallas_interpret
from repro.kernels.backproject.kernel import backproject_pallas
from repro.kernels.backproject.ops import _pick_blocks, backproject
from repro.kernels.correction.kernel import correct_pallas
from repro.kernels.correction.ops import correct
from repro.kernels.sino_filter.kernel import scale_spectrum_pallas
from repro.kernels.sino_filter.ops import filter_sino

N_ANGLES, N_DET, N_ROWS = 3072, 2048, 32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # noqa: BLE001 — any failure: no TPU
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a persistent-cache entry for a described chip cannot be read back
    # without one; keep these compiles out of the cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_mosaic(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def test_correction_compiles_at_d1(one_chip):
    fn = jax.jit(lambda r, d, f: correct_pallas(r, d, f, interpret=False))
    _assert_mosaic(fn.lower(
        _spec((1, N_DET, N_DET), jnp.uint16, one_chip),
        _spec((N_DET, N_DET), jnp.float32, one_chip),
        _spec((N_DET, N_DET), jnp.float32, one_chip)).compile())


def test_spectrum_scale_compiles_at_d1(one_chip):
    nf = N_DET + 1           # rfft bins of the 2·n_det padded row
    fn = jax.jit(lambda re, im, f: scale_spectrum_pallas(
        re, im, f, interpret=False))
    _assert_mosaic(fn.lower(
        _spec((N_ANGLES * N_ROWS, nf), jnp.float32, one_chip),
        _spec((N_ANGLES * N_ROWS, nf), jnp.float32, one_chip),
        _spec((1, nf), jnp.float32, one_chip)).compile())


def test_backprojection_compiles_at_d1(one_chip):
    bh, bw, ba = _pick_blocks(N_DET, N_ANGLES, N_DET)
    fn = jax.jit(lambda s, c, si: backproject_pallas(
        s, c, si, out_size=N_DET, bh=bh, bw=bw, ba=ba, interpret=False))
    _assert_mosaic(fn.lower(
        _spec((N_ANGLES, N_DET), jnp.float32, one_chip),
        _spec((N_ANGLES, 1), jnp.float32, one_chip),
        _spec((N_ANGLES, 1), jnp.float32, one_chip)).compile())


def test_wrappers_compile_kernels_on_tpu(one_chip):
    """With the backend reporting a TPU, every wrapper emits a Mosaic
    kernel (never the interpreter, never the jnp reference)."""
    angles = _spec((N_ANGLES,), jnp.float32, one_chip)
    filt = _spec((N_DET + 1,), jnp.float32, one_chip)
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        _assert_mosaic(jax.jit(correct).lower(
            _spec((2, 8, N_DET), jnp.uint16, one_chip),
            _spec((8, N_DET), jnp.float32, one_chip),
            _spec((8, N_DET), jnp.float32, one_chip)).compile())
        _assert_mosaic(jax.jit(filter_sino).lower(
            _spec((2, N_ANGLES, N_DET), jnp.float32, one_chip),
            filt).compile())
        _assert_mosaic(jax.jit(backproject, static_argnums=2).lower(
            _spec((1, N_ANGLES, N_DET), jnp.float32, one_chip),
            angles, N_DET).compile())


def test_chain_steps_keep_f32_precision(topo):
    """No step of the standard chain leaves a convolution or matmul at
    the TPU's default (bf16-operand) precision: its rounding follows the
    compiled layout, so a mesh and one chip would disagree."""
    import numpy as np
    from jax.sharding import Mesh
    from repro.launch.rehearse_chain import compile_steps
    from repro.tomo import standard_chain

    mesh = Mesh(np.asarray(topo.devices[:1]), ("data",))
    chain = standard_chain(n_det=128, n_angles=64, n_rows=8, paganin=True)
    for p, compiled in compile_steps(chain, mesh):
        for line in compiled.as_text().splitlines():
            if " convolution(" in line or " dot(" in line:
                assert "operand_precision={highest" in line, (p.name, line)


# --------------------------------------------------- backend -> interpret
@pytest.mark.parametrize("backend,interpret", [("tpu", False),
                                               ("cpu", True)])
def test_interpret_mode_follows_backend(backend, interpret):
    with mock.patch.object(jax, "default_backend", lambda: backend):
        assert pallas_interpret() is interpret
        assert pallas_interpret(interpret) is interpret
        with pytest.raises(ValueError, match="interpret"):
            pallas_interpret(not interpret)


def test_interpret_mode_refuses_other_backends():
    with mock.patch.object(jax, "default_backend", lambda: "gpu"):
        with pytest.raises(RuntimeError, match="'gpu'"):
            pallas_interpret()


@pytest.mark.parametrize("wrapper", ["correct", "filter_sino",
                                     "backproject"])
def test_wrappers_refuse_a_mode_the_backend_cannot_run(wrapper):
    """All three wrappers take the same ``interpret`` argument and hold
    it to the backend: compiled Pallas is refused on a CPU."""
    x = jnp.ones((2, 8, 128), jnp.float32)
    calls = {
        "correct": lambda: correct(x.astype(jnp.uint16), x[0], x[0] * 2,
                                   interpret=False),
        "filter_sino": lambda: filter_sino(x, jnp.ones((129,)),
                                           interpret=False),
        "backproject": lambda: backproject(x, jnp.zeros((8,)), 16,
                                           interpret=False),
    }
    with mock.patch.object(jax, "default_backend", lambda: "cpu"):
        with pytest.raises(ValueError, match="interpret=False"):
            calls[wrapper]()
