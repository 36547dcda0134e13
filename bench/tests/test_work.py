"""The kernels' work counts depend on shapes alone."""
import inspect

import pytest

import harness
import metric_lib

SHAPES = {"backproject": dict(n_slices=32, n_angles=3072, n_det=2048,
                              out_size=2048),
          "sino_filter": dict(n_rows=32 * 3072, n_det=2048)}


@pytest.mark.parametrize("name", sorted(SHAPES))
def test_work_takes_only_shapes(name):
    work = metric_lib.load("work", name)
    params = inspect.signature(work.work).parameters
    assert sorted(params) == sorted(SHAPES[name])
    flops, nbytes = work.work(**SHAPES[name])
    assert flops > 0 and nbytes > 0
    assert work.work(**SHAPES[name]) == (flops, nbytes)


def test_backproject_work_is_per_pixel_angle_and_slice():
    work = metric_lib.load("work", "backproject").work
    f1, b1 = work(1, 3072, 2048, 2048)
    f2, b2 = work(2, 3072, 2048, 2048)
    assert (f2, b2) == (2 * f1, 2 * b1)
    assert f1 == 4 * 2048 * 2048 * 3072
    assert b1 == 4 * (3072 * 2048 + 2048 * 2048)
    # twice the angles, twice the operations
    assert work(1, 6144, 2048, 2048)[0] == 2 * f1


def test_sino_filter_work():
    work = metric_lib.load("work", "sino_filter")
    assert work.n_bins(2048) == 2049
    flops, nbytes = work.work(10, 2048)
    assert flops == 2 * 2049 * 10
    assert nbytes == 4 * (4 * 2049 * 10 + 2049)


def test_peaks_come_from_the_table():
    peaks = harness.peaks_for("TPU v5 lite")
    assert peaks["flops_per_s"] == 197e12
    assert peaks["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        harness.peaks_for("TPU v9 imaginary")
