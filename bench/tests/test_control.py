"""The control: the reference with the backprojection reading a
bfloat16 sinogram, in the program's place, fails the cell's limits
(the chip run of the same comparison at the cells' own size is
``calibrate.py``'s)."""
import numpy as np
import pytest

import reference
import rehearse

SEEDS = (2**31 + 1, 2**31 + 2, 3)


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("workload", ("d1_paganin.batch",
                                      "d1_preview.tune"))
def test_control_fails_the_limits(workload, seed):
    config = rehearse.tiny_config(workload)
    spec, phantom = config["process_list"], config["phantom"]
    rows = np.arange(rehearse.TINY["n_det"])
    sinos, mu = reference.filtered_sinograms(spec, phantom, seed)
    want = reference.volume_rows(spec, sinos, mu, rows)
    low, _ = reference.filtered_sinograms(spec, phantom, seed,
                                          round_to=frozenset({"fbp_input"}))
    errs = reference.compare(reference.volume_rows(spec, low, mu, rows),
                             want)
    limits = config["check"]["limits"]
    assert all(v > limits[k] for k, v in errs.items()), errs
