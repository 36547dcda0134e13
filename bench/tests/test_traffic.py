"""Traffic is data: a mix names the loop that paces it and may give
parameter variants; a cell with a new mix needs no code."""
import numpy as np
import pytest

import generator
import harness
import rehearse
from loops import open as open_loop

MIXES = {
    "open_uniform": {"loop": "open", "rate_per_s": 4.0,
                     "arrivals": "uniform"},
    "open_poisson": {"loop": "open", "rate_per_s": 4.0,
                     "arrivals": "poisson"},
    "closed_cutoff_sweep": {"loop": "closed", "clients": 2,
                            "variants": [{"sinogram_filter.cutoff": 0.5},
                                         {"sinogram_filter.cutoff": 0.8},
                                         {"sinogram_filter.cutoff": 1.0}]},
}


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_a_mix_of_data_runs_and_is_correct(mix):
    traffic = {"poll_s": 0.01, "warmup_requests": 2,
               "request_timeout_s": 60, **MIXES[mix]}
    result = harness.run_cell(
        harness.load_benchmark(), "d1_preview.tune", seed=2**31 + 11,
        seconds=1.0, trace=False, t_start=generator.clock(),
        platform="cpu", config=rehearse.tiny_config("d1_preview.tune"),
        traffic=traffic, log=lambda msg: None)
    assert result["correct"] is True, result["check"]
    assert result["failed"] == 0 and result["attempted"] >= 2


def test_variants_reach_the_served_chain_and_the_reference():
    config = rehearse.tiny_config("d1_preview.tune")
    source = generator.Source(config, MIXES["closed_cutoff_sweep"],
                              seed=5, stream=1, prefix="req")
    cutoffs = []
    for _ in range(4):
        _, _, spec = source.take()
        params = {e["plugin"]: e.get("params", {}) for e in spec["plugins"]}
        cutoffs.append(params["sinogram_filter"]["cutoff"])
    assert cutoffs == [0.5, 0.8, 1.0, 0.5]
    with pytest.raises(KeyError):
        generator.spec_for(config, 1, {"paganin_filter.tau": 1.0})


def test_poisson_arrivals_offer_every_seed_the_same_gaps():
    a = open_loop.gaps(4.0, 200, "poisson", [1, 1, 1])
    b = open_loop.gaps(4.0, 200, "poisson", [2**33 + 5, 1, 1])
    assert not np.array_equal(a, b)
    np.testing.assert_array_equal(np.sort(a), np.sort(b))
    assert abs(a.mean() - 0.25) < 0.01
    np.testing.assert_array_equal(open_loop.gaps(4.0, 3, "uniform", 0),
                                  [0.25] * 3)


def test_an_unknown_loop_is_an_error():
    with pytest.raises(FileNotFoundError):
        generator.drive(None, None, {"loop": "no_such_loop"}, count=1)
