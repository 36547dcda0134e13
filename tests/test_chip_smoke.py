"""chip_smoke.py's phase, driven on the CPU at a tiny size: the served
chain, the repeat scan's zero compiles and the reference comparison.
The script itself refuses to run without a TPU."""
import importlib.util
import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_phase_serves_and_checks_scans(chip_smoke):
    lines = []
    report = chip_smoke.serve_and_check(
        n_devices=1, n_rows=2, seeds=(1, 2), platform="cpu", n_det=32,
        n_angles=32, log=lines.append)
    assert report[1]["new_compiles"] > 0
    assert report[2]["new_compiles"] == 0
    for seed in (1, 2):
        r = report[seed]
        assert r["max_abs_diff"] <= chip_smoke.TOLERANCE * r["max_abs_ref"]
    assert set(chip_smoke.KERNEL_STEPS) <= set(report["kernels"])
    assert any(line.startswith("scan: 32 angles x 2 rows x 32 columns")
               for line in lines)


def test_phase_refuses_the_wrong_platform(chip_smoke):
    with pytest.raises(RuntimeError, match="expected tpu devices"):
        chip_smoke.serve_and_check(n_devices=1, n_rows=2, seeds=(1,),
                                   n_det=32, n_angles=32)


def test_script_without_tpu_fails_without_a_result(chip_smoke, capsys):
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr().out
    for line in out.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)
