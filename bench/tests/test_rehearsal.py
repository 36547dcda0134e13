"""Both cells end to end on the CPU at a tiny size: the traffic, the
served path, the reference comparison and the result line; and the
faults the comparison has to catch."""
import json
import os
import shutil
import subprocess
import sys

import jax.numpy as jnp
import pytest

import harness
import rehearse
from repro.tomo import plugins

CELLS = ("d1_paganin.batch", "d1_preview.tune")


@pytest.mark.parametrize("workload", CELLS)
def test_cell_runs_and_is_correct(workload):
    result = rehearse.run(workload)
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "check"]
    assert result["correct"] is True
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert result["check"]["failed"]["value"] == 0
    expect = {m["name"] for m in harness.metrics_of(
        harness.load_benchmark(), workload, trace=False)}
    assert set(result["metrics"]) == expect
    assert all(v["value"] > 0 for v in result["metrics"].values())
    assert result["device"]["platform"] == "cpu"
    json.dumps(result)


def _alter_answer(monkeypatch):
    """The backprojection's image altered where it is produced: one
    slice's first row off by 1% of the image's largest value."""
    process = plugins.FBPRecon.process_frames

    def altered(self, frames):
        img = process(self, frames)
        return img.at[0, 0, :].add(0.01 * jnp.max(jnp.abs(img)))

    monkeypatch.setattr(plugins.FBPRecon, "process_frames", altered)


def _bf16_correction(monkeypatch):
    """The correction kernel's output rounded to bfloat16."""
    correct = plugins.correct
    monkeypatch.setattr(plugins, "correct", lambda *a, **k: correct(
        *a, **k).astype(jnp.bfloat16).astype(jnp.float32))


@pytest.mark.parametrize("fault", [_alter_answer, _bf16_correction])
@pytest.mark.parametrize("workload", CELLS)
def test_a_wrong_answer_is_not_correct(workload, fault, monkeypatch):
    fault(monkeypatch)
    config = rehearse.tiny_config(workload)
    # every image row is checked, so the altered row is among them
    config["check"]["image_rows"] = rehearse.TINY["n_det"]
    result = rehearse.run(workload, config=config)
    assert result["correct"] is False
    assert result["failed"] == 0
    assert any(v["value"] > v["limit"] for k, v in result["check"].items()
               if k in ("max_err", "rms_err"))


def test_failed_requests_are_not_correct(monkeypatch):
    real = plugins.SyntheticTomoLoader.load
    cfg = rehearse.tiny_config("d1_preview.tune")
    calls = []

    def flaky(self):
        calls.append(1)
        # the warm-up passes; every request of the window fails
        if len(calls) > 3:
            raise RuntimeError("loader fault")
        return real(self)
    monkeypatch.setattr(plugins.SyntheticTomoLoader, "load", flaky)
    result = rehearse.run("d1_preview.tune", config=cfg)
    assert result["correct"] is False
    assert result["failed"] == result["attempted"] >= 1
    assert "preview_p90_s" not in result["metrics"]


def test_run_without_a_tpu_exits_1_with_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(harness.HERE, "run.py"),
         "--workload", "d1_preview.tune", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300)
    assert proc.returncode == 1
    assert proc.stdout == ""
    assert "no tpu devices" in proc.stderr


def test_run_alone_in_a_directory_exits_2(tmp_path):
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(harness.HERE, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "d1_preview.tune",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=tmp_path, timeout=300)
    assert proc.returncode == 2
    assert proc.stdout == ""
