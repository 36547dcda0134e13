"""Cells shrunk to a CPU-sized scan, for the tests."""
import harness

#: a D1-shaped scan shrunk for the CPU: angles, rows (at most), columns
TINY = {"n_angles": 64, "n_rows": 4, "n_det": 64}


def tiny_config(workload: str) -> dict:
    """The cell's configuration with its scan shrunk to :data:`TINY`."""
    _, config, _ = harness.cell_parts(harness.load_benchmark(), workload)
    for e in config["process_list"]["plugins"]:
        if e["plugin"] == "synthetic_tomo_loader":
            e["params"].update(
                n_angles=TINY["n_angles"], n_det=TINY["n_det"],
                n_rows=min(e["params"]["n_rows"], TINY["n_rows"]))
    config["check"]["image_rows"] = 16
    return config


def run(workload: str, seed: int = 2**31 + 7, seconds: float = 1.0,
        config: dict | None = None) -> dict:
    """One run of ``workload`` on the CPU at :data:`TINY` size."""
    return harness.run_cell(
        harness.load_benchmark(), workload, seed=seed, seconds=seconds,
        trace=False, t_start=harness.generator.clock(), platform="cpu",
        config=config or tiny_config(workload), log=lambda msg: None)
