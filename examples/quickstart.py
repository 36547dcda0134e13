"""Quickstart: the paper's standard full-field chain on a synthetic
scan, serial (PC) mode — loader → dark/flat correction → ring removal →
sinogram filter → FBP → saver.

    PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.core import InMemoryTransport, PluginRunner
from repro.tomo import phantom_truth, standard_chain


def main():
    chain = standard_chain(n_det=64, n_angles=96, n_rows=2, ring=True)
    runner = PluginRunner(chain, InMemoryTransport(), output_dir="out")
    datasets = runner.run()

    recon = np.asarray(datasets["recon"].materialise())
    truth = phantom_truth(runner.lineage[0].metadata["geometry"])
    sl = slice(8, -8)
    corr = np.corrcoef(truth[:, sl, sl].ravel(),
                       recon[:, sl, sl].ravel())[0, 1]
    print(f"reconstructed volume: {recon.shape}, "
          f"corr vs phantom = {corr:.3f}")
    print()
    print(runner.profiler.report())
    print("\nmanifest + intermediates described in out/savu_manifest.nxs.json")


if __name__ == "__main__":
    main()
