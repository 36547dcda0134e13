"""Seconds per scan in the program's host<->device copies
(``transfer.h2d`` and ``transfer.d2h`` spans: the loader's counts, the
raw scan into the first step, the volume to the result fetch)."""
import spans


def read(run):
    return spans.per_request(
        run, lambda name: name in ("transfer.h2d", "transfer.d2h"))
