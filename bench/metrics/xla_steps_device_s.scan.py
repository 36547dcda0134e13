"""Device seconds per scan spent outside the three Mosaic kernels: the
XLA programs of the loader, Paganin, ring removal and the filter's
FFTs."""
import metric_lib
import reduce_trace

KERNELS = ("correct_pallas", "scale_spectrum_pallas", "backproject_pallas")


def read(run):
    if run.events is None or not run.done:
        return None
    lo, hi = run.trace_window
    total = reduce_trace.op_seconds(run.events, lo, hi)
    kernels = sum(reduce_trace.op_seconds(run.events, lo, hi, k)
                  for k in KERNELS)
    return (total - kernels) / len(run.done)
