"""The sinogram filter's spectrum-scale kernel's share of its roofline:
the least time the chip allows for its bytes and operations
(``work/sino_filter.py``, from shapes) over its device time in the
trace."""
import metric_lib


def read(run):
    if run.events is None or not run.done:
        return None
    work = metric_lib.load("work", "sino_filter")
    seconds, calls = metric_lib.kernel_seconds(run, work.KERNEL)
    if not calls:
        return None
    p = metric_lib.loader(run.config)
    rows = p["n_rows"] * p["n_angles"] * len(run.done)
    flops, nbytes = work.work(rows, p["n_det"])
    return metric_lib.roofline_share(run, flops, nbytes, seconds)
