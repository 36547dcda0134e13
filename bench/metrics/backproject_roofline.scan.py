"""The backprojection kernel's share of its roofline: the least time
the chip allows for its work (``work/backproject.py``, from shapes)
over the kernel's device time in the trace."""
import metric_lib


def read(run):
    if run.events is None or not run.done:
        return None
    work = metric_lib.load("work", "backproject")
    seconds, calls = metric_lib.kernel_seconds(run, work.KERNEL)
    if not calls:
        return None
    p = metric_lib.loader(run.config)
    slices = p["n_rows"] * len(run.done)
    flops, nbytes = work.work(slices, p["n_angles"], p["n_det"], p["n_det"])
    return metric_lib.roofline_share(run, flops, nbytes, seconds)
