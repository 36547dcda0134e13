"""Share of the traced window in which no operation ran on the device."""
import metric_lib


def read(run):
    return metric_lib.idle_share(run)
