"""90th percentile of the client-timed latency (submit until the
result's bytes are in hand) of every preview completed in the window."""
import metric_lib


def read(run):
    return metric_lib.percentile([r.latency_s for r in run.done], 90)
