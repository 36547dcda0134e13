"""Mean client time in ``GET /jobs/{id}/result`` per preview.  Jobs are
marked done when their steps are dispatched, so this includes the
device's tail of the job as well as the transfer."""


def read(run):
    times = [r.result_s for r in run.done]
    return sum(times) / len(times) if times else None
