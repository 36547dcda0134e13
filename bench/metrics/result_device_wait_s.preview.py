"""Seconds per preview the result fetch waits for the device
(``result.device_wait``): the device's tail that a job, marked done once
its steps are dispatched, leaves to the fetch."""
import spans


def read(run):
    return spans.per_request(run, "result.device_wait")
