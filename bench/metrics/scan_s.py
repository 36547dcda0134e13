"""Seconds per scan: all the time of the window over the scans completed
in it.  The window runs until the request in flight at its end has come
back, and that request counts."""


def read(run):
    n = len(run.done)
    return (run.t1 - run.t0) / n if n else None
