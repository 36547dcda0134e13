"""Seconds per scan in the server's ``result.send``: the npy header and
the volume's bytes written to the client's socket."""
import spans


def read(run):
    return spans.per_request(run, "result.send")
