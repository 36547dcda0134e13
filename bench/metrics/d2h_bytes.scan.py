"""Bytes per scan that the program copied from device to host: the
``bytes`` of its ``transfer.d2h`` spans (what feeds the service's
``transfer.d2h_bytes`` counter)."""
import spans


def read(run):
    total = spans.stat_sum(run, "transfer.d2h", "bytes")
    if total is None or not run.done:
        return None
    return total / len(run.done)
