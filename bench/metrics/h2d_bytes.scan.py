"""Bytes per scan that the program copied from host to device: the
``bytes`` of its ``transfer.h2d`` spans (what feeds the service's
``transfer.h2d_bytes`` counter)."""
import spans


def read(run):
    total = spans.stat_sum(run, "transfer.h2d", "bytes")
    if total is None or not run.done:
        return None
    return total / len(run.done)
