"""Device time of the Mosaic kernels (the Pallas shuffle route's
``bincount_tiles`` and ``bitonic_sort``) per completed sort, averaged over
the cell's chips (ms)."""
from bench import trace as tr


def read(run):
    if run.trace is None or run.calls == 0:
        return None
    s = tr.op_seconds(run.trace, tr.is_mosaic)
    return None if s is None else 1e3 * s / run.calls
