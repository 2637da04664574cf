"""Share of the kernel shuffle route's roofline (%): the least time its
shuffles could take at the HBM peak (``bench.roofline``) over the device
time of its Mosaic kernels per sort.  The time covers the Mosaic calls only;
the route's placement scatter, outside the kernels, is not in it."""
from bench import roofline
from bench import trace as tr


def read(run):
    if run.trace is None or run.calls == 0 or run.route_dense:
        return None
    s = tr.op_seconds(run.trace, tr.is_mosaic)
    if s is None:
        return None
    least = roofline.least_seconds(
        roofline.shuffle_bytes(run.items_per_call, run.item_bytes,
                               run.shuffles),
        run.peaks.hbm_bytes_per_s)
    return 100.0 * least / (s / run.calls)
