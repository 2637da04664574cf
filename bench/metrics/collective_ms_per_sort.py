"""Device time of the all-to-all collectives of the sharded hop
(``repro.core.distributed.keyed_hop``) per completed sort, averaged over the
cell's chips (ms)."""
from bench import trace as tr


def read(run):
    if run.trace is None or run.calls == 0:
        return None
    s = tr.op_seconds(run.trace, tr.is_all_to_all)
    return None if s is None else 1e3 * s / run.calls
