"""Own device time of the shuffle per completed sort, averaged over the
cell's chips (ms): on four chips every op of the ``ShardedEngine``'s hop
and scatter programs (``mr_hop``, ``mr_scatter``; ``bench.scopes``).  The
one-chip cells' shuffle is one scope of a larger program, and is read once
the harness passes that program's op names (``bench.scopes.op_names``)."""
from bench import scopes


def read(run):
    if run.trace is None or run.calls == 0:
        return None
    s = scopes.layer_seconds(run.trace, ("mr.shuffle", "mr.hop"))
    return None if s is None else 1e3 * s / run.calls
