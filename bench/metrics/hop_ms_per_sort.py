"""Own device time of the sharded hop per completed sort, averaged over the
cell's chips (ms): every op of the ``ShardedEngine``'s hop program
(``mr_hop``: the keyed ``all_to_all`` and its send-side stats;
``bench.scopes``)."""
from bench import scopes


def read(run):
    if run.trace is None or run.calls == 0:
        return None
    s = scopes.layer_seconds(run.trace, ("mr.hop",))
    return None if s is None else 1e3 * s / run.calls
