"""Share of the traced window in which no op runs on a chip, averaged over
the cell's chips (%)."""
from bench import trace as tr


def read(run):
    if run.trace is None:
        return None
    busy = tr.busy_s(run.trace)
    if busy is None:
        return None
    return 100.0 * (1.0 - busy / tr.window_s(run.trace))
