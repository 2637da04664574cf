"""The one traffic generator: it reads a mix's parameters and drives calls.

A mix is a data file ``bench/traffic/<mix>.json``; a new mix is a new file,
and no code changes.  Its keys:

- ``loop``: ``closed`` or ``open``.
  - ``closed``: ``clients`` clients, each sending its next call when the
    result of its last one is ready (``block_until_ready``), so that at most
    ``clients`` calls are in flight.
  - ``open``: calls arrive on a schedule, whether or not the earlier ones are
    done.  ``rate_per_s`` arrival events a second, ``burst`` calls at each
    event (1 when not given), and the gaps between events ``poisson``
    (exponential) or ``uniform`` (all equal) by ``arrivals``.  Every seed
    gets the same set of gaps, in another order, so that the seed changes the
    order of the work and not its amount.
- ``pool``: how many input sets the seed makes; call i takes set i mod pool.
- ``keep``: how many answers of the window are kept for the comparison, a
  sample drawn from the seed.
- ``warmup``: calls made before the window, counted as set-up.
- ``why``: one line on the users who send such traffic.

A closed window starts with the first call and closes at the completion of
the call in flight when ``seconds`` have passed, so no call is cut.  An open
window offers the calls that arrive in its first ``seconds`` and closes when
the last of them has completed: a late answer is late, never left out.  A
call's latency runs from its arrival (in a closed loop, its dispatch) to its
completion.  Calls complete in the order they were sent, as one device
stream runs them.

:func:`end_to_end` computes the window's end-to-end metrics by name, so that
a cell of a new mix reports what its entry in ``BENCHMARK.json`` names.
"""
from __future__ import annotations

import math
import queue
import random
import re
import threading
import time
from collections import deque
from typing import Callable, List, NamedTuple

import jax
from jax.profiler import TraceAnnotation

ARRIVALS = ("poisson", "uniform")


class Window(NamedTuple):
    calls: int               # calls completed in the window
    seconds: float           # the window's start to the last completion
    kept: dict               # call index -> its result, the seeded sample
    summaries: List[object]  # summarize(result) of every call, in call order
    latency_s: List[float]   # each call's arrival to its completion


def check_mix(mix: dict) -> None:
    """Raise on a mix this generator cannot drive as its file states."""
    loop = mix.get("loop")
    if loop == "closed":
        ok = int(mix.get("clients", 0)) >= 1
    elif loop == "open":
        ok = (float(mix.get("rate_per_s", 0)) > 0
              and mix.get("arrivals") in ARRIVALS
              and int(mix.get("burst", 1)) >= 1)
    else:
        ok = False
    ok = ok and int(mix.get("pool", 0)) >= 1 and int(mix.get("keep", -1)) >= 0
    if not ok or int(mix.get("warmup", -1)) < 0:
        raise ValueError(f"traffic mix {mix!r} is not one this generator "
                         f"drives: see bench/loop.py")


def arrival_times(mix: dict, seconds: float, seed: int) -> List[float]:
    """Each call's arrival, in seconds from the window's start, of an open
    mix: ``round(rate_per_s * seconds)`` events of ``burst`` calls, each a
    gap after the last (the first a gap after the start).  Poisson
    gaps are the exponential distribution's quantiles at the midpoints of
    equal steps of probability, shuffled by ``seed``."""
    rate = float(mix["rate_per_s"])
    n = max(1, round(rate * seconds))
    if mix["arrivals"] == "uniform":
        gaps = [1.0 / rate] * n
    else:
        gaps = [-math.log(1.0 - (k + 0.5) / n) / rate for k in range(n)]
        random.Random(seed).shuffle(gaps)
    times, t = [], 0.0
    for g in gaps:
        t += g
        times.extend([t] * int(mix.get("burst", 1)))
    return times


class _Record:
    """The window's results as they complete: a reservoir sample of ``keep``
    of them drawn from ``seed``, and every one's summary and latency."""

    def __init__(self, keep: int, seed: int, summarize):
        self.keep, self.rng, self.summarize = keep, random.Random(seed), \
            summarize
        self.kept: dict = {}
        self.summaries: List[object] = []
        self.latency_s: List[float] = []

    def add(self, i: int, out, latency: float) -> None:
        self.summaries.append(self.summarize(out))
        self.latency_s.append(latency)
        if len(self.kept) < self.keep:
            self.kept[i] = out
        else:
            j = self.rng.randrange(i + 1)
            if j < self.keep:
                del self.kept[sorted(self.kept)[j]]
                self.kept[i] = out

    def window(self, seconds: float) -> Window:
        return Window(len(self.summaries), seconds, self.kept,
                      self.summaries, self.latency_s)


def drive(mix: dict, call: Callable[[int], object], seconds: float, *,
          seed: int, summarize: Callable[[object], object]) -> Window:
    """Drive ``call(i)`` for i = 0, 1, ... as ``mix`` says, for a window of
    ``seconds``; ``summarize(result)`` is kept of every result."""
    check_mix(mix)
    rec = _Record(int(mix["keep"]), seed, summarize)
    if mix["loop"] == "closed":
        return _closed(call, seconds, int(mix["clients"]), rec)
    return _open(call, arrival_times(mix, seconds, seed), rec)


def _closed(call, seconds: float, clients: int, rec: _Record) -> Window:
    inflight: deque = deque()
    i = 0
    with TraceAnnotation("bench.window"):
        t0 = time.perf_counter()
        t_end = t0
        while True:
            while len(inflight) < clients and (i == 0
                                               or t_end - t0 < seconds):
                t_sent = time.perf_counter()
                with TraceAnnotation("bench.dispatch"):
                    out = call(i)
                inflight.append((i, t_sent, out))
                i += 1
            if not inflight:
                break
            j, t_sent, out = inflight.popleft()
            with TraceAnnotation("bench.wait"):
                jax.block_until_ready(out)
            t_end = time.perf_counter()
            rec.add(j, out, t_end - t_sent)
    return rec.window(t_end - t0)


def _open(call, arrivals: List[float], rec: _Record) -> Window:
    sent: queue.Queue = queue.Queue()
    ends: List[float] = []
    errors: List[BaseException] = []

    def wait_all():
        while True:
            item = sent.get()
            if item is None:
                return
            i, t_arrival, out = item
            try:
                with TraceAnnotation("bench.wait"):
                    jax.block_until_ready(out)
                ends.append(time.perf_counter())
                rec.add(i, out, ends[-1] - t_arrival)
            except BaseException as e:      # re-raised by the dispatcher
                errors.append(e)
                return

    waiter = threading.Thread(target=wait_all, name="bench-wait")
    with TraceAnnotation("bench.window"):
        t0 = time.perf_counter()
        waiter.start()
        try:
            for i, at in enumerate(arrivals):
                if errors:
                    break
                delay = t0 + at - time.perf_counter()
                if delay > 0:
                    with TraceAnnotation("bench.arrival"):
                        time.sleep(delay)
                with TraceAnnotation("bench.dispatch"):
                    out = call(i)
                sent.put((i, t0 + at, out))
        finally:
            sent.put(None)
            waiter.join()
    if errors:
        raise errors[0]
    return rec.window(ends[-1] - t0)


_PERCENTILE = re.compile(r"[A-Za-z0-9_.-]+_p(\d+(?:\.\d+)?)_ms")


def percentile(values: List[float], q: float) -> float:
    """The nearest-rank ``q``-th percentile: the smallest value that at least
    ``q`` percent of ``values`` do not exceed."""
    ordered = sorted(values)
    return ordered[max(0, math.ceil(q / 100.0 * len(ordered)) - 1)]


def end_to_end(name: str, win: Window, items_per_call: int) -> float:
    """The end-to-end metric ``name`` of a window, over all its calls and
    all its time:

    - ``items_per_s``: input items of all calls completed, over the window;
    - ``<anything>_p<q>_ms``: the ``q``-th percentile of all calls'
      latencies, in ms (e.g. ``query_p95_ms``).
    """
    if name == "items_per_s":
        return items_per_call * win.calls / win.seconds
    m = _PERCENTILE.fullmatch(name)
    if m:
        return 1e3 * percentile(win.latency_s, float(m.group(1)))
    raise KeyError(f"no end-to-end metric {name!r} in bench/loop.py")
