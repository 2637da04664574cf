"""Typed spans and events in a bounded ring buffer: the `Tracer` core.

The paper's whole argument is a *cost* claim — O(log_M N) rounds, bounded
per-round communication — and "BSP vs MapReduce" (arXiv 1203.2081) argues
communication is precisely the term that separates the models, so it must
be measurable per hop, not just totaled in :class:`~repro.core.costmodel.
CostAccum` after the fact.  This module is the recording half of
``repro.obs`` (DESIGN.md §12): a process-local, injectable :class:`Tracer`
that every layer grown since PR 1 reports into —

- ``engine.round`` events from :meth:`repro.core.engine.MREngine.run_round`
  (declared vs measured (V_r, M_r), per-round :class:`RoundStats`, host
  wall time);
- ``plan.execute`` / ``plan.stage`` spans from
  :func:`repro.core.plan.execute_plan` (plan digest, declared schedule,
  measured round deltas);
- ``exe.call`` / ``exe.compile`` / ``cache.hit`` / ``cache.miss`` from
  :mod:`repro.core.api` and ``MREngine.compile``;
- ``shuffle.route`` from the kernel-vs-dense decision in
  ``LocalEngine``/``ShardedEngine`` (beside the engine's ``route_log``);
- ``serve.*`` dispatch/queue/retry lifecycle from
  :class:`repro.serve.QueryService`;
- ``fault.*`` / ``ckpt.*`` / ``recover.*`` from :mod:`repro.core.recovery`.

**On the profiler's clock**: whenever jax is not tracing, every span —
:data:`NULL_TRACER`'s included — also opens a
``jax.profiler.TraceAnnotation`` of its kind, carrying its ``stage`` and
``round`` attrs (:func:`annotate`), so a ``jax.profiler`` trace shows the
program's host boundaries (``exe.call``, ``plan.stage``, ``engine.round``)
beside the device's ops.  With no profiler running that is one inactive
``TraceMe`` per span; only a live :class:`Tracer` records into its ring.

**Zero overhead on jitted paths** is a hard contract: instrumentation lives
at host boundaries only, the default hook is the no-op :data:`NULL_TRACER`,
and a live :class:`Tracer` silently drops :meth:`Tracer.event` calls made
while jax is tracing (:func:`not_tracing` is False), so a
jitted round program lowers to exactly the same HLO with or without a
tracer attached — outputs and :class:`~repro.core.costmodel.CostAccum`
stay bit-identical (``tests/test_obs.py``).  The one deliberate exception
is :meth:`Tracer.trace_event`, which records *at trace time* — that is the
correct semantics for the kernel-vs-dense route decision, which fires once
per traced shape exactly like the engine's ``route_log`` counters.

>>> tr = Tracer(clock=iter(range(100)).__next__)
>>> with tr.span("plan.stage", plan="sort", stage="entry"):
...     tr.event("engine.round", round=0, items_sent=4)
>>> [e.kind for e in tr.events()]
['engine.round', 'plan.stage']
>>> tr.events()[0].attrs["plan"]          # span context stamps its events
'sort'
>>> NULL_TRACER.enabled
False
"""
from __future__ import annotations

import hashlib
import time
from collections import deque
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple

import jax

from .metrics import MetricsRegistry

__all__ = ["TraceEvent", "Tracer", "NullTracer", "NULL_TRACER",
           "annotate", "plan_token", "round_event"]

#: attrs inherited from the innermost enclosing span that sets them
_CONTEXT_KEYS = ("plan", "stage", "digest")
#: span attrs a profiler annotation carries
_ANNOTATED_KEYS = ("stage", "round")


def not_tracing() -> bool:
    """True when jax is NOT currently tracing (host/eager execution)."""
    return jax.core.trace_ctx.is_top_level()


class _Annotation:
    """A ``jax.profiler.TraceAnnotation`` opened only when jax is not
    tracing (at trace time a host span would time the trace, not the
    run)."""

    __slots__ = ("kind", "attrs", "_me")

    def __init__(self, kind: str, attrs: Dict[str, Any]):
        self.kind = kind
        self.attrs = attrs
        self._me = None

    def __setitem__(self, key: str, value) -> None:
        pass

    def __enter__(self) -> "_Annotation":
        if not_tracing():
            self._me = jax.profiler.TraceAnnotation(self.kind, **{
                k: self.attrs[k] for k in _ANNOTATED_KEYS
                if self.attrs.get(k) is not None})
            self._me.__enter__()
        return self

    def __exit__(self, *exc) -> None:
        if self._me is not None:
            self._me.__exit__(*exc)
            self._me = None


def annotate(kind: str, **attrs) -> _Annotation:
    """A host span of ``kind`` on the profiler's clock only: where the ring
    buffer records the same boundary as an event (``exe.call``,
    ``engine.round``), or where no tracer is attached.  Reads no device
    value and never blocks."""
    return _Annotation(kind, attrs)


class _AbstractValue(Exception):
    """An attr held a traced (abstract) value — the event must be dropped."""


def _host_value(v):
    """Coerce an attr to a JSON-able host value; raise on traced values."""
    if v is None or isinstance(v, (bool, int, float, str)):
        return v
    if isinstance(v, jax.core.Tracer):
        raise _AbstractValue(type(v).__name__)
    shape = getattr(v, "shape", None)
    if shape is not None:
        if shape == ():                 # 0-d device/np scalar -> python
            return v.item()
        return f"<array{tuple(shape)}>"
    return str(v)


class TraceEvent:
    """One recorded observation: a kind, a timestamp, an optional duration,
    and a flat string-keyed attribute dict (host scalars only).

    ``dur`` is None for instant events and the span's wall seconds (in the
    tracer's clock) for span records; ``ts`` is the event (or span-start)
    time.  :meth:`signature` is the time-free identity used by determinism
    tests: two traces of the same seeded run must have equal signature
    sequences even though their timestamps differ."""

    __slots__ = ("kind", "ts", "dur", "attrs")

    def __init__(self, kind: str, ts: float, dur: Optional[float] = None,
                 attrs: Optional[Dict[str, Any]] = None):
        self.kind = kind
        self.ts = float(ts)
        self.dur = None if dur is None else float(dur)
        self.attrs = {} if attrs is None else attrs

    def signature(self) -> Tuple:
        """(kind, sorted attrs) — everything except wall-clock fields."""
        return (self.kind, tuple(sorted(self.attrs.items())))

    def to_dict(self) -> Dict[str, Any]:
        d = {"kind": self.kind, "ts": self.ts}
        if self.dur is not None:
            d["dur"] = self.dur
        d["attrs"] = self.attrs
        return d

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "TraceEvent":
        return cls(d["kind"], d["ts"], d.get("dur"), dict(d.get("attrs", {})))

    def __repr__(self) -> str:
        dur = "" if self.dur is None else f", dur={self.dur:.6f}"
        return f"TraceEvent({self.kind!r}, ts={self.ts:.6f}{dur}, {self.attrs})"


class _Span:
    """Context manager recording a span event at exit; supports
    ``sp["key"] = value`` to attach attrs discovered mid-span.  Also opens
    the span's profiler annotation (:func:`annotate`)."""

    __slots__ = ("_tracer", "kind", "attrs", "_t0", "_live", "_note")

    def __init__(self, tracer: "Tracer", kind: str, attrs: Dict[str, Any]):
        self._tracer = tracer
        self.kind = kind
        self.attrs = attrs
        self._t0 = 0.0
        self._live = False
        self._note = _Annotation(kind, attrs)

    def __setitem__(self, key: str, value) -> None:
        self.attrs[key] = value

    def __enter__(self) -> "_Span":
        # A span opened at jax trace time must not record (nor leak stack
        # frames a later eager event would inherit stale context from).
        self._live = not_tracing()
        if self._live:
            self._note.__enter__()
            self._tracer._stack.append(self.attrs)
            self._t0 = self._tracer.clock()
        return self

    def __exit__(self, exc_type=None, *exc) -> None:
        if not self._live:
            return
        self._note.__exit__(exc_type, *exc)
        tr = self._tracer
        tr._stack.pop()
        if exc_type is not None:
            # A span aborted by an exception (e.g. an injected ShardFailure)
            # is marked rather than dropped: aggregation must not read its
            # missing measured fields as a schedule violation.
            self.attrs["aborted"] = True
        tr._record(self.kind, dur=tr.clock() - self._t0, attrs=self.attrs,
                   ts=self._t0)


class Tracer:
    """Bounded ring buffer of :class:`TraceEvent` plus a
    :class:`~repro.obs.metrics.MetricsRegistry` (the tentpole hook object).

    - ``maxlen`` bounds the ring: old events are overwritten, never grown —
      :attr:`overwritten` counts the loss, so exporters can say when a
      trace is truncated.
    - ``clock`` is the injectable time source (``time.perf_counter`` by
      default; a :class:`repro.serve.VirtualClock` makes every timestamp
      deterministic under test).
    - :meth:`event` drops silently while jax traces — the jit/scan
      neutrality contract (see module docstring); :meth:`trace_event`
      records even then (route decisions).  Attr values are coerced to
      host scalars at record time; an abstract (traced) value drops the
      event instead of leaking a tracer.
    - :meth:`span` opens a context: events recorded inside inherit the
      span's ``plan``/``stage``/``digest`` attrs, and the span itself is
      recorded at exit with its wall duration.
    """

    enabled = True

    def __init__(self, maxlen: int = 65536,
                 clock: Callable[[], float] = time.perf_counter):
        if int(maxlen) < 1:
            raise ValueError(f"maxlen must be >= 1, got {maxlen}")
        self.maxlen = int(maxlen)
        self.clock = clock
        self.metrics = MetricsRegistry()
        self._buf: "deque[TraceEvent]" = deque(maxlen=self.maxlen)
        self._stack: List[Dict[str, Any]] = []
        self.recorded = 0           # total records, including overwritten
        self.skipped = 0            # dropped: at trace time / abstract attrs

    # -- recording -----------------------------------------------------------
    def event(self, kind: str, _dur: Optional[float] = None,
              **attrs) -> None:
        """Record an instant event (``_dur`` attaches a measured duration).
        No-op while jax is tracing — jitted paths stay untouched."""
        if not not_tracing():
            self.skipped += 1
            return
        self._record(kind, dur=_dur, attrs=attrs)

    def trace_event(self, kind: str, **attrs) -> None:
        """Record even at jax trace time — for decisions that happen once
        per traced shape (the kernel-vs-dense route).  Attrs must already
        be host values; abstract values drop the event."""
        self._record(kind, dur=None, attrs=attrs)

    def span(self, kind: str, **attrs) -> _Span:
        """Open a span context (recorded at exit with its duration)."""
        return _Span(self, kind, attrs)

    def count(self, name: str, n: int = 1) -> None:
        """Increment a metrics counter — gated like :meth:`event`, so
        jitted paths never count at trace time."""
        if not_tracing():
            self.metrics.counter(name).inc(n)

    def observe(self, name: str, value: float) -> None:
        """Record a histogram observation (gated like :meth:`event`)."""
        if not_tracing():
            self.metrics.histogram(name).observe(value)

    def _record(self, kind: str, dur: Optional[float],
                attrs: Dict[str, Any], ts: Optional[float] = None) -> None:
        try:
            clean = {k: _host_value(v) for k, v in attrs.items()}
        except _AbstractValue:
            self.skipped += 1
            return
        for frame in reversed(self._stack):
            for key in _CONTEXT_KEYS:
                if key not in clean and key in frame:
                    clean[key] = frame[key]
        self._buf.append(TraceEvent(
            kind, self.clock() if ts is None else ts, dur, clean))
        self.recorded += 1

    # -- introspection -------------------------------------------------------
    @property
    def overwritten(self) -> int:
        """Events lost to the ring bound (recorded minus retained)."""
        return max(0, self.recorded - len(self._buf))

    def events(self) -> List[TraceEvent]:
        """Snapshot of the retained events, oldest first."""
        return list(self._buf)

    def signatures(self) -> List[Tuple]:
        """Time-free identities of the retained events (determinism
        tests compare these across replays)."""
        return [e.signature() for e in self._buf]

    def clear(self) -> None:
        """Drop retained events and reset loss counters (metrics keep)."""
        self._buf.clear()
        self.recorded = 0
        self.skipped = 0

    def __len__(self) -> int:
        return len(self._buf)


class NullTracer:
    """The default hook: every recording method is a no-op and ``enabled``
    is False, so instrumented call sites guard with one attribute read.
    Its spans record nothing but still open their profiler annotation
    (:func:`annotate`).  ``metrics`` is a shared inert registry (guarded
    call sites never write it)."""

    enabled = False
    metrics = MetricsRegistry()

    def event(self, kind: str, _dur=None, **attrs) -> None:
        pass

    def trace_event(self, kind: str, **attrs) -> None:
        pass

    def span(self, kind: str, **attrs) -> _Annotation:
        return _Annotation(kind, attrs)

    def count(self, name: str, n: int = 1) -> None:
        pass

    def observe(self, name: str, value: float) -> None:
        pass

    def events(self) -> list:
        return []

    def signatures(self) -> list:
        return []

    def clear(self) -> None:
        pass

    def __len__(self) -> int:
        return 0

    @property
    def overwritten(self) -> int:
        return 0

    @property
    def clock(self) -> Callable[[], float]:
        return time.perf_counter


#: process-wide shared no-op tracer — the default value of every hook slot
NULL_TRACER = NullTracer()


def plan_token(plan) -> str:
    """Stable short digest of ``(plan.fingerprint, plan.shape_fingerprint)``
    — the same token :func:`repro.core.recovery.plan_digest` keys
    checkpoint directories by, so a trace's ``digest`` attr and a
    checkpoint directory name agree for the same plan."""
    token = repr((plan.fingerprint, plan.shape_fingerprint))
    return hashlib.sha1(token.encode("utf-8")).hexdigest()[:16]


def round_event(tr, t0: float, backend: str, round_idx, n_nodes, capacity,
                stats) -> None:
    """Record one ``engine.round`` event from a measured
    :class:`~repro.core.costmodel.RoundStats` (shared by
    ``MREngine.run_round`` and the plan entry stage).  Reading the stats
    forces a host sync on device backends — the documented cost of opting
    into per-round tracing; with :data:`NULL_TRACER` this is never called."""
    tr.event("engine.round", _dur=tr.clock() - t0, backend=backend,
             round=round_idx, n_nodes=n_nodes, capacity=capacity,
             items_sent=stats.items_sent, max_sent=stats.max_sent,
             max_received=stats.max_received, dropped=stats.dropped)
    tr.count("engine.rounds")
