"""Reduce a JAX profiler trace to device busy time, op times and idle gaps.

A run with ``--trace 1`` records its measured window with ``jax.profiler``
and reads the ``.xplane.pb`` back with ``jax.profiler.ProfileData``.  On a
TPU every chip is one plane named ``/device:TPU:<i>``:

- its ``XLA Ops`` line holds one event for each HLO operation that ran,
  named by the operation's HLO text (``%bitonic_sort.4 = (...)
  custom-call(...), custom_call_target="tpu_custom_call", ...``).  Events
  nest: a ``while`` op spans the ops of its body;
- its ``Async XLA Ops`` line holds asynchronous ops from start to done;
- its ``XLA Modules`` line holds one event for each program run
  (``jit_run(<fingerprint>)``).

The harness writes its own host spans into the same trace with
``jax.profiler.TraceAnnotation``: ``bench.window`` around the measured
window, ``bench.dispatch`` around each call into the program and
``bench.wait`` around each wait for a call's result.

From these the module gives a chip's busy time (the union of its op
intervals inside the window), its idle gaps (the rest of the window), each
gap's host phase (the harness span that overlaps it most, else ``other``),
the device time of a class of ops, and the ``breakdown`` of the result line.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Callable, Dict, List, NamedTuple, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW = "bench.window"
PHASES = ("bench.dispatch", "bench.wait")
# "%name = <type> opcode(...)": the first lower-case word after a space that
# opens a parenthesis is the opcode (types hold no such word).
_HLO = re.compile(r"^%(\S+) = (.*?) ([a-z][\w-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]*)"')


class Op(NamedTuple):
    name: str             # HLO instruction name, e.g. "bitonic_sort.4"
    kind: str             # HLO opcode, e.g. "custom-call", "fusion"
    target: str           # a custom call's target, else ""
    shape: str            # the HLO type of its result, cut to 48 characters
    module: str           # the program it ran in, e.g. "jit_run"
    start: int            # ns
    end: int              # ns


class Trace(NamedTuple):
    #: device plane name -> its XLA ops, sorted by start
    devices: Dict[str, List[Op]]
    #: device plane name -> its asynchronous ops, start to done
    async_ops: Dict[str, List[Op]]
    #: the harness's host spans (name, start, end), sorted by start
    host: List[Tuple[str, int, int]]
    #: the measured window (start, end), from the bench.window span
    window: Optional[Tuple[int, int]]


def find_xplane(log_dir: str) -> str:
    """The one ``.xplane.pb`` that ``jax.profiler.trace(log_dir)`` wrote."""
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, "
                                f"found {len(found)}")
    return found[0]


def parse_op(text: str, start: int, end: int, module: str = "") -> Op:
    """An :class:`Op` from an event's HLO text; text that is not HLO keeps
    its whole self as the name and an empty kind."""
    m = _HLO.match(text)
    if m is None:
        return Op(text, "", "", "", module, start, end)
    t = _TARGET.search(text)
    return Op(m.group(1), m.group(3), t.group(1) if t else "",
              m.group(2)[:48], module, start, end)


def _events(line):
    for ev in line.events:
        start = int(ev.start_ns)
        yield ev, start, start + int(ev.duration_ns)


def _module_of(modules: List[Tuple[int, int, str]], start: int) -> str:
    for s, e, name in modules:
        if s <= start < e:
            return name
    return ""


def from_profile(data) -> Trace:
    """Build a :class:`Trace` from a ``jax.profiler.ProfileData``."""
    devices: Dict[str, List[Op]] = {}
    async_ops: Dict[str, List[Op]] = {}
    host: List[Tuple[str, int, int]] = []
    names = set(PHASES) | {WINDOW}
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name) is None:
            for line in plane.lines:
                host.extend((ev.name, s, e) for ev, s, e in _events(line)
                            if ev.name in names)
            continue
        lines = {line.name: line for line in plane.lines}
        modules = sorted((s, e, ev.name.split("(", 1)[0])
                         for ev, s, e in _events(lines[MODULES_LINE])) \
            if MODULES_LINE in lines else []
        for key, out in ((OPS_LINE, devices), (ASYNC_LINE, async_ops)):
            if key in lines:
                out[plane.name] = sorted(
                    (parse_op(ev.name, s, e, _module_of(modules, s))
                     for ev, s, e in _events(lines[key])),
                    key=lambda o: (o.start, -o.end))
    host.sort(key=lambda h: h[1])
    windows = [(s, e) for name, s, e in host if name == WINDOW]
    window = (min(s for s, _ in windows), max(e for _, e in windows)) \
        if windows else None
    return Trace(devices, async_ops, [h for h in host if h[0] != WINDOW],
                 window)


def read(path: str) -> Trace:
    """Read an ``.xplane.pb`` file, or the one under a log directory."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path = find_xplane(path)
    return from_profile(ProfileData.from_file(path))


def merged(intervals, lo: int, hi: int) -> List[Tuple[int, int]]:
    """The union of ``intervals`` clipped to [lo, hi], as sorted disjoint
    intervals."""
    out: List[Tuple[int, int]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def _bounds(trace: Trace) -> Tuple[int, int]:
    if trace.window is None:
        raise ValueError("the trace holds no bench.window span")
    return trace.window


def window_s(trace: Trace) -> float:
    lo, hi = _bounds(trace)
    return (hi - lo) / 1e9


def busy_s(trace: Trace) -> Optional[float]:
    """Seconds of the window in which some op ran, averaged over the
    chips; None where the trace holds no chip."""
    if not trace.devices:
        return None
    lo, hi = _bounds(trace)
    per = [sum(e - s for s, e in merged(((o.start, o.end) for o in ops),
                                        lo, hi))
           for ops in trace.devices.values()]
    return sum(per) / len(per) / 1e9


def idle_gaps(ops: List[Op], lo: int, hi: int) -> List[Tuple[int, int]]:
    """The intervals of [lo, hi] in which no op of ``ops`` ran."""
    gaps, t = [], lo
    for s, e in merged(((o.start, o.end) for o in ops), lo, hi):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if t < hi:
        gaps.append((t, hi))
    return gaps


def host_phase(host: List[Tuple[str, int, int]], start: int,
               end: int) -> str:
    """The harness span that overlaps [start, end] most, without its
    ``bench.`` prefix; ``other`` where none does."""
    best, label = 0, "other"
    for name, s, e in host:
        if s >= end:
            break
        overlap = min(e, end) - max(s, start)
        if overlap > best:
            best, label = overlap, name.split(".", 1)[-1]
    return label


def op_seconds(trace: Trace, match: Callable[[Op], bool]) -> Optional[float]:
    """Device seconds of the window in which an op that ``match`` selects
    ran (synchronous or asynchronous), averaged over the chips; None where
    no chip ran such an op."""
    if not trace.devices:
        return None
    lo, hi = _bounds(trace)
    per = []
    for dev, ops in trace.devices.items():
        chosen = [(o.start, o.end) for o in ops + trace.async_ops.get(dev, [])
                  if match(o)]
        per.append(sum(e - s for s, e in merged(chosen, lo, hi)))
    if not any(per):
        return None
    return sum(per) / len(per) / 1e9


def is_mosaic(op: Op) -> bool:
    """A Pallas kernel compiled by Mosaic."""
    return op.kind == "custom-call" and op.target == "tpu_custom_call"


def is_all_to_all(op: Op) -> bool:
    """An all-to-all collective (synchronous, or its start or done)."""
    return op.kind.startswith("all-to-all")


def self_times(ops: List[Op], lo: int, hi: int) -> List[Tuple[Op, int]]:
    """Each op's own ns inside [lo, hi]: its span less the spans of the ops
    nested directly in it (a ``while`` op less its body's ops)."""
    own: List[List] = []
    stack: List[List] = []
    for o in ops:                          # sorted by (start, -end)
        s, e = max(o.start, lo), min(o.end, hi)
        if e <= s:
            continue
        while stack and stack[-1][0].end <= o.start:
            stack.pop()
        rec = [o, e - s]
        if stack:
            stack[-1][1] -= e - s
        stack.append(rec)
        own.append(rec)
    return [(o, max(t, 0)) for o, t in own]


def breakdown(trace: Trace, top: int = 10) -> dict:
    """The ``breakdown`` of a traced result line: the ``top`` device ops by
    their own device seconds, summed over the chips and keyed by program,
    op name, opcode (a custom call's target) and result type, and the
    ``top`` longest idle gaps of any chip, each named by the host phase that
    overlaps it."""
    lo, hi = _bounds(trace)
    per_op: Dict[str, float] = {}
    gaps: List[Tuple[str, float]] = []
    for ops in trace.devices.values():
        for o, t in self_times(ops, lo, hi):
            label = o.target if o.kind == "custom-call" else o.kind
            key = f"{o.module}/{o.name} {label} {o.shape}".strip("/ ")
            per_op[key] = per_op.get(key, 0.0) + t / 1e9
        for s, e in idle_gaps(ops, lo, hi):
            gaps.append((host_phase(trace.host, s, e), (e - s) / 1e9))
    device_ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    gaps.sort(key=lambda g: -g[1])
    return {"device_ops": [[k, v] for k, v in device_ops],
            "idle_gaps": [[k, v] for k, v in gaps[:top]]}
