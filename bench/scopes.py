"""Attribute a traced window's device time to the program's layers.

The program names its layers inside its compiled programs with
``jax.named_scope``, one scope of :data:`VOCABULARY` at each boundary
(``repro.core.plan``, ``engine``, ``sortmr``), and each plan stage by its
name.  An op's ``op_name`` is the path of scopes it was traced under, e.g.
``jit(run)/refine-1/mr.round/sort.lookup/jit(searchsorted)/while/body/lt``:
its layer is the innermost scope of the vocabulary (``sort.lookup``), its
stage the scope just outside the outermost one (``refine-1``).

A TPU profile does not carry ``op_name``: an op's event holds its HLO
instruction's text and the program it ran in (``bench.trace.Op``).  The
layer is found in one of two ways:

- the compiled program's HLO text (``compiled.as_text()``) maps each of its
  instructions to its ``op_name`` (:func:`op_names`); that reads the
  one-chip cells, whose window runs one jitted program;
- a program named for its layer gives the layer of all its ops
  (:data:`MODULE_LAYERS`): the ``ShardedEngine``'s hop and scatter
  programs, which is how the four-chip cell's shuffle is read.

Each op is charged its own time (``bench.trace.self_times``), so the layers
and ``unattributed`` add up to the chips' busy time.

The program also writes its host spans (``repro.obs``: ``exe.call``,
``plan.stage``, ``engine.round``, ...) into the same trace as
``jax.profiler.TraceAnnotation``s; :func:`program_spans` reads them, and
:func:`host_idle` charges the device's idle time inside ``exe.call`` to the
stage and round the host was in.
"""
from __future__ import annotations

import re
from collections import deque
from typing import Dict, List, NamedTuple, Optional, Tuple

from bench import trace as tr

#: the layer scopes the program names, from the plan's ends to its rounds
VOCABULARY = ("mr.prologue", "mr.round", "mr.shuffle", "mr.hop",
              "mr.epilogue", "sort.lookup")
#: programs whose every op is in one layer (ShardedEngine's two phases)
MODULE_LAYERS = {"jit_mr_hop": "mr.hop", "jit_mr_scatter": "mr.shuffle"}
#: the program's host spans (repro.obs), as the profiler records them
PROGRAM_SPANS = ("exe.call", "plan.execute", "plan.stage", "engine.round",
                 "pipeline.overlap")
UNATTRIBUTED = "unattributed"

_MODULE = re.compile(r"^HloModule (\S+?),?\s")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?(\S+) .*\{$")
_NAME = re.compile(r"^\s+(ROOT )?%?(\S+) = ")
_OP_NAME = re.compile(r'\bop_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_OPERAND = re.compile(r"(?<![=\w])%([\w.\-]+)")
_COMPUTATION_REF = re.compile(r"\b(?:calls|body|condition|to_apply)=%?([\w.\-]+)")


class Span(NamedTuple):
    name: str             # e.g. "plan.stage"
    start: int            # ns
    end: int              # ns
    attrs: dict           # the annotation's stats, e.g. {"stage": "entry"}


def _path(op_name: str) -> List[str]:
    return op_name.split("/")


def layer_of(op_name: str) -> str:
    """The innermost scope of :data:`VOCABULARY` in ``op_name``, or ""."""
    for part in reversed(_path(op_name)):
        if part in VOCABULARY:
            return part
    return ""


def stage_of(op_name: str) -> str:
    """The plan stage ``op_name`` lies in: the scope just outside its
    outermost layer scope, unless that is a transformation (``jit(run)``,
    ``vmap()``); "" for an op outside every stage."""
    path = _path(op_name)
    for i, part in enumerate(path):
        if part in VOCABULARY:
            if i > 0 and "(" not in path[i - 1]:
                return path[i - 1]
            return ""
    return ""


def op_names(hlo_text: str) -> Dict[Tuple[str, str], str]:
    """(program, instruction) -> ``op_name`` of a compiled program's HLO
    text; the program is the name the profiler shows (``jit_run``).

    An op the compiler made without metadata takes the first ``op_name``
    found in the computation it calls (a fusion built from a rewritten
    scatter: root first, depth first), else the nearest one among the ops
    that use its result (a copy made for its consumer, a sort that
    implements a scatter), breadth first; a computation's root is used by
    the op that calls it (a loop the compiler split off)."""
    m = _MODULE.match(hlo_text)
    module = m.group(1) if m else ""
    own: Dict[str, str] = {}
    called: Dict[str, str] = {}          # instruction -> computation it calls
    callers: List[Tuple[str, str]] = []  # (instruction, any computation it names)
    users: Dict[str, List[str]] = {}     # instruction -> instructions using it
    body: Dict[str, List[str]] = {}      # computation -> instructions, root first
    comp = None
    for line in hlo_text.splitlines():
        head = _COMPUTATION.match(line)
        if head is not None:
            comp = head.group(1)
            body[comp] = []
            continue
        m = _NAME.match(line)
        if m is None or comp is None:
            continue
        name = m.group(2)
        if m.group(1):
            body[comp].insert(0, name)
        else:
            body[comp].append(name)
        op = _OP_NAME.search(line)
        if op is not None:
            own[name] = op.group(1)
        calls = _CALLS.search(line)
        if calls is not None:
            called[name] = calls.group(1)
        for operand in _OPERAND.findall(line[m.end():]):
            users.setdefault(operand, []).append(name)
        callers.extend((name, c) for c in _COMPUTATION_REF.findall(line))
    for name, comp in callers:
        if body.get(comp):
            users.setdefault(body[comp][0], []).append(name)

    def inside(name, seen):
        if name in own:
            return own[name]
        comp = called.get(name)
        if comp is None or comp in seen:
            return None
        seen.add(comp)
        for inner in body.get(comp, ()):
            got = inside(inner, seen)
            if got is not None:
                return got
        return None

    def by_users(name):
        queue, seen = deque(users.get(name, ())), {name}
        while queue:
            user = queue.popleft()
            if user in seen:
                continue
            seen.add(user)
            got = inside(user, set())
            if got is not None:
                return got
            queue.extend(users.get(user, ()))
        return None

    out = {}
    for names in body.values():
        for name in names:
            got = inside(name, set()) or by_users(name)
            if got is not None:
                out[(module, name)] = got
    return out


def where(op: tr.Op, names: Optional[dict] = None) -> Tuple[str, str]:
    """(stage, layer) of one op; ("", "") where neither source names it."""
    op_name = (names or {}).get((op.module, op.name))
    if op_name is not None:
        return stage_of(op_name), layer_of(op_name)
    return "", MODULE_LAYERS.get(op.module, "")


def own_times(trace: tr.Trace, names: Optional[dict] = None):
    """(op, stage, layer, own ns in the window) of every chip's ops."""
    lo, hi = trace.window
    for ops in trace.devices.values():
        for op, t in tr.self_times(ops, lo, hi):
            yield (op,) + where(op, names) + (t,)


def attribute(trace: tr.Trace, names: Optional[dict] = None) -> dict:
    """Own device seconds of the window per layer and per (stage, layer),
    averaged over the chips.  ``layers`` plus ``unattributed`` (ops no
    source names) is ``busy_s``."""
    layers: Dict[str, float] = {}
    stages: Dict[str, Dict[str, float]] = {}
    n = max(len(trace.devices), 1)
    for _, stage, layer, t in own_times(trace, names):
        layer = layer or UNATTRIBUTED
        s = t / 1e9 / n
        layers[layer] = layers.get(layer, 0.0) + s
        if stage:
            row = stages.setdefault(stage, {})
            row[layer] = row.get(layer, 0.0) + s
    return {"busy_s": tr.busy_s(trace),
            "unattributed": layers.pop(UNATTRIBUTED, 0.0),
            "layers": layers, "stages": stages}


def unattributed_ops(trace: tr.Trace, names: Optional[dict] = None,
                     top: int = 10) -> List[list]:
    """The ``top`` ops no source names, by their own device seconds in the
    window summed over the chips, keyed as ``breakdown`` keys them."""
    per: Dict[str, float] = {}
    for op, _, layer, t in own_times(trace, names):
        if not layer:
            key = f"{op.module}/{op.name} {op.kind} {op.shape}"
            per[key] = per.get(key, 0.0) + t / 1e9
    return [[k, v] for k, v in sorted(per.items(), key=lambda kv: -kv[1])
            [:top]]


def layer_seconds(trace: tr.Trace, layers, names: Optional[dict] = None
                  ) -> Optional[float]:
    """Own device seconds of the window of the ops in ``layers``, averaged
    over the chips; None where no chip ran such an op."""
    if not trace.devices or trace.window is None:
        return None
    times = [t for _, _, layer, t in own_times(trace, names)
             if layer in layers]
    return sum(times) / len(trace.devices) / 1e9 if times else None


def program_spans(data) -> List[Span]:
    """The program's host spans in a ``jax.profiler.ProfileData``, sorted by
    start."""
    out = []
    for plane in data.planes:
        if tr.DEVICE_PLANE.match(plane.name) is not None:
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in PROGRAM_SPANS:
                    start = int(ev.start_ns)
                    out.append(Span(ev.name, start,
                                    start + int(ev.duration_ns),
                                    dict(ev.stats)))
    out.sort(key=lambda s: (s.start, -s.end))
    return out


def _label(spans: List[Span], t: int) -> str:
    """The stage (and round) the host was in at ``t``: the innermost
    ``plan.stage`` and ``engine.round`` spans that hold it."""
    stage = rnd = None
    for s in spans:
        if s.start <= t < s.end:
            if s.name == "plan.stage":
                stage = s.attrs.get("stage")
            elif s.name == "engine.round":
                rnd = s.attrs.get("round")
    parts = [str(stage)] if stage is not None else []
    if rnd is not None:
        parts.append(f"round {rnd}")
    return "/".join(parts) or "exe.call"


def host_idle(trace: tr.Trace, spans: List[Span]) -> Optional[dict]:
    """Device idle seconds of the window while the host was inside the
    program's ``exe.call`` span, averaged over the chips, in all and by the
    stage/round the host was in (at each idle stretch's midpoint); None
    where the trace holds no ``exe.call`` span or no chip."""
    calls = tr.merged(((s.start, s.end) for s in spans
                       if s.name == "exe.call"), *trace.window)
    if not calls or not trace.devices:
        return None
    lo, hi = trace.window
    total, by = 0, {}
    for ops in trace.devices.values():
        for gs, ge in tr.idle_gaps(ops, lo, hi):
            for cs, ce in calls:
                s, e = max(gs, cs), min(ge, ce)
                if e > s:
                    total += e - s
                    label = _label(spans, (s + e) // 2)
                    by[label] = by.get(label, 0) + (e - s)
    n = len(trace.devices)
    return {"total_s": total / n / 1e9,
            "by": {k: v / n / 1e9 for k, v in sorted(by.items())}}
