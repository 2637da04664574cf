#!/usr/bin/env python3
"""Run one cell with the profiler on and print where its device time goes.

    python bench/layers.py --workload is-a.local --seed 7 --seconds 10

The cell runs exactly as ``bench/run_cell.py --trace 1`` runs it, and its
lines come first, the result line included.  Then one more line,
``{"phase": "layers", ...}``, reads the same trace through
``bench.scopes``:

- ``layers``, ``stages``, ``unattributed``, ``busy_s``: the window's own
  device seconds per layer and per (stage, layer), averaged over the chips;
  the layers and ``unattributed`` add up to ``busy_s``;
  ``unattributed_ops`` lists the longest ops no layer names;
- ``host_idle``: device idle seconds while the host was inside the
  program's ``exe.call`` span, by the stage and round it was in;
- ``per_sort``: per completed sort, the device milliseconds of the
  sort's bucket lookups (``sort.lookup``), of the rest of its rounds
  (``mr.round``), of the shuffle (``mr.shuffle`` and ``mr.hop``), of the
  hop, of the host's idle time, and the shuffle's roofline share (%): the
  least time its shuffles take at the HBM peak (``bench.roofline``) over
  the shuffle's time.

The timed program's op names come from its compiled HLO text, fetched from
the persistent compilation cache after the run.
"""
import argparse
import importlib
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def op_names_of(cfg, plans, engine):
    """(program, instruction) -> op_name of the cell's timed program, or
    None for an engine that runs no single jitted program."""
    if not getattr(engine, "jittable", False):
        return None
    from bench import scopes
    plan = plans.build(cfg, engine)
    exe = engine.compile(plan)
    inputs, keys = plans.make_pool(cfg, 0, 1)
    compiled = exe._fn.lower(keys[0], *inputs[0]).compile()
    return scopes.op_names(compiled.as_text())


def per_sort(got, idle, calls, shuffle_bytes, peaks):
    """The candidate per-layer metrics of one traced window."""
    layers = got["layers"]

    def ms(*names):
        return 1e3 * sum(layers.get(n, 0.0) for n in names) / calls

    out = {"lookup_ms_per_sort": ms("sort.lookup"),
           "round_ms_per_sort": ms("mr.round"),
           "shuffle_ms_per_sort": ms("mr.shuffle", "mr.hop"),
           "hop_ms_per_sort": ms("mr.hop"),
           "host_idle_ms_per_sort":
               None if idle is None else 1e3 * idle["total_s"] / calls,
           "shuffle_roofline": None}
    shuffle_s = out["shuffle_ms_per_sort"] / 1e3
    if peaks is not None and shuffle_s > 0:
        from bench import roofline
        least = roofline.least_seconds(shuffle_bytes, peaks.hbm_bytes_per_s)
        out["shuffle_roofline"] = 100.0 * least / shuffle_s
    return out


def main(argv=None, **run_kwargs) -> int:
    """``run_kwargs`` go to ``bench.run_cell.run`` (rehearsals on the CPU
    pass ``require_chip=False`` and a tiny ``config_overrides``)."""
    argv = list(sys.argv[1:] if argv is None else argv)
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    args, _ = ap.parse_known_args(argv)

    from jax.profiler import ProfileData
    from bench import run_cell, scopes
    from bench import trace as trace_mod
    kept = {}
    read = trace_mod.read

    def read_and_keep(path):
        data = ProfileData.from_file(trace_mod.find_xplane(path))
        kept["spans"] = scopes.program_spans(data)
        kept["trace"] = trace_mod.from_profile(data)
        return kept["trace"]

    trace_mod.read = read_and_keep
    try:
        rc = run_cell.run(argv + ["--trace", "1"], **run_kwargs)
    finally:
        trace_mod.read = read
    if rc or "trace" not in kept:
        return rc

    import jax
    from bench import peaks as peaks_mod, roofline
    from repro.core import get_engine
    _, _, cfg, _ = run_cell.load_cell(args.workload,
                                      run_kwargs.get("config_overrides"))
    plans = importlib.import_module(f"bench.plans.{cfg['plan']}")
    engine = get_engine(cfg["engine"])
    names = op_names_of(cfg, plans, engine)
    trace = kept["trace"]
    calls = sum(1 for h in trace.host if h[0] == "bench.dispatch")
    spans = {}
    for sp in kept["spans"]:
        spans[sp.name] = spans.get(sp.name, 0) + 1
    got = scopes.attribute(trace, names)
    idle = scopes.host_idle(trace, kept["spans"])
    dev = jax.devices()[0]
    peaks = peaks_mod.peaks(dev.device_kind) if dev.platform == "tpu" \
        else None
    plan = plans.build(cfg, engine)
    least_bytes = roofline.shuffle_bytes(plans.items(cfg), plans.ITEM_BYTES,
                                         plans.shuffles(plan))
    print(json.dumps({
        "phase": "layers", "calls": calls, "window_s": trace_mod.window_s(
            trace), **got, "host_idle": idle,
        "program_spans": spans,
        "unattributed_ops": scopes.unattributed_ops(trace, names),
        "per_sort": per_sort(got, idle, calls, least_bytes, peaks)
        if calls else None}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
