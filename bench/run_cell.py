#!/usr/bin/env python3
"""Run one cell of the benchmark on the chip and print its result line.

    python bench/run_cell.py --workload is-a.local --seed 7 --seconds 10 --trace 0

The cell is an entry of ``workloads`` in ``BENCHMARK.json``.  Its
configuration (``bench/configs/<config>.json``) gives the deployment: the
engine, the plan family (``bench/plans/<plan>.py``, with its plain reference
``bench/reference/<plan>.py``) and the sizes.  Its traffic mix
(``bench/traffic/<traffic>.json``) is data that the one generator,
``bench/loop.py``, drives, and from whose window it computes the cell's
end-to-end metrics by name.  Each per-layer metric is read by ``bench/metrics/<name>.py``.

One run, one process:

1. JAX's persistent compilation cache goes to ``bench/.jax_cache`` in the
   checkout.
2. Without a TPU, or with another number of chips than the cell asks for,
   the run exits nonzero and prints no result.
3. The plan is built and compiled through the program's entry points
   (``get_engine``, ``sort_plan``, ``engine.compile``).
4. The inputs are made on the device from ``--seed``.
5. The mix's warm-up calls run; ``setup_s`` is the time from the process's
   start to here.
6. The window: calls of ``Executable.__call__`` until ``--seconds`` have
   passed and the call in flight has completed.  With ``--trace 1`` the
   profiler records it.
7. After the window, the answers kept from it are compared with the plain
   reference, and the run's other guarantees are checked.
8. The last line of standard output is one JSON object: ``correct``,
   ``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
   also ``breakdown``, and last ``checks``, each compared number beside its
   limit.  The same numbers are the last lines of standard error.

Earlier lines of standard output carry the set-up's parts, the window's
per-call times, compile counts, the program's ``memory_analysis()`` and the
allocator's counters.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import NamedTuple, Optional  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
CACHE_DIR = ROOT / "bench" / ".jax_cache"
#: platforms on which a kernel route's compiled program holds its Pallas
#: kernels as Mosaic custom calls (elsewhere they are interpreted)
MOSAIC_PLATFORMS = ("tpu",)
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


class NoChip(RuntimeError):
    """The machine does not hold the chips the cell asks for."""


class Run(NamedTuple):
    """What a per-layer metric reader gets from a run."""

    trace: object            # bench.trace.Trace of the window, or None
    calls: int               # calls completed in the window
    items_per_call: int      # input items one call answers
    item_bytes: int          # bytes of one item
    shuffles: int            # shuffle rounds of one call, from the plan
    route_dense: int         # shuffles a kernel engine routed dense
    peaks: object            # bench.peaks.Peaks of the chip


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, config_overrides: Optional[dict] = None,
              mix_overrides: Optional[dict] = None):
    """(spec, cell, config, mix) of workload ``name``; the overrides replace
    keys of its configuration and its mix (for rehearsals at tiny sizes)."""
    spec = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; pick from "
                         f"{sorted(cells)}")
    cell = cells[name]
    cfg = load_json(ROOT / "bench" / "configs" / f"{cell['config']}.json")
    cfg.update(config_overrides or {})
    mix = load_json(ROOT / "bench" / "traffic" / f"{cell['traffic']}.json")
    mix.update(mix_overrides or {})
    return spec, cell, cfg, mix


def use_cache() -> None:
    """Point JAX's persistent compilation cache at ``CACHE_DIR`` and cache
    every program, so that only a checkout's first run compiles."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def check_devices(devices, chips: int) -> None:
    if not devices or devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found "
                     f"{devices[0].platform if devices else 'no device'}")
    if len(devices) != chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found "
                     f"{len(devices)}")


class _Counts:
    """JAX's compile and persistent-cache events, counted as they come."""

    def __init__(self):
        import jax
        self.names = {"/jax/core/compile/backend_compile_duration":
                      "compiles",
                      "/jax/core/compile/jaxpr_trace_duration": "traces",
                      "/jax/compilation_cache/cache_hits": "cache_hits",
                      "/jax/compilation_cache/cache_misses": "cache_misses"}
        self.n = dict.fromkeys(self.names.values(), 0)
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on)

    def _on(self, event, *args, **kwargs):
        name = self.names.get(event)
        if name is not None:
            self.n[name] += 1

    def snapshot(self) -> dict:
        return dict(self.n)


def _say(obj) -> None:
    print(json.dumps(obj), flush=True)


def _check(checks: dict, name: str, value, limit, at_least=False) -> None:
    checks[name] = {"value": value, "limit": limit,
                    "ok": value >= limit if at_least else value <= limit,
                    "rule": ">=" if at_least else "<="}


def compare_answers(win, inputs, plans, reference, devices):
    """Compare the answers that the window kept with the plain reference.

    Returns (checks, failed calls).  Every call's dropped count is checked;
    the kept answers are compared key by key with the reference's answer for
    the same inputs, and, on more than one chip, must be spread over all of
    them."""
    import jax
    checks: dict = {}
    dropped = [int(d) for d in win.summaries]
    _check(checks, "dropped_keys", sum(dropped), 0)
    expected = {}
    mismatched, failed_calls, off_chip = 0, 0, 0
    for i, out in sorted(win.kept.items()):
        got, _ = plans.values(out)
        j = i % len(inputs)
        if j not in expected:
            expected[j] = reference.expected(jax.device_get(inputs[j][0]))
        bad = reference.mismatched(jax.device_get(got), expected[j])
        mismatched += bad
        failed_calls += bool(bad) or dropped[i] > 0
        off_chip += len(set(devices) - set(got.sharding.device_set))
    failed_calls += sum(d > 0 for i, d in enumerate(dropped)
                        if i not in win.kept)
    _check(checks, "mismatched_keys", mismatched, 0)
    if len(devices) > 1:
        _check(checks, "chips_missing_output", off_chip, 0)
    return checks, failed_calls


def report_checks(checks: dict) -> dict:
    """Print each compared number beside its limit on standard error, and
    return them for the result line."""
    for k, c in checks.items():
        print(f"check {k}: {c['value']} {c['rule']} {c['limit']} "
              f"{'ok' if c['ok'] else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    return {k: [c["value"], c["rule"], c["limit"]] for k, c in checks.items()}


def run(argv=None, *, require_chip: bool = True,
        config_overrides: Optional[dict] = None,
        mix_overrides: Optional[dict] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        raise SystemExit("--seed must be >= 0")

    spec, cell, cfg, mix = load_cell(args.workload, config_overrides,
                                     mix_overrides)
    import jax
    use_cache()
    devices = jax.devices()
    try:
        check_devices(devices, int(cell["chips"]))
    except NoChip as e:
        if require_chip:
            print(f"run_cell: {e}", file=sys.stderr)
            return 2
    counts = _Counts()
    from bench import loop, peaks as peaks_mod
    from bench import trace as trace_mod
    loop.check_mix(mix)
    sys.path.insert(0, str(ROOT / "src"))
    from repro.core import get_engine

    plans = importlib.import_module(f"bench.plans.{cfg['plan']}")
    reference = importlib.import_module(f"bench.reference.{cfg['plan']}")
    engine = get_engine(cfg["engine"])
    plan = plans.build(cfg, engine)
    exe = engine.compile(plan)
    sharding = None
    if getattr(engine, "mesh", None) is not None:
        from jax.sharding import NamedSharding, PartitionSpec
        sharding = NamedSharding(engine.mesh, PartitionSpec(engine.axis_name))
    pool_size = int(mix["pool"])
    inputs, keys = plans.make_pool(cfg, args.seed, pool_size, sharding)
    jax.block_until_ready((inputs, keys))
    t_pool = time.perf_counter() - T0

    def call(i):
        j = i % pool_size
        return exe(*inputs[j], key=keys[j])

    for w in range(int(mix["warmup"])):
        jax.block_until_ready(call(w))
    setup_s = time.perf_counter() - T0
    at_setup = counts.snapshot()
    _say({"phase": "setup", "setup_s": setup_s, "pool_ready_s": t_pool,
          "engine": engine.name, "plan_nodes": plan.n_nodes,
          "shuffles": plans.shuffles(plan), **at_setup})

    log_dir = None
    if args.trace:
        from jax.profiler import ProfileOptions
        log_dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        win = loop.drive(mix, call, args.seconds, seed=args.seed,
                         summarize=lambda out: plans.values(out)[1])
    finally:
        if log_dir is not None:
            jax.profiler.stop_trace()
    after = counts.snapshot()
    stats = [d.memory_stats() or {} for d in devices]
    memory_peak = max((s.get("peak_bytes_in_use", 0) for s in stats),
                      default=0)
    _say({"phase": "window", "calls": win.calls, "seconds": win.seconds,
          "latency_s_min_median_max": [min(win.latency_s),
                                       statistics.median(win.latency_s),
                                       max(win.latency_s)],
          "compiles_in_window": after["compiles"] - at_setup["compiles"],
          "traces_in_window": after["traces"] - at_setup["traces"],
          "bytes_in_use": [s.get("bytes_in_use") for s in stats],
          "peak_bytes_in_use": [s.get("peak_bytes_in_use") for s in stats]})

    trace = None
    if log_dir is not None:
        try:
            trace = trace_mod.read(log_dir)
        finally:
            shutil.rmtree(log_dir, ignore_errors=True)

    # -- the comparison, after the window ----------------------------------
    n_items = plans.items(cfg)
    checks, failed_calls = compare_answers(win, inputs, plans, reference,
                                           devices)
    route = getattr(engine, "route_log", None)
    route_dense = 0
    if getattr(engine, "shuffle_impl", "dense") == "kernel":
        route_dense = route.dense
        _check(checks, "dense_shuffles", route.dense, 0)
        _check(checks, "kernel_shuffles", route.kernel, 1, at_least=True)
    if getattr(engine, "jittable", False):
        # The timed program itself: the jitted function that every call of
        # the window ran (Executable._fn), lowered for the same arguments and
        # fetched from the compile cache.
        compiled = exe._fn.lower(keys[0], *inputs[0]).compile()
        ma = compiled.memory_analysis()
        if ma is not None:
            _say({"phase": "memory_analysis",
                  "temp_bytes": ma.temp_size_in_bytes,
                  "argument_bytes": ma.argument_size_in_bytes,
                  "output_bytes": ma.output_size_in_bytes})
        if (getattr(engine, "shuffle_impl", "dense") == "kernel"
                and devices[0].platform in MOSAIC_PLATFORMS):
            _check(checks, "mosaic_kernels",
                   compiled.as_text().count("tpu_custom_call"), 1,
                   at_least=True)
    correct = all(c["ok"] for c in checks.values())

    # -- the result line ---------------------------------------------------
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    metrics = {}
    units = {m["name"]: m["unit"] for m in spec["end_to_end"]
             + spec["per_layer"]}
    if not args.trace:
        for m in spec["end_to_end"]:
            if args.workload in m.get("workloads", [args.workload]):
                value = setup_s if m["name"] == "setup_s" else \
                    loop.end_to_end(m["name"], win, n_items)
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    line = {"correct": correct, "attempted": win.calls,
            "failed": failed_calls, "metrics": metrics, "device": device}
    if trace is not None:
        busy = trace_mod.busy_s(trace)
        device["busy_s"] = busy
        device["window_s"] = trace_mod.window_s(trace)
        peak = peaks_mod.peaks(dev.device_kind) \
            if dev.platform == "tpu" else None
        ctx = Run(trace, win.calls, n_items, plans.ITEM_BYTES,
                  plans.shuffles(plan), route_dense, peak)
        for m in spec["per_layer"]:
            if args.workload not in m.get("workloads", [args.workload]):
                continue
            reader = importlib.import_module(f"bench.metrics.{m['name']}")
            value = reader.read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": units[m["name"]]}
        line["breakdown"] = trace_mod.breakdown(trace)
    line["checks"] = report_checks(checks)
    _say(line)
    return 0


if __name__ == "__main__":
    sys.exit(run())
