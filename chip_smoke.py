#!/usr/bin/env python3
"""Drive the round engine's main path once on a TPU and check every answer.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the sharded path over four chips only

One chip runs four phases through the normal entry points (``get_engine``,
``engine.compile``, ``QueryService``):

- ``sort_plan`` at 2^24 keys on the dense ``local`` engine;
- ``sort_plan`` at 2^22 keys on the ``pallas`` engine, whose shuffles must
  all take the kernel route with the Pallas kernels compiled by Mosaic, and
  bit-identical to the dense engine on the same plan;
- a ``multisearch_plan`` of 2^18 batched lookups;
- a ``QueryService`` answering 48 sort queries in batches of 16.

``--chips 4`` runs ``sort_plan`` at 2^26 keys on a ``ShardedEngine`` over all
four chips, so each chip carries the one-chip load, against ``LocalEngine``
on one of them, and nothing else.

Every phase compares its results with a numpy reference built from the same
``--seed`` data and requires ``stats.dropped == 0``.  Earlier lines report
each phase's wall time, compile time, its compiled program's temporaries
(``memory_analysis()``) and the allocator's counters; the last line
is one JSON object, ``{"ok": true, "device": {...}}``.  Without a TPU, with
another number of chips than asked for, or when any check fails, the script
exits nonzero and prints no result.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

ROOT = Path(__file__).resolve().parent


def _require(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip smoke check failed: {what}")


def _report(phase: str, **fields) -> None:
    """Print one phase's line, with each device's allocator counters read
    while the phase's outputs are still live."""
    import jax
    stats = [d.memory_stats() for d in jax.devices()]
    print(json.dumps({"phase": phase, **fields,
                      "bytes_in_use": [s["bytes_in_use"] for s in stats],
                      "peak_bytes_in_use": [s["peak_bytes_in_use"]
                                            for s in stats]}), flush=True)


def _timed(fn, *args):
    """Run ``fn`` once to its end; return (out, seconds)."""
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


def _stats_equal(a, b) -> bool:
    import numpy as np
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(a, b))


def _keys(n: int, seed: int):
    import numpy as np
    return np.random.default_rng(seed).standard_normal(n, dtype=np.float32)


def compile_plan(engine, plan, key, *inputs):
    """AOT-compile ``engine.compile(plan)`` for ``(key, *inputs)``.
    Returns (compiled program, seconds)."""
    import jax
    exe = engine.compile(plan)
    t0 = time.perf_counter()
    compiled = jax.jit(lambda k, *xs: exe(*xs, key=k)).lower(
        key, *inputs).compile()
    return compiled, time.perf_counter() - t0


def run_compiled(engine, plan, *inputs, seed: int, compiled=None):
    """Run ``engine.compile(plan)`` on ``inputs``, AOT-compiling it unless
    ``compiled`` = (program, compile seconds) is given.  Returns (result,
    compiled program, {"compile_s", "wall_s", "temp_bytes"}), where
    ``temp_bytes`` is the program's temporaries by ``memory_analysis()``."""
    import jax
    key = jax.random.PRNGKey(seed)
    if compiled is None:
        compiled = compile_plan(engine, plan, key, *inputs)
    program, compile_s = compiled
    res, wall_s = _timed(program, key, *inputs)
    _require(int(res.stats.dropped) == 0,
             f"{plan.name} on {engine.name} dropped "
             f"{int(res.stats.dropped)} items")
    return res, program, {
        "compile_s": compile_s, "wall_s": wall_s,
        "temp_bytes": program.memory_analysis().temp_size_in_bytes}


def _check_sorted(res, x_host, what: str) -> None:
    import numpy as np
    _require(np.array_equal(np.asarray(res.values), np.sort(x_host)),
             f"{what} differs from np.sort")


def phase_local_sort(seed: int, n: int = 1 << 24, M: int = 4096) -> None:
    import jax.numpy as jnp
    from repro.core import get_engine, sort_plan
    engine = get_engine("local")
    x_host = _keys(n, seed)
    plan = sort_plan(n, M, levels=2)
    res, _, t = run_compiled(engine, plan, jnp.asarray(x_host), seed=seed)
    _check_sorted(res, x_host, f"local sort of {n} keys")
    _report("local_sort", n=n, M=M, **t)


def phase_pallas_sort(seed: int, n: int = 1 << 22, M: int = 4096) -> None:
    import jax.numpy as jnp
    from repro.core import get_engine, sort_plan
    plan = sort_plan(n, M, levels=2)
    x_host = _keys(n, seed)
    x = jnp.asarray(x_host)
    engine = get_engine("pallas")
    res, compiled, t = run_compiled(engine, plan, x, seed=seed)
    _check_sorted(res, x_host, f"pallas sort of {n} keys")
    route = engine.route_log
    _require(route.kernel > 0 and route.dense == 0,
             f"pallas shuffles routed kernel={route.kernel} "
             f"dense={route.dense}")
    _require("tpu_custom_call" in compiled.as_text(),
             "the pallas sort program holds no Mosaic kernel")
    dense, _, t_dense = run_compiled(get_engine("local"), plan, x, seed=seed)
    _require(_stats_equal(res.stats, dense.stats),
             "pallas and dense sort stats differ")
    _require(bool((res.values == dense.values).all()),
             "pallas and dense sort values differ")
    _report("pallas_sort", n=n, M=M, **t, route_kernel=route.kernel,
            route_dense=route.dense, dense_compile_s=t_dense["compile_s"],
            dense_wall_s=t_dense["wall_s"])


def phase_multisearch(seed: int, n_queries: int = 1 << 18,
                      n_pivots: int = 64, M: int = 16) -> None:
    import numpy as np
    import jax.numpy as jnp
    from repro.core import get_engine, multisearch_plan
    rng = np.random.default_rng(seed)
    queries = rng.standard_normal(n_queries, dtype=np.float32)
    pivots = rng.standard_normal(n_pivots, dtype=np.float32)
    engine = get_engine("local")
    res, _, t = run_compiled(
        engine, multisearch_plan(n_queries, n_pivots, M),
        jnp.asarray(queries), jnp.asarray(pivots), seed=seed)
    want = np.searchsorted(np.sort(pivots), queries, side="left")
    _require(np.array_equal(np.asarray(res.buckets), want),
             "multisearch buckets differ from np.searchsorted")
    _report("multisearch", n_queries=n_queries, n_pivots=n_pivots, M=M, **t)


def phase_serve(seed: int, n: int = 1 << 18, M: int = 4096,
                n_queries: int = 48, batch: int = 16) -> None:
    import numpy as np
    from repro.core import get_engine, sort_plan
    from repro.serve import QueryService
    engine = get_engine("local")
    svc = QueryService(engine, max_batch=batch)
    plan = sort_plan(n, M, levels=2)
    xs = np.random.default_rng(seed).standard_normal((n_queries, n),
                                                     dtype=np.float32)
    t0 = time.perf_counter()
    svc.warmup([plan])
    warmup_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    tickets = [svc.submit(plan, x) for x in xs]
    results = [t.wait() for t in tickets]     # raises on a failed ticket
    wall_s = time.perf_counter() - t0
    _require(svc.failed == 0 and svc.requeued == 0,
             f"service failed={svc.failed} requeued={svc.requeued}")
    _require(all(t.done and not t.failed for t in tickets),
             "a ticket did not complete")
    for x, res in zip(xs, results):
        _require(int(res.stats.dropped) == 0, "a served sort dropped items")
        _check_sorted(res, x, "a served sort")
    _report("serve", n=n, M=M, queries=n_queries, batch=batch,
            dispatches=svc.dispatches, warmup_s=warmup_s, wall_s=wall_s)


def phase_sharded_sort(seed: int, n_chips: int, n: int = 1 << 26,
                       M: int = 4096) -> None:
    import numpy as np
    import jax
    import jax.numpy as jnp
    from repro.core import get_engine, sort_plan
    devices = jax.devices()
    engine = get_engine("sharded")
    _require(engine.n_shards == n_chips,
             f"sharded engine spans {engine.n_shards} devices")
    plan = sort_plan(n, M, levels=2, align=engine.aligned_nodes)
    x_host = _keys(n, seed)
    x = jnp.asarray(x_host)
    key = jax.random.PRNGKey(seed)
    local = get_engine("local")
    with ThreadPoolExecutor(1) as pool:
        # The one-chip comparison compiles while the sharded sort runs.
        local_program = pool.submit(compile_plan, local, plan, key, x)
        exe = engine.compile(plan)
        # Eager engine: the one call also compiles its per-round programs.
        res, wall_s = _timed(lambda v: exe(v, key=key), x)
        _require(int(res.stats.dropped) == 0,
                 f"sharded sort dropped {int(res.stats.dropped)} items")
        _check_sorted(res, x_host, f"sharded sort of {n} keys")
        _require({s.device for s in res.values.addressable_shards}
                 == set(devices), "sharded sort output is not on every chip")
        values, stats_s = np.asarray(res.values), res.stats
        del res
        local_res, _, t = run_compiled(local, plan, x, seed=seed,
                                       compiled=local_program.result())
    _require(np.array_equal(np.asarray(local_res.values), values)
             and _stats_equal(local_res.stats, stats_s),
             "sharded and one-chip local sorts differ")
    _report("sharded_sort", n=n, M=M, chips=n_chips,
            wall_s_with_compiles=wall_s, local_compile_s=t["compile_s"],
            local_wall_s=t["wall_s"], local_temp_bytes=t["temp_bytes"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the one-chip phases; 4: only the sharded sort")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of every phase's data and PRNG keys")
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT / "src"))
    import jax
    from repro.launch.cache import enable_compile_cache

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform})",
              file=sys.stderr)
        return 1
    if len(devices) != args.chips:
        print(f"chip_smoke: asked for {args.chips} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 1
    print(json.dumps({"compile_cache": enable_compile_cache(ROOT)}),
          flush=True)
    if args.chips == 1:
        phase_local_sort(args.seed)
        phase_pallas_sort(args.seed)
        phase_multisearch(args.seed)
        phase_serve(args.seed)
    else:
        phase_sharded_sort(args.seed, args.chips)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
