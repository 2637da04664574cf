"""Benchmark harness — one benchmark per paper claim/bound.

The paper is analytic (no experimental tables); each benchmark therefore
(1) measures wall time of our implementation of the corresponding theorem,
(2) derives the quantity the paper bounds (rounds R, communication C,
congestion, fan-in) and reports it against the O(.) claim.

Output: ``name,us_per_call,derived`` CSV (one line per benchmark).

  PYTHONPATH=src python -m benchmarks.run [--quick]
"""
import argparse
import os
import math
import time

import numpy as np
import jax
import jax.numpy as jnp


def _timeit(fn, n=3, warmup=1):
    for _ in range(warmup):
        fn()
    t0 = time.perf_counter()
    for _ in range(n):
        fn()
    return (time.perf_counter() - t0) / n * 1e6      # us


def bench_prefix_sums(quick):
    from repro.core import LocalEngine, prefix_plan, prefix_sum_opt, log_M
    n, M = (20000, 64) if not quick else (2000, 32)
    x = jnp.ones(n, jnp.int32)
    exe = LocalEngine().compile(prefix_plan(n, M, dtype=jnp.int32))
    res = exe(x)
    us_faithful = _timeit(lambda: jax.block_until_ready(exe(x).values))
    us_opt = _timeit(lambda: jax.block_until_ready(prefix_sum_opt(x)))
    print(f"prefix_tree_lemma2.2,{us_faithful:.0f},"
          f"rounds={int(res.stats.rounds)}|bound=O(log_M N)={2*log_M(n, M)+1}"
          f"|comm={int(res.stats.communication)}")
    print(f"prefix_opt_cumsum,{us_opt:.0f},speedup={us_faithful/us_opt:.1f}x")


def bench_random_indexing(quick):
    from repro.core import MRCost, random_indexing
    n, M = (20000, 64) if not quick else (2000, 32)
    c = MRCost()
    random_indexing(n, jax.random.PRNGKey(0), M, cost=c)
    us = _timeit(lambda: jax.block_until_ready(
        random_indexing(n, jax.random.PRNGKey(0), M)))
    print(f"random_indexing_lemma2.3,{us:.0f},"
          f"rounds={c.rounds}|max_leaf={c.max_reducer_io}|M={M}")


def bench_multisearch(quick):
    from repro.core import MRCost, multisearch, multisearch_opt
    rng = np.random.default_rng(0)
    nq, m, M = (8192, 1024, 32) if not quick else (1024, 128, 16)
    q = jnp.asarray(rng.normal(size=nq).astype(np.float32))
    piv = jnp.sort(jnp.asarray(rng.normal(size=m).astype(np.float32)))
    res = multisearch(q, piv, M)
    flat = multisearch(q, piv, M, pipelined=False)
    us = _timeit(lambda: jax.block_until_ready(
        multisearch(q, piv, M).buckets), n=2)
    us_opt = _timeit(lambda: jax.block_until_ready(multisearch_opt(q, piv)))
    print(f"multisearch_thm4.1,{us:.0f},"
          f"rounds={res.rounds}|congestion={res.max_congestion}"
          f"|unpipelined={flat.max_congestion}")
    print(f"multisearch_opt,{us_opt:.0f},speedup={us/us_opt:.1f}x")


def bench_sorting(quick):
    import warnings
    from repro.core import sort_opt, log_M
    rng = np.random.default_rng(0)
    n, M = (20000, 64) if not quick else (2000, 32)
    x = jnp.asarray(rng.normal(size=n).astype(np.float32))

    # The §4.3 sort through the plan API (the one sorter left: the legacy
    # host-recursive sample_sort now delegates here too).
    from repro.core import LocalEngine, sort_plan
    key = jax.random.PRNGKey(0)
    engine = LocalEngine()
    exe = engine.compile(sort_plan(n, M))
    res = exe(x, key=key)
    out = jax.block_until_ready(res.values)         # compile + correctness
    assert bool(jnp.all(jnp.diff(out) >= 0))
    us_eng = _timeit(lambda: jax.block_until_ready(exe(x, key=key).values),
                     n=3)
    us_opt = _timeit(lambda: jax.block_until_ready(sort_opt(x)))
    print(f"engine_sample_sort_local,{us_eng:.0f},"
          f"rounds={int(res.stats.rounds)}|comm={int(res.stats.communication)}"
          f"|dropped={int(res.stats.dropped)}"
          f"|comm_bound~N*log_M N={n*log_M(n, M)}")
    print(f"sort_opt_laxsort,{us_opt:.0f},speedup={us_eng/us_opt:.1f}x")

    # The deprecated wrapper surface costs only its per-call plan build +
    # cache lookup on top of the compiled executable.
    from repro.core import sample_sort_mr
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        us_wrap = _timeit(lambda: jax.block_until_ready(
            sample_sort_mr(x, M, engine=engine, key=key).values), n=3)
    print(f"sample_sort_mr_wrapper,{us_wrap:.0f},"
          f"overhead_vs_executable={us_wrap/us_eng:.2f}x")


def bench_funnel(quick):
    from repro.core import MRCost, funnel_write, scatter_combine_opt
    rng = np.random.default_rng(0)
    P, N, M = (8192, 256, 32) if not quick else (1024, 64, 16)
    addrs = jnp.asarray(rng.integers(0, N, P).astype(np.int32))
    vals = jnp.asarray(rng.normal(size=P).astype(np.float32))
    mem = jnp.zeros(N, jnp.float32)
    c = MRCost()
    funnel_write(addrs, vals, mem, jnp.add, M, cost=c,
                 identity=jnp.float32(0))
    us = _timeit(lambda: jax.block_until_ready(
        funnel_write(addrs, vals, mem, jnp.add, M,
                     identity=jnp.float32(0)).memory), n=2)
    us_opt = _timeit(lambda: jax.block_until_ready(
        scatter_combine_opt(addrs, vals, mem, "sum")))
    print(f"funnel_write_thm3.2,{us:.0f},"
          f"rounds={c.rounds}|P={P}|comm={c.communication}")
    print(f"funnel_opt_scatter,{us_opt:.0f},speedup={us/us_opt:.1f}x")


def bench_queues(quick):
    from repro.core import make_queues, enqueue, dequeue
    V, M, cap, burst = 8, 32, 1024, 512
    q = make_queues(V, cap, jnp.float32(0))
    dests = jnp.zeros(burst, jnp.int32)
    payload = jnp.arange(float(burst))

    def drain():
        qq, _ = enqueue(q, dests, payload)
        rounds = 0
        while int(jnp.sum(qq.size)) > 0:
            qq, out, valid = dequeue(qq, M)
            rounds += 1
        return rounds
    rounds = drain()
    us = _timeit(drain, n=1)
    print(f"fifo_queues_thm4.2,{us:.0f},"
          f"burst={burst}|M={M}|rounds={rounds}|bound=C/M+O(1)="
          f"{burst//M + 2}")


def bench_shuffle(quick):
    """Dense vs kernel-backed shuffle over an (N, fan-in) grid — routed
    through the engines, with the grid extended past the old kernel cliffs.

    The engine hot loop (DESIGN.md §7): same FIFO/drop contract, two
    implementations.  Fan-in = N / V (expected arrivals per node); capacity
    is sized to 2x fan-in so the drop path stays exercised but rare.  The
    grid includes shapes past the old single-VMEM-tile cliff (n > 2^18);
    off TPU the old int32-key-cliff point (n=40000, V=2^16) is skipped —
    its count matrices are compile-heavy in interpret mode.

    Three in-bench asserts per grid point:

    - **route**: ``route_log`` must show the pallas engine *took* the
      kernel path (no silent dense fallback) — the multi-tile radix
      rewrite's acceptance claim;
    - **parity**: kernel and dense results are bit-identical (mailbox,
      validity, stats);
    - **speed** (TPU only): the kernel path must not be slower than dense.
      CPU interpret mode is semantics-only — the dense/kernel ratio there
      tracks dispatch overhead, not Mosaic — so off TPU the ratio is
      reported, never asserted.

    The deterministic route/parity fractions go under ``"series"`` in
    BENCH_shuffle.json (tools/bench_compare.py gates them in CI at 1.0);
    wall times land in rows and "info", never gated.
    """
    import json
    from repro.core import kshuffle as K
    from repro.core.engine import LocalEngine, get_engine
    rng = np.random.default_rng(0)
    on_tpu = jax.default_backend() == "tpu"
    past_cliff = (1 << 18) + 4096            # > _MAX_SORT_N: multi-tile
    grid_n = ((1024, 4096, past_cliff) if quick
              else (1024, 4096, 16384, past_cliff, 1 << 19))
    grid = [(n, V) for n in grid_n for V in (16, 64, 256)]
    if on_tpu:
        grid.append((40000, 1 << 16))        # old int32-key cliff point
    keng = get_engine("pallas")
    deng = LocalEngine()
    rows, kernel_routes, parities = [], 0, 0
    for n, V in grid:
        fan_in = max(n // V, 1)
        cap = max(2 * fan_in, 2)
        dests = jnp.asarray(rng.integers(0, V, n).astype(np.int32))
        payload = jnp.asarray(rng.normal(size=n).astype(np.float32))
        d_fn = jax.jit(lambda d, p, V=V, cap=cap: deng.shuffle(d, p, V, cap))
        k_fn = jax.jit(lambda d, p, V=V, cap=cap: keng.shuffle(d, p, V, cap))
        keng.route_log.reset()
        box_k, st_k = jax.block_until_ready(k_fn(dests, payload))
        routed = keng.route_log.snapshot() == (1, 0)
        assert routed, \
            f"bench_shuffle: kernel path not taken at N{n}_V{V} " \
            f"(route_log={keng.route_log.snapshot()})"
        kernel_routes += 1
        box_d, st_d = jax.block_until_ready(d_fn(dests, payload))
        parity = bool(jnp.array_equal(box_d.valid, box_k.valid)
                      & jnp.array_equal(box_d.payload, box_k.payload)) \
            and all(int(a) == int(b) for a, b in zip(st_d, st_k))
        assert parity, f"bench_shuffle: kernel diverged from dense at " \
                       f"N{n}_V{V}"
        parities += 1
        us_d = _timeit(lambda: jax.block_until_ready(d_fn(dests, payload)))
        us_k = _timeit(lambda: jax.block_until_ready(k_fn(dests, payload)))
        if on_tpu:
            assert us_k <= us_d, \
                f"bench_shuffle: kernel slower than dense on TPU at " \
                f"N{n}_V{V}: {us_k:.0f}us vs {us_d:.0f}us"
        rows.append({"n": n, "V": V, "fan_in": fan_in, "cap": cap,
                     "us_dense": us_d, "us_kernel": us_k,
                     "dense_vs_kernel": us_d / us_k,
                     "multi_tile": n > K._MAX_SORT_N,
                     "kernel_route": routed, "parity": parity,
                     "dropped": int(st_d.dropped)})
        print(f"shuffle_dense_N{n}_V{V},{us_d:.0f},"
              f"fan_in={fan_in}|cap={cap}|dropped={int(st_d.dropped)}")
        print(f"shuffle_kernel_N{n}_V{V},{us_k:.0f},"
              f"dense_vs_kernel={us_d/us_k:.2f}x|parity={parity}"
              f"|route=kernel|backend={jax.default_backend()}")
    # Deterministic acceptance series: every grid point must take the
    # kernel path and match the dense oracle bit-for-bit (the asserts
    # above already hard-fail; the series lets the CI gate see it too).
    series = {"shuffle_kernel_route_fraction": kernel_routes / len(grid),
              "shuffle_parity_fraction": parities / len(grid)}
    info = {"max_dense_vs_kernel": max(r["dense_vs_kernel"] for r in rows),
            "min_dense_vs_kernel": min(r["dense_vs_kernel"] for r in rows),
            "points_past_old_cliff": sum(r["multi_tile"] for r in rows)}
    payload_json = {"bench": "shuffle_kernel_vs_dense",
                    "backend": jax.default_backend(),
                    "tpu_speed_asserted": on_tpu,
                    "rows": rows, "series": series, "info": info}
    with open("BENCH_shuffle.json", "w", encoding="utf-8") as f:
        json.dump(payload_json, f, indent=2)
    print(f"shuffle_bench_json,0,wrote BENCH_shuffle.json "
          f"({len(rows)} rows, route_fraction="
          f"{series['shuffle_kernel_route_fraction']:.2f})")


def bench_kernels(quick):
    from repro.kernels import ops, ref
    rng = np.random.default_rng(0)
    b, h, s, d = (2, 4, 256, 64) if not quick else (1, 2, 128, 32)
    q = jnp.asarray(rng.normal(size=(b, h, s, d)).astype(np.float32))
    k, v = q, q
    us_k = _timeit(lambda: jax.block_until_ready(
        ops.flash_attention(q, k, v, block_q=64, block_k=64)), n=2)
    us_r = _timeit(lambda: jax.block_until_ready(
        ref.flash_attention_ref(q.reshape(b*h, s, d), k.reshape(b*h, s, d),
                                v.reshape(b*h, s, d))))
    print(f"kernel_flash_attention,{us_k:.0f},interpret_vs_ref={us_k/us_r:.1f}x"
          f"|note=CPU interpret mode; TPU is the target")

    x = jnp.asarray(rng.normal(size=(8, 2048)).astype(np.float32))
    us_k = _timeit(lambda: jax.block_until_ready(ops.prefix_scan(x)), n=3)
    print(f"kernel_prefix_scan,{us_k:.0f},blocked 2-pass (Lem 2.2 in VMEM)")

    ids = jnp.asarray(rng.integers(0, 384, 8192).astype(np.int32))
    us_k = _timeit(lambda: jax.block_until_ready(ops.bincount(ids, 384)), n=3)
    print(f"kernel_bincount,{us_k:.0f},one-hot MXU histogram")

    kk = jnp.asarray(rng.normal(size=(4, 512)).astype(np.float32))
    us_k = _timeit(lambda: jax.block_until_ready(
        ops.bitonic_sort(kk, kk)[0]), n=2)
    print(f"kernel_bitonic_sort,{us_k:.0f},log^2(n) dense stages")

    a = jnp.asarray(rng.uniform(0.8, 1, (2, 512, 64)).astype(np.float32))
    xx = jnp.asarray(rng.normal(size=(2, 512, 64)).astype(np.float32))
    us_k = _timeit(lambda: jax.block_until_ready(ops.ssm_scan(a, xx)), n=2)
    us_r = _timeit(lambda: jax.block_until_ready(ref.ssm_scan_ref(a, xx)),
                   n=2)
    print(f"kernel_ssm_scan,{us_k:.0f},chunked_vs_sequential_ref="
          f"{us_r/us_k:.1f}x")


def bench_moe_dispatch(quick):
    from repro.configs import get_config
    from repro.models.moe import init_moe, apply_moe
    cfg = get_config("kimi-k2-1t-a32b", reduced=True)
    p = init_moe(jax.random.PRNGKey(0), cfg)
    x = jnp.asarray(np.random.default_rng(0).normal(
        size=(2, 64, cfg.d_model)).astype(np.float32))
    out = apply_moe(p, cfg, x)
    us = _timeit(lambda: jax.block_until_ready(apply_moe(p, cfg, x).y), n=2)
    print(f"moe_dispatch_einsum,{us:.0f},"
          f"dropped={float(out.dropped_frac):.3f}|aux={float(out.aux_loss):.2f}")


def bench_geometry(quick):
    from repro.core import (LocalEngine, hull2d_plan, hull3d_plan,
                            hull3d_round_bound, hull_round_bound, lp_plan,
                            lp_round_bound)
    rng = np.random.default_rng(0)
    engine = LocalEngine()
    n, M = (4000, 64) if not quick else (500, 32)
    pts = jnp.asarray(rng.normal(size=(n, 2)).astype(np.float32))
    key = jax.random.PRNGKey(0)
    fn = engine.compile(hull2d_plan(n, M))
    res = jax.block_until_ready(fn(pts, key=key))      # compile + rounds
    us = _timeit(lambda: jax.block_until_ready(fn(pts, key=key).points), n=3)
    print(f"hull2d_engine_s1.4,{us:.0f},rounds={int(res.stats.rounds)}"
          f"|bound={hull_round_bound(n, M)}|h={int(res.count)}"
          f"|dropped={int(res.stats.dropped)}|n={n}|M={M}")

    n3 = 24 if not quick else 14
    pts3 = jnp.asarray(rng.normal(size=(n3, 3)).astype(np.float32))
    fn3 = engine.compile(hull3d_plan(n3, M))
    res3 = jax.block_until_ready(fn3(pts3))
    us = _timeit(lambda: jax.block_until_ready(fn3(pts3).mask), n=2)
    print(f"hull3d_crcw_thm3.2,{us:.0f},rounds={int(res3.stats.rounds)}"
          f"|bound={hull3d_round_bound(n3, M)}"
          f"|verts={int(np.sum(np.asarray(res3.mask)))}|n={n3}")

    nc, d = (24, 3) if not quick else (16, 3)
    A = jnp.asarray(rng.normal(size=(nc, d)).astype(np.float32))
    b = jnp.asarray(rng.uniform(1, 2, nc).astype(np.float32))
    cvec = jnp.asarray(np.array([1.0, -0.5, 0.25], np.float32))
    fnl = engine.compile(lp_plan(nc, d, M))
    resl = jax.block_until_ready(fnl(cvec, A, b))
    us = _timeit(lambda: jax.block_until_ready(fnl(cvec, A, b).objective),
                 n=3)
    print(f"lp_ddim_funnel_s1.4,{us:.0f},rounds={int(resl.stats.rounds)}"
          f"|bound={lp_round_bound(nc, d, M)}|d={d}"
          f"|Min-CRCW over C({nc},{d}) bases")


def bench_cost_model(quick):
    from repro.core import MRCost, LocalEngine, sort_plan, HardwareModel
    n, M = 4096, 64
    x = jnp.asarray(np.random.default_rng(0).normal(size=n
                                                    ).astype(np.float32))
    res = LocalEngine().compile(sort_plan(n, M))(x)
    c = MRCost()
    c.absorb(res.stats)
    hw = HardwareModel(chips=256, device_kind="TPU v5 lite")
    t = hw.shuffle_time(c)
    print(f"cost_model_T,{t*1e6:.1f},T=t+R*L+C/B on 256 chips"
          f"|R={c.rounds}|C={c.communication}")


def bench_plan(quick):
    """Batched-throughput bench for the plan/compile/execute split.

    One compiled sort Executable serves B independent queries either
    sequentially (B single jitted calls) or through ``Executable.batch(B)``
    (the whole round program vmapped into one device program).  Each B row
    carries an in-bench parity check — batched output must be bit-identical
    to the sequential loop — and the machine-readable results land in
    BENCH_plan.json (queries/sec vs B) for the CI artifact.
    """
    import json
    import warnings
    from repro.core import LocalEngine, sample_sort_mr, sort_plan
    n, M = 128, 64            # dispatch-bound per query: the serving regime
    batch_sizes = (1, 8, 64) if not quick else (1, 8, 32)
    engine = LocalEngine()
    exe = engine.compile(sort_plan(n, M))
    key = jax.random.PRNGKey(0)
    rng = np.random.default_rng(0)
    rows = []
    for B in batch_sizes:
        xs = jnp.asarray(rng.normal(size=(B, n)).astype(np.float32))
        keys = jax.random.split(key, B)
        batched = exe.batch(B)
        out = batched(xs, keys=keys)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)
            singles = [sample_sort_mr(xs[i], M, engine=engine, key=keys[i])
                       for i in range(B)]
            parity = all(
                np.array_equal(np.asarray(out.values[i]),
                               np.asarray(singles[i].values))
                for i in range(B))
            assert parity, f"batch({B}) diverged from the sequential loop"

            # Sequential baseline: B legacy sample_sort_mr calls (each a
            # cached-compile + one jitted dispatch), measured as a loop.
            def seq():
                for i in range(B):
                    jax.block_until_ready(sample_sort_mr(
                        xs[i], M, engine=engine, key=keys[i]).values)
            us_seq = _timeit(seq, n=3)
        jax.block_until_ready(batched(xs, keys=keys).values)
        us_batch = _timeit(lambda: jax.block_until_ready(
            batched(xs, keys=keys).values), n=3)
        qps_batch = B / (us_batch / 1e6)
        speedup = us_seq / us_batch
        rows.append({"B": B, "us_batch": us_batch, "us_sequential": us_seq,
                     "qps_batched": qps_batch,
                     "speedup_vs_sequential": speedup, "parity": parity})
        print(f"plan_batch_B{B},{us_batch:.0f},"
              f"qps={qps_batch:.0f}|vs_sequential={speedup:.1f}x"
              f"|parity={parity}")
    payload = {"bench": "plan_batch_sort", "n": n, "M": M,
               "backend": jax.default_backend(),
               "cache": engine.cache_info()._asdict(), "rows": rows}
    with open("BENCH_plan.json", "w", encoding="utf-8") as f:
        json.dump(payload, f, indent=2)
    print(f"plan_bench_json,0,wrote BENCH_plan.json ({len(rows)} rows)")


def bench_shape(quick):
    """Dense (frozen-shape) vs shape-scheduled execution (DESIGN.md §9).

    For each (N, M) grid point the same plan is built twice — ``shape=False``
    freezes the entry mailbox footprint for the whole program, ``shape=True``
    gives every stage its live (V_r, M_r) — and both are compiled on
    LocalEngine and timed.  Each cell carries an **in-bench parity assert**
    (bit-identical outputs and CostAccum — the shape schedule is a physical
    optimization, never a semantic one) and reports peak/total declared
    mailbox bytes.  A third **kernel column** compiles the shaped plan on
    the pallas engine: every per-stage shuffle must route through the
    multi-tile radix kernel (``route_log`` asserts no silent dense
    fallback — the old size cliffs used to knock entry-level stages off
    the kernel path) and reproduce the dense result bit-for-bit.  The grid
    is fixed (no --quick variation) so the series in BENCH_shape.json are
    comparable across runs: ``tools/bench_compare.py`` gates regressions
    against the committed baseline in CI.
    """
    import json
    from repro.core import LocalEngine, get_engine, hull2d_plan, prefix_plan
    from repro.core.funnel import funnel_write_plan
    from repro.core.plan import execute_plan

    engine = LocalEngine()
    kengine = get_engine("pallas")
    rng = np.random.default_rng(0)
    rows = []
    route_counts = [0, 0]                     # [kernel, dense] decisions

    def run_pair(family, label, make_plan_call, out_leaf, n_calls):
        """Measure one grid point: ``make_plan_call(shape, eng) -> (plan,
        call)`` where ``call()`` runs the program and returns its result."""
        t, peak, total, res = {}, {}, {}, {}
        for s in (False, True):
            plan, call = make_plan_call(s, engine)
            res[s] = jax.block_until_ready(call())
            t[s] = _timeit(lambda: jax.block_until_ready(out_leaf(call())),
                           n=n_calls)
            peak[s] = plan.peak_mailbox_slots() * 4        # float32/int32
            total[s] = plan.total_mailbox_slots() * 4
        # Parity assert: frozen and shaped must agree bit-for-bit, outputs
        # and accounting alike.
        for la, lb in zip(jax.tree_util.tree_leaves(res[False]),
                          jax.tree_util.tree_leaves(res[True])):
            assert np.array_equal(np.asarray(la), np.asarray(lb)), \
                f"bench_shape: {label} diverged between frozen and shaped"
        # Kernel column: the shaped plan on the pallas engine.  Every
        # per-stage routing decision (made while the first call traces)
        # must take the kernel, and the result must match the dense column.
        kengine.route_log.reset()
        _, call_k = make_plan_call(True, kengine)
        res_k = jax.block_until_ready(call_k())
        routed = kengine.route_log.snapshot()
        assert routed[0] > 0 and routed[1] == 0, \
            f"bench_shape: {label} fell back to dense on the kernel " \
            f"engine (route_log={routed})"
        route_counts[0] += routed[0]
        route_counts[1] += routed[1]
        for la, lb in zip(jax.tree_util.tree_leaves(res[True]),
                          jax.tree_util.tree_leaves(res_k)):
            assert np.array_equal(np.asarray(la), np.asarray(lb)), \
                f"bench_shape: {label} kernel column diverged from dense"
        us_kernel = _timeit(lambda: jax.block_until_ready(
            out_leaf(call_k())), n=n_calls)
        speedup = t[False] / t[True]
        rows.append({"family": family, "label": label,
                     "us_frozen": t[False], "us_shaped": t[True],
                     "us_kernel": us_kernel,
                     "kernel_stage_routes": routed[0],
                     "speedup": speedup,
                     "peak_bytes_frozen": peak[False],
                     "peak_bytes_shaped": peak[True],
                     "total_bytes_frozen": total[False],
                     "total_bytes_shaped": total[True],
                     "parity": True})
        print(f"shape_{family}_{label},{t[True]:.0f},"
              f"frozen={t[False]:.0f}us|speedup={speedup:.2f}x"
              f"|kernel={us_kernel:.0f}us|kernel_routes={routed[0]}"
              f"|peak_bytes={peak[False]}->{peak[True]}|parity=True")

    key = jax.random.PRNGKey(0)
    for n, M in ((500, 32), (1000, 32), (2000, 64)):
        pts = jnp.asarray(rng.normal(size=(n, 2)).astype(np.float32))

        def hull_pc(s, eng, n=n, M=M, pts=pts):
            exe = eng.compile(hull2d_plan(n, M, shape=s))
            return exe.plan, lambda: exe(pts, key=key)
        run_pair("hull2d", f"n{n}_M{M}", hull_pc, lambda r: r.points, 2)
    for n, M in ((10000, 64), (30000, 64), (60000, 64)):
        x = jnp.asarray(rng.integers(0, 9, n).astype(np.int32))

        def prefix_pc(s, eng, n=n, M=M, x=x):
            exe = eng.compile(prefix_plan(n, M, physical=True, shape=s))
            return exe.plan, lambda: exe(x)
        run_pair("prefix", f"n{n}_M{M}", prefix_pc, lambda r: r.values, 3)
    for P, N, M in ((2048, 128, 32), (8192, 256, 32)):
        addrs = jnp.asarray(rng.integers(0, N, P).astype(np.int32))
        vals = jnp.asarray(rng.normal(size=P).astype(np.float32))
        mem = jnp.zeros(N, jnp.float32)

        def funnel_pc(s, eng, P=P, N=N, M=M, addrs=addrs, vals=vals,
                      mem=mem):
            # identity must stay static for compile(); jit execute_plan
            # directly instead.
            plan = funnel_write_plan(P, N, M, jnp.add, identity=0.0,
                                     shape=s)
            fn = jax.jit(lambda a, v, m: execute_plan(plan, eng,
                                                      (a, v, m)))
            return plan, lambda: fn(addrs, vals, mem)
        run_pair("funnel", f"P{P}_N{N}_M{M}", funnel_pc,
                 lambda r: r.memory, 2)

    # The acceptance claim is absolute and machine-local: the shaped path
    # must beat the frozen path >= 2x at the largest hull2d/prefix point.
    largest = {fam: [r for r in rows if r["family"] == fam][-1]
               for fam in ("hull2d", "prefix", "funnel")}
    assert largest["hull2d"]["speedup"] >= 2.0 or \
        largest["prefix"]["speedup"] >= 2.0, \
        "shape schedule must be >= 2x at the largest hull2d/prefix point"
    # Gated series must be deterministic across machines, so only the
    # declared-byte ratios go under "series" (tools/bench_compare.py fails
    # CI on >1.3x regression *relative to the committed baseline*);
    # wall-clock speedups are reported per row and under "info".
    series = {f"{fam}_total_bytes_ratio":
              r["total_bytes_frozen"] / r["total_bytes_shaped"]
              for fam, r in largest.items()}
    series["hull2d_peak_bytes_ratio"] = (
        largest["hull2d"]["peak_bytes_frozen"]
        / largest["hull2d"]["peak_bytes_shaped"])
    # Deterministic kernel-column acceptance: the fraction of per-stage
    # routing decisions that took the multi-tile radix kernel (asserted
    # 1.0 per grid point above; the series lets the CI gate see it too).
    series["shape_kernel_route_fraction"] = (
        route_counts[0] / max(sum(route_counts), 1))
    info = {f"{fam}_speedup_largest": r["speedup"]
            for fam, r in largest.items()}
    payload = {"bench": "shape_schedule",
               "backend": jax.default_backend(), "rows": rows,
               "series": series, "info": info}
    with open("BENCH_shape.json", "w", encoding="utf-8") as f:
        json.dump(payload, f, indent=2)
    print(f"shape_bench_json,0,wrote BENCH_shape.json ({len(rows)} rows)")


def bench_serve(quick):
    """Coalescing query service vs sequential calls (DESIGN.md §10).

    One seeded mixed workload (sort/multisearch/hull2d/lp traffic from
    ``repro.serve.loadgen``) is run three ways: (1) the sequential
    baseline — one compiled ``exe(*inputs, key=...)`` call per query; (2)
    a warmed ``QueryService`` in a backlogged closed loop at
    ``max_batch=16`` — the coalesced-throughput claim, with an **in-bench
    bit-identity assert** against the baseline, a flat-``trace_count``
    assert (steady traffic never retraces after ``warmup``), and the
    acceptance floor ``>= 3x`` sequential QPS; (3) an open-loop offered-
    load sweep with seeded Poisson arrivals on a :class:`VirtualClock`,
    whose latency/occupancy rows are pure queueing behavior (virtual time
    + fixed seed) — deterministic across machines, so those
    (plus the same-machine QPS/p99 ratios) are the ``"series"`` the CI
    regression gate holds.  Workload sizes are fixed (no ``--quick``
    variation) so BENCH_serve.json stays comparable across runs.
    """
    import json
    from repro.core import LocalEngine
    from repro.serve import QueryService, VirtualClock
    from repro.serve.loadgen import (TrafficConfig, assert_results_equal,
                                     make_suite, make_workload,
                                     run_closed_loop, run_open_loop,
                                     run_sequential)
    engine = LocalEngine()
    cfg = TrafficConfig()
    suite = make_suite(engine, cfg)
    workload = make_workload(suite, cfg)
    plans = [plan for plan, _ in suite.values()]
    B = 16

    seq_results, seq_wall, seq_lat = run_sequential(engine, workload)
    qps_seq = len(workload) / seq_wall

    svc = QueryService(engine, max_batch=B, max_wait_ms=5.0,
                       max_pending=256)
    warm = svc.warmup(plans)
    svc_results, svc_wall = run_closed_loop(svc, workload, concurrency=64)
    # The acceptance assertions: identical bits, no steady-state retraces.
    assert_results_equal(seq_results, svc_results, "bench_serve")
    assert svc.trace_counts() == warm, \
        f"steady traffic retraced: {warm} -> {svc.trace_counts()}"
    qps_svc = len(workload) / svc_wall
    speedup = qps_svc / qps_seq
    assert speedup >= 3.0, \
        f"coalescing must be >= 3x sequential QPS at B={B}, got {speedup:.2f}x"
    st = svc.stats()
    print(f"serve_closed_loop_B{B},{svc_wall/len(workload)*1e6:.0f},"
          f"qps={qps_svc:.0f}|sequential_qps={qps_seq:.0f}"
          f"|speedup={speedup:.1f}x|occupancy={st['mean_occupancy']:.1f}"
          f"|dispatches={st['dispatches']}|identity=True")

    # Offered-load sweep: Poisson open-loop arrivals (the loadgen default —
    # deterministic-interval arrivals understate queueing by never
    # clustering) on a virtual clock, so the measured p50/p99 waits and
    # occupancy isolate the batching window (the deadline floor at low
    # load, window fills at high load).  Seeded + virtual time keeps the
    # queueing series bit-deterministic across machines for the CI gate.
    open_rows = []
    for qps in (200.0, 2000.0, 20000.0, 200000.0):
        clock = VirtualClock()
        svc_o = QueryService(engine, max_batch=B, max_wait_ms=5.0,
                             max_pending=64, clock=clock)
        svc_o.warmup(plans)
        c0 = engine.cache_info()
        row = run_open_loop(svc_o, make_workload(suite, cfg), qps, clock,
                            process="poisson", seed=cfg.seed)
        c1 = engine.cache_info()
        looked_up = (c1.hits - c0.hits) + (c1.misses - c0.misses)
        # hit rate of plan-cache lookups during traffic (warmed: no lookups
        # at all is reported as 1.0 — nothing ever compiled mid-flight)
        row["cache_hit_rate"] = ((c1.hits - c0.hits) / looked_up
                                 if looked_up else 1.0)
        open_rows.append(row)
        print(f"serve_open_qps{qps:.0f},{row['p99_wait_ms']*1e3:.0f},"
              f"p50_wait_ms={row['p50_wait_ms']:.2f}"
              f"|p99_wait_ms={row['p99_wait_ms']:.2f}"
              f"|occupancy={row['mean_occupancy']:.2f}"
              f"|accepted={row['accepted']}|rejected={row['rejected']}")

    lo, hi = open_rows[0], open_rows[-1]
    series = {
        # Gated series must be deterministic across machines and runs, so
        # only the virtual-time queueing figures qualify: occupancy and
        # p99 headroom at the highest offered load, and the p99 *collapse*
        # from deadline-bound (low load) to window-bound (high load) — the
        # continuous-batching latency claim.  The wall-clock QPS speedup
        # is asserted >= 3x in-bench above (every run, every machine) and
        # reported under "info"; gating its run-to-run noise at 1.3x would
        # make CI flaky, the same reason bench_shape keeps wall speedups
        # out of its series.
        "serve_occupancy_hiload": hi["mean_occupancy"],
        "serve_p99_headroom_hiload": cfg_headroom(hi, 5.0),
        "serve_p99_collapse": lo["p99_wait_ms"] / hi["p99_wait_ms"],
    }
    info = {"qps_speedup": speedup,
            "qps_sequential": qps_seq, "qps_service": qps_svc,
            "p50_latency_s": st["p50_latency_s"],
            "p99_latency_s": st["p99_latency_s"],
            "p99_sequential_s": float(np.percentile(seq_lat, 99)),
            "pad_fraction": st["pad_fraction"]}
    payload = {"bench": "serve_continuous_batching", "max_batch": B,
               "max_wait_ms": 5.0, "n_queries": cfg.n_queries,
               "families": list(cfg.families),
               "backend": jax.default_backend(),
               "cache": engine.cache_info()._asdict(),
               "closed_loop": {"wall_s_sequential": seq_wall,
                               "wall_s_service": svc_wall,
                               "dispatches": st["dispatches"],
                               "mean_occupancy": st["mean_occupancy"]},
               "open_loop": open_rows, "series": series, "info": info}
    with open("BENCH_serve.json", "w", encoding="utf-8") as f:
        json.dump(payload, f, indent=2)
    print(f"serve_bench_json,0,wrote BENCH_serve.json "
          f"({len(open_rows)} open-loop rows)")


def bench_faults(quick):
    """Recovery overhead and time-to-recover vs checkpoint interval
    (DESIGN.md §11).

    One seeded sort program is killed mid-flight by an injected shard
    failure (``FaultConfig.fail_at`` pins the shuffle attempt, so the
    scenario is identical on every machine) and recovered from its last
    round-boundary checkpoint at ``checkpoint_every`` ∈ {1, 2, 4}.  Every
    row carries an **in-bench bit-identity assert** — recovered outputs
    and CostAccum must equal the fault-free run exactly.  The gated
    ``"series"`` are deterministic and higher-is-better: replay efficiency
    ``total_rounds / (total + replayed)`` at dense and sparse checkpoint
    intervals (degrades if recovery starts replaying more completed
    rounds) and checkpoint density (checkpoints per MB written — degrades
    if the round-boundary snapshot bloats).  Wall-clock recovery overhead
    is reported per row and under ``"info"``, never gated (same policy as
    bench_shape/bench_serve).
    """
    import json
    import tempfile
    from repro.core import LocalEngine, execute_plan, sort_plan
    from repro.core.recovery import (Checkpointer, FaultConfig,
                                     run_plan_with_recovery)
    engine = LocalEngine()
    n, M = 512, 32             # fixed: the series must compare across runs
    plan = sort_plan(n, M, align=engine.aligned_nodes)
    x = jnp.asarray(np.random.default_rng(0).permutation(n)
                    .astype(np.float32))
    ref = jax.block_until_ready(execute_plan(plan, engine, (x,)))
    us_free = _timeit(lambda: jax.block_until_ready(
        execute_plan(plan, engine, (x,)).values), n=2 if quick else 3)

    # Count the program's shuffle attempts, then kill the last one — the
    # worst case for replay (maximum completed work at stake).
    from repro.core.recovery import with_faults
    probe = with_faults(engine, FaultConfig())
    execute_plan(plan, probe, (x,))
    kill_at = probe.injector.calls - 1

    rows = []
    for every in (1, 2, 4):
        def recover(every=every, record=None):
            with tempfile.TemporaryDirectory() as d:
                ck = Checkpointer(d, plan=plan, every=every)
                out, rep = run_plan_with_recovery(
                    plan, engine, (x,),
                    faults=FaultConfig(fail_at=(kill_at,)),
                    checkpointer=ck)
                jax.block_until_ready(out.values)
                if record is not None:
                    record.append((out, rep))
            return out

        recorded = []
        recover(record=recorded)
        out, rep = recorded[0]
        assert rep.restarts == 1, "the injected failure must fire once"
        for la, lb in zip(jax.tree_util.tree_leaves(ref),
                          jax.tree_util.tree_leaves(out)):
            assert np.array_equal(np.asarray(la), np.asarray(lb)), \
                f"bench_faults: recovery at every={every} diverged"
        us_rec = _timeit(recover, n=1 if quick else 2)
        total = plan.total_rounds
        rows.append({
            "checkpoint_every": every,
            "us_recovered": us_rec, "us_fault_free": us_free,
            "recovery_overhead": us_rec / us_free,
            "rounds_total": total,
            "rounds_replayed": rep.rounds_replayed,
            "checkpoints_written": rep.checkpoints_written,
            "checkpoint_bytes": rep.checkpoint_bytes,
            "parity": True,
        })
        print(f"faults_recover_e{every},{us_rec:.0f},"
              f"overhead={us_rec/us_free:.2f}x"
              f"|replayed={rep.rounds_replayed}/{total}"
              f"|ckpts={rep.checkpoints_written}"
              f"|ckpt_bytes={rep.checkpoint_bytes}|parity=True")

    by_every = {r["checkpoint_every"]: r for r in rows}
    eff = lambda r: r["rounds_total"] / (r["rounds_total"]
                                         + r["rounds_replayed"])
    series = {
        "faults_replay_efficiency_e1": eff(by_every[1]),
        "faults_replay_efficiency_e4": eff(by_every[4]),
        "faults_ckpt_density": (by_every[1]["checkpoints_written"] * 1e6
                                / by_every[1]["checkpoint_bytes"]),
    }
    info = {f"recovery_overhead_e{r['checkpoint_every']}":
            r["recovery_overhead"] for r in rows}
    payload = {"bench": "fault_recovery", "n": n, "M": M,
               "kill_at_shuffle": kill_at,
               "backend": jax.default_backend(),
               "rows": rows, "series": series, "info": info}
    with open("BENCH_faults.json", "w", encoding="utf-8") as f:
        json.dump(payload, f, indent=2)
    print(f"faults_bench_json,0,wrote BENCH_faults.json ({len(rows)} rows)")


def cfg_headroom(row, max_wait_ms):
    """How far under the deadline the p99 wait sits at this load (>= 1 is
    'windows fill before the deadline'); higher is better, deterministic."""
    return max_wait_ms / max(row["p99_wait_ms"], 1e-9)


def bench_obs(quick):
    """Observability coverage and overhead (DESIGN.md §12).

    Two costs matter for ``repro.obs``: the tracer must see everything at
    host boundaries (coverage) and must cost nothing when disabled or on
    jitted paths (overhead).  The gated ``"series"`` are deterministic and
    higher-is-better: **stage coverage** (fraction of a traced eager sort's
    declared stages that appear as ``plan.stage`` spans — drops below 1.0
    if an instrumentation hook is lost in a refactor), **round coverage**
    (``engine.round`` events per declared shuffle round, entry included),
    and **serve event density** (lifecycle events per query in a seeded
    VirtualClock open-loop run — drops if a dispatch/queue/retry hook is
    lost).  Wall-clock tracing overhead on the jitted path is reported
    under ``"info"``, never gated.  Every run carries an in-bench
    neutrality assert: traced and untraced outputs (values + CostAccum)
    must be bit-identical.
    """
    import json
    from repro.core import LocalEngine, execute_plan, sort_plan
    from repro.obs import Tracer, summarize
    from repro.serve import QueryService, VirtualClock
    from repro.serve.loadgen import (TrafficConfig, make_suite,
                                     make_workload, run_open_loop)

    n, M = 512, 32             # fixed: the series must compare across runs
    tr = Tracer()
    eng_on, eng_off = LocalEngine(tracer=tr), LocalEngine()
    plan = sort_plan(n, M, align=eng_off.aligned_nodes)
    x = jnp.asarray(np.random.default_rng(0).permutation(n)
                    .astype(np.float32))

    # -- neutrality: eager traced vs eager untraced, bit for bit ---------
    out_on = execute_plan(plan, eng_on, (x,))
    out_off = execute_plan(plan, eng_off, (x,))
    for la, lb in zip(jax.tree_util.tree_leaves(out_on),
                      jax.tree_util.tree_leaves(out_off)):
        assert np.array_equal(np.asarray(la), np.asarray(lb)), \
            "bench_obs: tracing changed the output"

    # -- coverage from the trace alone -----------------------------------
    s = summarize(tr)
    assert s["schedule_ok"], "bench_obs: measured rounds != declared"
    stage_rows = len(s["stages"])
    stage_cov = stage_rows / len(plan.stages)
    # engine.round fires once per physical shuffle; account stages declare
    # rounds without shuffling, so the denominator is the shuffle stages
    shuffle_stages = sum(1 for st in plan.stages if st.shuffles) or 1
    rounds_seen = sum(1 for e in tr.events() if e.kind == "engine.round")
    round_cov = rounds_seen / shuffle_stages

    # -- jitted-path overhead (info only): tracer on vs off --------------
    exe_on, exe_off = eng_on.compile(plan), eng_off.compile(plan)
    reps = 3 if quick else 10
    us_on = _timeit(lambda: jax.block_until_ready(exe_on(x).values), n=reps)
    us_off = _timeit(lambda: jax.block_until_ready(exe_off(x).values),
                     n=reps)

    # -- serve lifecycle density (seeded, VirtualClock) ------------------
    cfg = TrafficConfig(n_queries=32, seed=7)
    clock = VirtualClock()
    str_ = Tracer(clock=clock)
    seng = LocalEngine(tracer=str_)
    svc = QueryService(seng, max_batch=4, max_wait_ms=5.0, clock=clock,
                       tracer=str_)
    row = run_open_loop(svc, make_workload(make_suite(seng, cfg), cfg),
                        offered_qps=800.0, clock=clock,
                        process="poisson", seed=cfg.seed)
    serve_events = sum(1 for e in str_.events()
                       if e.kind.startswith("serve."))
    serve_density = serve_events / cfg.n_queries

    series = {
        "obs_stage_coverage": stage_cov,
        "obs_round_coverage": round_cov,
        "obs_serve_event_density": serve_density,
    }
    info = {"tracing_overhead_jitted": us_on / us_off,
            "eager_events": len(tr), "serve_events": serve_events,
            "serve_accepted": row["accepted"]}
    payload = {"bench": "observability", "n": n, "M": M,
               "backend": jax.default_backend(),
               "rows": [{"stage_rows": stage_rows,
                         "declared_stages": len(plan.stages),
                         "rounds_seen": rounds_seen,
                         "shuffle_stages": shuffle_stages,
                         "us_traced": us_on, "us_untraced": us_off,
                         "neutrality": True}],
               "series": series, "info": info}
    with open("BENCH_obs.json", "w", encoding="utf-8") as f:
        json.dump(payload, f, indent=2)
    print(f"obs_coverage,{us_on:.0f},stage_cov={stage_cov:.2f}"
          f"|round_cov={round_cov:.2f}|serve_density={serve_density:.2f}"
          f"|overhead={us_on/us_off:.2f}x|neutral=True")
    print("obs_bench_json,0,wrote BENCH_obs.json (1 row)")


BENCHES = [bench_prefix_sums, bench_random_indexing, bench_multisearch,
           bench_sorting, bench_funnel, bench_queues, bench_shuffle,
           bench_kernels, bench_moe_dispatch, bench_geometry,
           bench_cost_model, bench_plan, bench_shape, bench_serve,
           bench_faults, bench_obs]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None,
                    help="run a single benchmark by name, e.g. "
                         "--only serve (matches bench_<name>)")
    args, _ = ap.parse_known_args()
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    benches = BENCHES
    if args.only:
        want = args.only if args.only.startswith("bench_") \
            else f"bench_{args.only}"
        benches = [b for b in BENCHES if b.__name__ == want]
        if not benches:
            raise SystemExit(f"no benchmark named {want}; have "
                             f"{[b.__name__ for b in BENCHES]}")
    print("name,us_per_call,derived")
    for b in benches:
        b(args.quick)


if __name__ == "__main__":
    main()
