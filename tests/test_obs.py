"""repro.obs: tracing neutrality, determinism, exporters, serve/recovery events.

Pins the DESIGN.md §12 contracts:

- **neutrality** — attaching a live :class:`Tracer` changes nothing:
  outputs *and* CostAccum stay bit-identical on all four backends
  (Reference / Local / Sharded / Pallas) for sort and hull2d, because
  instrumentation lives at host boundaries and drops at jax trace time;
- **determinism** — two traced replays of one seeded fault-injected
  recovery run produce identical event signature sequences (timestamps
  excluded by construction);
- the tracer core (ring bound, span context, under-jit drop, NullTracer),
  the metrics registry snapshot schema, both exporters, the summary's
  measured-vs-declared schedule check, the serve dispatch causes and the
  per-plan ``max_wait_ms`` override, the Poisson open-loop arrivals, and
  the per-engine ``route_log``;
- **layer names** — the compiled programs name their layers
  (``jax.named_scope``: ``mr.prologue``, ``mr.round``, ``sort.lookup``,
  ``mr.shuffle``, ``mr.hop``, ``mr.epilogue``), and the host spans
  ``exe.call``, ``plan.stage`` and ``engine.round`` reach a
  ``jax.profiler`` trace with or without a live tracer.
"""
import json
import pathlib
import subprocess
import sys
import tempfile

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import (LocalEngine, ReferenceEngine, ShardedEngine,
                        get_engine, hull2d_plan, sort_plan)
from repro.core.plan import execute_plan
from repro.core.recovery import (Checkpointer, FaultConfig, FaultInjector,
                                 run_plan_with_recovery, with_faults)
from repro.obs import (NULL_TRACER, MetricsRegistry, TraceEvent, Tracer,
                       read_jsonl, summarize, to_chrome_trace,
                       write_chrome_trace, write_jsonl)
from repro.serve import QueryService, VirtualClock
from repro.serve.loadgen import (TrafficConfig, arrival_times, make_suite,
                                 make_workload, run_open_loop)

RNG = np.random.default_rng(11)


def _leaves(tree):
    return [np.asarray(l) for l in jax.tree_util.tree_leaves(tree)]


def _assert_bitwise_equal(a, b, ctx=""):
    la, lb = _leaves(a), _leaves(b)
    assert len(la) == len(lb), ctx
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(x, y, err_msg=ctx)


# ---------------------------------------------------------------------------
# Tracer core
# ---------------------------------------------------------------------------

class TestTracerCore:
    def test_ring_bound_and_overwritten(self):
        tr = Tracer(maxlen=4, clock=iter(range(100)).__next__)
        for i in range(10):
            tr.event("k", i=i)
        assert len(tr) == 4
        assert tr.recorded == 10
        assert tr.overwritten == 6
        assert [e.attrs["i"] for e in tr.events()] == [6, 7, 8, 9]

    def test_span_context_inheritance(self):
        tr = Tracer(clock=iter(range(100)).__next__)
        with tr.span("plan.execute", plan="p", digest="d"):
            with tr.span("plan.stage", stage="s") as sp:
                tr.event("engine.round", round=0)
                sp["measured_rounds"] = 1
        kinds = [e.kind for e in tr.events()]
        assert kinds == ["engine.round", "plan.stage", "plan.execute"]
        ev = tr.events()[0]
        assert ev.attrs["plan"] == "p" and ev.attrs["stage"] == "s"
        assert ev.attrs["digest"] == "d"
        stage = tr.events()[1]
        assert stage.attrs["measured_rounds"] == 1
        assert stage.dur is not None and stage.ts <= stage.ts + stage.dur

    def test_event_dropped_under_jit(self):
        tr = Tracer()

        @jax.jit
        def f(x):
            tr.event("should.not.record", x=1)
            tr.count("nope")
            return x + 1

        out = f(jnp.ones(2))
        assert float(out[0]) == 2.0
        assert len(tr) == 0 and tr.skipped == 1
        assert tr.metrics.snapshot()["counters"] == {}

    def test_trace_event_records_under_jit(self):
        tr = Tracer()

        @jax.jit
        def f(x):
            tr.trace_event("shuffle.route", impl="kernel", n=4)
            return x * 2

        f(jnp.ones(2))
        f(jnp.ones(2))   # cached lowering: no second trace
        assert [e.kind for e in tr.events()] == ["shuffle.route"]

    def test_abstract_attr_drops_event(self):
        tr = Tracer()

        @jax.jit
        def f(x):
            tr.trace_event("bad", val=x)      # traced value -> dropped
            return x

        f(jnp.ones(2))
        assert len(tr) == 0 and tr.skipped == 1

    def test_null_tracer_is_inert(self):
        assert not NULL_TRACER.enabled
        NULL_TRACER.event("x", a=1)
        NULL_TRACER.count("c")
        with NULL_TRACER.span("s", k=1) as sp:
            sp["ignored"] = 2
        assert len(NULL_TRACER) == 0
        assert NULL_TRACER.events() == []
        assert NULL_TRACER.metrics.snapshot()["counters"] == {}

    def test_signatures_exclude_time(self):
        a = Tracer(clock=iter(range(100)).__next__)
        b = Tracer(clock=iter(range(1000, 1100)).__next__)
        for tr in (a, b):
            with tr.span("plan.stage", stage="s"):
                tr.event("engine.round", round=0)
        assert a.signatures() == b.signatures()

    def test_maxlen_validated(self):
        with pytest.raises(ValueError):
            Tracer(maxlen=0)


class TestMetricsRegistry:
    def test_snapshot_schema(self):
        m = MetricsRegistry()
        m.counter("a").inc()
        m.counter("a").inc(2)
        m.gauge("g").set(4.5)
        h = m.histogram("h")
        for v in (1.0, 2.0, 3.0):
            h.observe(v)
        snap = m.snapshot()
        assert set(snap) == {"counters", "gauges", "histograms"}
        assert snap["counters"]["a"] == 3
        assert snap["gauges"]["g"] == 4.5
        hs = snap["histograms"]["h"]
        assert hs["count"] == 3 and hs["min"] == 1.0 and hs["max"] == 3.0
        assert hs["mean"] == 2.0

    def test_counter_rejects_negative(self):
        with pytest.raises(ValueError):
            MetricsRegistry().counter("c").inc(-1)


# ---------------------------------------------------------------------------
# Neutrality: tracing on vs off, bit for bit, all four backends
# ---------------------------------------------------------------------------

def _backends():
    return [lambda **kw: ReferenceEngine(**kw),
            lambda **kw: LocalEngine(**kw),
            lambda **kw: ShardedEngine(**kw),
            lambda **kw: get_engine("pallas", **kw)]


class TestNeutrality:
    @pytest.mark.parametrize("make", _backends())
    def test_sort_bit_identical(self, make):
        x = jnp.asarray(RNG.normal(size=48).astype(np.float32))
        tr = Tracer()
        e_on, e_off = make(tracer=tr), make()
        plan = sort_plan(48, 8, align=e_off.aligned_nodes)
        out_on = e_on.compile(plan)(x)
        out_off = e_off.compile(plan)(x)
        # EngineSortResult flattens to (values, CostAccum fields): the
        # comparison covers outputs AND cost accounting.
        _assert_bitwise_equal(out_on, out_off, f"sort on {e_off.name}")
        assert tr.recorded > 0          # the tracer did observe the run

    @pytest.mark.parametrize("make", _backends())
    def test_hull2d_bit_identical(self, make):
        pts = jnp.asarray(RNG.normal(size=(24, 2)).astype(np.float32))
        tr = Tracer()
        e_on, e_off = make(tracer=tr), make()
        plan = hull2d_plan(24, 8, align=e_off.aligned_nodes)
        out_on = e_on.compile(plan)(pts)
        out_off = e_off.compile(plan)(pts)
        _assert_bitwise_equal(out_on, out_off, f"hull2d on {e_off.name}")
        assert tr.recorded > 0


# ---------------------------------------------------------------------------
# Schedule: measured rounds == declared rounds, from the trace alone
# ---------------------------------------------------------------------------

class TestScheduleFromTrace:
    def test_eager_execute_plan_records_schedule(self):
        tr = Tracer()
        eng = LocalEngine(tracer=tr)
        plan = sort_plan(64, 8, align=eng.aligned_nodes)
        x = jnp.asarray(RNG.permutation(64).astype(np.float32))
        execute_plan(plan, eng, (x,))       # eager call: host boundaries run
        s = summarize(tr)
        assert s["schedule_ok"]
        rows = {r["stage"]: r for r in s["stages"]}
        assert rows     # at least one stage row recorded
        declared = sum(st.rounds for st in plan.stages)
        assert s["totals"]["rounds"] == declared
        # the entry stage's shuffle shows up as an engine.round event too
        assert rows["entry"]["shuffle_rounds"] >= 1

    def test_jitted_path_stays_dark_but_correct(self):
        tr = Tracer()
        eng = LocalEngine(tracer=tr)
        plan = sort_plan(64, 8, align=eng.aligned_nodes)
        exe = eng.compile(plan)
        x = jnp.asarray(RNG.permutation(64).astype(np.float32))
        exe(x)
        kinds = {e.kind for e in tr.events()}
        # compile/call surface recorded; per-round interior dropped under jit
        assert "exe.call" in kinds and "cache.miss" in kinds
        assert "plan.stage" not in kinds and "engine.round" not in kinds


# ---------------------------------------------------------------------------
# Recovery: replay determinism, events view, ckpt events
# ---------------------------------------------------------------------------

def _traced_recovery_run(tmp):
    tr = Tracer()
    eng = LocalEngine(tracer=tr)
    plan = sort_plan(64, 8, align=eng.aligned_nodes)
    x = jnp.asarray(np.random.default_rng(3).permutation(64)
                    .astype(np.float32))
    ck = Checkpointer(tmp, plan=plan, every=1)
    out, rep = run_plan_with_recovery(
        plan, eng, (x,), faults=FaultConfig(fail_at=(1,), seed=5),
        checkpointer=ck)
    return tr, out, rep


class TestRecoveryTraces:
    def test_replay_trace_signatures_deterministic(self):
        with tempfile.TemporaryDirectory() as d1, \
                tempfile.TemporaryDirectory() as d2:
            tr1, out1, rep1 = _traced_recovery_run(d1)
            tr2, out2, rep2 = _traced_recovery_run(d2)
        assert tr1.signatures() == tr2.signatures()
        _assert_bitwise_equal(out1, out2, "recovery replay outputs")
        assert rep1.restarts == rep2.restarts == 1

    def test_recovery_events_and_summary(self):
        with tempfile.TemporaryDirectory() as d:
            tr, out, rep = _traced_recovery_run(d)
        kinds = {e.kind for e in tr.events()}
        assert {"fault.failure", "ckpt.save", "ckpt.restore",
                "recover.restart", "plan.stage", "engine.round"} <= kinds
        s = summarize(tr)
        assert s["schedule_ok"]
        assert s["recovery"]["failures"] == 1
        assert s["recovery"]["restarts"] == 1
        assert s["recovery"]["restores"] == 1
        assert s["recovery"]["ckpt_saves"] == rep.checkpoints_written
        assert s["recovery"]["ckpt_bytes"] == rep.checkpoint_bytes
        assert s["recovery"]["aborted_stages"] == 1

    def test_injector_events_legacy_view(self):
        inj = FaultInjector(FaultConfig(fail_at=(0,), fail_shard=0))
        eng = with_faults(LocalEngine(), inj)
        with pytest.raises(Exception):
            eng.shuffle(jnp.zeros(4, jnp.int32), jnp.arange(4.0), 4, 2)
        assert inj.events == [("failure", 0, 0)]
        assert inj.failures == 1
        # the view is reconstructed, not a mutable list
        eng.shuffle(jnp.zeros(4, jnp.int32), jnp.arange(4.0), 4, 2)
        assert inj.events == [("failure", 0, 0)]

    def test_injector_mirrors_into_engine_tracer(self):
        tr = Tracer()
        eng = with_faults(LocalEngine(tracer=tr), FaultConfig(fail_at=(0,)))
        with pytest.raises(Exception):
            eng.shuffle(jnp.zeros(4, jnp.int32), jnp.arange(4.0), 4, 2)
        assert [e.kind for e in tr.events()] == ["fault.failure"]
        assert tr.metrics.snapshot()["counters"]["fault.failures"] == 1


# ---------------------------------------------------------------------------
# Exporters + CLI
# ---------------------------------------------------------------------------

def _sample_trace():
    tr = Tracer(clock=iter(np.arange(0.0, 10.0, 0.25)).__next__)
    with tr.span("plan.execute", plan="sort", digest="abc", backend="local"):
        with tr.span("plan.stage", stage="entry", rounds=1) as sp:
            tr.event("engine.round", round=0, items_sent=4, max_sent=2,
                     max_received=2, dropped=0)
            sp["measured_rounds"] = 1
    tr.event("serve.submit", plan="sort", uid=1, pending=1)
    return tr


class TestExporters:
    def test_jsonl_round_trip(self):
        tr = _sample_trace()
        with tempfile.TemporaryDirectory() as d:
            p = pathlib.Path(d) / "t.jsonl"
            n = write_jsonl(tr, p)
            back = read_jsonl(p)
        assert n == len(back) == len(tr)
        assert [e.signature() for e in back] == tr.signatures()
        assert [e.ts for e in back] == [e.ts for e in tr.events()]

    def test_chrome_trace_structure(self):
        tr = _sample_trace()
        doc = to_chrome_trace(tr)
        assert set(doc) == {"traceEvents", "displayTimeUnit"}
        rows = doc["traceEvents"]
        metas = [r for r in rows if r["ph"] == "M"]
        slices = [r for r in rows if r["ph"] == "X"]
        instants = [r for r in rows if r["ph"] == "i"]
        assert {m["args"]["name"] for m in metas} == {"engine", "plan",
                                                      "serve"}
        assert len(slices) == 2          # the two spans
        assert len(instants) == 2        # round + submit
        # spans carry microsecond durations
        assert all(s["dur"] > 0 for s in slices)
        # deterministic: same trace -> same JSON
        assert json.dumps(doc) == json.dumps(to_chrome_trace(tr))

    def test_chrome_trace_file_is_json(self):
        tr = _sample_trace()
        with tempfile.TemporaryDirectory() as d:
            p = pathlib.Path(d) / "t.json"
            write_chrome_trace(tr, p)
            doc = json.loads(p.read_text())
        assert "traceEvents" in doc

    def test_cli_table_and_exit_code(self):
        tr = _sample_trace()
        repo = pathlib.Path(__file__).resolve().parents[1]
        with tempfile.TemporaryDirectory() as d:
            p = pathlib.Path(d) / "t.jsonl"
            write_jsonl(tr, p)
            out = subprocess.run(
                [sys.executable, str(repo / "tools" / "trace_summary.py"),
                 str(p)], capture_output=True, text=True)
            assert out.returncode == 0, out.stderr
            assert "entry" in out.stdout and "OK" in out.stdout
            diff = subprocess.run(
                [sys.executable, str(repo / "tools" / "trace_summary.py"),
                 str(p), "--diff", str(p)],
                capture_output=True, text=True)
        assert diff.returncode == 0, diff.stderr
        assert "0 drifted" in diff.stdout


# ---------------------------------------------------------------------------
# Serve: dispatch causes, per-plan deadline override, failure events
# ---------------------------------------------------------------------------

def _service(tracer=None, **kw):
    clock = VirtualClock()
    eng = LocalEngine(tracer=tracer)
    kw.setdefault("max_batch", 2)
    kw.setdefault("max_wait_ms", 5.0)
    svc = QueryService(eng, clock=clock, tracer=tracer, **kw)
    return svc, clock


class TestServeEvents:
    def test_window_and_deadline_causes(self):
        tr = Tracer()
        svc, clock = _service(tr)
        plan = sort_plan(4, 4)
        xs = [jnp.asarray(RNG.normal(size=4).astype(np.float32))
              for _ in range(3)]
        svc.submit(plan, xs[0])
        svc.submit(plan, xs[1])             # fills the window
        svc.submit(plan, xs[2])             # partial
        clock.advance(0.005)
        svc.step()                          # deadline sweep
        s = summarize(tr)
        assert s["serve"]["causes"] == {"window": 1, "deadline": 1}
        assert s["serve"]["deadline_events"] == 1
        assert s["serve"]["submitted"] == 3
        assert s["serve"]["completed"] == 3

    def test_per_plan_max_wait_override(self):
        tr = Tracer()
        svc, clock = _service(tr)
        fast = sort_plan(4, 4)
        svc.register(fast, max_wait_ms=1.0)
        t = svc.submit(fast, jnp.asarray([3., 1., 2., 0.]))
        clock.advance(0.002)                # past 1 ms, below service 5 ms
        svc.step()
        assert t.done
        dl = [e for e in tr.events() if e.kind == "serve.deadline"]
        assert len(dl) == 1
        assert dl[0].attrs["deadline_ms"] == 1.0
        # submit-time override works too, and clears via register(None)
        svc.register(fast, max_wait_ms=None)
        t2 = svc.submit(fast, jnp.asarray([3., 1., 2., 0.]),
                        max_wait_ms=2.0)
        clock.advance(0.003)
        svc.step()
        assert t2.done
        assert tr.events()[-2].kind == "serve.deadline"
        assert tr.events()[-2].attrs["deadline_ms"] == 2.0

    def test_default_deadline_unchanged_without_override(self):
        svc, clock = _service()
        plan = sort_plan(4, 4)
        t = svc.submit(plan, jnp.asarray([1., 0., 3., 2.]))
        clock.advance(0.002)
        assert svc.step() == 0 and not t.done    # 5 ms default still holds
        clock.advance(0.003)
        svc.step()
        assert t.done

    def test_requeue_and_fail_events(self):
        tr = Tracer()
        clock = VirtualClock()
        eng = with_faults(LocalEngine(tracer=tr),
                          FaultConfig(fail_at=tuple(range(64))))
        svc = QueryService(eng, max_batch=1, max_retries=1, clock=clock,
                           tracer=tr)
        plan = sort_plan(4, 4)
        t = svc.submit(plan, jnp.asarray([3., 1., 2., 0.]))  # window of 1
        svc.drain()
        assert t.failed
        kinds = [e.kind for e in tr.events()]
        assert "serve.dispatch_error" in kinds
        assert "serve.requeue" in kinds
        assert "serve.fail" in kinds
        s = summarize(tr)
        assert s["serve"]["failed"] == 1
        assert s["serve"]["requeued"] == 1
        assert s["serve"]["dispatch_errors"] == 2   # initial + retry


# ---------------------------------------------------------------------------
# Load generation: Poisson open loop
# ---------------------------------------------------------------------------

class TestPoissonOpenLoop:
    def test_arrival_times_deterministic_and_distinct(self):
        a = arrival_times(32, 200.0, "poisson", seed=4)
        b = arrival_times(32, 200.0, "poisson", seed=4)
        c = arrival_times(32, 200.0, "poisson", seed=5)
        d = arrival_times(32, 200.0, "deterministic")
        assert np.array_equal(a, b)
        assert not np.array_equal(a, c)
        assert not np.array_equal(a, d)
        assert np.all(np.diff(a) >= 0)          # arrivals are ordered
        assert np.array_equal(d, np.arange(32) / 200.0)
        with pytest.raises(ValueError):
            arrival_times(4, 100.0, "uniform")

    def test_poisson_row_replays_and_reports_metrics(self):
        cfg = TrafficConfig(families=("sort",), n_queries=24, seed=2,
                            sort_n=16, sort_M=8)

        def one_run():
            clock = VirtualClock()
            tr = Tracer(clock=clock)
            eng = LocalEngine(tracer=tr)
            svc = QueryService(eng, max_batch=4, max_wait_ms=5.0,
                               clock=clock, tracer=tr)
            suite = make_suite(eng, cfg)
            wl = make_workload(suite, cfg)
            return run_open_loop(svc, wl, 600.0, clock,
                                 process="poisson", seed=9)

        r1, r2 = one_run(), one_run()
        assert r1["process"] == "poisson"
        assert r1 == r2                          # VirtualClock-deterministic
        assert r1["accepted"] == 24
        snap = r1["metrics"]
        assert snap["counters"]["serve.submits"] == 24
        assert snap["counters"]["serve.completed"] == 24
        assert snap["histograms"]["serve.wait_ms"]["count"] == 24
        assert snap["histograms"]["serve.occupancy"]["count"] == \
            snap["counters"]["serve.dispatches"]


# ---------------------------------------------------------------------------
# Per-engine route_log
# ---------------------------------------------------------------------------

class TestPerEngineRouteLog:
    def test_route_log_scoped_per_engine(self):
        e1 = get_engine("pallas")
        e2 = get_engine("pallas")
        dests = jnp.asarray(RNG.integers(0, 4, 16).astype(np.int32))
        vals = jnp.asarray(RNG.normal(size=16).astype(np.float32))
        e1.shuffle(dests, vals, 4, 8)
        assert sum(e1.route_log.snapshot()) == 1
        assert sum(e2.route_log.snapshot()) == 0
        e2.shuffle(dests, vals, 4, 8)
        e2.shuffle(dests, vals, 4, 8)
        assert sum(e1.route_log.snapshot()) == 1
        assert sum(e2.route_log.snapshot()) == 2

    def test_route_events_on_engine_tracer(self):
        tr = Tracer()
        eng = get_engine("pallas", tracer=tr)
        dests = jnp.asarray(RNG.integers(0, 4, 16).astype(np.int32))
        vals = jnp.asarray(RNG.normal(size=16).astype(np.float32))
        eng.shuffle(dests, vals, 4, 8)
        routes = [e for e in tr.events() if e.kind == "shuffle.route"]
        assert len(routes) == 1
        assert routes[0].attrs["impl"] in ("kernel", "dense")
        k, d = eng.route_log.snapshot()
        assert routes[0].attrs["impl"] == ("kernel" if k else "dense")


# ---------------------------------------------------------------------------
# Layer names in the compiled programs; host spans on the profiler's clock
# ---------------------------------------------------------------------------

#: the layer scopes every compiled sort plan carries
_SORT_LAYERS = ("mr.prologue", "mr.round", "sort.lookup", "mr.shuffle",
                "mr.epilogue")
_SORT_N, _SORT_M = 256, 16


def _op_names(hlo_text):
    """Every ``op_name`` of an HLO text, split into its scope path."""
    import re
    return [n.split("/") for n in re.findall(r'op_name="([^"]*)"', hlo_text)]


@pytest.fixture(scope="module")
def sort_hlo():
    """The compiled two-level sort plan's HLO text on ``local`` and on
    ``pallas`` (kernels interpreted on the CPU), compiled once."""
    out = {}
    for name in ("local", "pallas"):
        eng = get_engine(name)
        plan = sort_plan(_SORT_N, _SORT_M, levels=2, align=eng.aligned_nodes)
        exe = eng.compile(plan)
        x = jnp.zeros((_SORT_N,), jnp.float32)
        out[name] = (plan, exe._fn.lower(jax.random.PRNGKey(0), x)
                     .compile().as_text())
    return out


class TestLayerScopes:
    @pytest.mark.parametrize("layer", _SORT_LAYERS)
    @pytest.mark.parametrize("name", ["local", "pallas"])
    def test_sort_plan_ops_carry_each_layer(self, sort_hlo, name, layer):
        _, text = sort_hlo[name]
        assert any(layer in path for path in _op_names(text)), layer

    @pytest.mark.parametrize("name", ["local", "pallas"])
    def test_sort_plan_ops_carry_stage_names(self, sort_hlo, name):
        plan, text = sort_hlo[name]
        paths = _op_names(text)
        shuffling = [st.name for st in plan.stages if st.shuffles]
        assert shuffling == ["entry", "refine-1", "local-sort"]
        for stage in shuffling:
            # The stage scope encloses its engine layers.
            under = [p for p in paths if stage in p]
            assert any("mr.shuffle" in p[p.index(stage):] for p in under), \
                stage
        # The lookups happen in the two routing stages, inside a round.
        lookup = {p[p.index("mr.round") - 1] for p in paths
                  if "sort.lookup" in p and "mr.round" in p}
        assert lookup == {"entry", "refine-1"}

    def test_sharded_programs_name_hop_and_scatter(self):
        eng = ShardedEngine()
        V, cap, n = 8, 8, 32
        dests = jnp.asarray(RNG.integers(-1, V, n).astype(np.int32))
        vals = jnp.asarray(RNG.normal(size=n).astype(np.float32))
        hop = eng._build_hop(V, dests.ndim, 1)
        hop_text = hop.lower(dests, vals).compile().as_text()
        local_dest, recv, _, _ = hop(dests, vals)
        scatter = eng._build_scatter(V, cap, len(recv), False)
        sc_text = scatter.lower(local_dest, *recv).compile().as_text()
        assert hop_text.startswith("HloModule jit_mr_hop")
        assert sc_text.startswith("HloModule jit_mr_scatter")
        assert any("mr.hop" in p for p in _op_names(hop_text))
        assert any("mr.shuffle" in p for p in _op_names(sc_text))


def _profiled_sharded_sort(tracer):
    """Host spans (name, stats) that a ``jax.profiler`` trace of one eager
    ``sharded`` two-level sort holds, and the engine."""
    import glob
    import os
    from jax.profiler import ProfileData
    eng = ShardedEngine(tracer=tracer)
    plan = sort_plan(128, 16, levels=2, align=eng.aligned_nodes)
    exe = eng.compile(plan)
    x = jnp.asarray(RNG.normal(size=128).astype(np.float32))
    jax.block_until_ready(exe(x))          # compiles outside the trace
    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d):
            jax.block_until_ready(exe(x))
        (path,) = glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                            recursive=True)
        data = ProfileData.from_file(path)
    spans = [(ev.name, dict(ev.stats)) for plane in data.planes
             if not plane.name.startswith("/device:")
             for line in plane.lines for ev in line.events
             if ev.name in ("exe.call", "plan.stage", "engine.round")]
    return spans, eng, plan


class TestHostSpansOnProfiler:
    @pytest.mark.parametrize("live", [False, True], ids=["null", "tracer"])
    def test_eager_sharded_sort_writes_host_spans(self, live):
        tracer = Tracer() if live else None
        spans, eng, plan = _profiled_sharded_sort(tracer)
        names = [n for n, _ in spans]
        assert names.count("exe.call") == 1
        stages = [st["stage"] for n, st in spans if n == "plan.stage"]
        assert stages == [st.name for st in plan.stages]
        rounds = [st for n, st in spans if n == "engine.round"]
        # entry, refine-1 and local-sort each run one eager round
        assert len(rounds) == 3
        assert all("round" in st for st in rounds)
        if live:
            assert any(e.kind == "plan.stage" for e in eng.tracer.events())
        else:
            assert eng.tracer is NULL_TRACER

    def test_no_profiler_null_tracer_records_nothing(self):
        eng = ShardedEngine()
        plan = sort_plan(64, 8, align=eng.aligned_nodes)
        x = jnp.asarray(RNG.normal(size=64).astype(np.float32))
        out = eng.compile(plan)(x)
        assert int(out.stats.dropped) == 0
        assert eng.tracer is NULL_TRACER
        assert len(NULL_TRACER) == 0 and NULL_TRACER.events() == []
        assert NULL_TRACER.metrics.snapshot()["counters"] == {}

    def test_span_at_trace_time_opens_no_annotation(self):
        seen = []

        @jax.jit
        def f(x):
            with NULL_TRACER.span("plan.stage", stage="s") as sp:
                seen.append(sp._me)
            return x + 1

        f(jnp.ones(2))
        assert seen == [None]
        with NULL_TRACER.span("plan.stage", stage="s") as sp:
            assert sp._me is not None       # eager: the annotation is open
