"""The layer attribution (``bench.scopes``) and its readers, on a trace with
layer names, the program's host spans and known times
(``data/scoped_trace.pbtxt``: its header lists them)."""
from pathlib import Path

import pytest

from bench import scopes
from bench import trace as tr
from bench.metrics import hop_ms_per_sort, shuffle_ms_per_sort
from bench.peaks import PEAKS
from bench.run_cell import Run

DATA = Path(__file__).parent / "data"
NS = 1e-9

#: the compiled HLO text of the trace's jit_run program, cut to the lines
#: that name its ops: copy.6 carries no op_name and no op uses it; fusion.7,
#: a fusion the compiler built without metadata, takes the op_name inside
#: the computation it calls; sort.9, a sort the compiler made, takes its
#: user fusion.8's
HLO = """\
HloModule jit_run, entry_computation_layout={(s32[8]{0})->s32[8]{0}}

%fused_computation.7 (param_0.1: s32[8], param_1.1: s32[8]) -> s32[8] {
  %param_0.1 = s32[8]{0} parameter(0)
  %reshape.2 = s32[8]{0} reshape(s32[8]{0} %param_0.1), metadata={op_name="jit(run)/refine-1/mr.shuffle/add"}
  ROOT %scatter.1 = s32[8]{0} scatter(s32[8]{0} %reshape.2, s32[8]{0} %param_1.1), to_apply=%add.1
}

%body.1 (p: (s32[], s32[8])) -> (s32[], s32[8]) {
  ROOT %fusion.3 = s32[8]{0} fusion(s32[8]{0} %p.2), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(run)/entry/mr.round/sort.lookup/jit(searchsorted)/while/body/lt" source_file="sortmr.py" source_line=1}
}

ENTRY %main.1 (p.1: s32[8]) -> s32[8] {
  %fusion.1 = s32[8]{0} fusion(s32[8]{0} %p.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(run)/mr.prologue/sort" source_file="sortmr.py" source_line=1}
  %while.2 = (s32[], s32[8]{0}) while((s32[], s32[8]{0}) %tuple.1), condition=%cond.1, body=%body.1, metadata={op_name="jit(run)/entry/mr.round/sort.lookup/jit(searchsorted)/while"}
  %fusion.4 = s32[8]{0} fusion(s32[8]{0} %p.3), kind=kLoop, calls=%fused_computation.4, metadata={op_name="jit(run)/entry/mr.round/jit(_where)/select_n"}
  %sort.5 = (s32[8]{0}, s32[8]{0}) sort(s32[8]{0} %p.4, s32[8]{0} %p.5), dimensions={0}, is_stable=true, to_apply=%lt.1, metadata={op_name="jit(run)/entry/mr.shuffle/sort"}
  %copy.6 = s32[8]{0} copy(s32[8]{0} %p.9)
  %fusion.7 = s32[8]{0} fusion(s32[8]{0} %p.6, s32[8]{0} %p.7), kind=kCustom, calls=%fused_computation.7
  %sort.9 = (s32[8]{0}, s32[8]{0}) sort(s32[8]{0} %fusion.7, s32[8]{0} %p.8), dimensions={0}, to_apply=%lt.2
  %get-tuple-element.9 = s32[8]{0} get-tuple-element((s32[8]{0}, s32[8]{0}) %sort.9), index=0
  ROOT %fusion.8 = s32[8]{0} fusion(s32[8]{0} %get-tuple-element.9), kind=kLoop, calls=%fused_computation.8, metadata={op_name="jit(run)/mr.epilogue/searchsorted"}
}
"""


@pytest.fixture(scope="module")
def profile():
    from jax.profiler import ProfileData
    return ProfileData.from_text_proto(
        (DATA / "scoped_trace.pbtxt").read_text())


@pytest.fixture(scope="module")
def trace(profile):
    return tr.from_profile(profile)


def _run(trace, calls=2):
    return Run(trace, calls, items_per_call=1000, item_bytes=4, shuffles=3,
               route_dense=0, peaks=PEAKS["TPU v5 lite"])


@pytest.mark.parametrize("op_name, layer, stage", [
    ("jit(run)/refine-1/mr.round/sort.lookup/jit(searchsorted)/while/body/lt",
     "sort.lookup", "refine-1"),
    ("jit(run)/entry/mr.round/jit(_where)/select_n", "mr.round", "entry"),
    ("jit(run)/local-sort/mr.shuffle/sort", "mr.shuffle", "local-sort"),
    ("jit(run)/mr.prologue/sort", "mr.prologue", ""),
    ("jit(mr_hop)/mr.hop/all_to_all", "mr.hop", ""),
    ("jit(run)/entry/add", "", ""),
    ("", "", ""),
])
def test_layer_and_stage_of(op_name, layer, stage):
    assert scopes.layer_of(op_name) == layer
    assert scopes.stage_of(op_name) == stage


def test_op_names_from_hlo_text():
    names = scopes.op_names(HLO)
    assert names[("jit_run", "fusion.1")] == "jit(run)/mr.prologue/sort"
    assert scopes.layer_of(names[("jit_run", "fusion.3")]) == "sort.lookup"
    assert ("jit_run", "copy.6") not in names
    assert names[("jit_run", "fusion.7")] == \
        "jit(run)/refine-1/mr.shuffle/add"
    assert scopes.layer_of(names[("jit_run", "sort.9")]) == "mr.epilogue"
    assert {k[1] for k in names} == {
        "fusion.1", "while.2", "fusion.3", "fusion.4", "sort.5", "fusion.7",
        "sort.9", "get-tuple-element.9", "fusion.8", "param_0.1",
        "reshape.2", "scatter.1"}


def test_layers_and_unattributed_add_to_busy(trace):
    got = scopes.attribute(trace, scopes.op_names(HLO))
    assert got["busy_s"] == pytest.approx(9500 * NS)
    want = {"mr.prologue": 1000, "sort.lookup": 3000, "mr.round": 500,
            "mr.shuffle": 3000, "mr.hop": 1000, "mr.epilogue": 750}
    assert got["layers"] == pytest.approx({k: v * NS for k, v in want.items()})
    assert got["unattributed"] == pytest.approx(250 * NS)
    assert sum(got["layers"].values()) + got["unattributed"] == \
        pytest.approx(got["busy_s"])
    assert set(got["stages"]) == {"entry", "refine-1"}
    assert got["stages"]["entry"] == pytest.approx({
        "sort.lookup": 3000 * NS, "mr.round": 500 * NS,
        "mr.shuffle": 1500 * NS})
    assert got["stages"]["refine-1"] == pytest.approx(
        {"mr.shuffle": 500 * NS})


def test_program_names_alone(trace):
    # Without the op names only the hop and scatter programs are named.
    got = scopes.attribute(trace)
    assert got["layers"] == pytest.approx({"mr.hop": 1000 * NS,
                                           "mr.shuffle": 1000 * NS})
    assert got["unattributed"] == pytest.approx(7500 * NS)
    assert sum(got["layers"].values()) + got["unattributed"] == \
        pytest.approx(got["busy_s"])
    assert scopes.layer_seconds(trace, ("sort.lookup",)) is None
    assert scopes.layer_seconds(trace, ("sort.lookup",),
                                scopes.op_names(HLO)) == \
        pytest.approx(3000 * NS)


def test_readers(trace):
    run = _run(trace)
    assert hop_ms_per_sort.read(run) == pytest.approx(1e3 * 1000 * NS / 2)
    assert shuffle_ms_per_sort.read(run) == pytest.approx(1e3 * 2000 * NS / 2)
    for reader in (hop_ms_per_sort, shuffle_ms_per_sort):
        assert reader.read(_run(None)) is None
        assert reader.read(_run(trace, calls=0)) is None


def test_readers_find_nothing_in_unnamed_programs():
    # A program without the named hop and scatter (the recorded trace of
    # bench/tests/test_trace.py) gives no reading, and no error.
    from jax.profiler import ProfileData
    small = tr.from_profile(ProfileData.from_text_proto(
        (DATA / "small_trace.pbtxt").read_text()))
    assert hop_ms_per_sort.read(_run(small)) is None
    assert shuffle_ms_per_sort.read(_run(small)) is None


def test_program_spans(profile):
    spans = scopes.program_spans(profile)
    assert [s.name for s in spans] == ["exe.call", "exe.call", "plan.stage",
                                       "engine.round", "plan.stage",
                                       "engine.round"]
    assert spans[2].attrs == {"stage": "entry"}
    assert spans[5].attrs == {"round": 1}
    assert (spans[1].start, spans[1].end) == (16500, 19500)


def test_host_idle_inside_exe_call(profile, trace):
    got = scopes.host_idle(trace, scopes.program_spans(profile))
    assert got["total_s"] == pytest.approx(1050 * NS)
    assert got["by"] == pytest.approx({"entry/round 0": 500 * NS,
                                       "refine-1/round 1": 500 * NS,
                                       "exe.call": 50 * NS})
    assert scopes.host_idle(trace, []) is None


def test_unattributed_ops(trace):
    top = scopes.unattributed_ops(trace, scopes.op_names(HLO))
    assert top == [["jit_run/copy.6 copy s32[8]{0}", pytest.approx(500 * NS)]]
