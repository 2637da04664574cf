"""The trace reduction and every metric reader, on a small trace with known
times (``data/small_trace.pbtxt``: its header lists them)."""
from pathlib import Path

import pytest

from bench import roofline
from bench import trace as tr
from bench.peaks import PEAKS, peaks
from bench.run_cell import Run

DATA = Path(__file__).parent / "data" / "small_trace.pbtxt"
NS = 1e-9


@pytest.fixture(scope="module")
def trace():
    from jax.profiler import ProfileData
    return tr.from_profile(ProfileData.from_text_proto(DATA.read_text()))


def _run(trace, calls=2, route_dense=0):
    return Run(trace, calls, items_per_call=1000, item_bytes=4, shuffles=3,
               route_dense=route_dense, peaks=PEAKS["TPU v5 lite"])


def test_window_and_busy(trace):
    assert trace.window == (1000, 11000)
    assert tr.window_s(trace) == pytest.approx(10000 * NS)
    # TPU:0 busy 5000 ns (nested ops counted once, the op after the window
    # left out), TPU:1 2610 ns.
    assert tr.busy_s(trace) == pytest.approx((5000 + 2610) / 2 * NS)


def test_ops_are_parsed_from_hlo_text(trace):
    ops = {o.name: o for o in trace.devices["/device:TPU:0"]}
    assert ops["bitonic_sort.2"].kind == "custom-call"
    assert ops["bitonic_sort.2"].target == "tpu_custom_call"
    assert ops["bitonic_sort.2"].module == "jit_run"
    assert ops["custom-call.3"].target == "AllocateBuffer"
    assert ops["fusion.4"].kind == "fusion"
    assert ops["fusion.4"].module == "jit_hop"
    assert ops["all-to-all.5"].kind == "all-to-all"
    assert ops["fusion.4"].shape == "s32[16]{0}"


def test_op_classes(trace):
    # Only tpu_custom_call kernels: not AllocateBuffer, nor a fusion whose
    # operand is named custom-call.
    assert tr.op_seconds(trace, tr.is_mosaic) == pytest.approx(1500 * NS)
    # Synchronous on TPU:0 (500); start to done on TPU:1 (1500).
    assert tr.op_seconds(trace, tr.is_all_to_all) == pytest.approx(1000 * NS)
    assert tr.op_seconds(trace, lambda o: o.name == "nothing") is None


def test_idle_gaps_and_phases(trace):
    ops = trace.devices["/device:TPU:0"]
    assert tr.idle_gaps(ops, 1000, 11000) == [(4000, 4500), (6500, 11000)]
    assert tr.host_phase(trace.host, 6500, 11000) == "dispatch"
    assert tr.host_phase(trace.host, 4000, 4500) == "wait"
    assert tr.host_phase(trace.host, 20000, 21000) == "other"


def test_self_times(trace):
    own = {o.name: t for o, t in
           tr.self_times(trace.devices["/device:TPU:0"], 1000, 11000)}
    assert own == {"while.1": 1900, "bitonic_sort.2": 1000,
                   "custom-call.3": 100, "fusion.4": 1500,
                   "all-to-all.5": 500}


def test_breakdown(trace):
    b = tr.breakdown(trace)
    assert b["device_ops"][0] == [
        "jit_run/bincount_tiles.1 tpu_custom_call (s32[8,128]{1,0}, "
        "s32[8,128]{1,0}, s32[8,128]{1,", pytest.approx(2000 * NS)]
    assert b["device_ops"][1] == ["jit_run/while.1 while (s32[], s32[8]{0})",
                                  pytest.approx(1900 * NS)]
    assert sum(v for _, v in b["device_ops"]) == pytest.approx(
        (5000 + 2610) * NS)
    assert [g[0] for g in b["idle_gaps"]] == ["dispatch", "wait", "wait",
                                              "dispatch", "wait", "wait"]
    assert [g[1] for g in b["idle_gaps"]] == pytest.approx(
        [4500 * NS, 3000 * NS, 2000 * NS, 1390 * NS, 1000 * NS, 500 * NS])


def test_device_idle_pct(trace):
    from bench.metrics import device_idle_pct
    assert device_idle_pct.read(_run(trace)) == pytest.approx(
        100 * (1 - (5000 + 2610) / 2 / 10000))
    assert device_idle_pct.read(_run(None)) is None


def test_kernel_ms_per_sort(trace):
    from bench.metrics import kernel_ms_per_sort
    assert kernel_ms_per_sort.read(_run(trace)) == pytest.approx(
        1500 * NS * 1e3 / 2)


def test_kernel_shuffle_roofline(trace):
    from bench.metrics import kernel_shuffle_roofline
    least = 3 * 1000 * (4 + 4 + 4) / 819e9
    assert kernel_shuffle_roofline.read(_run(trace)) == pytest.approx(
        100 * least / (1500 * NS / 2))
    # A shuffle routed dense leaves the route's roofline unread.
    assert kernel_shuffle_roofline.read(_run(trace, route_dense=1)) is None


def test_collective_ms_per_sort(trace):
    from bench.metrics import collective_ms_per_sort
    assert collective_ms_per_sort.read(_run(trace)) == pytest.approx(
        1000 * NS * 1e3 / 2)


def test_readers_find_nothing_in_an_empty_trace():
    from bench.metrics import (collective_ms_per_sort, device_idle_pct,
                               kernel_ms_per_sort, kernel_shuffle_roofline)
    empty = tr.Trace({}, {}, [], (0, 1000))
    for reader in (device_idle_pct, kernel_ms_per_sort,
                   kernel_shuffle_roofline, collective_ms_per_sort):
        assert reader.read(_run(empty)) is None


def test_roofline_bytes():
    # n items of w bytes: read with a 4-byte destination, written once.
    assert roofline.shuffle_bytes(1 << 23, 4, 3) == 3 * (1 << 23) * 12
    assert roofline.least_seconds(819e9, 819e9) == 1.0


def test_unknown_device_kind_is_an_error():
    assert peaks("TPU v5 lite").hbm_bytes_per_s == 819e9
    with pytest.raises(ValueError):
        peaks("cpu")
