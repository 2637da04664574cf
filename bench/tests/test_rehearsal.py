"""The whole run of each cell on the CPU at a tiny size, with the look for
a chip skipped: inputs, plan, loop, comparison and result line."""
import pytest

from .helpers import last_json, run_py

CELLS = [("is-a.local", 1), ("is-a.pallas", 1), ("is-b.sharded4", 4)]


@pytest.mark.parametrize("workload,devices", CELLS)
def test_cell_runs_correct(workload, devices):
    rc, out, err = run_py(["-m", "bench.tests.cpu_run", "--workload",
                           workload], devices=devices)
    assert rc == 0, err[-3000:]
    line = last_json(out)
    assert line["correct"] is True, line
    assert line["failed"] == 0 and line["attempted"] >= 1
    assert list(line)[-1] == "checks"
    assert set(line["metrics"]) == {"items_per_s", "setup_s"}
    assert line["metrics"]["items_per_s"]["value"] > 0
    assert line["device"]["count"] == devices
    assert line["checks"]["mismatched_keys"] == [0, "<=", 0]
    if workload == "is-a.pallas":
        assert line["checks"]["dense_shuffles"] == [0, "<=", 0]
    if devices > 1:
        assert line["checks"]["chips_missing_output"] == [0, "<=", 0]
    assert err.rstrip().splitlines()[-1].startswith("check ")


def test_cell_runs_correct_under_an_open_mix():
    """A mix is data: an open Poisson loop drives a cell with no new code."""
    import json
    mix = {"loop": "open", "arrivals": "poisson", "rate_per_s": 8,
           "burst": 2}
    rc, out, err = run_py(["-m", "bench.tests.cpu_run", "--workload",
                           "is-a.local", "--mix", json.dumps(mix)])
    assert rc == 0, err[-3000:]
    line = last_json(out)
    assert line["correct"] is True, line
    assert line["attempted"] == 2 * round(8 * 0.5)


def test_traced_run_has_breakdown():
    rc, out, err = run_py(["-m", "bench.tests.cpu_run", "--workload",
                           "is-a.local", "--trace", "1"])
    assert rc == 0, err[-3000:]
    line = last_json(out)
    assert line["correct"] is True
    # No chip plane on the CPU: the device readers find nothing to read.
    assert line["metrics"] == {}
    assert line["device"]["window_s"] > 0
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
