"""A run with the timed path broken underneath reads ``correct: false``:
once for each fault a cell can have, each caught by the check named beside
it."""
import pytest

from .helpers import last_json, run_py

FAULTS = [("is-a.local", "unchanged", 1, "mismatched_keys"),
          ("is-a.local", "half", 1, "mismatched_keys"),
          ("is-a.local", "altered", 1, "mismatched_keys"),
          ("is-a.pallas", "altered", 1, "mismatched_keys"),
          ("is-a.pallas", "dense_route", 1, "dense_shuffles"),
          ("is-a.pallas", "no_kernels", 1, "mosaic_kernels"),
          ("is-b.sharded4", "no_exchange", 4, "mismatched_keys"),
          ("is-b.sharded4", "altered", 4, "mismatched_keys"),
          ("is-b.sharded4", "gathered", 4, "chips_missing_output")]


@pytest.mark.parametrize("workload,fault,devices,check", FAULTS)
def test_fault_fails_the_check(workload, fault, devices, check):
    rc, out, err = run_py(["-m", "bench.tests.cpu_run", "--workload",
                           workload, "--fault", fault], devices=devices)
    assert rc == 0, err[-3000:]
    line = last_json(out)
    assert line["correct"] is False, line
    value, rule, limit = line["checks"][check]
    assert (value < limit) if rule == ">=" else (value > limit), line
    failing = {k for k, (v, r, lim) in line["checks"].items()
               if ((v < lim) if r == ">=" else (v > lim))}
    if check == "mismatched_keys":
        assert line["failed"] >= 1
    else:
        # The answer itself is right: only the named guarantee catches it.
        assert line["checks"]["mismatched_keys"][0] == 0
        route = {"dense_shuffles", "kernel_shuffles"}
        assert failing <= ({check} | route if check in route else {check})
