"""The control, a sort by the keys' high bits only, fails the comparison."""
import pytest

from .helpers import ROOT, run_py

CODE = """
import json, sys
sys.path.insert(0, {root!r})
from bench import control
from bench.tests.cpu_run import TINY, private_cache
with private_cache():
    rc = control.main(["--workload", {w!r}, "--seeds", "3", "4", "5",
                       "--seconds", "0.3"],
                      require_chip=False, config_overrides=TINY)
sys.exit(rc)
"""


@pytest.mark.parametrize("workload,devices", [("is-a.local", 1),
                                              ("is-b.sharded4", 4)])
def test_control_reads_not_correct(workload, devices):
    import json
    rc, out, err = run_py(["-c", CODE.format(root=str(ROOT), w=workload)],
                          devices=devices)
    assert rc == 0, err[-3000:]
    lines = [json.loads(l) for l in out.splitlines() if l.startswith("{")]
    assert len(lines) == 3
    for line in lines:
        assert line["correct"] is False
        assert line["checks"]["mismatched_keys"][0] > 0
