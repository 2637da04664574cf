"""``bench/layers.py`` on the CPU at a tiny size: the cell's run, then the
layers line, with the program's host spans read from the same trace."""
import pytest

from .helpers import last_json, run_py

RUN = """
import sys
from bench import layers
from bench.tests.cpu_run import TINY, private_cache
with private_cache():
    sys.exit(layers.main(["--workload", sys.argv[1], "--seed", "5",
                          "--seconds", "0.5"], require_chip=False,
                         config_overrides=TINY))
"""


@pytest.mark.parametrize("workload,devices,stages", [
    ("is-a.local", 1, False), ("is-b.sharded4", 4, True)])
def test_layers_line_follows_the_result_line(workload, devices, stages):
    rc, out, err = run_py(["-c", RUN, workload], devices=devices)
    assert rc == 0, err[-3000:]
    lines = out.strip().splitlines()
    line = last_json(out)
    assert line["phase"] == "layers"
    result = last_json("\n".join(lines[:-1]))
    assert result["correct"] is True
    assert line["calls"] == result["attempted"]
    # No chip plane on the CPU: no device seconds to attribute.
    assert line["busy_s"] is None and line["layers"] == {}
    spans = line["program_spans"]
    assert spans["exe.call"] == line["calls"]
    # The eager sharded plan runs its stages and rounds on the host; the
    # jitted one-chip plan runs them inside one program.
    assert ("plan.stage" in spans) == stages
    assert ("engine.round" in spans) == stages
