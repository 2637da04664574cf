"""Without a TPU, or without the program, a run prints no result and exits
nonzero."""
import shutil
import subprocess

from .helpers import ROOT, run_py


def test_no_tpu_exits_nonzero_and_prints_nothing():
    rc, out, err = run_py(["bench/run_cell.py", "--workload", "is-a.local",
                           "--seed", "1", "--seconds", "1", "--trace", "0"])
    assert rc != 0
    assert out == ""
    assert "no TPU" in err


def test_four_chip_cell_without_tpu_exits_nonzero():
    rc, out, _ = run_py(["bench/run_cell.py", "--workload", "is-b.sharded4",
                         "--seed", "1", "--seconds", "1", "--trace", "1"],
                        devices=4)
    assert rc != 0 and out == ""


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".jax_cache",
                                                  "__pycache__"))
    rc, out, _ = run_py(["bench/run_cell.py", "--workload", "is-a.local",
                         "--seed", "1", "--seconds", "1", "--trace", "0"],
                        cwd=tmp_path)
    assert rc != 0 and out == ""
    # Past the look for a chip, the missing program still stops the run.
    rc, out, err = run_py(["-m", "bench.tests.cpu_run", "--workload",
                           "is-a.local"], cwd=tmp_path)
    assert rc != 0 and out == ""
    assert "repro" in err


def test_control_without_tpu_exits_nonzero():
    rc, out, _ = run_py(["bench/control.py", "--workload", "is-a.local",
                         "--seeds", "1"])
    assert rc != 0 and out == ""
