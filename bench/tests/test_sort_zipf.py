"""The ``zipf.local`` cell: its key generator against YCSB's, and its whole
run on the CPU at a reduced size."""
import json

import numpy as np

from bench.plans import sort_zipf

from .helpers import ROOT, last_json, run_py

CONFIG = ROOT / "bench" / "configs" / "ycsb-zipf-sort.json"


def test_generator_matches_ycsb():
    # YCSB's Utils: FNV_OFFSET_BASIS_64 and FNV_PRIME_64
    assert sort_zipf.FNV_OFFSET_BASIS_64 == 0xCBF29CE484222325
    assert sort_zipf.FNV_PRIME_64 == 1099511628211
    # fnvhash64(0): FNV-1a over eight zero bytes, as a signed long's abs
    h = 0xCBF29CE484222325
    for _ in range(8):
        h = h * 1099511628211 % 2 ** 64
    assert int(sort_zipf.fnvhash64(np.zeros(1, np.uint64))[0]) == \
        2 ** 64 - h
    cfg = json.loads(CONFIG.read_text())
    keys = sort_zipf.ycsb_keys(cfg, 1 << 20, np.random.default_rng(1))
    assert keys.dtype == np.int32
    assert keys.min() >= 0 and keys.max() < cfg["record_count"]
    # Item 0 is drawn with probability 1 / zetan; it hashes to one key.
    share = np.bincount(keys).max() / keys.size
    assert abs(share * cfg["zetan"] - 1) < 0.03


def test_zipf_cell_runs_correct():
    rc, out, err = run_py(["-m", "bench.tests.cpu_run", "--workload",
                           "zipf.local"])
    assert rc == 0, err[-3000:]
    line = last_json(out)
    assert line["correct"] is True, line
    assert line["checks"]["dropped_keys"] == [0, "<=", 0]
    assert line["checks"]["mismatched_keys"] == [0, "<=", 0]
    assert set(line["metrics"]) == {"items_per_s", "setup_s"}


def test_zipf_cell_runs_correct_where_a_key_overflows_a_bucket():
    """At 2^16 keys and M 256 the commonest key holds about 2480 copies,
    over three buckets of 768 slots: the spread path has to carry it."""
    script = (
        "import sys; from bench import run_cell; "
        "from bench.tests.cpu_run import private_cache\n"
        "with private_cache():\n"
        "    sys.exit(run_cell.run(['--workload', 'zipf.local', '--seed', "
        "'3000000019', '--seconds', '0.5', '--trace', '0'], "
        "require_chip=False, config_overrides={'total_keys': 1 << 16, "
        "'M': 256}))")
    rc, out, err = run_py(["-c", script])
    assert rc == 0, err[-3000:]
    line = last_json(out)
    assert line["correct"] is True, line
    assert line["checks"]["dropped_keys"] == [0, "<=", 0]
