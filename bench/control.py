#!/usr/bin/env python3
"""The control of a cell's comparison: a sort that breaks a stated guarantee.

    python bench/control.py --workload is-a.local --seeds 11 12 13 --seconds 3

The control is the plain reference put in the program's place, with one
guarantee of the configuration broken: it sorts the keys on the device by
the keys with their low ``CONTROL_BITS`` bits cleared, the shortcut of a
radix sort that skips its last digit.  Its answer is a permutation of the
keys that is in order only by their high bits.

For each seed, in one process, the control takes the cell's inputs, runs
through the cell's own loop for a short window, and its answers are compared
exactly as a run of the cell compares the program's.  It prints one JSON
line a seed with the compared numbers; every one of them has to read
``correct: false``.  Like a run, it exits nonzero without the cell's chips.
"""
import argparse
import json
import sys
from pathlib import Path
from typing import NamedTuple

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
from bench import run_cell  # noqa: E402

CONTROL_BITS = 4


class _Stats(NamedTuple):
    dropped: object


class _Result(NamedTuple):
    values: object
    stats: _Stats


def control_sort():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def sort(keys):
        order = jnp.argsort(keys >> CONTROL_BITS, stable=True)
        return _Result(keys[order], _Stats(jnp.zeros((), jnp.int32)))
    return sort


def main(argv=None, *, require_chip: bool = True, config_overrides=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    spec, cell, cfg, mix = run_cell.load_cell(args.workload, config_overrides)
    import importlib
    import jax
    run_cell.use_cache()
    devices = jax.devices()
    try:
        run_cell.check_devices(devices, int(cell["chips"]))
    except run_cell.NoChip as e:
        if require_chip:
            print(f"control: {e}", file=sys.stderr)
            return 2
    from bench import loop
    plans = importlib.import_module(f"bench.plans.{cfg['plan']}")
    reference = importlib.import_module(f"bench.reference.{cfg['plan']}")
    sharding = None
    if len(devices) > 1:
        from jax.sharding import Mesh, NamedSharding, PartitionSpec
        sharding = NamedSharding(Mesh(devices, ("nodes",)),
                                 PartitionSpec("nodes"))
    sort = control_sort()
    for seed in args.seeds:
        inputs, _ = plans.make_pool(cfg, seed, int(mix["pool"]), sharding)
        jax.block_until_ready(sort(*inputs[0]))
        win = loop.drive(mix, lambda i: sort(*inputs[i % len(inputs)]),
                         args.seconds, seed=seed,
                         summarize=lambda out: plans.values(out)[1])
        checks, failed = run_cell.compare_answers(win, inputs, plans,
                                                  reference, devices)
        line = {"seed": seed, "correct": all(c["ok"] for c in
                                             checks.values()),
                "attempted": win.calls, "failed": failed,
                "checks": run_cell.report_checks(checks)}
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
