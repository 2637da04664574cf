"""Drive a cell's run on the CPU at a tiny size, with the look for a chip
skipped, and optionally with a fault planted in the program underneath.

    JAX_PLATFORMS=cpu python -m bench.tests.cpu_run --workload is-a.local \\
        [--fault altered] [--trace 1] [--mix '{"loop": "open", ...}']

Faults:

- ``unchanged``: the call returns its input, as a step that leaves its
  state unchanged;
- ``half``: half of the keys are left out of the sort (copies of the other
  half take their place);
- ``no_exchange``: the sharded hop leaves out the exchange between chips:
  each chip keeps the items it owns and loses the rest;
- ``altered``: one key of the answer is altered where it is produced;
- ``gathered``: the answer is moved onto the first chip, as a program that
  gathers its output onto one device;
- ``dense_route``: the kernel route's size guard refuses every shuffle, so
  each one takes the dense route;
- ``no_kernels``: the harness looks for the Mosaic kernels in the timed
  program on the CPU too, where the interpreted kernels leave none, as a
  program that lost its kernels would on the chip.

``--mix`` replaces keys of the cell's traffic mix, to drive the cell with
another mix.

The last line of standard output is the run's result line.
"""
import argparse
import contextlib
import json
import shutil
import sys
import tempfile
from pathlib import Path
from unittest import mock

ROOT = Path(__file__).resolve().parents[2]
for _p in (str(ROOT), str(ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

TINY = {"total_keys": 1 << 12, "max_key": 1 << 9, "M": 64}


def _patch_call(change_inputs=None, change_result=None):
    from repro.core.api import Executable
    orig = Executable.__call__

    def call(self, *inputs, key=None):
        xs = change_inputs(*inputs) if change_inputs else inputs
        res = orig(self, *xs, key=key)
        return change_result(res, *inputs) if change_result else res
    return mock.patch.object(Executable, "__call__", call)


def _local_hop(dests, leaves, axis_name, n_nodes):
    """keyed_hop with the all_to_all left out."""
    import jax.numpy as jnp
    from jax import lax
    local_v = n_nodes // lax.psum(1, axis_name)
    flat = dests.reshape(-1).astype(jnp.int32)
    n_local = flat.shape[0]
    shard = lax.axis_index(axis_name)
    mine = (flat >= 0) & (flat // local_v == shard)
    local_dest = jnp.where(mine, flat - shard * local_v, -1)
    return local_dest, [l.reshape((n_local,) + l.shape[dests.ndim:])
                        for l in leaves]


@contextlib.contextmanager
def private_cache():
    """Point the harness's compile cache at a directory of this process's
    own, so that test runs leave the checkout's cache alone."""
    from bench import run_cell
    path = Path(tempfile.mkdtemp(prefix="bench-cache-"))
    try:
        with mock.patch.object(run_cell, "CACHE_DIR", path):
            yield
    finally:
        shutil.rmtree(path, ignore_errors=True)


def fault(name):
    if name is None:
        return contextlib.nullcontext()
    if name == "unchanged":
        return _patch_call(change_result=lambda res, x: res._replace(
            values=x))
    if name == "half":
        def halve(x):
            h = x.shape[0] // 2
            return (x.at[h:2 * h].set(x[:h]),)
        return _patch_call(change_inputs=halve)
    if name == "altered":
        return _patch_call(change_result=lambda res, x: res._replace(
            values=res.values.at[x.shape[0] // 3].add(1)))
    if name == "no_exchange":
        return mock.patch("repro.core.distributed.keyed_hop", _local_hop)
    if name == "gathered":
        import jax
        return _patch_call(change_result=lambda res, x: res._replace(
            values=jax.device_put(res.values, jax.devices()[0])))
    if name == "dense_route":
        return mock.patch("repro.core.kshuffle.kernel_fits",
                          lambda *a, **k: False)
    if name == "no_kernels":
        from bench import run_cell
        return mock.patch.object(run_cell, "MOSAIC_PLATFORMS",
                                 ("tpu", "cpu"))
    raise ValueError(f"unknown fault {name!r}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--fault", default=None)
    ap.add_argument("--seed", type=int, default=(1 << 40) + 3)
    ap.add_argument("--seconds", default="0.5")
    ap.add_argument("--trace", default="0")
    ap.add_argument("--mix", type=json.loads, default=None)
    args = ap.parse_args(argv)
    from bench import run_cell
    with private_cache(), fault(args.fault):
        return run_cell.run(["--workload", args.workload, "--seed",
                             str(args.seed), "--seconds", args.seconds,
                             "--trace", args.trace],
                            require_chip=False, config_overrides=TINY,
                            mix_overrides=args.mix)


if __name__ == "__main__":
    sys.exit(main())
