"""Ahead-of-time compiles of the shuffle-path Pallas kernels for a TPU v5e.

The TPU compiler is installed with jaxlib, so these tests compile for a
*described* v5e (``v5e:2x2`` topology, one chip of it) without a chip
attached: Mosaic refuses misaligned blocks, unsupported lowerings and VMEM
overflows here exactly as it would on the device.  Nothing runs, so
correctness stays with the interpret-mode tests (``test_kernels.py``,
``test_kernel_shuffle.py``); this file is the only one that describes a TPU.
"""
import os

import pytest
import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.bincount import MAX_BUCKETS, bincount_tiles
from repro.kernels.bitonic_sort import MAX_ROW_WIDTH, bitonic_sort
from repro.kernels.prefix_scan import prefix_scan
from repro.core import kshuffle

TILE = kshuffle._TILE_N


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    saved_log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"    # else libtpu logs under /tmp
    # A described chip's executables cannot be read back from the
    # persistent cache; keep these compiles out of it.
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            described = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield described
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was_on)
        compilation_cache.reset_cache()
        if saved_log_dir is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = saved_log_dir


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("n_tiles,n_buckets", [
    (256, 64), (256, 1024), (8, MAX_BUCKETS), (3, 100)])
def test_bincount_tiles_compiles(one_chip, n_tiles, n_buckets):
    ids = jax.ShapeDtypeStruct((n_tiles, TILE), jnp.int32, sharding=one_chip)
    _compile(lambda t: bincount_tiles(t, n_buckets), ids)


@pytest.mark.parametrize("rows,n,dtype", [
    (1024, TILE, jnp.int32), (64, TILE, jnp.float32), (3, 100, jnp.int32),
    (8, MAX_ROW_WIDTH, jnp.int32)])
def test_bitonic_sort_compiles(one_chip, rows, n, dtype):
    keys = jax.ShapeDtypeStruct((rows, n), dtype, sharding=one_chip)
    vals = jax.ShapeDtypeStruct((rows, n), jnp.int32, sharding=one_chip)
    _compile(bitonic_sort, keys, vals)


@pytest.mark.parametrize("rows,n,dtype", [
    (8, 4096, jnp.float32), (3, 1000, jnp.int32)])
@pytest.mark.parametrize("exclusive", [False, True])
def test_prefix_scan_compiles(one_chip, rows, n, dtype, exclusive):
    x = jax.ShapeDtypeStruct((rows, n), dtype, sharding=one_chip)
    _compile(lambda a: prefix_scan(a, exclusive=exclusive), x)


def test_kernel_shuffle_compiles(one_chip, monkeypatch):
    """One whole kernel shuffle round at a real width: 2^22 items into
    1024 nodes, the shape the pallas engine routes through the kernels."""
    # jax.default_backend() is the CPU here; steer the wrappers to Mosaic.
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    n, n_nodes, cap = 1 << 22, 1024, 3 * (1 << 22) // 1024
    assert kshuffle.kernel_fits(n, n_nodes)
    dests = jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip)
    payload = jax.ShapeDtypeStruct((n,), jnp.float32, sharding=one_chip)
    compiled = _compile(
        lambda d, p: kshuffle.kernel_shuffle(d, p, n_nodes, cap), dests,
        payload)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.output_size_in_bytes < 16e9
