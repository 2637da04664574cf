"""Per-kernel shape/dtype sweeps: Pallas (interpret=True on CPU) vs ref.py."""
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.kernels import ops, ref
# raw kernel entry points (explicit interpret flag), not the ops wrappers
from repro.kernels.bincount import bincount as raw_bincount
from repro.kernels.bincount import bincount_tiles as raw_bincount_tiles
# the package re-exports the bitonic_sort *function*; reach the submodule
# explicitly to monkeypatch its row-block budget
import repro.kernels.bitonic_sort
bitonic_mod = sys.modules["repro.kernels.bitonic_sort"]
from repro.kernels.bitonic_sort import bitonic_sort as raw_bitonic_sort
from repro.kernels.prefix_scan import prefix_scan as raw_prefix_scan

RNG = np.random.default_rng(1234)

# The shuffle-path kernels must agree with their oracles in interpret mode
# (CPU CI) and compiled mode (Mosaic; only runnable on a TPU backend).
COMPILED = pytest.param(
    False, id="compiled",
    marks=pytest.mark.skipif(jax.default_backend() != "tpu",
                             reason="compiled Pallas needs a TPU backend"))
INTERPRET_MODES = [pytest.param(True, id="interpret"), COMPILED]


@pytest.mark.parametrize("rows,n,block_n", [
    (1, 16, 8), (4, 1000, 256), (8, 2048, 512), (2, 17, 8), (16, 128, 128),
])
@pytest.mark.parametrize("dtype", [np.float32, np.int32])
@pytest.mark.parametrize("exclusive", [False, True])
def test_prefix_scan(rows, n, block_n, dtype, exclusive):
    if dtype == np.int32:
        x = jnp.asarray(RNG.integers(-5, 50, (rows, n)).astype(dtype))
    else:
        x = jnp.asarray(RNG.normal(size=(rows, n)).astype(dtype))
    got = ops.prefix_scan(x, exclusive=exclusive, block_n=block_n)
    want = ref.prefix_scan_ref(x, exclusive=exclusive)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("n,n_buckets,block_t", [
    (100, 8, 32), (5000, 50, 1024), (1024, 384, 256), (7, 3, 8),
])
def test_bincount(n, n_buckets, block_t):
    ids = jnp.asarray(RNG.integers(-1, n_buckets, n).astype(np.int32))
    got = ops.bincount(ids, n_buckets, block_t=block_t)
    want = ref.bincount_ref(ids, n_buckets)
    np.testing.assert_array_equal(got, want)


def _bincount_tiles_oracle(tiles, n_buckets):
    """numpy oracle: per-tile histogram + the two exclusive scans."""
    t = np.asarray(tiles)
    C = np.stack([np.bincount(row[row >= 0], minlength=n_buckets)
                  for row in t]).astype(np.int32) if t.shape[0] else \
        np.zeros((0, n_buckets), np.int32)
    P = np.cumsum(C, axis=0) - C                  # cross-tile exclusive
    F = np.cumsum(C, axis=1) - C                  # in-tile bucket offsets
    return C, P, F


@pytest.mark.parametrize("interpret", INTERPRET_MODES)
@pytest.mark.parametrize("T,tile_n,n_buckets", [
    (1, 32, 8),          # single tile: prefix must be all-zero
    (5, 16, 8),          # multi-tile carry across grid steps
    (3, 7, 100),         # n_buckets > items per tile
    (4, 8, 1),           # single bucket
    (0, 16, 8),          # no tiles
    (2, 0, 8),           # empty tiles
])
def test_bincount_tiles(T, tile_n, n_buckets, interpret):
    tiles = jnp.asarray(RNG.integers(-1, n_buckets, (T, tile_n))
                        .astype(np.int32))
    got = raw_bincount_tiles(tiles, n_buckets, interpret=interpret)
    want = _bincount_tiles_oracle(tiles, n_buckets)
    for g, w, name in zip(got, want, ("counts", "tile_prefix",
                                      "bucket_offsets")):
        np.testing.assert_array_equal(np.asarray(g), w, err_msg=name)


@pytest.mark.parametrize("interpret", INTERPRET_MODES)
def test_bincount_tiles_totals_match_bincount(interpret):
    """tile_prefix[-1] + counts[-1] is the global histogram."""
    tiles = jnp.asarray(RNG.integers(-1, 13, (6, 32)).astype(np.int32))
    C, P, _ = raw_bincount_tiles(tiles, 13, interpret=interpret)
    want = raw_bincount(tiles.reshape(-1), 13, block_t=64,
                        interpret=interpret)
    np.testing.assert_array_equal(np.asarray(P[-1] + C[-1]), np.asarray(want))


def test_bitonic_sort_grids_over_row_blocks(monkeypatch):
    """Row counts past one VMEM block split across grid steps (the T-tile
    sort of the radix shuffle): shrink the budget so a small case grids,
    including a non-multiple tail row block."""
    monkeypatch.setattr(bitonic_mod, "_ROW_BLOCK_ELEMS", 8 * 128)
    rows, n = 20, 100                 # n_pad 128 -> block_rows 8 -> grid 3
    base = RNG.permutation(rows * n * 4)[:rows * n].reshape(rows, n)
    k = jnp.asarray(base.astype(np.int32))
    v = jnp.asarray(RNG.normal(size=(rows, n)).astype(np.float32))
    ks, vs = raw_bitonic_sort(k, v, interpret=True)
    kr, vr = ref.bitonic_sort_ref(k, v)
    np.testing.assert_array_equal(np.asarray(ks), np.asarray(kr))
    np.testing.assert_array_equal(np.asarray(vs), np.asarray(vr))


def test_bitonic_sort_single_row_width_guard(monkeypatch):
    monkeypatch.setattr(bitonic_mod, "MAX_ROW_WIDTH", 64)
    with pytest.raises(ValueError, match="single-VMEM-tile"):
        raw_bitonic_sort(jnp.zeros((1, 9), jnp.int32),
                         jnp.zeros((1, 9), jnp.float32), interpret=True)


@pytest.mark.parametrize("rows,n", [(1, 8), (2, 64), (3, 100), (1, 7), (4, 256)])
@pytest.mark.parametrize("dtype", [np.float32, np.int32])
def test_bitonic_sort(rows, n, dtype):
    if dtype == np.int32:
        # unique keys so the value permutation is deterministic
        base = RNG.permutation(rows * n * 4)[:rows * n].reshape(rows, n)
        k = jnp.asarray(base.astype(dtype))
    else:
        k = jnp.asarray(RNG.normal(size=(rows, n)).astype(dtype))
    v = jnp.asarray(RNG.normal(size=(rows, n)).astype(np.float32))
    ks, vs = ops.bitonic_sort(k, v)
    kr, vr = ref.bitonic_sort_ref(k, v)
    np.testing.assert_allclose(ks, kr, rtol=1e-6)
    np.testing.assert_allclose(vs, vr, rtol=1e-6)


class TestAwkwardShapes:
    """Oracle equivalence off the happy path: non-power-of-two and
    non-block-multiple lengths, all-dropped ids, n_buckets > n, and empty
    inputs — the shapes the kernel-backed shuffle feeds the kernels."""

    @pytest.mark.parametrize("interpret", INTERPRET_MODES)
    @pytest.mark.parametrize("n,n_buckets,block_t", [
        (0, 8, 32),          # empty input
        (13, 64, 8),         # n_buckets > n, non-block-multiple
        (31, 5, 16),         # non-power-of-two, non-block-multiple
        (6, 100, 1024),      # block_t > n
    ])
    def test_bincount_awkward(self, n, n_buckets, block_t, interpret):
        ids = jnp.asarray(RNG.integers(-1, n_buckets, n).astype(np.int32))
        got = raw_bincount(ids, n_buckets, block_t=block_t,
                           interpret=interpret)
        np.testing.assert_array_equal(got, ref.bincount_ref(ids, n_buckets))

    @pytest.mark.parametrize("interpret", INTERPRET_MODES)
    def test_bincount_all_dropped(self, interpret):
        ids = jnp.full((40,), -1, jnp.int32)
        got = raw_bincount(ids, 7, block_t=16, interpret=interpret)
        np.testing.assert_array_equal(got, jnp.zeros((7,), jnp.int32))

    @pytest.mark.parametrize("interpret", INTERPRET_MODES)
    @pytest.mark.parametrize("rows,n,block_n", [
        (2, 0, 8),           # empty scan axis
        (1, 1, 8),           # single element
        (3, 13, 8),          # non-block-multiple
        (2, 700, 512),       # non-power-of-two tail block
    ])
    @pytest.mark.parametrize("exclusive", [False, True])
    def test_prefix_scan_awkward(self, rows, n, block_n, exclusive,
                                 interpret):
        x = jnp.asarray(RNG.integers(-9, 9, (rows, n)).astype(np.int32))
        got = raw_prefix_scan(x, block_n=block_n, exclusive=exclusive,
                              interpret=interpret)
        np.testing.assert_array_equal(got,
                                      ref.prefix_scan_ref(x,
                                                          exclusive=exclusive))

    @pytest.mark.parametrize("interpret", INTERPRET_MODES)
    @pytest.mark.parametrize("rows,n", [
        (1, 0),              # empty row
        (2, 1),              # single element
        (1, 5),              # non-power-of-two (padding path)
        (3, 33),             # just past a power of two
    ])
    def test_bitonic_sort_awkward(self, rows, n, interpret):
        # unique int keys: the value permutation is then deterministic
        base = RNG.permutation(max(rows * n, 1) * 4)[:rows * n]
        k = jnp.asarray(base.reshape(rows, n).astype(np.int32))
        v = jnp.asarray(RNG.normal(size=(rows, n)).astype(np.float32))
        ks, vs = raw_bitonic_sort(k, v, interpret=interpret)
        kr, vr = ref.bitonic_sort_ref(k, v)
        np.testing.assert_array_equal(ks, kr)
        np.testing.assert_array_equal(vs, vr)


@pytest.mark.parametrize("b,hq,hkv,s,d,causal", [
    (2, 4, 2, 128, 64, True),
    (1, 2, 2, 200, 32, False),     # exercises seq padding + key masking
    (1, 8, 2, 256, 64, True),
    (1, 2, 1, 100, 48, True),      # MQA + head-dim not 2^k
    (2, 4, 4, 64, 128, False),
])
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_flash_attention(b, hq, hkv, s, d, causal, dtype):
    q = jnp.asarray(RNG.normal(size=(b, hq, s, d)).astype(np.float32)).astype(dtype)
    k = jnp.asarray(RNG.normal(size=(b, hkv, s, d)).astype(np.float32)).astype(dtype)
    v = jnp.asarray(RNG.normal(size=(b, hkv, s, d)).astype(np.float32)).astype(dtype)
    got = ops.flash_attention(q, k, v, causal=causal, block_q=64, block_k=64)
    g = hq // hkv
    kk = jnp.repeat(k, g, axis=1).reshape(b * hq, s, d)
    vv = jnp.repeat(v, g, axis=1).reshape(b * hq, s, d)
    want = ref.flash_attention_ref(
        q.reshape(b * hq, s, d), kk, vv, causal=causal).reshape(b, hq, s, d)
    tol = 2e-4 if dtype == np.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=tol, atol=tol)


@pytest.mark.parametrize("b,t,d,block_t", [
    (2, 100, 16, 64), (1, 513, 8, 128), (3, 64, 32, 16), (1, 16, 4, 16),
])
def test_ssm_scan(b, t, d, block_t):
    a = jnp.asarray(RNG.uniform(0.8, 1.0, size=(b, t, d)).astype(np.float32))
    x = jnp.asarray(RNG.normal(size=(b, t, d)).astype(np.float32))
    got = ops.ssm_scan(a, x, block_t=block_t)
    want = ref.ssm_scan_ref(a, x)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_flash_attention_decode_shape():
    """serve_step pattern: 1 query token against a long KV cache."""
    b, h, skv, d = 2, 4, 512, 64
    q = jnp.asarray(RNG.normal(size=(b, h, 1, d)).astype(np.float32))
    k = jnp.asarray(RNG.normal(size=(b, h, skv, d)).astype(np.float32))
    v = jnp.asarray(RNG.normal(size=(b, h, skv, d)).astype(np.float32))
    got = ops.flash_attention(q, k, v, causal=False, block_q=64, block_k=128)
    want = ref.flash_attention_ref(q.reshape(b * h, 1, d),
                                   k.reshape(b * h, skv, d),
                                   v.reshape(b * h, skv, d),
                                   causal=False).reshape(b, h, 1, d)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
