"""Per-kernel shape/dtype sweeps against the pure-jnp ref.py oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Histogram, build_exact, merge
from repro.kernels import (
    bucket_sizes_pallas,
    cumulative_counts_pallas,
    merge_pallas,
    sort_kv_pallas,
    sort_tiles_pallas,
    summarize_pallas,
)
from repro.kernels import ref

RNG = np.random.default_rng(42)


@pytest.mark.parametrize("n", [100, 8192, 50_000])
@pytest.mark.parametrize("T", [4, 64, 257])
@pytest.mark.parametrize("dtype", [np.float32, np.float64, np.int32])
def test_bucket_count_sweep(n, T, dtype):
    if dtype == np.int32:
        x = RNG.integers(-100, 100, size=n).astype(dtype)
    else:
        x = (RNG.normal(size=n) * 10).astype(dtype)
    b = np.sort(RNG.normal(size=T + 1) * 10).astype(np.float32)
    got = cumulative_counts_pallas(jnp.asarray(x), jnp.asarray(b))
    want = ref.cumulative_counts_ref(jnp.asarray(x), jnp.asarray(b))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("block_rows", [8, 64])
def test_bucket_count_block_shapes(block_rows):
    x = RNG.normal(size=5000).astype(np.float32)
    b = np.sort(RNG.normal(size=33)).astype(np.float32)
    got = cumulative_counts_pallas(
        jnp.asarray(x), jnp.asarray(b), block_rows=block_rows
    )
    want = ref.cumulative_counts_ref(jnp.asarray(x), jnp.asarray(b))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want))


def test_bucket_sizes_sum_to_n():
    x = RNG.gumbel(size=20_000).astype(np.float32)
    h = build_exact(jnp.asarray(x), 64)
    sizes = bucket_sizes_pallas(jnp.asarray(x), h.boundaries)
    assert float(np.asarray(sizes).sum()) == 20_000
    np.testing.assert_allclose(np.asarray(sizes), np.asarray(h.sizes))


@pytest.mark.parametrize("tiles,tile_len", [(1, 128), (4, 1024), (3, 4096)])
@pytest.mark.parametrize("dtype", [np.float32, np.int32])
def test_tile_sort_sweep(tiles, tile_len, dtype):
    if dtype == np.int32:
        x = RNG.integers(-1000, 1000, size=(tiles, tile_len)).astype(dtype)
    else:
        x = RNG.normal(size=(tiles, tile_len)).astype(dtype)
    got = sort_tiles_pallas(jnp.asarray(x))
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref.sort_tiles_ref(jnp.asarray(x)))
    )


def test_tile_sort_with_duplicates_and_extremes():
    x = np.concatenate([
        np.full(100, 3.0), np.full(50, -7.0),
        RNG.integers(0, 5, 874).astype(np.float32),
    ]).astype(np.float32)[None, :1024]
    got = sort_tiles_pallas(jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(got), np.sort(x, -1))


@pytest.mark.parametrize(
    "tile_len",
    [256, 512, pytest.param(2048, marks=pytest.mark.slow)],
)
def test_kv_sort_preserves_payload_multiset(tile_len):
    keys = RNG.integers(0, 7, size=(2, tile_len)).astype(np.float32)
    vals = RNG.normal(size=(2, tile_len)).astype(np.float32)
    gk, gv = sort_kv_pallas(jnp.asarray(keys), jnp.asarray(vals))
    gk, gv = np.asarray(gk), np.asarray(gv)
    np.testing.assert_allclose(gk, np.sort(keys, -1))
    for r in range(2):
        # per-key payload multisets survive (ties handled correctly)
        for kk in np.unique(keys[r]):
            np.testing.assert_allclose(
                np.sort(gv[r][gk[r] == kk]), np.sort(vals[r][keys[r] == kk])
            )


@pytest.mark.parametrize("k,T,beta", [(1, 4, 2), (3, 16, 16), (7, 18, 5), (2, 8, 1)])
def test_merge_kernel_vs_core(k, T, beta):
    hs = [
        build_exact(
            jnp.asarray(RNG.normal(size=int(RNG.integers(T, 400))).astype(np.float32)),
            T,
        )
        for _ in range(k)
    ]
    stacked = Histogram(
        jnp.stack([h.boundaries for h in hs]),
        jnp.stack([h.sizes for h in hs]),
    )
    bo, so = merge_pallas(stacked.boundaries, stacked.sizes, beta)
    want = merge(stacked, beta)
    np.testing.assert_allclose(np.asarray(bo), np.asarray(want.boundaries), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(so), np.asarray(want.sizes), atol=1e-2)


@pytest.mark.slow
@pytest.mark.parametrize("tile_len,T_tile", [(1024, 64), (4096, 256)])
def test_summarize_pipeline_bound(tile_len, T_tile):
    n_tiles = 8
    x = RNG.gumbel(size=n_tiles * tile_len).astype(np.float32)
    h = summarize_pallas(
        jnp.asarray(x), tile_len=tile_len, T_tile=T_tile, T_out=T_tile
    )
    n = x.size
    err = np.abs(np.asarray(h.sizes) - n / T_tile).max()
    assert err <= 2 * n / T_tile + 2 * n_tiles
    assert float(np.asarray(h.sizes).sum()) == pytest.approx(n)


@pytest.mark.parametrize(
    "backend, flag, want",
    [("cpu", None, True), ("tpu", None, False), ("tpu", True, True),
     ("cpu", False, False)],
)
def test_interpret_default_follows_backend(monkeypatch, backend, flag, want):
    """The wrappers' interpret=None runs the interpreter only on the CPU:
    a TPU caller gets the Mosaic lowering, never a silent interpreter."""
    from repro.kernels import tile_sort

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert tile_sort.resolve_interpret(flag) is want
