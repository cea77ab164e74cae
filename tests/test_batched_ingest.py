"""Shape-stable batched Summarizer: bit-exactness and compile stability.

Two guarantees of the padded ingest pipeline (core/histogram.py,
core/stream.py):

* **bit-exactness** — ``build_exact_padded`` (and its vmapped batched form,
  and therefore every summary the store writes) is bit-identical to
  ``build_exact`` on the unpadded values: the +inf sentinel sorts past every
  real value and the masked cut indices never reach it;
* **compile stability** — summarizing any mix of partition lengths costs
  O(log max_n) compiled executables (one per power-of-two shape bucket), not
  one per distinct length, asserted both on the store's dispatch-shape log
  and on the actual jit cache.
"""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import jax.numpy as jnp

from repro.core import (
    HistogramStore,
    build_exact,
    build_exact_padded,
    build_exact_padded_batched,
    pad_pow2,
)

settings.register_profile("ci", deadline=None, max_examples=15)
settings.load_profile("ci")


@st.composite
def padded_case(draw):
    # n and T drawn from quantized sets so jitted shapes repeat across cases
    n = draw(st.sampled_from([1, 2, 7, 64, 65, 200, 513]))
    T = draw(st.sampled_from([1, 4, 32]))
    seed = draw(st.integers(0, 2**31 - 1))
    kind = draw(st.sampled_from(["normal", "dups", "sorted"]))
    rng = np.random.default_rng(seed)
    if kind == "normal":
        v = rng.normal(size=n) * rng.uniform(0.1, 100)
    elif kind == "dups":
        v = rng.integers(0, max(2, n // 4), size=n).astype(float)
    else:
        v = np.sort(rng.gumbel(size=n))
    return v.astype(np.float32), T


@given(padded_case())
def test_build_exact_padded_bitexact(case):
    """Padding + masked cuts reproduce build_exact bit for bit — including
    duplicate-heavy and pre-sorted inputs, and T > n."""
    v, T = case
    padded, n = pad_pow2(v)
    h0 = build_exact(jnp.asarray(v), T)
    h1 = build_exact_padded(jnp.asarray(padded), n, T)
    np.testing.assert_array_equal(
        np.asarray(h0.boundaries), np.asarray(h1.boundaries)
    )
    np.testing.assert_array_equal(np.asarray(h0.sizes), np.asarray(h1.sizes))


@given(st.integers(0, 2**31 - 1))
def test_batched_rows_equal_single_padded(seed):
    """The one-dispatch (k, n_pad) stack gives each row exactly the result
    of summarizing that row alone."""
    rng = np.random.default_rng(seed)
    T = 16
    vs = [
        rng.normal(size=int(rng.integers(T, 512))).astype(np.float32)
        for _ in range(4)
    ]
    pads = [pad_pow2(v, min_len=512) for v in vs]
    stack = np.stack([p[0] for p in pads])
    ns = np.asarray([p[1] for p in pads], np.int32)
    hb = build_exact_padded_batched(jnp.asarray(stack), ns, T)
    for i, v in enumerate(vs):
        h0 = build_exact(jnp.asarray(v), T)
        np.testing.assert_array_equal(
            np.asarray(hb.boundaries[i]), np.asarray(h0.boundaries)
        )
        np.testing.assert_array_equal(
            np.asarray(hb.sizes[i]), np.asarray(h0.sizes)
        )


def test_store_summaries_bitexact_vs_legacy_build():
    """Every summary the store writes through the padded pipeline equals the
    legacy per-partition ``build_exact(values, min(T, n))`` bit for bit."""
    rng = np.random.default_rng(7)
    T = 64
    store = HistogramStore(num_buckets=T)
    for pid, n in enumerate([3, 63, 64, 65, 900, 4096, 5000]):
        v = rng.gumbel(size=n).astype(np.float32)
        store.ingest(pid, v)
        want = build_exact(jnp.asarray(v), min(T, n))
        s = store.summaries[pid]
        np.testing.assert_array_equal(s.boundaries, np.asarray(want.boundaries))
        np.testing.assert_array_equal(s.sizes, np.asarray(want.sizes))
        assert s.n == n


def test_compile_stability_50_random_length_ingests():
    """50 ingests of random lengths compile O(log max_n) executables, not
    O(#distinct lengths)."""
    rng = np.random.default_rng(11)
    T = 64
    max_n = 8192
    store = HistogramStore(num_buckets=T)
    cache_before = build_exact_padded_batched._cache_size()
    lengths = rng.integers(T, max_n + 1, size=50)
    assert len(set(lengths)) > 20  # the mix really is ragged
    for pid, n in enumerate(lengths):
        store.ingest(pid, rng.normal(size=int(n)).astype(np.float32))
    bound = int(np.log2(max_n)) + 2
    # every dispatch was a (1, n_pad, T) shape with n_pad a power of two
    assert len(store.summarize_shapes) <= bound
    assert all(
        n_pad & (n_pad - 1) == 0 for (_, n_pad, _) in store.summarize_shapes
    )
    compiled = build_exact_padded_batched._cache_size() - cache_before
    assert compiled <= bound
    # and the store still answers correctly over the ragged mix
    h, eps = store.query(0, 49, beta=16)
    assert float(np.asarray(h.sizes).sum()) == pytest.approx(lengths.sum())


def test_ingest_many_groups_shapes_and_matches_sequential():
    """ingest_many groups partitions into one dispatch per shape bucket and
    produces a store indistinguishable from sequential ingest."""
    rng = np.random.default_rng(3)
    T = 32
    parts = {
        d: rng.normal(size=int(rng.integers(T, 3000))).astype(np.float32)
        for d in range(40)
    }
    s_bulk = HistogramStore(num_buckets=T)
    s_bulk.ingest_many(parts)
    n_pads = {1 << (len(v) - 1).bit_length() for v in parts.values()}
    assert len(s_bulk.summarize_shapes) <= len(n_pads) + 1
    s_seq = HistogramStore(num_buckets=T)
    for d in sorted(parts):
        s_seq.ingest(d, parts[d])
    for (a, b) in [(0, 39), (5, 17), (12, 12)]:
        h1, e1 = s_bulk.query(a, b, beta=8)
        h2, e2 = s_seq.query(a, b, beta=8)
        np.testing.assert_array_equal(
            np.asarray(h1.boundaries), np.asarray(h2.boundaries)
        )
        np.testing.assert_array_equal(
            np.asarray(h1.sizes), np.asarray(h2.sizes)
        )
        assert e1 == e2


def test_empty_partition_rejected():
    store = HistogramStore(num_buckets=8)
    with pytest.raises(ValueError):
        store.ingest(0, np.asarray([], np.float32))


def _check_ragged_summarize(n, tile_len, T, rng):
    from repro.kernels import summarize_pallas

    x = rng.gumbel(size=n).astype(np.float32)
    h = summarize_pallas(jnp.asarray(x), tile_len=tile_len, T_tile=T, T_out=T)
    k = -(-n // tile_len)
    assert float(np.asarray(h.sizes).sum()) == pytest.approx(n)
    assert np.abs(np.asarray(h.sizes) - n / T).max() <= 2 * n / T + 2 * k
    b = np.asarray(h.boundaries)
    assert np.all(np.isfinite(b))  # the +inf sentinel never leaks
    assert b[-1] == pytest.approx(x.max())
    assert b[0] == pytest.approx(x.min())


def test_summarize_pallas_ragged_tail():
    """The Pallas tile-sort Summarizer accepts lengths that are not a
    multiple of tile_len: the sentinel-padded tail tile is masked out."""
    rng = np.random.default_rng(5)
    _check_ragged_summarize(2 * 512 + 117, 512, 32, rng)


@pytest.mark.slow
@pytest.mark.parametrize("n", [517, 1024, 3 * 1024 + 517, 2 * 1024 + 1])
def test_summarize_pallas_ragged_sweep(n):
    """Tail shapes across the tile grid: sub-tile, exact, mid, off-by-one —
    divisible lengths take the exact same path as before the padding."""
    rng = np.random.default_rng(5)
    _check_ragged_summarize(n, 1024, 64, rng)
