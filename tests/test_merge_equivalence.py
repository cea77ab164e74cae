"""The vectorized rank-select merge is bit-identical to paper Algorithm 1."""
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (
    Histogram,
    build_exact,
    merge,
    merge_histograms_sequential,
)
from repro.core import histogram
from repro.core.interval_tree import merge_stacks
from repro.kernels import merge_pallas

settings.register_profile("ci", deadline=None, max_examples=60)
settings.load_profile("ci")


@st.composite
def stacked_histograms(draw):
    k = draw(st.integers(1, 5))
    T = draw(st.integers(2, 16))
    beta = draw(st.integers(1, T))
    seed = draw(st.integers(0, 2**31 - 1))
    rng = np.random.default_rng(seed)
    hs = []
    for _ in range(k):
        n = int(rng.integers(T, 300))
        dup = rng.integers(0, 2)
        v = (
            rng.integers(0, 20, size=n).astype(np.float32)
            if dup
            else rng.normal(size=n).astype(np.float32) * 5
        )
        hs.append(build_exact(jnp.asarray(v), T))
    return hs, beta


@given(stacked_histograms())
def test_vectorized_equals_sequential(args):
    hs, beta = args
    stacked = Histogram(
        jnp.stack([h.boundaries for h in hs]),
        jnp.stack([h.sizes for h in hs]),
    )
    hv = merge(stacked, beta)
    hq = merge_histograms_sequential(hs, beta)
    np.testing.assert_allclose(
        np.asarray(hv.boundaries), np.asarray(hq.boundaries)
    )
    np.testing.assert_allclose(
        np.asarray(hv.sizes), np.asarray(hq.sizes), atol=1e-2
    )


@given(stacked_histograms())
def test_pallas_kernel_equals_sequential(args):
    hs, beta = args
    stacked = Histogram(
        jnp.stack([h.boundaries for h in hs]),
        jnp.stack([h.sizes for h in hs]),
    )
    bo, so = merge_pallas(stacked.boundaries, stacked.sizes, beta)
    hq = merge_histograms_sequential(hs, beta)
    np.testing.assert_allclose(np.asarray(bo), np.asarray(hq.boundaries))
    np.testing.assert_allclose(np.asarray(so), np.asarray(hq.sizes), atol=1e-2)


# ---------------------------------------------------------------------------
# Bit identity of the one-sort pre-histogram against argsort-then-gather
# ---------------------------------------------------------------------------

_T = 12
_K_PAD = 8


def _pre_histogram_argsort(histograms):
    """The pre-histogram by argsort and two permutation gathers — the
    form the multi-operand sort replaced, kept here as the oracle."""
    b, s = histograms.boundaries, histograms.sizes
    k = b.shape[0]
    mass = jnp.concatenate([s, jnp.zeros((k, 1), s.dtype)], axis=-1).reshape(-1)
    flat = b.reshape(-1)
    order = jnp.argsort(flat, stable=True)
    cum = jnp.cumsum(mass[order])
    return flat[order], cum[:-1]


def _summary_row(rng, features, T_row):
    n = int(rng.integers(T_row, 300))
    if "ties" in features:
        v = rng.integers(-3, 5, size=n).astype(np.float32)
    else:
        v = (rng.normal(size=n) * 5).astype(np.float32)
    if "signed_zero" in features:
        zeros = rng.random(n) < 0.3
        v[zeros] = np.where(rng.random(zeros.sum()) < 0.5, -0.0, 0.0)
    sv = np.sort(v)
    cuts = histogram._cut_indices(n, T_row)
    b = sv[np.minimum(cuts, n - 1)]
    s = np.diff(cuts).astype(np.float32)
    if "signed_zero" in features:
        # numpy orders ±0 arbitrarily; flip the sign of boundary zeros at
        # random so -0.0 and 0.0 sit next to each other in and across rows
        z = b == 0
        b[z] = np.where(rng.random(z.sum()) < 0.5, -0.0, 0.0)
    if T_row < _T:
        # a narrower source padded to T with zero-size tail buckets
        b = np.concatenate([b, np.repeat(b[-1:], _T - T_row)])
        s = np.concatenate([s, np.zeros(_T - T_row, np.float32)])
    return b, s


def _stacked_batch(features, Q, seed):
    """``(Q, k_pad, T+1)``/``(Q, k_pad, T)`` stacks shaped like the served
    path's merge inputs, with the requested hard cases."""
    rng = np.random.default_rng(seed)
    B = np.empty((Q, _K_PAD, _T + 1), np.float32)
    S = np.empty((Q, _K_PAD, _T), np.float32)
    for q in range(Q):
        k = int(rng.integers(1, _K_PAD + 1)) if "pad_rows" in features else _K_PAD
        for j in range(k):
            T_row = int(rng.integers(2, _T + 1)) if "tail_pad" in features else _T
            B[q, j], S[q, j] = _summary_row(rng, features, T_row)
        # pad rows as _gather_rows makes them: a real row, mass masked to 0
        B[q, k:] = B[q, 0]
        S[q, k:] = S[q, 0] * np.float32(0.0)
    return B, S


def _bits(x):
    # compare raw float32 bits: array_equal alone calls -0.0 equal to 0.0
    return np.asarray(x).view(np.uint32)


@pytest.mark.parametrize("seed", range(3))
@pytest.mark.parametrize("Q", [1, 5])
@pytest.mark.parametrize(
    "features",
    [
        ("ties",),
        ("pad_rows",),
        ("tail_pad",),
        ("signed_zero",),
        ("ties", "pad_rows", "tail_pad", "signed_zero"),
    ],
    ids="+".join,
)
def test_one_sort_pre_histogram_is_bit_identical(features, Q, seed):
    B, S = _stacked_batch(features, Q, seed)
    rng = np.random.default_rng(seed + 1000)
    beta = int(rng.integers(1, _T + 1))
    b, s = jnp.asarray(B), jnp.asarray(S)

    pre = jax.vmap(lambda b, s: histogram.pre_histogram(Histogram(b, s)))
    got_pos, got_A = jax.jit(pre)(b, s)
    oracle = jax.vmap(lambda b, s: _pre_histogram_argsort(Histogram(b, s)))
    want_pos, want_A = jax.jit(oracle)(b, s)
    assert np.array_equal(_bits(got_pos), _bits(want_pos))
    assert np.array_equal(_bits(got_A), _bits(want_A))

    got = merge_stacks(b, s, beta=beta)
    with mock.patch.object(histogram, "pre_histogram", _pre_histogram_argsort):
        want = jax.jit(
            jax.vmap(lambda b, s: merge.__wrapped__(Histogram(b, s), beta))
        )(b, s)
    assert np.array_equal(_bits(got[0]), _bits(want.boundaries))
    assert np.array_equal(_bits(got[1]), _bits(want.sizes))
    for q in range(Q):  # the unbatched merge agrees with its batched form
        one = merge(Histogram(b[q], s[q]), beta)
        assert np.array_equal(_bits(one.boundaries), _bits(got[0][q]))
        assert np.array_equal(_bits(one.sizes), _bits(got[1][q]))


# ---------------------------------------------------------------------------
# Bit identity of the two-level counted cut against the binary search
# ---------------------------------------------------------------------------


def _searchsorted_cut(A, targets):
    """The cut by binary search — the form the two-level count replaced,
    kept here as the oracle."""
    return jnp.searchsorted(A, targets, side="right")


_BLOCK = histogram._CUT_BLOCK


def _crafted_cdf(case, L, rng):
    """A non-decreasing ``A`` of length ``L`` and targets that hit the
    requested hard case."""
    if case == "all_zero_mass":
        return np.zeros(L, np.float32), np.zeros(7, np.float32)
    mass = rng.integers(0, 4, size=L).astype(np.float32)
    if case == "tied_runs":
        # long runs of zero mass, some across block edges
        mass[rng.random(L) < 0.9] = 0.0
    A = np.cumsum(mass, dtype=np.float32)
    n = float(A[-1])
    beta = int(rng.integers(2, 300))
    even = np.arange(1, beta, dtype=np.float32) * np.float32(n / beta)
    if case == "target_equals_A":
        return A, np.concatenate([even, A[rng.integers(0, L, size=64)]])
    if case == "signed_zero_and_nan":
        # the binary search's total order: -0.0 before 0.0, NaN after +inf
        odd = np.array([-np.inf, -0.0, 0.0, np.inf, np.nan], np.float32)
        return A, np.concatenate([even, odd])
    if case == "block_edge":
        # targets at, just under and between the values either side of
        # every block edge, so that cuts fall on a multiple of the block
        edge = np.arange(_BLOCK, L, _BLOCK)
        lo, hi = A[edge - 1], A[edge]
        mid = lo + (hi - lo) / 2
        return A, np.concatenate(
            [even, lo, hi, mid, np.nextafter(hi, np.float32(-np.inf))]
        ).astype(np.float32)
    return A, even


@pytest.mark.parametrize("L", [1, _BLOCK - 1, _BLOCK, _BLOCK + 1, 4065, 16263])
@pytest.mark.parametrize(
    "case",
    [
        "tied_runs",
        "all_zero_mass",
        "target_equals_A",
        "block_edge",
        "signed_zero_and_nan",
    ],
)
def test_counted_cut_equals_searchsorted(case, L):
    # 4065 and 16263 are len(A) of the pull-up (k 2) and the planner
    # (k_pad 8) at T 2,032: neither is a multiple of the block
    A, targets = _crafted_cdf(case, L, np.random.default_rng(L))
    A, targets = jnp.asarray(A), jnp.asarray(targets)
    got = jax.jit(histogram._count_le)(A, targets)
    want = jax.jit(_searchsorted_cut)(A, targets)
    assert np.array_equal(np.asarray(got), np.asarray(want))


_SERVED_T = 2032


def _served_stack(features, Q, k_pad, seed):
    """``(Q, k_pad, T+1)``/``(Q, k_pad, T)`` exact summaries at the served
    width T = 2,032, with the requested hard cases."""
    rng = np.random.default_rng(seed)
    T = _SERVED_T
    B = np.empty((Q, k_pad, T + 1), np.float32)
    S = np.empty((Q, k_pad, T), np.float32)
    for q in range(Q):
        k = int(rng.integers(1, k_pad + 1)) if "pad_rows" in features else k_pad
        for j in range(k):
            if "unit_sizes" in features:
                # one value a bucket: every A is an integer and every
                # target j·N/β lands on one
                n = T
            else:
                n = int(rng.integers(T, 3 * T))
            if "ties" in features:
                v = rng.integers(-40, 40, size=n).astype(np.float32)
            else:
                v = rng.gumbel(size=n).astype(np.float32)
            cuts = histogram._cut_indices(n, T)
            B[q, j] = np.sort(v)[np.minimum(cuts, n - 1)]
            S[q, j] = np.diff(cuts)
        B[q, k:] = B[q, 0]  # pad rows as _gather_rows makes them
        S[q, k:] = S[q, 0] * np.float32(0.0)
    if "all_zero_mass" in features:
        S[0] = 0.0
    return B, S


@pytest.mark.parametrize(
    "k_pad, beta",
    [(8, 254), (2, _SERVED_T)],
    ids=["planner", "pullup"],
)
@pytest.mark.parametrize(
    "features",
    [("pad_rows",), ("ties",), ("all_zero_mass",), ("unit_sizes",)],
    ids="+".join,
)
def test_counted_cut_merge_is_bit_identical(features, k_pad, beta):
    B, S = _served_stack(features, 2, k_pad, seed=17)
    b, s = jnp.asarray(B), jnp.asarray(S)
    got = merge_stacks(b, s, beta=beta)
    with mock.patch.object(histogram, "_count_le", _searchsorted_cut):
        want = jax.jit(
            jax.vmap(lambda b, s: merge.__wrapped__(Histogram(b, s), beta))
        )(b, s)
    assert np.array_equal(_bits(got[0]), _bits(want.boundaries))
    assert np.array_equal(_bits(got[1]), _bits(want.sizes))
    one = merge(Histogram(b[1], s[1]), beta)
    assert np.array_equal(_bits(one.boundaries), _bits(want.boundaries[1]))
    assert np.array_equal(_bits(one.sizes), _bits(want.sizes[1]))
