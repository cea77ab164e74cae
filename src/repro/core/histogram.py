"""Equi-depth histogram construction and merging with quality guarantees.

Implements the core contribution of

    Yıldız, Büyüktanır, Emekci — "Equi-depth Histogram Construction for Big
    Data with Quality Guarantees" (cs.DB, 2016)

as pure-JAX, jit/vmap/shard_map-compatible primitives.

Representation
--------------
A ``T``-bucket equi-depth histogram over a value set ``P`` is

    H = {(b_1, s_1), ..., (b_T, s_T), (b_{T+1}, 0)}

stored as ``boundaries`` of shape ``(T+1,)`` (increasing) and ``sizes`` of
shape ``(T,)``.  Bucket ``i`` spans ``[b_i, b_{i+1})`` (the last bucket is
closed on the right) and holds ``s_i`` values.  For an *exact* equi-depth
histogram every ``s_i`` is ``|P|/T`` (±1 when ``T ∤ |P|``).

The merge (paper Algorithm 1)
-----------------------------
Given ``k`` exact ``T``-bucket histograms, the paper builds the pre-histogram
``H⁰`` whose boundaries are the ``k(T+1)`` sorted source boundaries and whose
approximate cumulative sizes ``A(m, H⁰)`` are computed under the
*left-collapse* assumption: all values of a source bucket are presumed to sit
at the bucket's left boundary.  Equivalently

    A(m, H⁰) = Σ_j  size_j · 1[left_j ≤ b_m]                       (★)

i.e. ``A`` is the CDF of point masses (one per source bucket, at its left
boundary) evaluated at the sorted boundary positions.  The paper then merges
consecutive ``H⁰`` buckets with a sequential two-pointer sweep until β buckets
remain (its main ``while`` loop).

**Parallel rank-select equivalence** (our TPU adaptation, proven equivalent
and bit-exactly tested against the sequential reference): because ``A`` is
non-decreasing, the sweep's cut for target ``t_j = j·N/β`` is exactly

    cut_j = #{m : A[m] ≤ t_j}  (= searchsorted(A, t_j, side='right'))
    b*_j  = pos[cut_j]                       (interior boundaries, j=1..β-1)
    S*_j  = A[cut_j - 1]                     (cumulative size at the cut)

so the whole merge is one sort + one cumsum + one batched count, taken in
two levels over blocks of ``A`` (``_count_le``) with no loop:
``O(kT log kT)`` work at ``O(log)`` depth instead of the paper's ``O(kT)``
sequential loop.  Output is identical (see tests/test_merge_equivalence.py).

Error bounds (paper Theorems 1 and 2)
-------------------------------------
For exact ``T``-bucket inputs whose per-bucket size is exactly ``|P_i|/T``,
every output bucket size and every contiguous range of output buckets is
within ``± ε_max`` of ideal, with

    ε_max < 2N/T = (2β/T) · (N/β).

When ``T ∤ |P_i|`` exact inputs have per-bucket sizes ``⌊|P_i|/T⌋`` or
``⌈|P_i|/T⌉``; Proof 1's two divided-bucket terms each grow by at most 1, so
the bound degrades to ``2N/T + 2k`` (this integer slack is what the property
tests assert; it vanishes under the paper's divisibility assumption).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "Histogram",
    "build_exact",
    "build_exact_batched",
    "build_exact_padded",
    "build_exact_padded_batched",
    "pad_pow2",
    "next_pow2",
    "merge",
    "merge_histograms_sequential",
    "pre_histogram",
    "quantile",
    "cdf_left_collapse",
    "cdf_interp",
    "range_count",
    "boundary_error",
    "size_error",
    "theoretical_eps_max",
    "sample_histogram",
]


class Histogram(NamedTuple):
    """An (approximate) equi-depth histogram.

    boundaries: ``(..., T+1)`` increasing bucket boundaries.
    sizes:      ``(..., T)``   per-bucket value counts (float for mergeability
                               at ``N ≥ 2^24``; exact integers below that).
    """

    boundaries: jax.Array
    sizes: jax.Array

    @property
    def num_buckets(self) -> int:
        return self.sizes.shape[-1]

    @property
    def n(self) -> jax.Array:
        """Total number of summarized values."""
        return jnp.sum(self.sizes, axis=-1)

    def cumulative(self) -> jax.Array:
        """``S(i, H)`` for i = 1..T, shape ``(..., T)``."""
        return jnp.cumsum(self.sizes, axis=-1)


# ---------------------------------------------------------------------------
# Exact construction (the paper's Summarizer)
# ---------------------------------------------------------------------------


def _cut_indices(n: int, T: int) -> np.ndarray:
    """Sorted-array cut positions: bucket i covers [cuts[i], cuts[i+1])."""
    return np.floor(np.arange(T + 1) * n / T).astype(np.int32)


@functools.partial(jax.jit, static_argnames=("num_buckets", "count_dtype"))
def build_exact(
    values: jax.Array, num_buckets: int, count_dtype=jnp.float32
) -> Histogram:
    """Exact ``T``-bucket equi-depth histogram of a 1-D value array.

    Sorts the partition and cuts it into ``T`` near-equal runs — the paper's
    "well-known straight-forward" offline construction.  ``O(n log n)``.
    """
    n = values.shape[0]
    T = num_buckets
    if n < 1:
        raise ValueError("cannot summarize an empty partition")
    sv = jnp.sort(values)
    cuts = jnp.asarray(_cut_indices(n, T))
    boundaries = sv[jnp.minimum(cuts, n - 1)]
    sizes = jnp.diff(cuts).astype(count_dtype)
    return Histogram(boundaries=boundaries, sizes=sizes)


def build_exact_batched(
    values: jax.Array, num_buckets: int, count_dtype=jnp.float32
) -> Histogram:
    """vmap of :func:`build_exact` over a leading batch axis.

    ``values``: ``(k, n)`` → histogram with ``boundaries (k, T+1)``,
    ``sizes (k, T)``.  Used for VMEM-tile-level summaries and per-layer
    telemetry.
    """
    fn = functools.partial(
        build_exact, num_buckets=num_buckets, count_dtype=count_dtype
    )
    return jax.vmap(fn)(values)


# ---------------------------------------------------------------------------
# Shape-stable (mask-aware) construction — the batched Summarizer pipeline
# ---------------------------------------------------------------------------
#
# ``build_exact`` is jitted on the partition *shape*, so a stream of
# variable-length partitions costs one fresh XLA compile per distinct length.
# The padded variant below fixes the executable shape instead: partitions are
# padded with a +inf sentinel to a power-of-two length bucket and the cut
# indices are computed from the *true* length ``n`` (a traced scalar), so
# every length in a 2× band shares one compiled program — O(log max_n) total
# compiles for any mix of lengths.  Because the sentinel sorts past every
# real value and no cut index ever reaches it (``cuts ≤ n``, reads clamped to
# ``n-1``), the result is bit-identical to ``build_exact`` on the unpadded
# values (property-tested in tests/test_batched_ingest.py).


def next_pow2(k: int) -> int:
    """Smallest power of two ≥ ``k`` (``k ≥ 1``) — THE padding rule for
    every shape-stable batch/length axis (summarizer stacks, merge batch
    padding, tree pull-up batches); keep it single-sourced so the bounded
    jit-cache guarantees stay in sync."""
    return 1 << max(0, k - 1).bit_length()


def pad_pow2(values, min_len: int = 1) -> tuple[np.ndarray, int]:
    """Pad a 1-D array to the next power-of-two length with a +inf sentinel.

    Returns ``(padded, n)`` where ``n`` is the true length.  Integer dtypes
    use their max value as the sentinel; either way the pad elements sort to
    the tail and are never selected by the masked cut indices.
    """
    v = np.asarray(values).reshape(-1)
    n = int(v.shape[0])
    if n < 1:
        raise ValueError("cannot summarize an empty partition")
    n_pad = next_pow2(max(n, min_len))
    if n_pad == n:
        return v, n
    if np.issubdtype(v.dtype, np.floating):
        fill = np.array(np.inf, v.dtype)
    else:
        fill = np.array(np.iinfo(v.dtype).max, v.dtype)
    return np.concatenate([v, np.full(n_pad - n, fill, v.dtype)]), n


def _masked_cuts(n: jax.Array, T: int) -> jax.Array:
    """``floor(i·n/T)`` for i = 0..T with a *traced* ``n`` — exact integer
    arithmetic (``i·(n%T) < T² `` fits int32) so the cuts match
    :func:`_cut_indices` bit for bit."""
    i = jnp.arange(T + 1, dtype=jnp.int32)
    q, r = n // T, n % T
    return i * q + (i * r) // T


def _build_exact_masked(values, n, num_buckets, count_dtype):
    # named scopes tag the device ops of each phase in a profiler trace
    with jax.named_scope("summarize.sort"):
        sv = jnp.sort(values)  # sentinel pad sorts past every real value
    with jax.named_scope("summarize.cut"):
        n = jnp.asarray(n, jnp.int32)
        cuts = _masked_cuts(n, num_buckets)
        boundaries = sv[jnp.minimum(cuts, n - 1)]
        sizes = jnp.diff(cuts).astype(count_dtype)
    return Histogram(boundaries=boundaries, sizes=sizes)


@functools.partial(jax.jit, static_argnames=("num_buckets", "count_dtype"))
def build_exact_padded(
    values: jax.Array, n, num_buckets: int, count_dtype=jnp.float32
) -> Histogram:
    """Mask-aware :func:`build_exact` over a sentinel-padded partition.

    ``values``: ``(n_pad,)`` — the true values followed by +inf padding
    (see :func:`pad_pow2`); ``n``: true length, traced.  Bit-identical to
    ``build_exact(values[:n], num_buckets)``; compiles once per ``n_pad``.
    """
    return _build_exact_masked(values, n, num_buckets, count_dtype)


@functools.partial(jax.jit, static_argnames=("num_buckets", "count_dtype"))
def build_exact_padded_batched(
    values: jax.Array, ns, num_buckets: int, count_dtype=jnp.float32
) -> Histogram:
    """One-dispatch summarizer for a ``(k, n_pad)`` stack of padded
    partitions with true lengths ``ns`` of shape ``(k,)`` — the vmapped form
    of :func:`build_exact_padded`.  The whole stack is summarized by a
    single XLA program keyed only on ``(k, n_pad, T)``."""
    fn = functools.partial(
        _build_exact_masked, num_buckets=num_buckets, count_dtype=count_dtype
    )
    return jax.vmap(fn)(values, jnp.asarray(ns, jnp.int32))


# ---------------------------------------------------------------------------
# The merge — parallel rank-select form (production path)
# ---------------------------------------------------------------------------


def pre_histogram(histograms: Histogram) -> tuple[jax.Array, jax.Array]:
    """Assemble the paper's pre-histogram ``H⁰`` from stacked summaries.

    ``histograms``: stacked summaries — ``boundaries (k, T+1)``, ``sizes
    (k, T)`` (the per-source bucket counts; sources may have *different* T by
    padding with zero-size buckets).

    Returns ``(pos, A)`` where ``pos`` is the sorted flat boundary sequence,
    shape ``(k(T+1),)``, and ``A`` the left-collapse cumulative sizes of
    equation (★), shape ``(k(T+1) - 1,)`` — ``A[m-1] = A(m, H⁰)`` in paper
    notation.
    """
    b = histograms.boundaries
    s = histograms.sizes
    k = b.shape[0]
    # Point mass of each source bucket sits at its left boundary; the last
    # boundary of every source carries zero mass — the paper's (b_{T+1}, 0).
    mass = jnp.concatenate(
        [s, jnp.zeros((k, 1), dtype=s.dtype)], axis=-1
    ).reshape(-1)
    flat = b.reshape(-1)
    # One stable sort keyed on the boundaries alone carries the masses
    # along as its payload, so no permutation gather follows it.  Stability
    # keeps the masses of tied boundaries in input order, which fixes ``A``
    # at every tie and with it every cut.
    pos, mass = jax.lax.sort((flat, mass), num_keys=1, is_stable=True)
    cum = jnp.cumsum(mass)
    return pos, cum[:-1]


# Width of a block of ``A`` in the two-level count: one lane row of a TPU
# vector register.
_CUT_BLOCK = 128


def _count_le(A: jax.Array, targets: jax.Array) -> jax.Array:
    """``#{m : A[m] ≤ t}`` for each target ``t``, for a non-decreasing ``A``.

    This is ``searchsorted(A, targets, side="right")`` without its binary
    search, which lowers to a ``while`` loop of one scalar gather a step.
    ``A`` is padded with ``+inf`` to whole blocks of ``_CUT_BLOCK``; a
    target's block is the last whose first element is ≤ it, found by
    counting the block heads, and its cut is that block's offset plus a
    count inside the one block row it gathers.  Exact under ties: every
    earlier block is ≤ its successor's head ≤ t, every later head is > t.
    Both counts use ``searchsorted``'s own comparator (``method=
    "compare_all"``), so ±0 and NaN order as in the binary search.
    """
    L = A.shape[0]
    B = _CUT_BLOCK
    rows = jnp.pad(A, (0, -L % B), constant_values=jnp.inf).reshape(-1, B)
    count = functools.partial(jnp.searchsorted, side="right", method="compare_all")
    block = jnp.maximum(count(rows[:, 0], targets) - 1, 0)
    within = jax.vmap(count)(rows[block], targets)
    # only a NaN target counts the +inf padding
    return jnp.minimum(block * B + within, L)


@functools.partial(jax.jit, static_argnames=("beta",))
def merge(histograms: Histogram, beta: int) -> Histogram:
    """Merge ``k`` stacked ``T``-bucket summaries into a β-bucket histogram.

    Vectorized rank-select equivalent of paper Algorithm 1 (see module
    docstring).  Fully jit-able: one sort of the boundaries that carries
    the masses along + cumsum + a count of ``A ≤ t_j`` for each of the β-1
    targets (``_count_le``: block heads, then one block row), with no loop.
    """
    # named scopes tag the device ops of each phase in a profiler trace
    with jax.named_scope("merge.presort"):
        pos, A = pre_histogram(histograms)
    with jax.named_scope("merge.cut"):
        n = jnp.sum(histograms.sizes)
        targets = jnp.arange(1, beta, dtype=A.dtype) * (n / beta)
        cut = _count_le(A, targets)  # (β-1,) in [0, len(A)]
        interior = pos[cut]
        boundaries = jnp.concatenate([pos[:1], interior, pos[-1:]])
        # Cumulative size at each cut: A[cut-1], with A[-1] treated as 0.
        s_at_cut = jnp.where(cut > 0, A[jnp.maximum(cut - 1, 0)], 0.0)
        full = jnp.concatenate(
            [jnp.zeros((1,), A.dtype), s_at_cut, n[None].astype(A.dtype)]
        )
        sizes = jnp.diff(full)
    return Histogram(boundaries=boundaries, sizes=sizes)


def merge_list(histograms: Sequence[Histogram], beta: int) -> Histogram:
    """Merge a Python list of (possibly differently-sized) summaries.

    Sources with differing bucket counts are padded with zero-size buckets at
    their last boundary, which leaves equation (★) unchanged.
    """
    T_max = max(h.sizes.shape[-1] for h in histograms)
    bs, ss = [], []
    for h in histograms:
        T = h.sizes.shape[-1]
        pad = T_max - T
        bs.append(
            jnp.concatenate([h.boundaries, jnp.repeat(h.boundaries[-1:], pad)])
        )
        ss.append(
            jnp.concatenate([h.sizes, jnp.zeros((pad,), dtype=h.sizes.dtype)])
        )
    stacked = Histogram(jnp.stack(bs), jnp.stack(ss))
    return merge(stacked, beta)


# ---------------------------------------------------------------------------
# The merge — faithful sequential Algorithm 1 (reference / paper baseline)
# ---------------------------------------------------------------------------


def merge_histograms_sequential(
    histograms: Sequence[Histogram] | Histogram, beta: int
) -> Histogram:
    """Direct host-side port of paper Algorithm 1 (two-pointer sweep).

    Used (a) as the paper-faithful baseline in benchmarks and (b) as the
    oracle for the equivalence property test of the vectorized `merge`.
    Runs in ``O(kT log k + kT)`` like the paper; not jit-able by design.
    """
    if isinstance(histograms, Histogram):
        b = np.asarray(histograms.boundaries)
        s = np.asarray(histograms.sizes)
    else:
        b = np.stack([np.asarray(h.boundaries) for h in histograms])
        s = np.stack([np.asarray(h.sizes) for h in histograms])
    k = b.shape[0]
    mass = np.concatenate([s, np.zeros((k, 1), s.dtype)], axis=-1).reshape(-1)
    flat = b.reshape(-1)
    order = np.argsort(flat, kind="stable")
    pos = flat[order]
    cum = np.cumsum(mass[order])
    A = cum[:-1]  # A[m-1] == A(m, H⁰)
    n = float(s.sum())

    out_b = [pos[0]]
    out_s = []
    prev_cum = 0.0
    nxt = 0  # 0-based index into A; paper's `next` pointer (monotone)
    for j in range(1, beta):
        target = j * n / beta
        # Paper inner while: advance while A(next, H⁰) ≤ current · N/β.
        while nxt < A.shape[0] and A[nxt] <= target:
            nxt += 1
        # MERGEBUCKETS(last, next-1): emitted bucket ends at boundary of the
        # first H⁰ bucket whose cumulative size exceeds the target.
        out_b.append(pos[nxt])
        cum_here = A[nxt - 1] if nxt > 0 else 0.0
        out_s.append(cum_here - prev_cum)
        prev_cum = cum_here
    out_b.append(pos[-1])
    out_s.append(n - prev_cum)
    return Histogram(
        boundaries=jnp.asarray(np.array(out_b)),
        sizes=jnp.asarray(np.array(out_s, dtype=np.float32)),
    )


# ---------------------------------------------------------------------------
# Queries
# ---------------------------------------------------------------------------


def cdf_left_collapse(hist: Histogram, x: jax.Array) -> jax.Array:
    """CDF estimate under the paper's left-collapse assumption.

    Count of values ``< x`` ≈ total mass of buckets with left boundary ≤ x.
    Within ``±2N/T`` of truth for exact inputs (Theorem 2 with a one-bucket
    range).
    """
    left = hist.boundaries[..., :-1]
    cum = hist.cumulative()
    idx = jnp.searchsorted(left, x, side="right")
    padded = jnp.concatenate([jnp.zeros_like(cum[..., :1]), cum], axis=-1)
    return padded[idx]


def cdf_interp(hist: Histogram, x: jax.Array) -> jax.Array:
    """Piecewise-linear CDF estimate (mass uniform inside each bucket)."""
    b = hist.boundaries
    cum = jnp.concatenate(
        [jnp.zeros_like(hist.sizes[..., :1]), hist.cumulative()], axis=-1
    )
    return jnp.interp(x, b, cum)


def quantile(hist: Histogram, q: jax.Array) -> jax.Array:
    """Approximate q-quantile (vector ``q`` ok) by inverse interpolated CDF.

    Rank error is bounded by the paper's ``ε_max``: the returned value's true
    rank is within ``q·N ± 2N/T`` for exact single-level summaries.
    """
    b = hist.boundaries
    cum = jnp.concatenate(
        [jnp.zeros_like(hist.sizes[..., :1]), hist.cumulative()], axis=-1
    )
    n = cum[..., -1]
    return jnp.interp(jnp.asarray(q) * n, cum, b)


def range_count(hist: Histogram, lo: jax.Array, hi: jax.Array) -> jax.Array:
    """Approximate number of values in ``[lo, hi)`` (Theorem 2 quantity)."""
    return cdf_interp(hist, hi) - cdf_interp(hist, lo)


# ---------------------------------------------------------------------------
# Error metrics (paper Eq. 9 and Eq. 10) and the theoretical bound
# ---------------------------------------------------------------------------


def boundary_error(approx: Histogram, exact: Histogram) -> jax.Array:
    """μ_b — normalized RMS boundary deviation (paper Eq. 9)."""
    B = approx.num_buckets
    ba, be = approx.boundaries, exact.boundaries
    vmax, vmin = be[-1], be[0]
    rms = jnp.sqrt(jnp.mean((ba - be) ** 2))
    return B / (vmax - vmin) * rms


def size_error(approx: Histogram, exact: Histogram) -> jax.Array:
    """μ_s — normalized RMS bucket-size deviation (paper Eq. 10)."""
    B = approx.num_buckets
    n = jnp.sum(exact.sizes)
    rms = jnp.sqrt(jnp.mean((approx.sizes - exact.sizes) ** 2))
    return B / n * rms


def theoretical_eps_max(n: float, T: int, k: int = 1, exact_inputs: bool = True) -> float:
    """Paper bound ``ε_max < 2N/T`` (+``2k`` integer slack, module docstring)."""
    slack = 0.0 if exact_inputs else 2.0 * k
    return 2.0 * n / T + slack


def empirical_sizes(values: jax.Array, boundaries: jax.Array) -> jax.Array:
    """TRUE per-bucket counts of ``values`` under ``boundaries``.

    Bucket i spans ``[b_i, b_{i+1})``; the last bucket is right-closed
    (paper convention).  This — not the reported approximate sizes — is what
    the paper's μ_s (Eq. 10) measures: how far the *actual* occupancy of the
    approximate buckets deviates from N/B.
    """
    v = jnp.sort(values.reshape(-1))
    b = boundaries
    lo = jnp.searchsorted(v, b[:-1], side="left")
    hi = jnp.searchsorted(v, b[1:], side="left")
    sizes = (hi - lo).astype(jnp.float32)
    eq_last = jnp.sum((v == b[-1]).astype(jnp.float32))
    return sizes.at[-1].add(eq_last)


def empirical_size_error(approx: Histogram, values: jax.Array) -> jax.Array:
    """μ_s (paper Eq. 10) with true bucket occupancy under approx boundaries."""
    B = approx.num_buckets
    n = values.size
    true_sizes = empirical_sizes(values, approx.boundaries)
    rms = jnp.sqrt(jnp.mean((true_sizes - n / B) ** 2))
    return B / n * rms


# ---------------------------------------------------------------------------
# The paper's comparison baseline: corrected tuple-level random sampling
# ---------------------------------------------------------------------------


def sample_histogram(
    values: jax.Array, num_buckets: int, sample_size: int, key: jax.Array
) -> Histogram:
    """`tuple` baseline of paper §7 — random sample + exact histogram of it.

    "Corrected" per the paper: the global min and max are force-included so
    sparse edges are represented.  Sizes are scaled back to ``N``.
    """
    n = values.shape[0]
    idx = jax.random.randint(key, (sample_size,), 0, n)
    sample = values[idx]
    vmin = jnp.min(values)
    vmax = jnp.max(values)
    sample = jnp.concatenate([vmin[None], sample, vmax[None]])
    h = build_exact(sample, num_buckets)
    scale = n / sample.shape[0]
    return Histogram(boundaries=h.boundaries, sizes=h.sizes * scale)
