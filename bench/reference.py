"""Plain numpy reference for equi-depth histogram answers.

Imports nothing of the program.  An answer over an interval is
``(boundaries (β+1,), sizes (β,), eps)``; ``measure`` compares it with the
interval's raw values by four numbers, each with its limit in ``LIMITS``:

- ``mass_gap``: ``|Σ sizes − N|``.  Counts are exact in float32 up to
  2^24, so an answer that holds every acked value reads 0.
- ``bad_bounds``: boundaries that are not among the raw values (the
  paper's boundaries are order statistics of the data) or that step down.
- ``err_over_eps``: the answer's largest error over its reported ε, of
  three errors: a reported size against N/β, a contiguous run of reported
  sizes against its share, and a bucket's true occupancy against N/β.  Float32
  data holds ties, and a boundary inside a run of equal values makes its
  buckets' true counts ambiguous by that run's length, so each bucket's
  true-occupancy error is allowed the multiplicity of its two boundary
  values.  The configuration guarantees every answer within its ε: limit 1.
- ``eps_over_bound``: the reported ε over the bound that :func:`eps_bound`
  works out from the interval alone (its values, T, and its canonical
  cover), so that an answer cannot pass by reporting a wider ε.  Sound
  answers read 1; summaries at half the configured T read about 2.

``equi_depth`` is the exact histogram of the same semantics; in a lower
precision it is the control that ``measure`` must refuse.
"""
from __future__ import annotations

import numpy as np

LIMITS = {"mass_gap": 0.0, "bad_bounds": 0.0, "err_over_eps": 1.0, "eps_over_bound": 1.5}


def cover(lo: int, hi: int) -> list[int]:
    """Levels of the nodes in the canonical cover of windows ``lo..hi`` by a
    power-of-two segment tree over the windows (the paper's interval
    decomposition): at most two nodes a level."""
    levels, l, r, level = [], lo, hi + 1, 0
    while l < r:
        if l & 1:
            levels.append(level)
            l += 1
        if r & 1:
            r -= 1
            levels.append(level)
        l >>= 1
        r >>= 1
        level += 1
    return levels


def eps_bound(lo: int, hi: int, per_window: int, T: int) -> float:
    """Bound on the error of an answer over windows ``lo..hi`` of
    ``per_window`` values each, every window summarized exactly in ``T``
    buckets.  The paper's Theorem 1: merging ``k`` summaries of ``N``
    values adds at most ``2N/T + 2k`` to their own errors.  A level-``l``
    node merges two level-``l-1`` nodes of ``2^(l-1)·v`` values each, so
    ``ε_l = 2ε_(l-1) + 2·2^l·v/T + 4 = l·2^l·2v/T + 4(2^l - 1)``; an answer
    merges its cover's nodes once more."""
    levels = cover(lo, hi)
    n = (hi - lo + 1) * per_window
    nodes = sum(l * (1 << l) * 2.0 * per_window / T + 4.0 * ((1 << l) - 1) for l in levels)
    return nodes + 2.0 * n / T + 2.0 * len(levels)


def measure(bounds, sizes, eps: float, values: np.ndarray, beta: int,
            bound: float) -> dict[str, float]:
    """The compared numbers of one answer over ``values`` (unsorted), whose
    ε may be at most ``bound`` (:func:`eps_bound`)."""
    b = np.asarray(bounds, np.float32).reshape(-1)
    s = np.asarray(sizes, np.float64).reshape(-1)
    v = np.asarray(values, np.float32).reshape(-1)
    n = v.size
    ideal = n / beta
    steps_down = int(np.sum(b[1:] < b[:-1]))
    bs = np.sort(b)
    # left[j] = #{v < b_j}, right[j] = #{v <= b_j}, by one pass over the data:
    # v < b_j exactly when j >= #{boundaries <= v}
    edges = bs.size + 1
    left = np.cumsum(np.bincount(np.searchsorted(bs, v, side="right"), minlength=edges))[: bs.size]
    right = np.cumsum(np.bincount(np.searchsorted(bs, v, side="left"), minlength=edges))[: bs.size]
    ties = (right - left).astype(np.float64)
    true = (left[1:] - left[:-1]).astype(np.float64)
    true[-1] += right[-1] - left[-1]  # the last bucket is right-closed
    drift = np.concatenate([[0.0], np.cumsum(s)]) - np.arange(s.size + 1) * ideal
    err = max(
        float(np.abs(s - ideal).max()),
        float(drift.max() - drift.min()),
        float((np.abs(true - ideal) - ties[:-1] - ties[1:]).max()),
    )
    return {
        "mass_gap": float(abs(s.sum() - n)),
        "bad_bounds": float(int(np.sum(ties == 0)) + steps_down),
        "err_over_eps": err / eps if eps > 0 else float("inf"),
        "eps_over_bound": eps / bound,
    }


def equi_depth(values: np.ndarray, beta: int, dtype=np.float32):
    """Exact equi-depth histogram of ``values`` computed in ``dtype``:
    boundaries at ranks ``⌊iN/β⌋`` of the sorted values, sizes the rank
    differences.  Each size is within 1 of N/β, so ε = 1."""
    v = np.sort(np.asarray(values).astype(dtype).reshape(-1))
    n = v.size
    cuts = np.concatenate([[0], (np.arange(1, beta) * n) // beta, [n]])
    bounds = np.concatenate([v[cuts[:-1]], v[-1:]])
    sizes = np.diff(cuts).astype(dtype)
    return bounds.astype(np.float32), sizes.astype(np.float32), 1.0


def worst(readings: list[dict[str, float]]) -> dict[str, float]:
    """Each number's largest reading over many answers."""
    return {k: max((r[k] for r in readings), default=float("nan")) for k in LIMITS}


def within(numbers: dict[str, float]) -> bool:
    return all(numbers[k] <= LIMITS[k] for k in LIMITS)
