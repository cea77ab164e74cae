"""The numpy reference that decides ``correct``: it agrees with a plain
sort-based check, passes the exact histogram and refuses faulty answers."""
import os
import sys

import ml_dtypes
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import reference  # noqa: E402

BETA = 16


def sorted_check(bounds, sizes, eps, values, beta):
    """The same three numbers from the sorted data, bucket by bucket."""
    b = np.asarray(bounds, np.float32)
    s = np.asarray(sizes, np.float64)
    pooled = np.sort(np.asarray(values, np.float32))
    n, ideal = pooled.size, pooled.size / beta
    left = np.searchsorted(pooled, b, side="left")
    right = np.searchsorted(pooled, b, side="right")
    true = np.array([left[i + 1] - left[i] for i in range(beta)], np.float64)
    true[-1] += right[-1] - left[-1]
    ties = (right - left).astype(np.float64)
    drift = [sum(s[:i]) - i * ideal for i in range(beta + 1)]
    err = max(np.abs(s - ideal).max(), max(drift) - min(drift),
              (np.abs(true - ideal) - ties[:-1] - ties[1:]).max())
    bad = int(np.sum(right == left)) + int(np.sum(b[1:] < b[:-1]))
    return {"mass_gap": abs(s.sum() - n), "bad_bounds": float(bad), "err_over_eps": err / eps,
            "eps_over_bound": 1.0}


def data(seed, n=5000):
    rng = np.random.default_rng(seed)
    # rounded values hold many ties, as float32 data does at scale
    return np.round(rng.gumbel(10, 3, n), 1).astype(np.float32)


def program_like(values, seed):
    """An approximate answer: the exact one with sizes moved by a little."""
    b, s, _ = reference.equi_depth(values, BETA)
    jitter = np.random.default_rng(seed).integers(-3, 4, BETA).astype(np.float32)
    jitter[-1] -= jitter.sum()
    return b, s + jitter, 40.0


@pytest.mark.parametrize("seed", [0, 1, 2**31 + 5])
def test_measure_matches_sorted_check(seed):
    v = data(seed)
    b, s, eps = program_like(v, seed)
    got = reference.measure(b, s, eps, v, BETA, eps)
    want = sorted_check(b, s, eps, v, BETA)
    assert got == pytest.approx(want)
    assert reference.within(got)


def test_exact_float32_passes_and_bfloat16_control_fails():
    v = np.random.default_rng(3).gumbel(50, 10, 20000).astype(np.float32)
    assert reference.within(reference.measure(*reference.equi_depth(v, BETA), v, BETA, 1.0))
    control = reference.measure(*reference.equi_depth(v, BETA, ml_dtypes.bfloat16), v, BETA, 1.0)
    assert not reference.within(control)
    assert control["bad_bounds"] > 0 and control["err_over_eps"] > 1


def _nudge(b, s, e):
    b = b.copy()
    b[BETA // 2] = np.nextafter(b[BETA // 2], np.float32(np.inf))
    return b, s, e


def _drop_mass(b, s, e):
    s = s.copy()
    s[0] -= 1
    return b, s, e


def _skew(b, s, e):
    s = s.copy()
    s[0] += 2 * e
    s[1] -= 2 * e
    return b, s, e


def _reverse(b, s, e):
    return b[::-1].copy(), s, e


def _wider_eps(b, s, e):
    return b, s, 2 * e


@pytest.mark.parametrize("fault, number", [
    (_nudge, "bad_bounds"), (_drop_mass, "mass_gap"),
    (_skew, "err_over_eps"), (_reverse, "bad_bounds"), (_wider_eps, "eps_over_bound"),
])
def test_faulty_answers_are_refused(fault, number):
    v = data(7)
    b, s, eps = fault(*program_like(v, 7))
    got = reference.measure(b, s, eps, v, BETA, program_like(v, 7)[2])
    assert got[number] > reference.LIMITS[number]
    assert not reference.within(got)


def test_no_reading_is_not_correct():
    assert not reference.within(reference.worst([]))


def _composed_bound(lo, hi, v, T):
    """Theorem 1 applied node by node, as a tree is built: a leaf is exact,
    a node merges its two children, the answer merges the cover."""
    def node(level):
        if level == 0:
            return 0.0
        return 2 * node(level - 1) + 2.0 * (v << level) / T + 4

    levels = reference.cover(lo, hi)
    return sum(node(l) for l in levels) + 2.0 * (hi - lo + 1) * v / T + 2 * len(levels)


@pytest.mark.parametrize("lo, hi", [(0, 0), (0, 30), (1, 30), (5, 17), (3, 300), (0, 1023)])
def test_eps_bound_composes_theorem_1_over_the_cover(lo, hi):
    assert reference.eps_bound(lo, hi, 200_000, 2032) == pytest.approx(
        _composed_bound(lo, hi, 200_000, 2032), rel=1e-12)
    assert reference.eps_bound(lo, lo, 4096, 64) == 2.0 * 4096 / 64 + 2


def test_half_the_summary_buckets_reads_about_twice_the_bound():
    full = reference.eps_bound(1, 30, 200_000, 2032)
    half = reference.eps_bound(1, 30, 200_000, 1016)
    assert 1.9 < half / full < 2.0 and half / full > reference.LIMITS["eps_over_bound"]
