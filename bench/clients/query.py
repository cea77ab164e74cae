"""``query``: one closed loop of ``svc.query_many`` batches of ``batch``
panels over the preloaded windows.

``span`` is ``{"uniform": [lo, hi]}`` or ``{"choice": [...]}`` (a number or
``"windows"``).  The client sends every panel those spans allow, over every
metric, in an order drawn from the seed, and starts the order again when it
is through: no panel recurs within a cycle.  Each batch is timed from the
call until its answers are on the host; one answer a batch, drawn from the
seed, is kept for the reference.
"""
from __future__ import annotations

import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from generator import Panel, Request, cover_size, next_pow2, resolve, seed_rng

# Entries of a tenant's answer cache (the service's default LRU size): a
# cycling client whose cycle is longer than a batch and this together never
# hits it.
ANSWER_CACHE = 128

# Warm-up batches sent at once, so that their compiles overlap.
WARM_THREADS = 8


class Client:
    role = "query"

    def __init__(self, spec, config, data, seed, index):
        self.data = data
        self.windows = int(config["windows"])
        self.batch = int(spec["batch"])
        self.beta = int(config["beta"])
        self.rng = seed_rng(seed, 2, index)
        self.keep_rng = seed_rng(seed, 3, index)
        span = spec["span"]
        if "uniform" in span:
            a, b = (resolve(x, config) for x in span["uniform"])
            self.spans = list(range(a, b + 1))
        else:
            self.spans = [resolve(x, config) for x in span["choice"]]
        every = [(m, lo, hi) for m in range(data.metrics) for lo, hi in self.ranges()]
        self.order = [every[i] for i in self.rng.permutation(len(every))]
        self.pos = 0

    def ranges(self) -> list[tuple[int, int]]:
        """Every (lo, hi) this client can send."""
        return [(hi - s + 1, hi) for s in sorted(set(self.spans))
                for hi in range(s - 1, self.windows)]

    def panel(self) -> tuple[int, int, int]:
        key = self.order[self.pos % len(self.order)]
        self.pos += 1
        return key

    def shapes(self) -> tuple[list[int], list[int]]:
        """Miss counts and padded cover sizes the window's batches can have:
        every panel of a batch misses the answer cache while a cycle holds
        more panels than a batch and the cache together; otherwise hits
        and repeats lower the count."""
        L, B = len(self.order), self.batch
        qs = [B] if B + ANSWER_CACHE <= L else list(range(1, min(B, L) + 1))
        ks = sorted({next_pow2(cover_size(lo, hi)) for lo, hi in self.ranges()})
        return qs, ks

    def warm_batches(self) -> list[list[tuple[str, int, int]]]:
        """One batch of distinct panels for every (misses, padded cover)
        pair the window can produce: one panel of that cover, the rest of
        covers no larger."""
        qs, ks = self.shapes()
        by_k: dict[int, list[tuple[int, int]]] = {}
        for lo, hi in self.ranges():
            by_k.setdefault(next_pow2(cover_size(lo, hi)), []).append((lo, hi))
        names = self.data.names
        rng = seed_rng(0, 6)
        batches = []
        for K in ks:
            small = [(m, lo, hi) for k, rs in by_k.items() if k <= K
                     for lo, hi in rs for m in range(self.data.metrics)]
            first = (0, *by_k[K][0])
            rest = [small[i] for i in rng.permutation(len(small)) if small[i] != first]
            for Q in qs:
                if Q - 1 <= len(rest):  # else fewer distinct panels exist: unreachable
                    picked = [first] + rest[: Q - 1]
                    batches.append([(names[m], lo, hi) for m, lo, hi in picked])
        return batches

    def warm(self, open_service, load) -> None:
        """Every batch shape on a throwaway service loaded as the served one
        is (its arena planes have the same shapes)."""
        with open_service() as scratch:
            load(scratch)
            with ThreadPoolExecutor(WARM_THREADS) as pool:
                for f in [pool.submit(scratch.query_many, b, beta=self.beta)
                          for b in self.warm_batches()]:
                    f.result()

    def run(self, svc, t_end: float, annotate, stats) -> None:
        names = self.data.names
        while time.perf_counter() < t_end:
            panels = [self.panel() for _ in range(self.batch)]
            distinct = set(panels)
            stats.distinct += len(distinct)
            stats.cover_nodes += sum(cover_size(lo, hi) for _, lo, hi in distinct)
            batch = [(names[m], lo, hi) for m, lo, hi in panels]
            t0 = time.perf_counter()
            with annotate("bench.query_many"):
                answers = svc.query_many(batch, beta=self.beta)
            t1 = time.perf_counter()
            bad = sum(1 for a in answers if a[0] is None or getattr(a, "degraded", False))
            stats.requests.append(Request(t0, t1, len(panels) - bad))
            stats.attempted += len(panels)
            stats.failed += bad
            k = int(self.keep_rng.integers(len(panels)))
            h, eps = answers[k]
            if h is not None and not getattr(answers[k], "degraded", False):
                m, lo, hi = panels[k]
                stats.kept.append(
                    (Panel(names[m], m, lo, hi), np.array(h.boundaries), np.array(h.sizes), float(eps))
                )

    def check_panels(self) -> list[Panel]:
        return []  # read-only: the answers kept in the window are checked
