"""The one traffic generator: data, preload, warm-up and client loops of a cell.

A traffic mix is a JSON file under ``bench/traffic/`` that this module reads;
a configuration is a JSON file under ``bench/configs/``.  Nothing here knows
a mix or a configuration by name, so a new cell brings data files only.

Traffic keys (all optional except ``clients``):

- ``preload``: windows per metric ingested in set-up, or ``"all"`` for the
  configuration's ``windows`` (default 0).
- ``value_pool``: when set, every window's values are one of this many
  seeded pool windows, cycled over window ids from a per-metric offset;
  otherwise each metric draws its own windows from its own distribution.
- ``check_answers``: answers compared in full with the reference (default 32).
- ``clients``: one entry per client thread, by ``kind``:

  - ``query``: closed loop of ``svc.query_many`` batches of ``batch``
    panels over the preloaded windows.  ``span`` is ``{"uniform": [lo,
    hi]}`` or ``{"choice": [...]}`` (a number or ``"windows"``).  The
    client sends every panel those spans allow, over every metric, in an
    order drawn from the seed, and starts the order again when it is
    through: no panel recurs within a cycle.
  - ``ingest_many``: closed loop of ``registry.ingest_many`` calls of
    ``windows_per_call`` consecutive windows (a number or ``"windows"``),
    metrics round robin.  A tenant holds the configuration's ``windows``;
    once a metric's tenant is full the metric's next call starts a new
    tenant ``<metric>.<n>``, the next period of the same deployment.

Every request is timed on the host clock from when it was sent until its
answer or ack is back.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from reference import cover

# Entries of a tenant's answer cache (the service's default LRU size): a
# cycling client whose cycle is longer than a batch and this together never
# hits it.
ANSWER_CACHE = 128

# Whole tenants an ingest client loads on a throwaway service in set-up:
# the second is loaded with the first's programs, as every call of the
# window is.
WARM_TENANTS = 2


def seed_rng(seed: int, *stream: int) -> np.random.Generator:
    """A generator for one named stream of a run's seed (any whole number)."""
    return np.random.default_rng([seed % (1 << 64), *stream])


def next_pow2(k: int) -> int:
    return 1 if k <= 1 else 1 << (k - 1).bit_length()


def cover_size(lo: int, hi: int) -> int:
    """Nodes in the canonical segment-tree cover of leaf slots ``lo..hi``."""
    return len(cover(lo, hi))


def metric_names(n: int) -> list[str]:
    return [f"metric_{i:03d}" for i in range(n)]


class Data:
    """Raw float32 values of every (metric, window) a cell can touch.

    Values are Gumbel-skewed (``loc - scale·ln E`` with ``E ~ Exp(1)``),
    ``loc`` and ``scale`` drawn per metric (or per pool window) from the
    configuration's ranges.  The same seed gives the same values.
    """

    def __init__(self, config: dict, traffic: dict, seed: int):
        dist = config["values"]
        self.metrics = int(config["metrics"])
        self.per_window = int(config["values_per_window"])
        self.names = metric_names(self.metrics)
        rng = seed_rng(seed, 1)
        pool = traffic.get("value_pool")

        def draw(n_windows: int) -> np.ndarray:
            loc = rng.uniform(*dist["loc"])
            scale = rng.uniform(*dist["scale"])
            e = rng.standard_exponential((n_windows, self.per_window), np.float32)
            np.maximum(e, np.finfo(np.float32).tiny, out=e)  # ln 0 would be infinite
            return (loc - scale * np.log(e)).astype(np.float32)

        if pool:
            self.pool = np.concatenate([draw(1) for _ in range(int(pool))])
            self.offset = rng.integers(0, int(pool), self.metrics)
            self.arrays = None
        else:
            self.pool = None
            self.arrays = [draw(int(config["windows"])) for _ in range(self.metrics)]

    def window(self, m: int, w: int) -> np.ndarray:
        if self.arrays is not None:
            return self.arrays[m][w % len(self.arrays[m])]
        return self.pool[(w + self.offset[m]) % len(self.pool)]

    def pooled(self, m: int, lo: int, hi: int) -> np.ndarray:
        """The raw values of windows ``lo..hi`` of metric ``m``, in one array."""
        w = np.arange(lo, hi + 1)
        if self.arrays is not None:
            return self.arrays[m][w % len(self.arrays[m])].reshape(-1)
        return self.pool[(w + self.offset[m]) % len(self.pool)].reshape(-1)


@dataclasses.dataclass(frozen=True)
class Panel:
    """One interval of one tenant: the service's windows ``lo..hi`` of
    ``tenant`` hold the data's windows ``shift + lo .. shift + hi`` of
    metric ``m``."""

    tenant: str
    m: int
    lo: int
    hi: int
    shift: int = 0

    def values(self, data: Data) -> np.ndarray:
        return data.pooled(self.m, self.shift + self.lo, self.shift + self.hi)


@dataclasses.dataclass
class Request:
    """One timed request, from when it was sent until its answer or ack."""

    start: float
    end: float
    work: int  # panels answered or values acked

    @property
    def latency(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class ClientStats:
    kind: str
    requests: list[Request] = dataclasses.field(default_factory=list)
    attempted: int = 0  # panels or windows
    failed: int = 0
    distinct: int = 0  # query: distinct panels sent, summed over batches
    cover_nodes: int = 0  # query: their canonical nodes
    kept: list = dataclasses.field(default_factory=list)  # answers to check

    def work(self) -> int:
        return sum(r.work for r in self.requests)


def _resolve(x, config):
    return int(config["windows"]) if x == "windows" else int(x)


class QueryClient:
    def __init__(self, spec, config, data, seed, index):
        self.data = data
        self.windows = int(config["windows"])
        self.batch = int(spec["batch"])
        self.beta = int(config["beta"])
        self.rng = seed_rng(seed, 2, index)
        self.keep_rng = seed_rng(seed, 3, index)
        span = spec["span"]
        if "uniform" in span:
            a, b = (_resolve(x, config) for x in span["uniform"])
            self.spans = list(range(a, b + 1))
        else:
            self.spans = [_resolve(x, config) for x in span["choice"]]
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

    def run(self, svc, t_end: float, annotate, stats: ClientStats) -> None:
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


class IngestManyClient:
    def __init__(self, spec, config, data, seed, index):
        self.windows = int(config["windows"])
        self.per_call = _resolve(spec["windows_per_call"], config)
        self.data = data
        self.period = [0] * data.metrics  # the tenant each metric is filling
        self.acked = [-1] * data.metrics  # its newest acked window
        self.loaded: dict[str, Panel] = {}  # tenant -> all its acked windows

    def _call(self, svc, m: int, period: int, first: int, last: int) -> str:
        """Ingest windows ``first..last`` of metric ``m``'s tenant of
        ``period``; returns the tenant."""
        shift = period * self.windows
        tenant = f"{self.data.names[m]}.{period}"
        parts = {w: self.data.window(m, shift + w) for w in range(first, last + 1)}
        svc.registry.ingest_many(tenant, parts)
        return tenant

    def _last(self, first: int) -> int:
        return min(first + self.per_call, self.windows) - 1

    def warm(self, svc) -> None:
        for period in range(WARM_TENANTS):
            for first in range(0, self.windows, self.per_call):
                self._call(svc, 0, period, first, self._last(first))

    def run(self, svc, t_end: float, annotate, stats: ClientStats) -> None:
        m = 0
        per_window = self.data.per_window
        while time.perf_counter() < t_end:
            period, first = self.period[m], self.acked[m] + 1
            last = self._last(first)
            n = last - first + 1
            stats.attempted += n
            t0 = time.perf_counter()
            try:
                with annotate("bench.ingest_many"):
                    tenant = self._call(svc, m, period, first, last)
            except Exception:  # a failed call acks nothing; counted, not raised
                stats.requests.append(Request(t0, time.perf_counter(), 0))
                stats.failed += n
            else:
                stats.requests.append(Request(t0, time.perf_counter(), n * per_window))
                self.loaded[tenant] = Panel(tenant, m, 0, last, period * self.windows)
                self.acked[m] = last
                if last == self.windows - 1:
                    self.period[m], self.acked[m] = period + 1, -1
            m = (m + 1) % self.data.metrics


CLIENTS = {"query": QueryClient, "ingest_many": IngestManyClient}


class Cell:
    """One configuration under one traffic mix, made from one seed."""

    def __init__(self, config: dict, traffic: dict, seed: int):
        self.config = config
        self.traffic = traffic
        self.seed = seed
        W = int(config["windows"])
        pre = traffic.get("preload", 0)
        self.preload = W if pre == "all" else int(pre)
        self.data = Data(config, traffic, seed)
        specs = traffic["clients"]
        self.clients = [
            CLIENTS[spec["kind"]](spec, config, self.data, seed, i)
            for i, spec in enumerate(specs)
        ]
        self.stats = [ClientStats(spec["kind"]) for spec in specs]

    # ---- set-up -----------------------------------------------------------
    def load(self, svc) -> None:
        """Ingest the preloaded windows, one WAL group commit per metric."""
        if not self.preload:
            return
        for m, name in enumerate(self.data.names):
            svc.registry.ingest_many(
                name, {w: self.data.window(m, w) for w in range(self.preload)}
            )

    def warm(self, open_service, threads: int = 8) -> None:
        """Compile every program the window will run, on throwaway services
        so that the served one stays as loaded, its answer cache empty.
        Ingest programs on one; query programs on another loaded as the
        served one is (its arena planes have the same shapes), one batch
        per reachable shape, several batches at once so their compiles
        overlap."""
        loaders = [c for c in self.clients if isinstance(c, IngestManyClient)]
        if loaders:
            with open_service() as scratch:
                for c in loaders:
                    c.warm(scratch)
        batches = []
        for c in self.clients:
            if isinstance(c, QueryClient):
                batches += [(c.beta, b) for b in c.warm_batches()]
        if batches:
            with open_service() as scratch:
                self.load(scratch)
                with ThreadPoolExecutor(threads) as pool:
                    for f in [pool.submit(scratch.query_many, b, beta=beta) for beta, b in batches]:
                        f.result()

    # ---- the measured window ------------------------------------------------
    def run(self, svc, seconds: float, annotate) -> tuple[float, float]:
        """Run every client for ``seconds``; returns the window's start and
        end (the last request's completion) on the host clock."""
        t_start = time.perf_counter()
        t_end = t_start + seconds
        errors: list[BaseException] = []

        def body(client, stats):
            try:
                client.run(svc, t_end, annotate, stats)
            except BaseException as e:  # re-raised in the caller after join
                errors.append(e)

        threads = [
            threading.Thread(target=body, args=(c, s), name=f"bench-{s.kind}")
            for c, s in zip(self.clients[1:], self.stats[1:])
        ]
        for t in threads:
            t.start()
        body(self.clients[0], self.stats[0])
        for t in threads:
            t.join()
        if errors:
            raise errors[0]
        ends = [r.end for s in self.stats for r in s.requests]
        return t_start, max(ends, default=time.perf_counter())

    # ---- what the reference checks ------------------------------------------
    def check_panels(self) -> tuple[list[Panel], list[Panel]]:
        """After the window: ``(full, sample)`` panels to ask the service.

        ``full`` holds one panel per tenant loaded in the window over all
        its acked windows (their mass must be all the acked values);
        ``sample`` a seeded draw of ``check_answers`` panels inside them,
        compared in full.  Only ingest cells ask: a read-only cell's answers
        were kept in the window."""
        full = [p for c in self.clients if isinstance(c, IngestManyClient)
                for p in c.loaded.values()]
        rng = seed_rng(self.seed, 4)
        sample = []
        n = int(self.traffic.get("check_answers", 32))
        while len(sample) < n and full:
            p = full[int(rng.integers(len(full)))]
            lo = int(rng.integers(0, p.hi + 1))
            hi = int(rng.integers(lo, p.hi + 1))
            sample.append(dataclasses.replace(p, lo=lo, hi=hi))
        return full, sample

    def kept_answers(self) -> list:
        """A seeded draw of ``check_answers`` answers the window kept."""
        kept = [k for s in self.stats for k in s.kept]
        n = min(len(kept), int(self.traffic.get("check_answers", 32)))
        idx = seed_rng(self.seed, 5).choice(len(kept), n, replace=False) if n else []
        return [kept[i] for i in sorted(idx)]
