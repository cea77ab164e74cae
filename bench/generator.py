"""The one traffic generator: data, preload, warm-up and client loops of a cell.

A traffic mix is a JSON file under ``bench/traffic/``; a configuration is a
JSON file under ``bench/configs/``.  What varies between deployments is
found by name as a file of its own, so a new cell brings new files only:

- the value model ``bench/values/<values.distribution>.py`` defines
  ``build(config, traffic, seed)``, which returns the cell's data: an
  object with ``metrics``, ``per_window``, ``names``, ``window(m, w)`` and
  ``pooled(m, lo, hi)`` (float32).  :class:`Windows` is the common layout;
  a model gives it the function that draws a metric's windows.
- the client kind ``bench/clients/<kind>.py`` of each entry of the mix's
  ``clients`` defines one class, ``Client``, with a ``role`` (``"query"``
  or ``"ingest"``: the end-to-end readers read by role), built as
  ``Client(spec, config, data, seed, index)``, and with

  - ``warm(open_service, load)``: compile every shape the window can
    produce, on throwaway services (``open_service()`` opens one;
    ``load(svc)`` ingests the mix's preload into it);
  - ``run(svc, t_end, annotate, stats)``: the client's loop until
    ``t_end``, each request recorded in ``stats`` (:class:`ClientStats`)
    with the start the client gives it: a closed loop times from the
    call, an open loop from when the request was due;
  - ``check_panels()``: panels whose mass (all the values the client had
    acked) and answers are checked once the window has closed;
  - optionally ``lead_in(svc)``: the client's first requests, made on the
    served service at the end of set-up, after the warm-up's services
    have closed, so that the window opens on the client's steady state.

:func:`find_parts` finds both; a name with no file is an error before any
work.  Traffic keys read here (all optional except ``clients``):

- ``preload``: windows per metric ingested in set-up, or ``"all"`` for the
  configuration's ``windows`` (default 0).
- ``value_pool``: read by :class:`Windows`.
- ``check_answers``: answers compared in full with the reference (default 32).
- ``clients``: one entry per client thread; the rest of an entry is its
  kind's to read.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import os
import re
import sys
import threading
import time

import numpy as np

from reference import cover

ROLES = ("query", "ingest")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


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


def resolve(x, config) -> int:
    """A count from a mix: a number, or ``"windows"`` for the configuration's."""
    return int(config["windows"]) if x == "windows" else int(x)


def load_module(path: str):
    """The Python file at ``path`` as a module of its own."""
    stem = os.path.basename(path)[:-3].replace(".", "_").replace("-", "_")
    name = f"bench_{os.path.basename(os.path.dirname(path))}_{stem}"
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod  # as an import would: dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


def load_part(bench: str, folder: str, name: str):
    """``bench/<folder>/<name>.py``: a value model or a client kind."""
    path = os.path.join(bench, folder, f"{name}.py")
    if not isinstance(name, str) or not NAME.match(name) or not os.path.isfile(path):
        raise FileNotFoundError(f"no file bench/{folder}/{name}.py for {name!r}")
    return load_module(path)


class Windows:
    """Raw float32 values of every (metric, window) a cell can touch.

    ``draw(rng, n)`` gives ``n`` windows of one metric, shape ``(n,
    per_window)``, from the seed's stream 1.  Each metric draws the
    configuration's ``windows``, and window ``w`` reads ``w`` modulo that;
    where the mix sets ``value_pool``, that many single windows are drawn
    instead and every window is one of them, cycled over window ids from
    a per-metric offset.  The same seed gives the same values.
    """

    def __init__(self, config: dict, traffic: dict, seed: int, draw):
        self.metrics = int(config["metrics"])
        self.per_window = int(config["values_per_window"])
        self.names = metric_names(self.metrics)
        rng = seed_rng(seed, 1)
        pool = traffic.get("value_pool")
        if pool:
            self.pool = np.concatenate([draw(rng, 1) for _ in range(int(pool))])
            self.offset = rng.integers(0, int(pool), self.metrics)
            self.arrays = None
        else:
            self.pool = None
            self.arrays = [draw(rng, int(config["windows"])) for _ in range(self.metrics)]

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

    def values(self, data) -> np.ndarray:
        return data.pooled(self.m, self.shift + self.lo, self.shift + self.hi)


def sample_panels(full: list[Panel], n: int, rng: np.random.Generator) -> list[Panel]:
    """``n`` panels drawn inside the panels of ``full``."""
    sample = []
    while len(sample) < n and full:
        p = full[int(rng.integers(len(full)))]
        lo = int(rng.integers(0, p.hi + 1))
        hi = int(rng.integers(lo, p.hi + 1))
        sample.append(dataclasses.replace(p, lo=lo, hi=hi))
    return sample


@dataclasses.dataclass
class Request:
    """One timed request, from its start (as its client gives it) until its
    answer or ack."""

    start: float
    end: float
    work: int  # panels answered or values acked

    @property
    def latency(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class ClientStats:
    kind: str
    role: str
    requests: list[Request] = dataclasses.field(default_factory=list)
    attempted: int = 0  # panels or windows
    failed: int = 0
    distinct: int = 0  # query: distinct panels sent, summed over batches
    cover_nodes: int = 0  # query: their canonical nodes
    kept: list = dataclasses.field(default_factory=list)  # answers to check


def find_parts(bench: str, config: dict, traffic: dict) -> tuple:
    """``(value model, client classes)`` that the configuration and the mix
    name, from the files under ``bench``."""
    model = load_part(bench, "values", config["values"]["distribution"])
    kinds = [load_part(bench, "clients", spec["kind"]).Client for spec in traffic["clients"]]
    for spec, kind in zip(traffic["clients"], kinds):
        if kind.role not in ROLES:
            raise ValueError(f"client kind {spec['kind']!r} has role {kind.role!r}, "
                             f"not one of {ROLES}")
    return model, kinds


class Cell:
    """One configuration under one traffic mix, made from one seed, with
    the value model and client classes of :func:`find_parts`."""

    def __init__(self, config: dict, traffic: dict, seed: int, parts: tuple):
        model, kinds = parts
        specs = traffic["clients"]
        self.config = config
        self.traffic = traffic
        self.seed = seed
        pre = traffic.get("preload", 0)
        self.preload = int(config["windows"]) if pre == "all" else int(pre)
        self.data = model.build(config, traffic, seed)
        self.clients = [kind(spec, config, self.data, seed, i)
                        for i, (kind, spec) in enumerate(zip(kinds, specs))]
        self.stats = [ClientStats(spec["kind"], kind.role) for spec, kind in zip(specs, kinds)]

    # ---- set-up -----------------------------------------------------------
    def load(self, svc) -> None:
        """Ingest the preloaded windows, one WAL group commit per metric."""
        if not self.preload:
            return
        for m, name in enumerate(self.data.names):
            svc.registry.ingest_many(
                name, {w: self.data.window(m, w) for w in range(self.preload)}
            )

    def warm(self, open_service) -> None:
        """Compile every program the window will run, on throwaway services
        so that the served one stays as loaded, its answer cache empty."""
        for c in self.clients:
            c.warm(open_service, self.load)

    def lead_in(self, svc) -> None:
        """The requests each client makes on the served service before the
        window (its ``lead_in(svc)``, where its kind has one)."""
        for c in self.clients:
            if hasattr(c, "lead_in"):
                c.lead_in(svc)

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

        ``full`` holds every client's ``check_panels()`` (their mass must be
        all the values acked); ``sample`` a seeded draw of ``check_answers``
        panels inside them, compared in full.  A read-only client checks
        none: its answers were kept in the window."""
        full = [p for c in self.clients for p in c.check_panels()]
        return full, self.sample(full)

    def sample(self, full: list[Panel]) -> list[Panel]:
        """The seeded draw of ``check_answers`` panels inside ``full``."""
        n = int(self.traffic.get("check_answers", 32))
        return sample_panels(full, n, seed_rng(self.seed, 4))

    def kept_answers(self) -> list:
        """A seeded draw of ``check_answers`` answers the window kept."""
        kept = [k for s in self.stats for k in s.kept]
        n = min(len(kept), int(self.traffic.get("check_answers", 32)))
        idx = seed_rng(self.seed, 5).choice(len(kept), n, replace=False) if n else []
        return [kept[i] for i in sorted(idx)]
