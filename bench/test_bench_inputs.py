"""The existing cells' inputs are pinned: for ``logstats-daily`` and its tiny
copy (``tiny-daily``: its sizes under ``bench/rehearsal/``), the values the
value model gives, and for the planner and the backfill the window's first
three requests (after the lead-in), the panels checked after it and the
warm-up, all bit for bit as the generator made them before value models and
client kinds became files of their own (digests of that generator's output,
seeds 7 and 3141592653).  The backfill's lead-in makes the loop's first
calls in set-up, so its window opens on the calls that follow them: its
window and checked panels are pinned with the lead-in."""
import contextlib
import hashlib
import json
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

import generator  # noqa: E402
import tiny  # noqa: E402

PINNED = {
    "values/tiny-daily/planner/7": "5547c4c0dc09b8ba",
    "values/tiny-daily/backfill/7": "5ceaa0f7f962763d",
    "values/logstats-daily/planner/7": "4fac8074a8f38d8e",
    "values/logstats-daily/backfill/7": "dd71c88d639f3094",
    "window/planner/7": "31f0f943eef05009",
    "check/planner/7": "af6070f14d34ae76",
    "warm/planner/7": "9d4f46cf54e0281d",
    "window/backfill/7": "6a7afea5273a69c9",
    "check/backfill/7": "dbbac979fcb467c4",
    "warm/backfill/7": "b085179b42045caa",
    "values/tiny-daily/planner/3141592653": "77f2a4602f3c1e03",
    "values/tiny-daily/backfill/3141592653": "afe2e9f0c0e9622f",
    "values/logstats-daily/planner/3141592653": "aecd1990c0f8f86a",
    "values/logstats-daily/backfill/3141592653": "c88412357a92f503",
    "window/planner/3141592653": "bf6ba3a7c66608de",
    "check/planner/3141592653": "a0a65706a78bd8dc",
    "warm/planner/3141592653": "a839074eb044f66b",
    "window/backfill/3141592653": "91cc104a4b1692ee",
    "check/backfill/3141592653": "df1a8d2756a2781b",
    "warm/backfill/3141592653": "25cba4fbf6f6d11f",
}


def digest(items) -> str:
    h = hashlib.sha256()
    for x in items:
        if isinstance(x, np.ndarray):
            h.update(f"{x.dtype.str}{x.shape}".encode())
            h.update(np.ascontiguousarray(x).tobytes())
        else:
            h.update(repr(x).encode())
    return h.hexdigest()[:16]


def config(name: str) -> dict:
    """``logstats-daily``, or as ``tiny-daily`` at its rehearsal sizes."""
    if name == "tiny-daily":
        return tiny.config("logstats-daily")
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


def traffic(name: str, small: bool) -> dict:
    if small:
        return tiny.traffic(name)
    with open(os.path.join(BENCH, "traffic", f"{name}.json")) as f:
        return json.load(f)


def cell(c: dict, t: dict, seed: int) -> generator.Cell:
    return generator.Cell(c, t, seed, generator.find_parts(BENCH, c, t))


class Calls:
    """A ``t_end`` that lets a client's loop run ``n`` times."""

    def __init__(self, n):
        self.n = n

    def __gt__(self, _now):
        self.n -= 1
        return self.n >= 0


class Answer(tuple):
    degraded = False


class FakeHist:
    def __init__(self, beta):
        self.boundaries = np.arange(beta + 1, dtype=np.float32)
        self.sizes = np.ones(beta, np.float32)


class FakeService:
    """Records every call; answers each panel alike."""

    def __init__(self, log):
        self.log = log
        self.registry = self

    def query_many(self, panels, beta=64):
        self.log.append(("query_many", beta, tuple(panels)))
        return [Answer((FakeHist(beta), 1.0)) for _ in panels]

    def ingest_many(self, tenant, parts):
        self.log.append(("ingest_many", tenant, tuple(parts), digest(list(parts.values()))))


def values(cname: str, tname: str, seed: int) -> str:
    cfg = config(cname)
    small = cname == "tiny-daily"
    d = cell(cfg, traffic(tname, small), seed).data
    W, M = int(cfg["windows"]), int(cfg["metrics"])
    if small:  # every window, twice round, and a pooled range
        arrs = [d.window(m, w) for m in range(M) for w in range(2 * W)]
        arrs += [d.pooled(m, 3, 17) for m in range(M)]
    elif tname == "planner":  # the first and last windows
        arrs = [d.window(0, 0), d.window(0, W - 1), d.pooled(0, 29, 30)]
    else:  # the whole value pool
        arrs = [d.window(0, w) for w in range(int(traffic(tname, False)["value_pool"]))]
    return digest(arrs)


def window(tname: str, seed: int) -> str:
    ce = cell(config("logstats-daily"), traffic(tname, False), seed)
    log = []
    ce.lead_in(FakeService(log))
    ce.clients[0].run(FakeService(log), Calls(3), lambda _: contextlib.nullcontext(), ce.stats[0])
    s = ce.stats[0]
    kept = [(p, b.tolist()[:2], eps) for p, b, _, eps in s.kept]
    return digest(log + [repr(kept), s.attempted, s.distinct, s.cover_nodes])


def check(tname: str, seed: int) -> str:
    ce = cell(config("logstats-daily"), traffic(tname, False), seed)
    ce.lead_in(FakeService([]))
    ce.clients[0].run(FakeService([]), Calls(3), lambda _: contextlib.nullcontext(), ce.stats[0])
    full, sample = ce.check_panels()
    return digest([repr(full), repr(sample), repr(ce.kept_answers())])


def warm(tname: str, seed: int) -> str:
    log = []

    @contextlib.contextmanager
    def open_service():
        yield FakeService(log)

    cell(config("logstats-daily"), traffic(tname, False), seed).warm(open_service)
    return digest(sorted(map(repr, log)))  # warm-up batches run on several threads


@pytest.mark.parametrize("key", sorted(PINNED))
def test_inputs_are_the_pinned_ones(key):
    what, *args, seed = key.split("/")
    got = {"values": values, "window": window, "check": check, "warm": warm}[what](*args, int(seed))
    assert got == PINNED[key]


@pytest.mark.parametrize("seed", [7, 3141592653])
def test_lead_in_is_the_loops_first_calls(seed):
    """Lead-in and window together send what the loop alone would send."""
    mix = traffic("backfill", False)
    n = mix["clients"][0]["lead_in_calls"]
    assert n > 0
    alone = json.loads(json.dumps(mix))
    alone["clients"][0]["lead_in_calls"] = 0
    logs = []
    for t, calls in ((mix, 3), (alone, n + 3)):
        ce = cell(config("logstats-daily"), t, seed)
        log = []
        ce.lead_in(FakeService(log))
        ce.clients[0].run(FakeService(log), Calls(calls), lambda _: contextlib.nullcontext(),
                          ce.stats[0])
        logs.append((log, ce.check_panels()))
    assert logs[0] == logs[1]
