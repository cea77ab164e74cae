#!/usr/bin/env python3
"""Faults planted under the timed path, and their readings at a cell's size.

    python3 bench/faults.py --workload <name> --fault <name> --seeds <n> [<n> ...] [--seconds s]

Each fault is a function of a ``pytest.MonkeyPatch`` that breaks the served
path: a step that leaves the state unchanged, half of each ingest call or
panel left out, an answer altered where it is produced, or summaries coarser
than the configuration states.  ``coarse_summaries`` is planted before
set-up (it changes how the service is built); the others as the window
opens.  The command runs the cell once a seed on the chip with the fault
planted and prints one JSON line a seed: ``correct`` and each compared
number beside its limit.  The benchmark's runs never run this; the tests
plant the same faults at tiny sizes on the CPU.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

import generator  # noqa: E402
import harness  # noqa: E402


def state_unchanged(mp):
    from repro.core.stream import HistogramStore

    mp.setattr(HistogramStore, "_apply", lambda self, summs: None)


def half_of_each_ingest(mp):
    from repro.core.tenant import TenantRegistry

    orig = TenantRegistry.ingest_many

    def ingest_many(self, tenant, partitions):
        keep = dict(list(partitions.items())[: len(partitions) // 2])
        return orig(self, tenant, keep)

    mp.setattr(TenantRegistry, "ingest_many", ingest_many)


def half_of_each_panel(mp):
    from repro.serve import HistogramService

    orig = HistogramService.query_many

    def query_many(self, panels, beta=64, **kw):
        return orig(self, [(n, lo, lo + (hi - lo) // 2) for n, lo, hi in panels], beta, **kw)

    mp.setattr(HistogramService, "query_many", query_many)


def altered_answer(mp):
    from repro.core.histogram import Histogram
    from repro.serve import HistogramService

    orig = HistogramService.query_many

    def query_many(self, panels, beta=64, **kw):
        out = []
        for h, eps in orig(self, panels, beta, **kw):
            b = np.array(h.boundaries)
            b[beta // 2] = np.nextafter(b[beta // 2], np.float32(np.inf))
            out.append((Histogram(b, h.sizes), eps))
        return out

    mp.setattr(HistogramService, "query_many", query_many)


def coarse_summaries(mp):
    """The service built with half the configured T: cheaper summaries whose
    answers honestly report a wider ε."""
    from repro.serve import HistogramService

    orig = HistogramService.__init__

    def init(self, *args, num_buckets, **kw):
        orig(self, *args, num_buckets=num_buckets // 2, **kw)

    mp.setattr(HistogramService, "__init__", init)


FAULTS = {f.__name__: f for f in (state_unchanged, half_of_each_ingest, half_of_each_panel,
                                  altered_answer, coarse_summaries)}
BEFORE_SETUP = {"coarse_summaries"}


def plant(mp, fault: str) -> None:
    """Plant ``fault`` for the next run: now, or as the window opens."""
    if fault in BEFORE_SETUP:
        FAULTS[fault](mp)
        return
    orig = generator.Cell.run

    def run(self, svc, seconds, annotate):
        FAULTS[fault](mp)
        return orig(self, svc, seconds, annotate)

    mp.setattr(generator.Cell, "run", run)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--fault", required=True, choices=sorted(FAULTS))
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    root = os.path.dirname(BENCH)
    caught = True
    for seed in args.seeds:
        with pytest.MonkeyPatch.context() as mp:
            plant(mp, args.fault)
            result, _ = harness.run_cell(root, args.workload, seed, args.seconds, False)
        caught &= not result["correct"]
        print(json.dumps({"workload": args.workload, "fault": args.fault, "seed": seed,
                          "correct": result["correct"], "checks": result["checks"]}), flush=True)
    return 0 if caught else 1


if __name__ == "__main__":
    sys.exit(main())
