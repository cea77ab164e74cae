#!/usr/bin/env python3
"""The control of ``correct``: the reference put in the program's place,
computed one precision lower than the configuration states.

    python3 bench/control.py --workload <name> --seeds <n> [<n> ...]

For each seed it makes the cell's data at the cell's own size, draws
``check_answers`` panels as the cell draws those it checks after the window
(``Cell.sample``, inside whole periods of every metric), answers each with
the exact equi-depth histogram computed in bfloat16 (the configuration
states float32) and compares it by ``reference.measure``.  The same
histogram in float32 is compared too: it has to pass, so that the control
fails for its precision and not for the comparison.  Prints one JSON line a
seed: each compared number's worst reading for both, beside its limit.
The benchmark's runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import ml_dtypes
import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

import generator  # noqa: E402
import harness  # noqa: E402


def whole_periods(cell: generator.Cell, periods: int = 2) -> list[generator.Panel]:
    """Every metric's windows of its first ``periods`` periods, a panel
    each, as an ingest client reports the tenants it loaded (a query
    client's panels lie inside the first)."""
    W = int(cell.config["windows"])
    return [generator.Panel(f"{name}.{p}", m, 0, W - 1, p * W)
            for m, name in enumerate(cell.data.names) for p in range(periods)]


def readings(root: str, workload: str, seed: int) -> dict:
    _w, config, traffic, parts, _e2e, _layer = harness.cell_parts(root, workload)
    reference = generator.load_module(os.path.join(root, config["reference"]))
    cell = generator.Cell(config, traffic, seed, parts)
    beta, T = int(config["beta"]), int(config["T"])
    per_window = cell.data.per_window
    asked = cell.sample(whole_periods(cell))
    out = {}
    for label, dtype in (("control_bfloat16", ml_dtypes.bfloat16), ("reference_float32", np.float32)):
        got = []
        for p in asked:
            values = p.values(cell.data)
            b, s, eps = reference.equi_depth(values, beta, dtype)
            bound = reference.eps_bound(p.lo, p.hi, per_window, T)
            got.append(reference.measure(b, s, eps, values, beta, bound))
        out[label] = reference.worst(got)
    out["limits"] = reference.LIMITS
    out["control_fails"] = not reference.within(out["control_bfloat16"])
    out["reference_passes"] = reference.within(out["reference_float32"])
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    root = os.path.dirname(BENCH)
    ok = True
    for seed in args.seeds:
        r = readings(root, args.workload, seed)
        ok &= r["control_fails"] and r["reference_passes"]
        print(json.dumps({"workload": args.workload, "seed": seed, **r}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
