#!/usr/bin/env python3
"""Drive the histogram service's main path once on a TPU and check every answer.

    python3 chip_smoke.py               # one TPU chip, deployment size
    python3 chip_smoke.py --four-chip   # distributed construction over 4 chips
    python3 chip_smoke.py --rehearsal   # CPU at tiny sizes (tier-1 tests)

One chip: ``HistogramService(shared_arena=True)`` at the paper's widths
(``configs/paper_logstats.py``: β = 254, T = 8β = 2032) holds 64 metrics ×
512 windows × 4096 Gumbel float32 values made from ``--seed``.  Each metric
is ingested with ``registry.ingest_many`` (one WAL group commit), one
256-panel ``query_many`` batch is answered, the service checkpoints,
closes, reopens from its data directory and answers the same batch again.
Every answer is checked against a numpy reference over the pooled raw
values of its interval, and the answers after the reopen must equal the
ones before it bit for bit.

Four chips (``--four-chip``): 2^24 float32 values sharded over a ``(4,)``
mesh through ``distributed_histogram`` and
``distributed_histogram_hierarchical`` (T = 40β), each checked against the
numpy reference and its lowered HLO checked for an all-gather that spans
all four devices.  Only that phase runs.

Everything runs in this one process.  Without a TPU (and without
``--rehearsal``) the script exits 2 and prints no result.  The last line
of standard output is ``{"ok": ..., "device": {"platform", "kind",
"count"}}``; the lines before it are one JSON object per phase.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import re
import shutil
import sys
import tempfile
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "src"))


@dataclasses.dataclass(frozen=True)
class ServiceSize:
    tenants: int
    windows: int
    values: int  # per window; at least T, so one summarizer shape serves all
    beta: int
    T: int
    panels: int


@dataclasses.dataclass(frozen=True)
class MeshSize:
    n: int  # total values; 2^24 keeps float32 counts exact
    beta: int
    T: int
    tiles_per_device: int  # hierarchical path: tiles summarized per device


# the paper's widths (configs/paper_logstats.py): β = 254, T = 8β
FULL = ServiceSize(tenants=64, windows=512, values=4096, beta=254, T=2032, panels=256)
FULL_MESH = MeshSize(n=1 << 24, beta=254, T=40 * 254, tiles_per_device=4)
TINY = ServiceSize(tenants=3, windows=16, values=256, beta=8, T=64, panels=12)
TINY_MESH = MeshSize(n=1 << 14, beta=16, T=40 * 16, tiles_per_device=4)


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def check_answer(bounds, sizes, eps, pooled, beta) -> list[str]:
    """Numpy reference checks of one β-bucket answer against ``pooled``,
    the sorted raw values of its interval (tests/test_interval_tree.py's
    Theorem 1/2 checks).  Returns the failed checks.

    Float32 data holds ties, and a boundary that falls inside a run of
    equal values makes its buckets' true counts ambiguous by that run's
    length, so the true-occupancy check allows each bucket the
    multiplicity of its two boundary values.
    """
    b = np.asarray(bounds)
    s = np.asarray(sizes, np.float64)
    n = pooled.size
    ideal = n / beta
    fails = []
    if abs(s.sum() - n) > 0.5:
        fails.append(f"sizes sum to {s.sum()}, not N={n}")
    if np.abs(s - ideal).max() > eps + 1e-3:
        fails.append(f"bucket size off by {np.abs(s - ideal).max()} > eps {eps}")
    # every contiguous range [i, j): |(cum_j - cum_i) - (j - i)·N/β| ≤ ε
    drift = np.concatenate([[0.0], np.cumsum(s)]) - np.arange(beta + 1) * ideal
    if drift.max() - drift.min() > eps + 1e-3:
        fails.append(f"range size off by {drift.max() - drift.min()} > eps {eps}")
    left = np.searchsorted(pooled, b, side="left")
    right = np.searchsorted(pooled, b, side="right")
    if np.any(right == left):
        fails.append(f"{int(np.sum(right == left))} boundaries not in the data")
    true = (left[1:] - left[:-1]).astype(np.float64)
    true[-1] += right[-1] - left[-1]  # the last bucket is right-closed
    ties = (right - left).astype(np.float64)
    over = np.abs(true - ideal) - (eps + ties[:-1] + ties[1:])
    if over.max() > 1e-3:
        fails.append(f"true occupancy exceeds eps + ties by {over.max()}")
    return fails


def _device_fields(jax) -> dict:
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": len(jax.devices())}


def _peak_bytes(jax):
    stats = jax.devices()[0].memory_stats()
    return None if not stats else stats.get("peak_bytes_in_use")


class _CompileCounter:
    """Counts programs lowered (in-memory jit cache misses) in this process."""

    def __init__(self, jax):
        self.lowered = 0

        def listen(name, _secs, **_kw):
            if name == "/jax/core/compile/jaxpr_to_mlir_module_duration":
                self.lowered += 1

        jax.monitoring.register_event_duration_secs_listener(listen)


def run_service(size: ServiceSize, seed: int, out_dir: str, jax) -> list[str]:
    from repro.core.interval_tree import _gather_rows, merge_stacks
    from repro.serve import HistogramService

    compiles = _CompileCounter(jax)
    rng = np.random.default_rng(seed)
    names = [f"metric_{i:02d}" for i in range(size.tenants)]
    t0 = time.perf_counter()
    data = {
        name: rng.gumbel(
            loc=rng.uniform(0.0, 100.0),
            scale=rng.uniform(0.5, 20.0),
            size=(size.windows, size.values),
        ).astype(np.float32)
        for name in names
    }
    panels = [(names[0], 0, size.windows - 1)]  # one panel over every window
    while len(panels) < size.panels:
        span = int(rng.integers(1, size.windows + 1))
        lo = int(rng.integers(0, size.windows - span + 1))
        panels.append((names[int(rng.integers(size.tenants))], lo, lo + span - 1))
    emit("data", seconds=time.perf_counter() - t0, tenants=size.tenants,
         windows=size.windows, values_per_window=size.values,
         total_values=size.tenants * size.windows * size.values)

    fails: list[str] = []
    os.makedirs(out_dir, exist_ok=True)
    data_dir = tempfile.mkdtemp(prefix="service-", dir=out_dir)

    def answer(svc, label: str):
        m0, g0 = merge_stacks._cache_size(), _gather_rows._cache_size()
        low0, disp0 = compiles.lowered, svc.registry.merge_dispatches
        t = time.perf_counter()
        host = []
        for a in svc.query_many(panels, beta=size.beta):
            h, e = a
            if h is None or getattr(a, "degraded", False):
                host.append((None, None, float(e), True))
            else:
                host.append((np.asarray(h.boundaries), np.asarray(h.sizes),
                             float(e), False))
        fields = {
            "seconds": time.perf_counter() - t,
            "panels": len(panels),
            "merge_dispatches": svc.registry.merge_dispatches - disp0,
            "merge_stacks_compiles": merge_stacks._cache_size() - m0,
            "gather_compiles": _gather_rows._cache_size() - g0,
            "programs_lowered": compiles.lowered - low0,
        }
        emit(label, **fields)
        if any(d for *_, d in host):
            fails.append(f"{label}: degraded answers")
        return host, fields

    def check_health(svc, label: str) -> None:
        h = svc.health()
        emit(f"{label}_health", status=h["status"], tenants=h["tenants"],
             degraded_served=h["degraded_served"],
             pack_fallbacks=h["pack_fallbacks"], quarantined=h["quarantined"],
             wal_fsyncs=(h["wal"] or {}).get("fsyncs"))
        if h["status"] != "ok" or h["degraded_served"] or h["pack_fallbacks"]:
            fails.append(f"{label}: health {h['status']}, degraded_served="
                         f"{h['degraded_served']}, pack_fallbacks={h['pack_fallbacks']}")
        if h["quarantined"]:
            fails.append(f"{label}: quarantined {h['quarantined']}")

    try:
        svc = HistogramService(data_dir, num_buckets=size.T, shared_arena=True)
        t = time.perf_counter()
        for name in names:
            svc.registry.ingest_many(
                name, {pid: data[name][pid] for pid in range(size.windows)}
            )
        emit("load", seconds=time.perf_counter() - t,
             arena_live_rows=svc.registry.arena.live_rows(),
             arena_capacity_bytes=4 * svc.registry.arena.capacity_floats())

        cold, cold_fields = answer(svc, "cold_batch")
        if cold_fields["merge_dispatches"] != 1:
            fails.append(f"cold batch took {cold_fields['merge_dispatches']} merge dispatches")
        t = time.perf_counter()
        checked = 0
        for (name, lo, hi), (b, s, eps, degraded) in zip(panels, cold):
            if degraded:
                continue
            pooled = np.sort(data[name][lo : hi + 1].reshape(-1))
            for f in check_answer(b, s, eps, pooled, size.beta):
                fails.append(f"{name}[{lo}..{hi}]: {f}")
            checked += 1
        emit("reference", seconds=time.perf_counter() - t, answers_checked=checked,
             failures=len(fails))
        check_health(svc, "before_reopen")

        t = time.perf_counter()
        svc.checkpoint()
        svc.close()
        del svc
        gc.collect()  # the closed registry's arena and device planes go now
        svc = HistogramService(data_dir, num_buckets=size.T, shared_arena=True)
        emit("reopen", seconds=time.perf_counter() - t,
             salvage=svc.salvage, recovery=svc.recovery)
        if svc.salvage is not None:
            fails.append(f"reopen salvaged the snapshot: {svc.salvage}")

        warm, warm_fields = answer(svc, "warm_batch")
        if warm_fields["programs_lowered"]:
            fails.append(f"warm batch lowered {warm_fields['programs_lowered']} programs")
        same = sum(
            np.array_equal(b0, b1) and np.array_equal(s0, s1) and e0 == e1
            for (b0, s0, e0, _), (b1, s1, e1, _) in zip(cold, warm)
        )
        emit("reopen_bit_identical", identical=same, of=len(panels))
        if same != len(panels):
            fails.append(f"{len(panels) - same} answers changed across the reopen")
        answer(svc, "cached_batch")  # the same panels again: LRU answer hits
        check_health(svc, "after_reopen")
        svc.close()
        emit("device_memory", peak_bytes_in_use=_peak_bytes(jax))
    finally:
        shutil.rmtree(data_dir, ignore_errors=True)
    return fails


def _all_gather_groups(hlo: str) -> list[list[int]]:
    groups = []
    for m in re.finditer(r"all_gather.*?replica_groups = dense<(\[\[.*?\]\])>", hlo):
        groups.extend(json.loads(m.group(1)))
    return groups


def run_four_chip(size: MeshSize, seed: int, jax) -> list[str]:
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import (
        distributed_histogram,
        distributed_histogram_hierarchical,
        theoretical_eps_max,
    )
    from repro.core.distributed import hierarchical_eps_bound
    from repro.launch.mesh import make_mesh

    fails: list[str] = []
    k = 4
    if len(jax.devices()) != k:
        return [f"the four-chip phase needs {k} devices, found {len(jax.devices())}"]
    mesh = make_mesh((k,), ("data",))
    t = time.perf_counter()
    x = np.random.default_rng(seed).gumbel(size=size.n).astype(np.float32)
    xs = jax.device_put(x, NamedSharding(mesh, P("data")))
    pooled = np.sort(x)
    emit("mesh_data", seconds=time.perf_counter() - t, n=size.n, devices=k)
    tile = size.n // k // size.tiles_per_device
    paths = {
        "distributed_histogram": (
            jax.jit(lambda v: distributed_histogram(v, size.T, size.beta, mesh, "data")),
            theoretical_eps_max(size.n, size.T, k=k, exact_inputs=False),
        ),
        # tile → device → mesh: exact tiles, then two merge levels
        "distributed_histogram_hierarchical": (
            jax.jit(lambda v: distributed_histogram_hierarchical(
                v, mesh, tile_size=tile, T_tile=size.T, T_device=size.T,
                beta=size.beta, data_axes=("data",), pod_axis=None)),
            hierarchical_eps_bound(
                size.n, [size.T, size.T], merges_k=[k * size.tiles_per_device, k]
            ),
        ),
    }
    for name, (fn, eps) in paths.items():
        t = time.perf_counter()
        groups = _all_gather_groups(fn.lower(xs).as_text())
        if not groups or any(sorted(g) != list(range(k)) for g in groups):
            fails.append(f"{name}: all-gather replica groups {groups}")
        t_lower = time.perf_counter() - t
        t = time.perf_counter()
        h = fn(xs)
        b, s = np.asarray(h.boundaries), np.asarray(h.sizes)
        t_first = time.perf_counter() - t
        t = time.perf_counter()
        h = fn(xs)
        b2, s2 = np.asarray(h.boundaries), np.asarray(h.sizes)
        t_warm = time.perf_counter() - t
        errs = check_answer(b, s, eps, pooled, size.beta)
        if not (np.array_equal(b, b2) and np.array_equal(s, s2)):
            errs.append("two runs of the same input differ")
        fails += [f"{name}: {e}" for e in errs]
        emit(name, lower_seconds=t_lower, first_call_seconds=t_first,
             warm_call_seconds=t_warm, eps_bound=eps,
             max_size_error=float(np.abs(s - size.n / size.beta).max()),
             all_gather_groups=groups, failures=len(errs))
    emit("device_memory", peak_bytes_in_use=_peak_bytes(jax))
    return fails


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the sharded construction over 4 chips")
    ap.add_argument("--rehearsal", action="store_true",
                    help="run on the CPU at tiny sizes instead of on a TPU")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=os.path.join(REPO, ".chip_smoke"),
                    help="working directory for the service's data directory")
    args = ap.parse_args(argv)

    import jax

    want = "cpu" if args.rehearsal else "tpu"
    platform = jax.devices()[0].platform
    if platform != want:
        print(f"chip_smoke: JAX found {platform!r}, this run needs {want!r}",
              file=sys.stderr)
        return 2
    device = _device_fields(jax)
    emit("device", **device)
    if args.four_chip:
        fails = run_four_chip(TINY_MESH if args.rehearsal else FULL_MESH,
                              args.seed, jax)
    else:
        fails = run_service(TINY if args.rehearsal else FULL, args.seed,
                            args.out, jax)
    for f in fails:
        print(f"chip_smoke: FAILED {f}", file=sys.stderr)
    print(json.dumps({"ok": not fails, "device": device}), flush=True)
    return 0 if not fails else 1


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    sys.exit(main())
