"""Ingest-path throughput: per-partition dispatch vs batched vs async.

The Summarizer-side benchmark for the shape-stable batched ingest pipeline
(core/histogram.py::build_exact_padded*, core/stream.py):

  * **per_partition** — the pre-batching baseline: one jitted ``build_exact``
    per partition, shape-keyed, so every distinct partition length pays a
    fresh XLA compile (measured on a subsample and reported as a rate,
    because running it over the full ragged set is exactly the pathology
    this PR removes);
  * **batched** — ``HistogramStore.ingest_many``: partitions grouped by
    power-of-two padded shape, one vmapped dispatch per group, one
    level-batched tree maintenance pass;
  * **async** — ``ingest_async`` + ``flush``: the background worker drains
    the queue in batches (same grouped summarizer) while the caller is free;
  * **compile counts** for each path, with the O(log max_n) bound asserted
    machine-readably;
  * **t_node trade-off** — geometric vs uniform ``T_node``: build time,
    node-storage floats, and the reported full-window ε of each.

Results print as CSV rows and are written to ``BENCH_ingest.json`` so the
perf trajectory is machine-readable from this PR onward (schema
``bench_ingest/v1``; CI smoke-checks it on tiny sizes via ``--smoke``).

Run standalone: ``PYTHONPATH=src python benchmarks/ingest_throughput.py``
or as a section of ``python -m benchmarks.run --only ingest``.
"""
from __future__ import annotations

import argparse
import json
import time

import jax.numpy as jnp
import numpy as np

from repro.core import HistogramStore, build_exact
from repro.core.histogram import build_exact_padded_batched

SCHEMA = "bench_ingest/v1"


def _rates(parts: dict[int, np.ndarray], seconds: float) -> dict:
    values = int(sum(v.size for v in parts.values()))
    return {
        "seconds": seconds,
        "partitions_per_sec": len(parts) / seconds,
        "values_per_sec": values / seconds,
    }


def run_per_partition(parts, T, sample: int) -> dict:
    """Legacy Summarizer: one shape-keyed ``build_exact`` per partition."""
    sub = dict(list(parts.items())[:sample])
    store = HistogramStore(num_buckets=T)
    before = build_exact._cache_size()
    t0 = time.perf_counter()
    for pid, v in sub.items():
        h = build_exact(jnp.asarray(v), min(T, v.shape[0]))
        h.sizes.block_until_ready()
        store.ingest_summary(pid, h)
    out = _rates(sub, time.perf_counter() - t0)
    out["compiles"] = build_exact._cache_size() - before
    out["measured_partitions"] = len(sub)
    return out


def run_batched(parts, T) -> tuple[dict, HistogramStore]:
    store = HistogramStore(num_buckets=T)
    before = build_exact_padded_batched._cache_size()
    t0 = time.perf_counter()
    store.ingest_many(parts)
    out = _rates(parts, time.perf_counter() - t0)
    out["compiles"] = build_exact_padded_batched._cache_size() - before
    out["dispatch_shapes"] = len(store.summarize_shapes)
    return out, store


def run_async(parts, T) -> dict:
    store = HistogramStore(num_buckets=T, async_ingest=True)
    t0 = time.perf_counter()
    for pid, v in parts.items():
        store.ingest_async(pid, v)
    t_enqueue = time.perf_counter() - t0
    store.flush()
    out = _rates(parts, time.perf_counter() - t0)
    out["enqueue_seconds"] = t_enqueue  # caller-visible Summarizer latency
    store.close()
    return out


def run_t_node_tradeoff(parts, T) -> dict:
    out = {}
    w = len(parts)
    for mode, label in ((None, "uniform"), ("geometric", "geometric")):
        store = HistogramStore(num_buckets=T, T_node=mode)
        t0 = time.perf_counter()
        store.ingest_many(parts)
        build_s = time.perf_counter() - t0
        node_floats = int(
            sum(
                nd.boundaries.size + nd.sizes.size
                for nd in store._tree.nodes.values()
            )
        )
        t0 = time.perf_counter()
        h, eps = store.query(0, w - 1, 64 if T >= 64 else T)
        np.asarray(h.sizes)
        query_s = time.perf_counter() - t0
        out[label] = {
            "build_seconds": build_s,
            "node_storage_floats": node_floats,
            "full_window_eps": float(eps),
            "full_window_query_seconds": query_s,
        }
    out["eps_ratio_uniform_over_geometric"] = (
        out["uniform"]["full_window_eps"]
        / out["geometric"]["full_window_eps"]
    )
    return out


def make_partitions(n_partitions, lo, hi, seed=0) -> dict[int, np.ndarray]:
    rng = np.random.default_rng(seed)
    return {
        d: rng.lognormal(-1.8, 0.55, size=int(rng.integers(lo, hi))).astype(
            np.float32
        )
        for d in range(n_partitions)
    }


def main(
    emit,
    *,
    n_partitions: int = 1000,
    len_lo: int = 2048,
    len_hi: int = 16384,
    baseline_sample: int = 128,
    out_path: str = "BENCH_ingest.json",
) -> dict:
    T = 256
    parts = make_partitions(n_partitions, len_lo, len_hi)
    max_n = max(v.size for v in parts.values())
    compile_bound = int(np.log2(max_n)) + 3

    per_part = run_per_partition(parts, T, baseline_sample)
    # cold = first-ever run (includes the O(log max_n) one-time compiles);
    # warm = steady state, the fair throughput comparison: the per-partition
    # baseline can never amortize its compiles (every new partition length
    # is a new executable) while the batched path's O(log) programs cover
    # every future ingest.
    batched_cold, _ = run_batched(parts, T)
    batched, _ = run_batched(parts, T)
    batched["cold_seconds"] = batched_cold["seconds"]
    batched["compiles"] = batched_cold["compiles"]
    batched["dispatch_shapes"] = batched_cold["dispatch_shapes"]
    asynced = run_async(parts, T)
    tnode = run_t_node_tradeoff(
        {d: parts[d] for d in range(min(256, n_partitions))}, T
    )

    speedup_batched = (
        batched["partitions_per_sec"] / per_part["partitions_per_sec"]
    )
    speedup_async = (
        asynced["partitions_per_sec"] / per_part["partitions_per_sec"]
    )
    result = {
        "schema": SCHEMA,
        "partitions": n_partitions,
        "total_values": int(sum(v.size for v in parts.values())),
        "T": T,
        "per_partition": per_part,
        "batched": batched,
        "async": asynced,
        "speedup_batched_vs_per_partition": speedup_batched,
        "speedup_async_vs_per_partition": speedup_async,
        "compile_bound": {
            "max_n": int(max_n),
            "bound": compile_bound,
            "batched_compiles": batched["compiles"],
            "bounded": batched["compiles"] <= compile_bound,
        },
        "t_node": tnode,
    }
    with open(out_path, "w") as f:
        json.dump(result, f, indent=2)

    emit(
        "ingest_per_partition_rate",
        per_part["partitions_per_sec"],
        f"parts/s, {per_part['measured_partitions']} sampled, "
        f"{per_part['compiles']} compiles",
    )
    emit(
        "ingest_batched_rate",
        batched["partitions_per_sec"],
        f"parts/s over {n_partitions} ragged partitions, "
        f"{batched['dispatch_shapes']} dispatch shapes, "
        f"{batched['compiles']} compiles (bound {compile_bound})",
    )
    emit(
        "ingest_async_rate",
        asynced["partitions_per_sec"],
        f"parts/s incl. flush; enqueue only "
        f"{asynced['enqueue_seconds'] * 1e3:.1f} ms",
    )
    emit(
        "ingest_speedup_batched",
        speedup_batched,
        f"x vs per-partition dispatch (target >= 10x)",
    )
    emit(
        "ingest_tnode_eps_ratio",
        tnode["eps_ratio_uniform_over_geometric"],
        f"uniform/geometric full-window eps; geometric stores "
        f"{tnode['geometric']['node_storage_floats'] / max(1, tnode['uniform']['node_storage_floats']):.1f}x the node floats",
    )
    emit("ingest_json", 0.0, f"written to {out_path}")
    return result


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--smoke",
        action="store_true",
        help="tiny sizes for CI: validates the pipeline + JSON schema only",
    )
    ap.add_argument("--out", default="BENCH_ingest.json")
    ap.add_argument("--partitions", type=int, default=1000)
    args = ap.parse_args()
    kw = dict(out_path=args.out, n_partitions=args.partitions)
    if args.smoke:
        kw.update(
            n_partitions=48, len_lo=256, len_hi=2048, baseline_sample=16
        )
    print("name,value,derived")
    main(
        lambda name, v, derived="": print(f"{name},{v:.1f},{derived}", flush=True),
        **kw,
    )
