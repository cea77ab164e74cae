"""Benchmark entry point: one section per paper table/figure.

``python -m benchmarks.run [--only fig14,...]`` prints
``name,us_per_call,derived`` CSV rows for:
  * error_vs_T        — paper Figures 14 & 15 (mu_b, mu_s vs T; merge vs tuple)
  * error_vs_days     — paper Figures 16 & 17 (error vs merged interval)
  * table2_runtimes   — paper Table 2 (summarize/merge/sample timings)
  * core_micro        — core-primitive microbenchmarks
  * interval_query    — flat vs segment-tree Merger (latency, qps, ε bound)
  * ingest            — per-partition vs batched vs async Summarizer
                        throughput + compile counts (writes BENCH_ingest.json)
  * tenant            — per-store loop vs registry-batched cross-tenant
                        query_many (writes BENCH_tenant.json)
  * retention         — 7-day sliding window vs unbounded store: steady-
                        state memory + query latency, bit-exactness vs a
                        flat rebuild (writes BENCH_retention.json)
  * arena             — shared node-storage arena: zero-copy cross-tenant
                        pack vs per-tenant host pack, batched pull-up
                        dispatches, amortized window slides
                        (writes BENCH_arena.json)
  * durability        — write-ahead-log ingest overhead vs no-WAL +
                        crash-recovery fidelity across three kill points
                        (writes BENCH_durability.json)
  * faults            — disarmed-failpoint overhead bound (≤ 1 % gate on
                        ingest + query) + fixed-seed chaos drill: degraded
                        rate, recovery time, zero acked loss
                        (writes BENCH_faults.json)
  * serving           — standing-query push plane vs naive dashboard
                        re-pull: update-latency p50/p99, one merge
                        dispatch per tick, dedup counters
                        (writes BENCH_serving.json)
  * replication       — hot-standby WAL shipping: ship-before-ack
                        overhead (≤ 1.1× gate), replica lag p50/p99,
                        kill -9 → promote failover drill with zero
                        acked loss (writes BENCH_replication.json)
  * roofline          — dry-run derived roofline rows (if results exist)
"""
import argparse
import sys

from benchmarks import core_micro, error_vs_T, error_vs_days, table2_runtimes
from benchmarks import ingest_throughput, interval_query, multi_tenant
from benchmarks import arena as arena_bench
from benchmarks import durability as durability_bench
from benchmarks import faults as faults_bench
from benchmarks import replication as replication_bench
from benchmarks import retention as retention_bench
from benchmarks import roofline_report
from benchmarks import serving as serving_bench
from repro.launch.compile_cache import use_compile_cache


def main() -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="all")
    args = ap.parse_args()
    chosen = set(args.only.split(",")) if args.only != "all" else None

    def emit(name: str, us_per_call: float, derived: str = "") -> None:
        print(f"{name},{us_per_call:.1f},{derived}", flush=True)

    print("name,us_per_call,derived")
    sections = {
        "error_vs_T": error_vs_T.main,
        "error_vs_days": error_vs_days.main,
        "table2": table2_runtimes.main,
        "core_micro": core_micro.main,
        "interval_query": interval_query.main,
        "ingest": ingest_throughput.main,
        "tenant": multi_tenant.main,
        "retention": retention_bench.main,
        "arena": arena_bench.main,
        "durability": durability_bench.main,
        "faults": faults_bench.main,
        "serving": serving_bench.main,
        "replication": replication_bench.main,
    }
    for key, fn in sections.items():
        if chosen is None or key in chosen:
            fn(emit)
    if chosen is None or "roofline" in chosen:
        try:
            roofline_report.main(emit)
        except Exception as e:  # dry-run results may not exist yet
            print(f"roofline,0.0,unavailable: {e}", file=sys.stderr)


if __name__ == "__main__":
    main()
