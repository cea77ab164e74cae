"""Serving launcher: batched generation with a reduced config on CPU.

``--metrics-dir DIR`` attaches a :class:`HistogramService` sidecar: each
request's generation latency is recorded as a durable histogram window,
and a standing subscription on the latency metric demonstrates the push
plane — the pushed update's p-quantile answer and eps are printed after
the batch, then the sidecar checkpoints and closes.

``--replicate-to DIR`` additionally ships the sidecar's WAL to a
hot-standby directory (core/replication.py): after the batch, a
replica-role service is opened over the shipped log and its
bounded-staleness answer (eps widened by the lag-drift bound) is printed
next to the primary's, demonstrating zero-loss WAL shipping end to end.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config, smoke as smoke_cfg
from repro.launch.compile_cache import use_compile_cache
from repro.models.model import init_model
from repro.serve import Engine, HistogramService, ServeConfig


def main() -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new-tokens", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument(
        "--metrics-dir", default=None,
        help="attach a HistogramService sidecar recording per-request "
        "generation latency, with a standing push subscription",
    )
    ap.add_argument(
        "--replicate-to", default=None,
        help="hot-standby directory: ship the sidecar's WAL there and "
        "print a replica-role bounded-staleness answer after the batch "
        "(requires --metrics-dir)",
    )
    args = ap.parse_args()
    if args.replicate_to is not None and args.metrics_dir is None:
        ap.error("--replicate-to requires --metrics-dir")

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_cfg(cfg)
    params, _ = init_model(cfg, jax.random.PRNGKey(0))
    eng = Engine(
        cfg, params,
        ServeConfig(
            max_seq=args.prompt_len + args.max_new_tokens + 1,
            max_new_tokens=args.max_new_tokens,
            temperature=args.temperature,
        ),
    )
    svc = sub = None
    if args.metrics_dir is not None:
        replicate_to = (args.replicate_to,) if args.replicate_to else ()
        svc = HistogramService(
            args.metrics_dir, num_buckets=64, replicate_to=replicate_to
        )
        # standing dashboard panel: p-latency over the whole run so far
        sub = svc.subscribe("gen_latency_ms", 0, 1 << 20, beta=64)

    rng = np.random.default_rng(0)
    prompts = [
        rng.integers(2, cfg.vocab_size, size=rng.integers(4, args.prompt_len + 1)).astype(np.int32)
        for _ in range(args.batch)
    ]
    latencies = []
    outs = []
    for i, p in enumerate(prompts):
        t0 = time.perf_counter()
        outs.append(eng.generate([p])[0])
        latencies.append((time.perf_counter() - t0) * 1e3)
        if svc is not None:
            svc.record("gen_latency_ms", i, np.float32([latencies[-1]]))
    for i, o in enumerate(outs):
        print(f"req{i}: prompt_len={len(prompts[i])} output={o.tolist()}")

    if svc is not None:
        svc.subscriptions.flush()  # push barrier: deliver the update
        update = sub.get(timeout=5.0)
        if update is not None:
            print(
                f"pushed update: metric=gen_latency_ms windows=0..{1 << 20} "
                f"eps={update.eps:g} degraded={update.degraded} "
                f"lag={update.lag_seconds * 1e3:.1f}ms"
            )
        stats = svc.subscriptions.stats()
        print(
            "subscription plane: "
            f"delivered={stats['updates_delivered']} "
            f"dispatches={stats['eval_batches']}"
        )
        if args.replicate_to is not None:
            replica = HistogramService(
                args.replicate_to, role="replica", num_buckets=64
            )
            replica.sync()
            ans = replica.query_many(
                [("gen_latency_ms", 0, 1 << 20)], beta=64
            )[0]
            repl = svc.health()["replication"]
            print(
                f"replica answer: eps={ans.eps:g} degraded={ans.degraded} "
                f"lag_s={ans.lag_seconds} "
                f"(primary shipped_lsn={repl['shipped_lsn']} "
                f"ships={repl['ships']})"
            )
            replica.close()
        svc.checkpoint()
        svc.close()


if __name__ == "__main__":
    main()
