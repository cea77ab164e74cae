"""Run one cell of ``BENCHMARK.json`` once and build its result line.

Everything a cell is made of is found by name: the workload entry names its
configuration (``configs`` → its ``file``, which names its value model and
its plain reference) and its traffic mix (``bench/traffic/<traffic>.json``,
which names its client kinds; see ``generator``); every metric is a reader
in ``bench/metrics/<metric>.py``.  A reader defines ``read(run, before,
after)``, which returns a number or ``None`` when it finds nothing to read,
and may define ``snapshot(svc)``, which is called just before and just after
the window and whose results it is given.  End-to-end readers read the
clients by role (:class:`Run`), so a new client kind reports them too.

:func:`run_cell` is the whole run; ``bench/run.py`` is its command line.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import json
import os
import shutil
import statistics
import sys
import tempfile
import time

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

import generator  # noqa: E402
import trace_reduce  # noqa: E402


class NoDevice(RuntimeError):
    """JAX found no device of the platform, or too few, for the cell."""


@dataclasses.dataclass
class Run:
    """What a metric reader is given."""

    cell: generator.Cell
    window_s: float
    setup_s: float
    stats: list[generator.ClientStats]  # one a client, in the mix's order
    compiles: int  # programs lowered inside the window
    trace: trace_reduce.TraceSummary | None
    peaks: dict

    def clients(self, role: str) -> list[generator.ClientStats]:
        """The stats of every client of ``role`` (``"query"`` or ``"ingest"``)."""
        return [s for s in self.stats if s.role == role]

    def requests(self, role: str) -> list[generator.Request]:
        return [r for s in self.clients(role) for r in s.requests]

    def latencies(self, role: str) -> list[float]:
        return [r.latency for r in self.requests(role)]

    def work(self, role: str) -> int:
        """Panels answered (query) or values acked (ingest) in the window."""
        return sum(r.work for r in self.requests(role))


def p95(values) -> float | None:
    """95th percentile over every value (Python's exclusive quantiles)."""
    values = list(values)
    if len(values) < 2:
        return None
    return statistics.quantiles(values, n=20)[-1]


def load_spec(root: str) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def cell_parts(root: str, workload: str):
    """``(workload entry, configuration, traffic, parts, end-to-end metrics,
    per-layer metrics)`` of the named cell: ``parts`` is the value model and
    client classes (``generator.find_parts``), each metric has its reader."""
    spec = load_spec(root)
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    config = _load_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = _load_json(os.path.join(root, "bench", "traffic", f"{w['traffic']}.json"))
    parts = generator.find_parts(os.path.join(root, "bench"), config, traffic)
    e2e = [m for m in spec["end_to_end"] if workload in m.get("workloads", [workload])]
    reported = {m["name"] for m in e2e}
    layer = [
        m for m in spec["per_layer"]
        if workload in m.get("workloads", [workload]) and m["moves"] in reported
    ]
    for m in e2e + layer:
        path = os.path.join(root, "bench", "metrics", f"{m['name']}.py")
        m["reader"] = generator.load_module(path)
    return w, config, traffic, parts, e2e, layer


def _peaks(root: str, kind: str) -> dict:
    table = _load_json(os.path.join(root, "bench", "peaks.json"))
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


class _CompileCounter:
    """Programs lowered (in-memory jit cache misses) in this process."""

    def __init__(self, jax):
        self.lowered = 0

        def listen(name, _secs, **_kw):
            if name == "/jax/core/compile/jaxpr_to_mlir_module_duration":
                self.lowered += 1

        jax.monitoring.register_event_duration_secs_listener(listen)


def _configure_cache(root: str) -> None:
    """Place the persistent compilation cache in the checkout (or where
    ``JAX_COMPILATION_CACHE_DIR`` says) and cache every program, so that
    only a checkout's first run of a cell compiles."""
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(root, ".jax_cache"))
    import jax

    from repro.launch.compile_cache import use_compile_cache

    os.makedirs(use_compile_cache(), exist_ok=True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def _arena_rows(svc) -> int:
    """Float32 slots the arena's planes hold (growth means a new gather shape)."""
    return svc.registry.arena.capacity_floats()


def run_cell(root: str, workload: str, seed: int, seconds: float, trace: bool,
             *, t0: float | None = None, platform: str = "tpu",
             cache: bool = True) -> tuple[dict, list[str]]:
    """Run the cell once: load, warm up, lead in, measure for ``seconds``, compare.

    Returns the result line (a dict) and lines for standard error, the
    compared numbers last, each with its limit.  Raises before any work when
    a name of the cell has no file, and :class:`NoDevice` when JAX's devices
    are not ``platform`` or fewer than the cell asks for.
    ``platform`` and ``cache`` let the tests rehearse a run on the CPU
    without touching JAX's persistent compilation cache.
    """
    t0 = time.perf_counter() if t0 is None else t0
    sys.path.insert(0, os.path.join(root, "src"))
    w, config, traffic, parts, e2e, layer = cell_parts(root, workload)
    if cache:
        _configure_cache(root)
    import jax

    devices = jax.devices()
    if devices[0].platform != platform or len(devices) < int(w["chips"]):
        raise NoDevice(
            f"cell {workload} needs {w['chips']} {platform} device(s); "
            f"JAX found {len(devices)} {devices[0].platform}"
        )
    kind = devices[0].device_kind
    peaks = _peaks(root, kind)
    reference = generator.load_module(os.path.join(root, config["reference"]))
    from repro.serve import HistogramService

    compiles = _CompileCounter(jax)
    cell = generator.Cell(config, traffic, seed, parts)
    T, beta = int(config["T"]), int(config["beta"])
    readers = layer if trace else e2e
    work = tempfile.mkdtemp(prefix="bench-")
    annotate = jax.profiler.TraceAnnotation
    try:
        svc = HistogramService(os.path.join(work, "data"), num_buckets=T, shared_arena=True)

        @contextlib.contextmanager
        def scratch_service():
            s = HistogramService(tempfile.mkdtemp(prefix="warm-", dir=work),
                                 num_buckets=T, shared_arena=True)
            try:
                yield s
            finally:
                s.close()

        cell.load(svc)
        cell.warm(scratch_service)
        # set-up's objects live as long as the service: keep the collector
        # from walking them again inside the window
        gc.collect()
        gc.freeze()
        cell.lead_in(svc)
        before = {m["name"]: getattr(m["reader"], "snapshot", lambda s: None)(svc) for m in readers}
        rows0 = _arena_rows(svc)
        lowered0 = compiles.lowered
        trace_dir = os.path.join(work, "trace")
        setup_s = time.perf_counter() - t0
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        with annotate(trace_reduce.WINDOW_SPAN):
            t_start, t_end = cell.run(svc, seconds, annotate)
        if trace:
            jax.profiler.stop_trace()
        in_window = compiles.lowered - lowered0
        rows1 = _arena_rows(svc)
        after = {m["name"]: getattr(m["reader"], "snapshot", lambda s: None)(svc) for m in readers}
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices[: int(w["chips"])])
        summary = None
        if trace:
            summary = trace_reduce.reduce(trace_reduce.load_events(trace_dir))
            shutil.rmtree(trace_dir, ignore_errors=True)
        # after the window: the ingest cells' answers, then free the service
        full, sample = cell.check_panels()
        asked = full + sample
        answers = svc.query_many([(p.tenant, p.lo, p.hi) for p in asked], beta=beta) if asked else []
        # a degraded or missing answer is one that never came
        got = [(None, None, None) if a[0] is None or getattr(a, "degraded", False)
               else (np.asarray(a[0].boundaries), np.asarray(a[0].sizes), float(a[1]))
               for a in answers]
        kept = cell.kept_answers() + [(p, *g) for p, g in zip(sample, got[len(full):])]
        full_mass = [(p, None if s is None else float(np.asarray(s, np.float64).sum()), eps)
                     for p, (_, s, eps) in zip(full, got[: len(full)])]
        svc.close()
        del svc, answers, got
    finally:
        gc.unfreeze()
        shutil.rmtree(work, ignore_errors=True)

    # the reference, on the host, once the program's state is freed
    per_window = cell.data.per_window
    readings = []
    unanswered = 0
    for p, b, s, eps in kept:
        if b is None:
            unanswered += 1
            continue
        bound = reference.eps_bound(p.lo, p.hi, per_window, T)
        readings.append(reference.measure(b, s, eps, p.values(cell.data), beta, bound))
    for p, mass, eps in full_mass:
        if mass is None:
            unanswered += 1
            continue
        n = (p.hi - p.lo + 1) * per_window
        readings.append({"mass_gap": abs(mass - n), "bad_bounds": 0.0, "err_over_eps": 0.0,
                         "eps_over_bound": eps / reference.eps_bound(p.lo, p.hi, per_window, T)})
    numbers = reference.worst(readings)
    attempted = sum(s.attempted for s in cell.stats)
    failed = sum(s.failed for s in cell.stats) + unanswered
    correct = bool(readings) and failed == 0 and reference.within(numbers)

    run = Run(cell, t_end - t_start, setup_s, cell.stats, in_window, summary, peaks)
    metrics = {}
    for m in readers:
        value = m["reader"].read(run, before[m["name"]], after[m["name"]])
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {
        "platform": devices[0].platform,
        "kind": kind,
        "count": len(devices),
        "memory_peak_bytes": peak,
    }
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = trace_reduce.breakdown(summary)
    checks = {k: {"value": numbers[k], "limit": reference.LIMITS[k]} for k in reference.LIMITS}
    result["checks"] = checks
    lines = [f"answers checked: {len(readings)}; failed requests: {failed}; "
             f"programs lowered in the window: {in_window}; "
             f"arena capacity (floats) at the window's start and end: {rows0}, {rows1}"]
    # the compared numbers, each beside its limit, last
    lines += [f"check {k}: {v['value']} (limit {v['limit']})" for k, v in checks.items()]
    return result, lines

