"""``ingest_many``: one closed loop of ``registry.ingest_many`` calls of
``windows_per_call`` consecutive windows (a number or ``"windows"``),
metrics round robin.

A tenant holds the configuration's ``windows``; once a metric's tenant is
full the metric's next call starts a new tenant ``<metric>.<n>``, the next
period of the same deployment.  Each call is timed from the call until its
ack; once the window has closed every tenant loaded is checked over all its
acked windows.

``lead_in_calls`` (default 0): the loop's first calls, made on the served
service at the end of set-up.  The first calls after the warm-up's service
closes take longer, while the memory they allocate comes back fresh; a
loader in steady state made them long ago.  The window goes on from the
next call.
"""
from __future__ import annotations

import time

from generator import Panel, Request, resolve

# Whole tenants loaded on a throwaway service in set-up: the second is
# loaded with the first's programs, as every call of the window is.
WARM_TENANTS = 2


class Client:
    role = "ingest"

    def __init__(self, spec, config, data, seed, index):
        self.windows = int(config["windows"])
        self.per_call = resolve(spec["windows_per_call"], config)
        self.lead_in_calls = int(spec.get("lead_in_calls", 0))
        self.data = data
        self.seed = seed
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

    def warm(self, open_service, load) -> None:
        with open_service() as scratch:
            for period in range(WARM_TENANTS):
                for first in range(0, self.windows, self.per_call):
                    self._call(scratch, 0, period, first, self._last(first))

    def _acked(self, m: int, tenant: str, period: int, last: int) -> None:
        self.loaded[tenant] = Panel(tenant, m, 0, last, period * self.windows)
        self.acked[m] = last
        if last == self.windows - 1:
            self.period[m], self.acked[m] = period + 1, -1

    def lead_in(self, svc) -> None:
        for i in range(self.lead_in_calls):
            m = i % self.data.metrics
            period, first = self.period[m], self.acked[m] + 1
            last = self._last(first)
            self._acked(m, self._call(svc, m, period, first, last), period, last)

    def run(self, svc, t_end: float, annotate, stats) -> None:
        m = self.lead_in_calls % self.data.metrics
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
                self._acked(m, tenant, period, last)
            m = (m + 1) % self.data.metrics

    def check_panels(self) -> list[Panel]:
        return list(self.loaded.values())
