"""Metrics: named timing/throughput counters for the driver loop.

Reference: BigDL `optim/Metrics.scala:31` — named counters backed by Spark
accumulators (`set(..., sc)` :65), pretty-printed in the driver log
(`summary` :103, used at DistriOptimizer.scala:298).

Host-side counters; distributed aggregation is unnecessary because the compiled
step is globally synchronous (there is nothing per-executor to merge).
"""

from __future__ import annotations

import time
from collections import defaultdict
from contextlib import contextmanager

__all__ = ["Metrics"]


class Metrics:
    def __init__(self):
        self._sums = defaultdict(float)
        self._counts = defaultdict(int)

    def set(self, name: str, value: float):
        self._sums[name] = value
        self._counts[name] = 1

    def add(self, name: str, value: float):
        self._sums[name] += value
        self._counts[name] += 1

    @contextmanager
    def time(self, name: str):
        t0 = time.perf_counter()
        yield
        self.add(name, time.perf_counter() - t0)

    def get(self, name: str):
        return self._sums[name], self._counts[name]

    def mean(self, name: str) -> float:
        c = self._counts[name]
        return self._sums[name] / c if c else 0.0

    def snapshot(self) -> dict:
        """All counters as ``{name: {mean, count, total}}`` — ONE exportable
        source for the epoch log, tool records, and telemetry consumers
        (replaces the ad-hoc per-caller counter paths)."""
        return {k: {"mean": self.mean(k), "count": self._counts[k],
                    "total": self._sums[k]} for k in sorted(self._sums)}

    def summary(self, unit_scale: float = 1.0) -> str:
        """Driver-log pretty-print: name, mean, count, total per counter
        (Metrics.scala:103 role, printed at DistriOptimizer.scala:298)."""
        parts = [f"{k}: mean {self.mean(k) * unit_scale:.6g} "
                 f"(count {self._counts[k]}, "
                 f"total {self._sums[k] * unit_scale:.6g})"
                 for k in sorted(self._sums)]
        return "[" + ", ".join(parts) + "]"

    def reset(self):
        self._sums.clear()
        self._counts.clear()
