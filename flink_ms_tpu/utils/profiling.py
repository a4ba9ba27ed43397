"""Tracing and per-step timing.

The reference's only measurement tooling is per-query wall-clock millis in
the load harnesses (ALSPredictRandom.java:62,93-94 — reproduced by the
clients in ``flink_ms_tpu.client``); its platform metrics live in the Flink
web UI [dep].  The TPU-native framework adds the two instruments SURVEY.md §5
calls for: XLA profiler traces (viewable in TensorBoard/Perfetto) and
per-step host-side timing with percentile summaries.
"""

from __future__ import annotations

import contextlib
import json
import math
import time
from typing import Dict, List, Optional


@contextlib.contextmanager
def trace(trace_dir: Optional[str]):
    """JAX/XLA profiler trace of the enclosed block, written to `trace_dir`
    (no-op when None).  Captures device (TPU) and host activity."""
    if not trace_dir:
        yield
        return
    import jax

    with jax.profiler.trace(trace_dir):
        yield


class StepTimer:
    """Wall-clock timer for repeated steps with percentile reporting.

    Usage::

        timer = StepTimer("als_iter")
        for _ in range(iters):
            with timer:
                step()
        print(timer.summary())
    """

    def __init__(self, name: str):
        self.name = name
        self.durations_s: List[float] = []
        self._t0: Optional[float] = None

    def __enter__(self) -> "StepTimer":
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        assert self._t0 is not None
        self.durations_s.append(time.perf_counter() - self._t0)
        self._t0 = None

    def percentile(self, q: float) -> float:
        if not self.durations_s:
            return float("nan")
        xs = sorted(self.durations_s)
        # nearest-rank: smallest value with cumulative share >= q
        idx = max(math.ceil(q / 100.0 * len(xs)) - 1, 0)
        return xs[min(idx, len(xs) - 1)]

    def merge(self, other: "StepTimer") -> "StepTimer":
        """Absorb another timer's observations (combining per-worker
        timers into one distribution — percentiles over the merged sample
        are exact, unlike averaging per-worker percentiles).  Returns
        self; ``other`` is untouched."""
        self.durations_s.extend(other.durations_s)
        return self

    def to_histogram(self):
        """This timer's observations bucketed into the serving plane's
        shared latency ladder (``obs.metrics.LATENCY_BUCKETS_S``) — the
        bridge that makes a bench percentile and a scraped serving
        percentile estimates over the IDENTICAL bucketization."""
        from ..obs.metrics import Histogram

        return Histogram(self.name).fill(self.durations_s)

    def stats(self) -> Dict[str, float]:
        n = len(self.durations_s)
        total = sum(self.durations_s)
        return {
            "name": self.name,
            "steps": n,
            "total_s": total,
            "mean_s": total / n if n else float("nan"),
            "p50_s": self.percentile(50),
            "p90_s": self.percentile(90),
            "p99_s": self.percentile(99),
            "p999_s": self.percentile(99.9),
        }

    def summary(self) -> str:
        s = self.stats()
        return (
            f"[{self.name}] {s['steps']} steps, {s['total_s']:.3f}s total, "
            f"mean {s['mean_s'] * 1e3:.2f}ms, p50 {s['p50_s'] * 1e3:.2f}ms, "
            f"p99 {s['p99_s'] * 1e3:.2f}ms"
        )

    def write_json(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.stats(), f)
            f.write("\n")
