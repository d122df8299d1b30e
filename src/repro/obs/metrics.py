"""Process-wide metrics registry: counters, gauges and histograms.

Everything is updated under one declared lock — ``obs.metrics`` at rank 82,
above every runtime lock — and ``snapshot()`` returns plain data, so
recording is legal from any instrumented seam and never does I/O. Two
entries are written: the counter ``engine.bytes_encode_read``, the encoded
routes' single read of the staged bytes (``tests/test_fused_kernels.py``,
``benchmarks/fig_encode.py``), and the gauge ``host_cache.used_bytes``,
which the host cache also records as a trace counter.
"""

from __future__ import annotations

import threading
from typing import Any, Dict, List

from repro.analysis.locks import declares_lock

__all__ = ["MetricsRegistry", "metrics"]

_HIST_SAMPLE_CAP = 512  # bounded reservoir per histogram


class _Hist:
    __slots__ = ("count", "total", "vmin", "vmax", "samples")

    def __init__(self):
        self.count = 0
        self.total = 0.0
        self.vmin = float("inf")
        self.vmax = float("-inf")
        self.samples: List[float] = []

    def observe(self, v: float) -> None:
        self.count += 1
        self.total += v
        self.vmin = min(self.vmin, v)
        self.vmax = max(self.vmax, v)
        if len(self.samples) < _HIST_SAMPLE_CAP:
            self.samples.append(v)

    def snapshot(self) -> Dict[str, float]:
        return {"count": self.count, "sum": self.total,
                "min": self.vmin if self.count else 0.0,
                "max": self.vmax if self.count else 0.0,
                "mean": (self.total / self.count) if self.count else 0.0}


@declares_lock("obs.metrics", rank=82, attrs=("_lock",))
class MetricsRegistry:
    """Thread-safe counters / gauges / histograms with dict snapshots."""

    def __init__(self):
        self._lock = threading.Lock()
        self._counters: Dict[str, float] = {}
        self._gauges: Dict[str, float] = {}
        self._hists: Dict[str, _Hist] = {}

    def inc(self, name: str, value: float = 1.0) -> None:
        with self._lock:
            self._counters[name] = self._counters.get(name, 0.0) + value

    def set_gauge(self, name: str, value: float) -> None:
        with self._lock:
            self._gauges[name] = value

    def observe(self, name: str, value: float) -> None:
        with self._lock:
            h = self._hists.get(name)
            if h is None:
                h = self._hists[name] = _Hist()
            h.observe(value)

    def get_counter(self, name: str) -> float:
        with self._lock:
            return self._counters.get(name, 0.0)

    def snapshot(self) -> Dict[str, Any]:
        """Plain-data view: {"counters": {...}, "gauges": {...},
        "histograms": {name: {count,sum,min,max,mean}}}."""
        with self._lock:
            return {
                "counters": dict(self._counters),
                "gauges": dict(self._gauges),
                "histograms": {k: h.snapshot()
                               for k, h in self._hists.items()},
            }

    def reset(self) -> None:
        with self._lock:
            self._counters.clear()
            self._gauges.clear()
            self._hists.clear()


#: The process-wide registry every instrumented seam records into.
metrics = MetricsRegistry()
