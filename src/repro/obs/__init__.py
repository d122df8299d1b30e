"""ckpttrace: zero-dependency tracing + metrics for the checkpoint lifecycle.

Two halves, both stdlib-only:

* :mod:`repro.obs.trace` — thread-aware spans recorded into per-thread ring
  buffers, exportable as Chrome trace-event JSON (loadable in Perfetto /
  ``chrome://tracing``). Off by default; ``span(...)`` is a near-free no-op
  when disabled. Its module docstring lists the spans with their nesting:
  ``train.*``, ``ckpt.capture_barrier``, ``save.prologue``,
  ``host_cache.reserve``, ``d2h.stage``/``d2h.wait``/``d2h.copy``,
  ``produce.file``, ``encode.*``, ``kernel.h2d``/``kernel.call``/
  ``kernel.d2h``, ``flush``, ``file.finalize``, ``manifest.write``,
  ``commit``, ``gc``, ``restore.*`` (``restore.h2d`` among them), and the
  multi-rank and fleet spans.
* :mod:`repro.obs.metrics` — a process-wide registry of counters / gauges /
  histograms.

Both modules pass the full ckptlint rule set (their internal locks are
declared at ranks 80/82 — *above* every runtime lock, so recording from any
instrumented seam is rank-legal — and no I/O happens under them).
"""

from .trace import (Tracer, add_span, counter, disable, enable, enabled,
                    flow_id, get_tracer, instant, span, tracing)
from .metrics import MetricsRegistry, metrics

__all__ = [
    "Tracer", "add_span", "counter", "disable", "enable", "enabled",
    "flow_id", "get_tracer", "instant", "span", "tracing",
    "MetricsRegistry", "metrics",
]
