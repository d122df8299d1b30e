"""Reduction from spans and a device trace to numbers.

Intervals are ``(start, end)`` pairs in seconds on the host's
``time.perf_counter`` clock. The device trace is mapped onto that clock
through one host annotation (``WINDOW_ANNOTATION``) whose start the
benchmark also reads on the host.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]
WINDOW_ANNOTATION = "chipbench.window"
_CALL = re.compile(r"(?<= )([a-z][a-z0-9_.-]*)\(")   # " fusion(" after the type


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def union_length(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in merge(intervals))


def clip(intervals: Iterable[Interval], t0: float, t1: float
         ) -> List[Interval]:
    return [(max(a, t0), min(b, t1)) for a, b in intervals
            if b > t0 and a < t1]


def gaps(intervals: Iterable[Interval], t0: float, t1: float
         ) -> List[Interval]:
    """The stretches of [t0, t1] that no interval covers."""
    out, cur = [], t0
    for a, b in merge(clip(intervals, t0, t1)):
        if a > cur:
            out.append((cur, a))
        cur = max(cur, b)
    if cur < t1:
        out.append((cur, t1))
    return out


def rate(nbytes: float, intervals: Iterable[Interval]) -> Optional[float]:
    """Bytes over the time any of the intervals was open, in GB/s."""
    busy = union_length(intervals)
    if busy <= 0 or nbytes <= 0:
        return None
    return nbytes / busy / 1e9


def idle_share(busy: Iterable[Interval], t0: float, t1: float) -> float:
    """Percent of [t0, t1] in which no device operation ran."""
    return 100.0 * (1.0 - union_length(clip(busy, t0, t1)) / (t1 - t0))


def top_ops(events: Sequence[Tuple[str, float, float]], t0: float,
            t1: float, n: int = 10) -> List[List]:
    """Device time by operation name within [t0, t1], largest first."""
    total: Dict[str, float] = {}
    for name, a, b in events:
        for ca, cb in clip([(a, b)], t0, t1):
            total[name] = total.get(name, 0.0) + (cb - ca)
    return [[k, v] for k, v in sorted(total.items(), key=lambda kv: -kv[1])
            [:n]]


def label_gaps(idle: Sequence[Interval],
               host: Sequence[Tuple[str, float, float]], n: int = 10
               ) -> List[List]:
    """Idle time by what the host was doing: each gap goes to the shortest
    host interval that covers half of it or more, else to the one that
    overlaps it most, else to ``other``."""
    total: Dict[str, float] = {}
    for a, b in idle:
        over = [(min(b, hb) - max(a, ha), hb - ha, name)
                for name, ha, hb in host if min(b, hb) > max(a, ha)]
        covering = [o for o in over if o[0] >= 0.5 * (b - a)]
        if covering:
            best = min(covering, key=lambda o: o[1])[2]
        else:
            best = max(over)[2] if over else "other"
        total[best] = total.get(best, 0.0) + (b - a)
    return [[k, v] for k, v in sorted(total.items(), key=lambda kv: -kv[1])
            [:n]]


# ------------------------------------------------------------ device trace
def find_xplane(trace_dir: str) -> Optional[str]:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return files[-1] if files else None


def read_xplane(path: str) -> Dict[str, object]:
    """Device events and the window annotation from a profiler trace, in
    the trace's own seconds: ``{"ops": {plane: [(name, t0, t1)]},
    "modules": {plane: [...]}, "window": (t0, t1) or None}``. ``ops`` are
    the "XLA Ops" line of each TPU plane, named by their HLO instruction
    (``%fusion.3``); ``modules`` the "XLA Modules" line, named by their jit
    (``jit_grad_step``)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops: Dict[str, List[Tuple[str, float, float]]] = {}
    modules: Dict[str, List[Tuple[str, float, float]]] = {}
    window = None
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    out, short = ops.setdefault(plane.name, []), op_name
                elif line.name == "XLA Modules":
                    out, short = modules.setdefault(plane.name, []), \
                        module_name
                else:
                    continue
                for ev in line.events:
                    t0 = ev.start_ns / 1e9
                    out.append((short(ev.name), t0,
                                t0 + ev.duration_ns / 1e9))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW_ANNOTATION:
                        t0 = ev.start_ns / 1e9
                        window = (t0, t0 + ev.duration_ns / 1e9)
    return {"ops": ops, "modules": modules, "window": window}


def op_name(hlo: str) -> str:
    """``%custom-call.7 = (u32[...]) custom-call(...), ...`` -> the name and
    what it calls: ``%custom-call.7 custom-call``."""
    name, _, rhs = hlo.partition(" = ")
    call = _CALL.search(rhs)
    return f"{name} {call.group(1)}" if call else name


def module_name(name: str) -> str:
    """``jit_grad_step(7673355781137992679)`` -> ``jit_grad_step``."""
    return name.split("(", 1)[0]


def within(events: Sequence[Tuple[str, float, float]],
           outer: Sequence[Tuple[str, float, float]], prefix: str
           ) -> List[Tuple[str, float, float]]:
    """The events that lie inside an ``outer`` event named ``prefix``."""
    spans = merge((a, b) for n, a, b in outer if n.startswith(prefix))
    out = []
    for ev in events:
        for a, b in spans:
            if a <= ev[1] and ev[2] <= b:
                out.append(ev)
                break
    return out


def to_host_clock(events: Sequence[Tuple[str, float, float]],
                  trace_window_start: float, host_window_start: float
                  ) -> List[Tuple[str, float, float]]:
    off = trace_window_start - host_window_start
    return [(n, a - off, b - off) for n, a, b in events]


# ------------------------------------------------------------ program spans
def spans_from(spans: Sequence[dict], name: str, t0: float) -> List[dict]:
    """Spans called ``name`` that began at or after ``t0``."""
    return [s for s in spans if s["name"] == name and s["t0"] >= t0]


def span_rate(spans: Sequence[dict], name: str, t0: float
              ) -> Optional[float]:
    """Bytes of the ``name`` spans begun after ``t0`` over the union of
    their intervals, in GB/s."""
    chosen = spans_from(spans, name, t0)
    return rate(sum(s["args"].get("bytes", 0) for s in chosen),
                [(s["t0"], s["t1"]) for s in chosen])
