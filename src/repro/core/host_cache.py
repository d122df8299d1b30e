"""Pre-allocated, pre-pinned host staging cache (paper §V-A1, §V-C).

On the target TPU system this is committed host memory the device runtime can
DMA into; here it is a single pre-allocated byte buffer with a blocking
first-fit interval allocator. Pre-allocation removes per-checkpoint alloc
overheads; the blocking behaviour implements the paper's back-pressure rule —
"if the host memory reserved for checkpointing is full, the next checkpoint
request waits for previous tensors to get evicted after they are flushed".
"""

from __future__ import annotations

import threading
import time
from typing import List, Optional, Tuple

import numpy as np

from repro.analysis.locks import declares_lock
from repro.obs import trace as obs
from repro.obs.metrics import metrics as obs_metrics


class CacheFullError(RuntimeError):
    pass


class Reservation:
    """A byte range inside the cache, exposed as a zero-copy memoryview."""

    __slots__ = ("start", "nbytes", "_cache", "_released")

    def __init__(self, start: int, nbytes: int, cache: "HostCache"):
        self.start = start
        self.nbytes = nbytes
        self._cache = cache
        self._released = False

    @property
    def view(self) -> memoryview:
        return self._cache._buf_view[self.start:self.start + self.nbytes]

    def array(self, dtype, shape) -> np.ndarray:
        """Zero-copy ndarray view over this reservation."""
        return np.frombuffer(self.view, dtype=dtype).reshape(shape)

    def release(self) -> None:
        if not self._released:
            self._released = True
            self._cache._free(self)


# Innermost lock of the hierarchy: reserve() may block on back-pressure,
# so nothing else may be held while other threads need the allocator.
@declares_lock("host_cache.alloc", rank=70, attrs=("_lock", "_freed"))
class HostCache:
    """Blocking first-fit allocator over one pre-allocated pinned buffer."""

    def __init__(self, capacity_bytes: int):
        self.capacity = int(capacity_bytes)
        # The "pinned" pool. One allocation for the lifetime of the engine.
        self._buf = np.zeros(self.capacity, dtype=np.uint8)
        self._buf_view = memoryview(self._buf)
        self._lock = threading.Lock()
        self._freed = threading.Condition(self._lock)
        # Sorted list of allocated (start, end) intervals.
        self._allocated: List[Tuple[int, int]] = []
        self.peak_usage = 0
        self.total_reserved = 0  # lifetime bytes, for stats

    # -- internals -----------------------------------------------------------
    def _place(self, sizes: List[int]) -> Optional[List[int]]:
        """First-fit start offsets for all of ``sizes`` at once, or None."""
        taken = list(self._allocated)
        starts: List[int] = []
        for nbytes in sizes:
            prev_end, start = 0, None
            for s, e in taken:
                if s - prev_end >= nbytes:
                    start = prev_end
                    break
                prev_end = e
            if start is None:
                if self.capacity - prev_end < nbytes:
                    return None
                start = prev_end
            starts.append(start)
            taken.append((start, start + nbytes))
            taken.sort()
        return starts

    def _free(self, res: Reservation) -> None:
        with self._lock:
            self._allocated.remove((res.start, res.start + res.nbytes))
            self._freed.notify_all()
            used = sum(e - s for s, e in self._allocated)
        obs_metrics.set_gauge("host_cache.used_bytes", used)
        if obs.enabled():
            obs.counter("host_cache.used_bytes", used)

    # -- public --------------------------------------------------------------
    def used_bytes(self) -> int:
        with self._lock:
            return sum(e - s for s, e in self._allocated)

    def reserve(self, nbytes: int, timeout: Optional[float] = None
                ) -> Reservation:
        """Reserve ``nbytes``; blocks until space frees up (back-pressure)."""
        return self.reserve_many([nbytes], timeout=timeout)[0]

    def reserve_many(self, sizes: List[int], timeout: Optional[float] = None
                     ) -> List[Reservation]:
        """Reserve every one of ``sizes`` together, or wait until all fit.

        All-or-nothing: a save whose buffers are half placed would hold
        fragments of the cache that the rest of it can never fit between,
        so two saves that each fit the cache alone would wait on each
        other forever. Each reservation is still released on its own."""
        sizes = [int(n) for n in sizes]
        total = sum(sizes)
        if total > self.capacity:
            raise CacheFullError(
                f"request of {total} B exceeds cache capacity "
                f"{self.capacity} B")
        t0 = time.perf_counter()
        with self._lock:
            while True:
                starts = self._place(sizes)
                if starts is not None:
                    break
                if not self._freed.wait(timeout=timeout):
                    raise CacheFullError(
                        f"timed out waiting for {total} B of cache space")
            self._allocated.extend((s, s + n) for s, n in zip(starts, sizes))
            self._allocated.sort()
            self.total_reserved += total
            used = sum(e - s for s, e in self._allocated)
            self.peak_usage = max(self.peak_usage, used)
        # Observability happens after the allocator lock is released (the
        # obs locks rank above host_cache.alloc, but no reason to hold it).
        obs_metrics.set_gauge("host_cache.used_bytes", used)
        if obs.enabled():
            obs.add_span("host_cache.reserve", t0, time.perf_counter(),
                         bytes=total)
            obs.counter("host_cache.used_bytes", used)
        return [Reservation(s, n, self) for s, n in zip(starts, sizes)]
