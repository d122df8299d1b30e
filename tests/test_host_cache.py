"""Pinned host-cache allocator: blocking back-pressure + interval invariants."""

import threading
import time

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.host_cache import CacheFullError, HostCache


def test_reserve_release_reuse():
    c = HostCache(1000)
    r1 = c.reserve(400)
    r2 = c.reserve(400)
    assert c.used_bytes() == 800
    with pytest.raises(CacheFullError):
        c.reserve(400, timeout=0.05)
    r1.release()
    r3 = c.reserve(400)  # reuses r1's interval
    assert r3.start == r1.start
    r2.release(); r3.release()
    assert c.used_bytes() == 0


def test_zero_copy_view():
    c = HostCache(1 << 16)
    r = c.reserve(256)
    arr = r.array(np.float32, (64,))
    arr[:] = np.arange(64, dtype=np.float32)
    # the same bytes are visible through a second view of the reservation
    again = np.frombuffer(r.view, dtype=np.float32)
    np.testing.assert_array_equal(again, np.arange(64, dtype=np.float32))


def test_oversized_request_raises():
    c = HostCache(100)
    with pytest.raises(CacheFullError, match="exceeds"):
        c.reserve(101)


def test_blocking_backpressure_unblocks():
    """A reserve that must wait is released when space frees (paper §V-A2:
    'the next checkpoint request needs to wait for previous tensors to get
    evicted')."""
    c = HostCache(100)
    r1 = c.reserve(80)
    got = {}

    def waiter():
        got["r"] = c.reserve(50, timeout=5)

    t = threading.Thread(target=waiter)
    t.start()
    time.sleep(0.05)
    assert "r" not in got          # still blocked
    r1.release()
    t.join(timeout=5)
    assert "r" in got
    got["r"].release()


def test_peak_usage_tracking():
    c = HostCache(1000)
    rs = [c.reserve(200) for _ in range(4)]
    assert c.peak_usage == 800
    for r in rs:
        r.release()
    c.reserve(100).release()
    assert c.peak_usage == 800  # historical peak


def test_parallel_reserve_release_stress():
    """Many threads hammering reserve/hold/release concurrently: no
    overlap, no lost frees, no deadlock (the flush pool + stage lane +
    producer lanes all hit the allocator at once in the real engine)."""
    c = HostCache(1 << 16)
    errors = []
    barrier = threading.Barrier(8)

    def worker(seed: int) -> None:
        rng = np.random.default_rng(seed)
        barrier.wait()
        try:
            for _ in range(200):
                r = c.reserve(int(rng.integers(1, 2048)), timeout=10)
                arr = r.array(np.uint8, (r.nbytes,))
                arr[:] = seed % 251  # touch the memory through the view
                if int(arr[0]) != seed % 251:
                    raise AssertionError("reservation bytes not visible")
                r.release()
        except BaseException as exc:  # noqa: BLE001
            errors.append(exc)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not errors, errors
    assert c.used_bytes() == 0
    assert c.peak_usage <= c.capacity


def test_fragmentation_after_interleaved_frees():
    """Interleaved frees leave non-adjacent gaps: a request larger than
    every gap must block even though the *total* free space would fit it,
    and succeed once the middle allocation frees (gaps coalesce because
    the free list is derived from the live intervals)."""
    c = HostCache(950)
    r1 = c.reserve(300)   # [0, 300)
    r2 = c.reserve(300)   # [300, 600)
    r3 = c.reserve(300)   # [600, 900)
    r1.release()
    r3.release()
    # free = [0,300) + [600,950): 650 B total, largest gap 350 B
    assert c.used_bytes() == 300
    with pytest.raises(CacheFullError):
        c.reserve(380, timeout=0.05)
    small = c.reserve(350)            # fits the tail gap exactly
    assert small.start == 600
    small.release()
    r2.release()                      # now one contiguous 950 B gap
    big = c.reserve(380)
    assert big.start == 0
    big.release()
    assert c.used_bytes() == 0


def test_backpressure_wakeup_ordering():
    """When space frees, exactly the waiters that fit proceed; the rest
    keep waiting until more space frees (notify_all + re-check loop)."""
    c = HostCache(100)
    r = c.reserve(100)
    satisfied = []
    lock = threading.Lock()

    def waiter(idx: int) -> None:
        got = c.reserve(60, timeout=10)
        with lock:
            satisfied.append((idx, got))

    threads = [threading.Thread(target=waiter, args=(i,)) for i in range(2)]
    for t in threads:
        t.start()
    time.sleep(0.1)
    assert not satisfied                  # both blocked behind r
    r.release()
    time.sleep(0.3)
    with lock:
        assert len(satisfied) == 1        # only one 60 B request fits
        _idx, first = satisfied[0]
    first.release()
    for t in threads:
        t.join(timeout=10)
    assert len(satisfied) == 2            # the second woke after the free
    satisfied[1][1].release()
    assert c.used_bytes() == 0


@settings(max_examples=50, deadline=None)
@given(st.lists(st.tuples(st.integers(1, 300), st.booleans()),
                min_size=1, max_size=40))
def test_property_intervals_never_overlap(ops):
    """Random reserve/release sequences keep allocated intervals disjoint."""
    c = HostCache(2048)
    live = []
    for size, release_one in ops:
        if release_one and live:
            live.pop(np.random.default_rng(size).integers(len(live))).release()
        else:
            try:
                live.append(c.reserve(size, timeout=0.01))
            except CacheFullError:
                if live:
                    live.pop(0).release()
        spans = sorted((r.start, r.start + r.nbytes) for r in live)
        for (s1, e1), (s2, _e2) in zip(spans, spans[1:]):
            assert e1 <= s2
        assert all(0 <= s and e <= 2048 for s, e in spans)


def test_reserve_many_places_all_or_nothing():
    """A save's buffers are placed together or not at all. Placed one by
    one, the second save below would take the 2-byte hole the first left,
    and its 8-byte buffer would then never fit between the survivors."""
    c = HostCache(10)
    first = c.reserve_many([4, 2, 4])
    first[1].release()
    done = []
    t = threading.Thread(target=lambda: done.append(c.reserve_many([2, 8])))
    t.start()
    time.sleep(0.1)
    assert not done and c.used_bytes() == 8  # waits, holds nothing
    first[0].release()
    first[2].release()
    t.join(timeout=5)
    assert not t.is_alive() and done
    assert [(r.start, r.nbytes) for r in done[0]] == [(0, 2), (2, 8)]
    for r in done[0]:
        r.release()
    assert c.used_bytes() == 0
