"""The readers of the spans inside the capture, the training iteration, the
delta encode's kernel round trip and the restore's host-to-device copy, on
hand-built spans and device events: each reads nothing where the program
records no such span, and the right number where it does."""

import math
from types import SimpleNamespace

import pytest

from chipbench import spec, traceutil


def span(name, t0, t1, lane="MainThread", **args):
    return {"name": name, "t0": t0, "t1": t1, "lane": lane, "args": args}


def ctx(spans=(), device=(), saves=(), ops=(), window=(10.0, 20.0)):
    return SimpleNamespace(spans=list(spans), device=list(device),
                           saves=list(saves), ops=list(ops), window=window,
                           traceutil=traceutil)


def read(name, c):
    return spec.load_reader(name)(c)


# ------------------------------------------------------------------ capture
STAGE = "dsllm-stage"
CAPTURE = [
    # two saves' barriers in the window, one before it
    span("ckpt.capture_barrier", 5.0, 6.0),
    span("ckpt.capture_barrier", 11.0, 13.0),
    span("ckpt.capture_barrier", 15.0, 16.0),
    span("save.prologue", 10.5, 10.6),
    # the staging lane: a wait straddling the first barrier's start, then
    # a copy and a wait inside it, a wait between the barriers and a copy
    # straddling the second one's start; a wait under the early barrier
    span("d2h.wait", 10.0, 11.5, STAGE), span("d2h.copy", 11.5, 12.0, STAGE),
    span("d2h.wait", 12.0, 12.5, STAGE), span("d2h.copy", 12.5, 12.8, STAGE),
    span("d2h.wait", 13.5, 14.0, STAGE), span("d2h.copy", 14.0, 15.5, STAGE),
    span("d2h.wait", 5.0, 6.0, STAGE),
]
SAVES = [{"step": 23}, {"step": 46}]


@pytest.mark.parametrize("name,want_ms", [
    ("capture_on_transfer_ms_per_save", (0.5 + 0.5) * 1000 / 2),
    ("capture_on_copy_ms_per_save", (0.5 + 0.3 + 0.5) * 1000 / 2)])
def test_capture_readers_split_the_barrier(name, want_ms):
    assert math.isclose(read(name, ctx(CAPTURE, saves=SAVES)), want_ms)


def test_capture_readers_stay_within_the_stall():
    c = ctx(CAPTURE, saves=SAVES)
    parts = sum(read(n, c) for n in ("capture_on_transfer_ms_per_save",
                                     "capture_on_copy_ms_per_save"))
    assert parts <= read("capture_stall_ms_per_save", c)


@pytest.mark.parametrize("name", ["capture_on_transfer_ms_per_save",
                                  "capture_on_copy_ms_per_save"])
def test_capture_readers_read_nothing_without_their_spans(name):
    # the parent program: one d2h.stage span, no children
    older = [s for s in CAPTURE if not s["name"].startswith("d2h.")] + \
        [span("d2h.stage", 10.0, 12.0, STAGE, bytes=1)]
    assert read(name, ctx(older, saves=SAVES)) is None
    assert read(name, ctx(CAPTURE, saves=[])) is None


# ----------------------------------------------------------- training step
LOOP = [
    span("train.iteration", 10.0, 12.0),
    span("train.batch", 10.0, 10.4), span("train.grad_dispatch", 10.4, 10.5),
    span("ckpt.capture_barrier", 10.5, 11.5),
    span("train.update_dispatch", 11.5, 11.6),
    span("train.loss_sync", 11.6, 12.0),
    span("train.iteration", 12.0, 13.0),
    span("train.batch", 12.0, 12.6), span("train.grad_dispatch", 12.6, 12.7),
    span("train.update_dispatch", 12.7, 12.8),
    span("train.loss_sync", 12.8, 13.0),
    span("train.batch", 9.0, 9.5),               # before the window
    span("train.batch", 10.0, 10.9, "dsllm-producer-0"),  # not the trainer
]
DEVICE = [("jit_grad_step", 10.2, 10.35), ("jit_grad_step", 10.45, 10.5),
          ("jit_update_step", 11.6, 11.8), ("jit_grad_step", 12.65, 12.9),
          ("jit_grad_step", 13.0, 19.0)]


def test_host_gap_share_takes_the_gaps_of_the_loop_spans():
    # gaps: 10-10.2 (batch, nested in the iteration), 10.35-10.45 (half
    # under batch, half under the shorter grad_dispatch), 10.5-11.6 (the
    # barrier: not the loop's), 11.8-12.65 (straddling two iterations,
    # most of it under the next batch), 12.9-13.0 (loss_sync), 19-20
    # (nothing: other)
    got = read("host_gap_share.train", ctx(LOOP, DEVICE))
    want = 100.0 * (0.2 + 0.1 + 0.85 + 0.1) / 10.0
    assert math.isclose(got, want)


def test_host_gap_share_reads_nothing_without_the_loop_spans():
    older = [s for s in LOOP if s["name"] in ("train.iteration",
                                              "ckpt.capture_barrier")]
    assert read("host_gap_share.train", ctx(older, DEVICE)) is None
    assert read("host_gap_share.train", ctx(LOOP, [])) is None


# ---------------------------------------------------------------- kernels
P0, P1 = "dsllm-producer-0", "dsllm-producer-1"
ENCODE = [
    span("encode.delta", 10.0, 12.0, P0, bytes=4),
    span("kernel.h2d", 10.5, 10.7, P0, bytes=4),
    span("kernel.call", 10.7, 10.8, P0, bytes=4),
    span("kernel.d2h", 10.8, 11.0, P0, bytes=4),
    span("encode.delta.base", 11.0, 11.5, P0, bytes=4),
    # a second lane, its encode overlapping the first in time
    span("encode.delta", 11.0, 13.0, P1, bytes=4),
    span("kernel.call", 11.0, 12.0, P1, bytes=4),
    # round trips outside any window encode: a chain replay, an encode
    # begun before the window, one on another lane
    span("kernel.call", 14.0, 15.0, P0, bytes=4),
    span("encode.delta", 5.0, 6.0, P0, bytes=4),
    span("kernel.call", 5.0, 6.0, P0, bytes=4),
    span("kernel.d2h", 12.5, 12.6, P0, bytes=4),
]


def test_delta_roundtrip_share_counts_the_children_of_window_encodes():
    got = read("delta_roundtrip_share", ctx(ENCODE))
    # union of the encodes 10-13 (3 s); round trips 10.5-11 and 11-12
    assert math.isclose(got, 100.0 * 1.5 / 3.0)
    assert 0.0 <= got <= 100.0


def test_delta_roundtrip_share_reads_nothing_without_round_trips():
    older = [s for s in ENCODE if not s["name"].startswith("kernel.")]
    assert read("delta_roundtrip_share", ctx(older)) is None
    assert read("delta_roundtrip_share", ctx([])) is None


# ---------------------------------------------------------------- restore
OPS = [{"op": "resume", "t0": 10.0, "t1": 14.0, "bytes": 8e9},
       {"op": "serve_load", "t0": 15.0, "t1": 17.0, "bytes": 2e9}]
RESTORE = [
    span("restore.read", 10.1, 12.0),
    span("restore.h2d", 12.0, 13.0, bytes=3e9),
    span("restore.assemble", 12.0, 12.1),
    span("restore.h2d", 13.5, 14.5, bytes=1e9),   # begun inside, ends after
    span("restore.h2d", 15.5, 16.0, bytes=1e9),
    span("restore.h2d", 8.0, 9.0, bytes=9e9),     # outside every op
]


@pytest.mark.parametrize("name,want", [
    ("restore_h2d_GBps.resume", 4e9 / 2.0 / 1e9),
    ("restore_h2d_GBps.serve", 1e9 / 0.5 / 1e9)])
def test_restore_h2d_rate_inside_its_operations(name, want):
    assert math.isclose(read(name, ctx(RESTORE, ops=OPS)), want)


@pytest.mark.parametrize("name", ["restore_h2d_GBps.resume",
                                  "restore_h2d_GBps.serve"])
def test_restore_h2d_reads_nothing_without_its_spans(name):
    older = [s for s in RESTORE if s["name"] != "restore.h2d"]
    assert read(name, ctx(older, ops=OPS)) is None
    assert read(name, ctx(RESTORE, ops=[])) is None
