"""Interval, idle-share and rate arithmetic of the trace reduction."""

import math

import pytest

from chipbench import traceutil as tu


def test_merge_and_union_overlapping_and_touching():
    iv = [(5.0, 6.0), (0.0, 2.0), (1.0, 3.0), (3.0, 4.0), (7.0, 7.0)]
    assert tu.merge(iv) == [(0.0, 4.0), (5.0, 6.0)]
    assert tu.union_length(iv) == 5.0


def test_clip_gaps_and_idle_share():
    busy = [(1.0, 2.0), (1.5, 3.0), (6.0, 12.0)]
    assert tu.clip(busy, 0.0, 10.0) == [(1.0, 2.0), (1.5, 3.0), (6.0, 10.0)]
    assert tu.gaps(busy, 0.0, 10.0) == [(0.0, 1.0), (3.0, 6.0)]
    assert math.isclose(tu.idle_share(busy, 0.0, 10.0), 40.0)
    assert tu.idle_share([], 0.0, 10.0) == 100.0


def test_rate_counts_overlapping_lanes_once():
    # two lanes writing 1 GB each over the same second: 2 GB/s, not 1
    assert math.isclose(tu.rate(2e9, [(0.0, 1.0), (0.0, 1.0)]), 2.0)
    assert math.isclose(tu.rate(3e9, [(0.0, 1.0), (2.0, 3.0)]), 1.5)
    assert tu.rate(0, [(0.0, 1.0)]) is None
    assert tu.rate(1e9, []) is None


def test_span_rate_skips_spans_before_the_window():
    spans = [{"name": "flush", "t0": 0.0, "t1": 1.0, "args": {"bytes": 9e9}},
             {"name": "flush", "t0": 5.0, "t1": 6.0, "args": {"bytes": 2e9}},
             {"name": "d2h.stage", "t0": 5.0, "t1": 7.0,
              "args": {"bytes": 1e9}}]
    assert math.isclose(tu.span_rate(spans, "flush", 4.0), 2.0)


def test_top_ops_and_gap_labels():
    events = [("fusion.1", 0.0, 1.0), ("conv", 1.0, 4.0),
              ("fusion.1", 4.0, 5.0), ("late", 9.0, 11.0)]
    assert tu.top_ops(events, 0.0, 10.0) == [["conv", 3.0],
                                             ["fusion.1", 2.0],
                                             ["late", 1.0]]
    idle = tu.gaps([(a, b) for _n, a, b in events], 0.0, 10.0)
    assert idle == [(5.0, 9.0)]
    host = [("train.iteration", 0.0, 10.0), ("ckpt.capture_barrier", 5.0,
                                             8.0)]
    # equal overlap goes to the innermost host span
    assert tu.label_gaps([(5.0, 8.0)], host) == [["ckpt.capture_barrier",
                                                  3.0]]
    assert tu.label_gaps([(20.0, 21.0)], host) == [["other", 1.0]]


@pytest.mark.parametrize("hlo,want", [
    ("%fusion.356 = bf16[4,4096]{1,0:T(8,128)(2,1)} fusion(f32[4] %a), "
     "kind=kOutput", "%fusion.356 fusion"),
    ("%while.111 = (s32[]{:T(128)}, f32[4,9]{1,0:T(8,128)}) while((s32[]"
     ") %tuple.200), condition=%c", "%while.111 while"),
    ("%custom-call.7 = (u32[1048576]{0}, s32[1,1]{1,0}) custom-call(u32["
     "1048576]{0} %p0), custom_call_target=\"tpu_custom_call\"",
     "%custom-call.7 custom-call"),
    ("96", "96"),
])
def test_op_names_from_hlo_text(hlo, want):
    assert tu.op_name(hlo) == want


def test_module_names_and_events_within_modules():
    assert tu.module_name("jit_grad_step(7673355781137992679)") \
        == "jit_grad_step"
    modules = [("jit_fused_xor_checksum", 1.0, 2.0), ("jit_grad_step", 3.0,
                                                      9.0)]
    ops = [("%custom-call.1 custom-call", 1.2, 1.5), ("%fusion.2 fusion",
                                                       1.5, 1.9),
           ("%custom-call.9 custom-call", 4.0, 5.0)]
    got = tu.within(ops, modules, "jit_fused_xor_checksum")
    assert got == ops[:2]
