"""ckpttrace suite: tracer semantics, Chrome-JSON schema, ring bounds,
the <1%-when-disabled overhead budget, the multi-rank lane/commit
ordering, and the metrics registry."""

import json
import threading
import time

import numpy as np
import pytest

from repro.core import (CheckpointManager, CheckpointPolicy, DeltaPolicy,
                        DistPolicy, EnginePolicy, StoragePolicy)
from repro.obs import trace
from repro.obs.metrics import MetricsRegistry


@pytest.fixture(autouse=True)
def _clean_tracer():
    """Tracing is process-global state: never leak it across tests."""
    trace.disable()
    yield
    trace.disable()


# ---------------------------------------------------------------- recording
def test_span_nesting_and_thread_attribution():
    t = trace.enable()
    with trace.span("outer", step=1):
        with trace.span("inner"):
            time.sleep(0.001)

    def worker():
        with trace.span("in-thread"):
            pass

    th = threading.Thread(target=worker, name="obs-test-worker")
    th.start()
    th.join()
    spans = {s["name"]: s for s in t.spans()}
    assert set(spans) == {"outer", "inner", "in-thread"}
    outer, inner = spans["outer"], spans["inner"]
    assert outer["t0"] <= inner["t0"] and inner["t1"] <= outer["t1"]
    assert outer["args"] == {"step": 1}
    # lane defaults to the recording thread's name
    assert spans["in-thread"]["lane"] == "obs-test-worker"
    assert outer["lane"] == threading.current_thread().name
    assert spans["in-thread"]["tid"] != outer["tid"]


def test_disabled_recording_is_a_silent_noop():
    assert not trace.enabled()
    with trace.span("x", bytes=1):
        pass
    trace.add_span("y", 0.0, 1.0)
    trace.instant("z")
    trace.counter("c", 3)
    t = trace.enable()
    assert t.events() == []


def test_span_name_prefix_filter():
    t = trace.enable()
    trace.add_span("encode.delta", 0.0, 1.0)
    trace.add_span("encode.compress", 0.0, 1.0)
    trace.add_span("encoder", 0.0, 1.0)     # prefix must not match this
    assert {s["name"] for s in t.spans("encode")} == \
        {"encode.delta", "encode.compress"}


def test_tracing_ctx_restores_outer_tracer():
    outer = trace.enable()
    with trace.tracing() as inner:
        assert trace.get_tracer() is inner
        trace.add_span("inner-only", 0.0, 1.0)
    assert trace.get_tracer() is outer
    assert outer.spans() == []


def test_ring_wraparound_keeps_newest_and_counts_drops():
    t = trace.enable(capacity_per_thread=8)
    for i in range(20):
        trace.add_span(f"s{i:02d}", float(i), float(i) + 0.5)
    assert t.dropped() == 12
    names = [s["name"] for s in t.spans()]
    assert names == [f"s{i:02d}" for i in range(12, 20)]  # newest survive
    assert t.to_chrome()["otherData"]["dropped_events"] == 12


# ------------------------------------------------------------ Chrome export
def test_chrome_json_schema(tmp_path):
    t = trace.enable()
    flow = trace.flow_id("save", 3)
    trace.instant("save.request", flow=flow, flow_phase="start", step=3)
    with trace.span("d2h.stage", flow=flow, bytes=42):
        pass
    trace.add_span("flush", 0.5, 0.9, lane="rank00000-flush-0", flow=flow)
    trace.add_span("commit", 1.0, 1.1, flow=flow, flow_phase="end")
    trace.counter("host_cache.used_bytes", 1 << 20)
    out = tmp_path / "trace.json"
    trace.disable().export(str(out))
    doc = json.loads(out.read_text())

    assert set(doc) >= {"traceEvents", "displayTimeUnit"}
    events = doc["traceEvents"]
    phases = {e["ph"] for e in events}
    assert {"M", "X", "i", "C"} <= phases
    for e in events:
        assert {"name", "ph", "pid", "tid"} <= set(e)
        if e["ph"] in ("X", "i", "C", "s", "t", "f"):
            assert isinstance(e["ts"], (int, float)) and e["ts"] >= 0
        if e["ph"] == "X":
            assert isinstance(e["dur"], (int, float)) and e["dur"] >= 0
    # every lane used by a span has a thread_name metadata track
    named_tids = {e["tid"] for e in events
                  if e["ph"] == "M" and e["name"] == "thread_name"}
    used_tids = {e["tid"] for e in events if e["ph"] in ("X", "i")}
    assert used_tids <= named_tids
    lanes = {e["args"]["name"] for e in events
             if e["ph"] == "M" and e["name"] == "thread_name"}
    assert "rank00000-flush-0" in lanes
    # flow linkage: start/step/finish all share the id; finish binds
    # to the enclosing slice
    flows = [e for e in events if e["ph"] in ("s", "t", "f")]
    assert flows and {e["id"] for e in flows} == {flow}
    assert all(e.get("bp") == "e" for e in flows if e["ph"] == "f")


# --------------------------------------------------------- overhead budget
def test_disabled_overhead_below_one_percent_of_iteration(tmp_path):
    """With tracing off, the instrumentation's cost at the density of a
    save every step must stay under 1% of such an iteration of a real
    (tiny) Trainer. The density is counted, not assumed: the events a
    traced run of the same loop records per iteration, on every lane
    (for this model's 85 device leaves, about 540)."""
    from repro.configs import get_config, smoke_variant
    from repro.training.loop import Trainer

    assert not trace.enabled()
    mgr = CheckpointManager.from_policy(str(tmp_path), None)
    tr = Trainer(smoke_variant(get_config("llama2-7b")), batch=2, seq_len=32,
                 manager=mgr)
    try:
        tr.run(2, ckpt_interval=1)     # warm the jit caches
        with trace.tracing() as t:
            tr.run(4, ckpt_interval=1)
        per_iter = len(t.events()) / 4
        tr.records.clear()
        tr.run(6, ckpt_interval=1)
    finally:
        mgr.close()
    assert per_iter > 8            # the loop's own spans, at least
    iter_s = sorted(r.iter_s for r in tr.records)[len(tr.records) // 2]

    n = 100_000
    t0 = time.perf_counter()
    for _ in range(n):
        with trace.span("x", step=1):
            pass
        trace.add_span("y", 0.0, 1.0, step=1)
    per_call = (time.perf_counter() - t0) / (2 * n)
    assert per_iter * per_call < 0.01 * iter_s, (
        f"disabled tracing costs {per_call * 1e9:.0f} ns/call x "
        f"{per_iter:.0f} events — {per_iter * per_call / iter_s:.2%} of a "
        f"{iter_s * 1e3:.1f} ms iteration")


# ----------------------------------------------------- multi-rank ordering
def _world4_delta_manager(directory: str) -> CheckpointManager:
    return CheckpointManager.from_policy(
        str(directory), CheckpointPolicy(
            engine=EnginePolicy(host_cache_bytes=64 << 20, flush_threads=1),
            storage=StoragePolicy(manifest_checksums=False),
            dist=DistPolicy(world=4),
            delta=DeltaPolicy(keyframe_every=2)))


def test_world4_delta_save_lanes_and_commit_ordering(tmp_path):
    """A coordinated world=4 differential save sequence must give every
    rank its own lane set (vote + engine lanes), and the commit span may
    only start once every rank's phase-1 vote span has ended."""
    rng = np.random.default_rng(0)
    state = {"model": {f"w{i}": rng.standard_normal(32768).astype(np.float32)
                       for i in range(8)},
             "meta": {"step": 0}}
    t = trace.enable()
    mgr = _world4_delta_manager(tmp_path)
    try:
        for s in (1, 2):
            state = {"model": {k: v + np.float32(s) / 256
                               for k, v in state["model"].items()},
                     "meta": {"step": s}}
            mgr.save(s, state).wait_persisted()
            mgr.wait_for_commit(s)
    finally:
        mgr.close()
    spans = t.spans()
    rank_lanes = {f"rank{r:05d}" for r in range(4)}

    votes = [s for s in spans if s["name"] == "vote"]
    assert {v["lane"] for v in votes} == rank_lanes
    commits = {s["args"]["step"]: s for s in spans if s["name"] == "commit"}
    assert set(commits) == {1, 2}
    for step, commit in commits.items():
        step_votes = [v for v in votes if v["args"]["step"] == step]
        assert len(step_votes) == 4
        assert commit["t0"] >= max(v["t1"] for v in step_votes), (
            f"step {step}: commit span started before every vote ended")
    # delta save (step 2) ran the XOR encoders on per-rank producer lanes
    delta_lanes = {s["lane"] for s in spans if s["name"] == "encode.delta"}
    assert delta_lanes and all(ln.startswith("rank") for ln in delta_lanes)
    # the Chrome export gives each rank lane its own named track
    lanes = {e["args"]["name"] for e in t.to_chrome()["traceEvents"]
             if e["ph"] == "M" and e["name"] == "thread_name"}
    assert rank_lanes <= lanes
    for r in range(4):
        assert any(ln.startswith(f"rank{r:05d}-") for ln in lanes), (
            f"rank {r} engine lanes missing from trace")


# ------------------------------------------------------------------ metrics
def test_metrics_registry_counters_gauges_histograms():
    m = MetricsRegistry()
    m.inc("bytes", 10)
    m.inc("bytes", 5)
    m.set_gauge("used", 7)
    for v in (0.1, 0.2, 0.3):
        m.observe("wait_s", v)
    snap = m.snapshot()
    assert snap["counters"]["bytes"] == 15
    assert snap["gauges"]["used"] == 7
    h = snap["histograms"]["wait_s"]
    assert h["count"] == 3
    assert h["min"] == pytest.approx(0.1)
    assert h["max"] == pytest.approx(0.3)
    assert h["mean"] == pytest.approx(0.2)
    m.reset()
    assert m.snapshot() == {"counters": {}, "gauges": {}, "histograms": {}}
    assert json.dumps(snap)  # snapshot is always JSON-serializable


def test_save_spans_carry_flow_links(tmp_path):
    """Single-rank save: the capture→flush→commit spans share one flow id
    so Perfetto can draw the cross-lane arrows."""
    state = {"model": {"w": np.arange(65536, dtype=np.float32)},
             "meta": {"step": 0}}
    t = trace.enable()
    mgr = CheckpointManager.from_policy(str(tmp_path), None)
    try:
        mgr.save(3, state).wait_persisted()
        mgr.wait_for_commit(3)
    finally:
        mgr.close()
    fid = trace.flow_id("save", 3)
    linked = {s["name"] for s in t.spans() if s["flow"] == fid}
    assert {"flush", "commit"} <= linked
    ends = [s for s in t.spans() if s["flow"] == fid
            and s["flow_phase"] == "end"]
    assert [s["name"] for s in ends] == ["commit"]
