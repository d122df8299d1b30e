"""The spans inside the capture, the training iteration, the delta
encode's kernel round trip and the restore's host-to-device copy: each
nests in its parent on the parent's lane and carries the parent's bytes."""

import jax
import numpy as np
import pytest

from repro.configs import get_config, smoke_variant
from repro.core import (CheckpointManager, CheckpointPolicy, DeltaPolicy,
                        EnginePolicy, StoragePolicy)
from repro.obs import trace
from repro.training.loop import Trainer

LOOP_CHILDREN = ("train.batch", "train.grad_dispatch",
                 "train.update_dispatch", "train.loss_sync")
KERNEL_SPANS = ("kernel.h2d", "kernel.call", "kernel.d2h")


@pytest.fixture(autouse=True)
def _clean_tracer():
    trace.disable()
    yield
    trace.disable()


def named(spans, name):
    return [s for s in spans if s["name"] == name]


def parent_of(child, parents):
    """The one span of ``parents`` on the child's lane that holds it."""
    found = [p for p in parents if p["lane"] == child["lane"]
             and p["t0"] <= child["t0"] and child["t1"] <= p["t1"]]
    assert len(found) == 1, (child, found)
    return found[0]


def stage_of(child, stages):
    """The ``d2h.stage`` of the child's shard that holds it: a shard lands
    while the one before it is copied, so successive stages overlap."""
    shard = (child["args"]["tensor"], child["args"]["step"])
    return parent_of(child, [s for s in stages if
                             (s["args"]["tensor"], s["args"]["step"]) == shard])


def tiny_trainer(directory):
    mgr = CheckpointManager.from_policy(str(directory), None)
    return Trainer(smoke_variant(get_config("llama2-7b")), batch=2,
                   seq_len=16, manager=mgr), mgr


def test_stage_and_iteration_children_nest_in_their_parents(tmp_path):
    tr, mgr = tiny_trainer(tmp_path)
    try:
        with trace.tracing() as t:
            tr.run(3, ckpt_interval=1)
    finally:
        mgr.close()
    spans = t.spans()

    stages = named(spans, "d2h.stage")
    assert stages
    for name in ("d2h.wait", "d2h.copy"):
        children = named(spans, name)
        assert len(children) == len(stages)
        for c in children:
            p = stage_of(c, stages)
            assert {k: c["args"][k] for k in ("bytes", "tensor", "step")} \
                == {k: p["args"][k] for k in ("bytes", "tensor", "step")}
    # the wait for the transfer comes first; the copy, on its own lane,
    # starts once the shard has landed and the previous copy is done
    copies = {id(stage_of(c, stages)): c for c in named(spans, "d2h.copy")}
    for w in named(spans, "d2h.wait"):
        p = stage_of(w, stages)
        c = copies[id(p)]
        assert w["t0"] == p["t0"] and w["t1"] <= c["t0"] \
            and c["t1"] == p["t1"]
    assert all(isinstance(c["args"]["after_capture"], bool)
               for c in named(spans, "d2h.copy"))

    iterations = named(spans, "train.iteration")
    assert len(iterations) == 3
    for name in LOOP_CHILDREN:
        children = named(spans, name)
        assert len(children) == 3
        assert {id(parent_of(c, iterations)) for c in children} \
            == {id(i) for i in iterations}


def test_restore_h2d_span_carries_the_device_bytes(tmp_path):
    tr, mgr = tiny_trainer(tmp_path)
    try:
        tr.run(1, ckpt_interval=1)
        with trace.tracing() as t:
            tr.resume(step=1)
    finally:
        mgr.close()
    h2d = named(t.spans(), "restore.h2d")
    assert len(h2d) == 1
    device = [x for x in jax.tree_util.tree_leaves((tr.params, tr.opt_state))
              if isinstance(x, jax.Array)]
    assert device
    assert h2d[0]["args"]["bytes"] == sum(x.nbytes for x in device)
    # same start as the enqueue it outlasts
    assemble, = named(t.spans(), "restore.assemble")
    assert h2d[0]["t0"] == assemble["t0"] and h2d[0]["t1"] >= assemble["t1"]


def test_delta_encode_round_trip_spans_nest_in_encode_delta(tmp_path,
                                                            monkeypatch):
    """The kernel path forced on host bytes (interpreted here): each
    encoded chunk records the three round-trip spans and the base XOR
    inside its ``encode.delta``, with the chunk's bytes."""
    from repro.kernels import ops as kops

    monkeypatch.setattr(kops, "host_fastpath", lambda: False)
    mgr = CheckpointManager.from_policy(str(tmp_path), CheckpointPolicy(
        engine=EnginePolicy(host_cache_bytes=1 << 20, flush_threads=1),
        storage=StoragePolicy(manifest_checksums=False),
        delta=DeltaPolicy(keyframe_every=4)))
    w = np.random.default_rng(0).standard_normal(3000).astype(np.float32)

    def save(step):
        state = {"model": {"w": w + np.float32(step)}, "meta": {"step": step}}
        mgr.save(step, state).wait_persisted()
        mgr.wait_for_commit(step)

    try:
        save(1)                                  # the keyframe
        with trace.tracing() as t:
            save(2)                              # one delta chunk
    finally:
        mgr.close()
    spans = t.spans()
    encodes = named(spans, "encode.delta")
    assert len(encodes) == 1 and encodes[0]["args"]["bytes"] == w.nbytes
    for name in KERNEL_SPANS:
        children = named(spans, name)
        assert len(children) == 1
        assert parent_of(children[0], encodes)
        assert children[0]["args"] == {"kernel": "fused_xor_checksum",
                                       "bytes": w.nbytes}
    h2d, call, d2h = (named(spans, n)[0] for n in KERNEL_SPANS)
    assert h2d["t1"] <= call["t0"] and call["t1"] <= d2h["t0"]
    base, = named(spans, "encode.delta.base")
    assert parent_of(base, encodes) and base["t0"] >= d2h["t1"]


def test_kernel_round_trip_gives_the_same_bytes_traced_or_not(monkeypatch):
    from repro.kernels import ops as kops

    rng = np.random.default_rng(1)
    a = rng.integers(0, 256, 5000, dtype=np.uint8)
    b = rng.integers(0, 256, 5000, dtype=np.uint8)
    delta, digest = kops.on_host(kops.fused_xor_checksum, a, b)
    with trace.tracing() as t:
        delta_t, digest_t = kops.on_host(kops.fused_xor_checksum, a, b,
                                         nbytes=a.nbytes)
    assert isinstance(delta, np.ndarray) and isinstance(delta_t, np.ndarray)
    np.testing.assert_array_equal(delta, delta_t)
    np.testing.assert_array_equal(delta.view(np.uint8)[:a.size],
                                  np.bitwise_xor(a, b))
    assert int(digest) == int(digest_t)
    assert [s["name"] for s in t.spans()] == list(KERNEL_SPANS)


@pytest.mark.parametrize("route", ["int8", "checksum"])
def test_int8_and_checksum_share_the_round_trip(route, monkeypatch):
    """The int8 encode and the file checksum take the same kernel round
    trip as the delta encode, and give the host oracle's answer."""
    from repro.core import codecs
    from repro.kernels import ops as kops

    raw = np.random.default_rng(2).standard_normal(1000).astype(
        np.float32).view(np.uint8)
    if route == "int8":
        def run():
            return codecs.encode_int8_block(raw, with_digest=True)
        kernel = "fused_quantize_int8"
    else:
        def run():
            return kops.tensor_checksum_fast(raw)
        kernel = "tensor_checksum"
    want = run()                                 # the NumPy oracle
    monkeypatch.setattr(kops, "host_fastpath", lambda: False)
    with trace.tracing() as t:
        got = run()
    assert got == want
    spans = t.spans()
    assert [s["name"] for s in spans] == list(KERNEL_SPANS)
    assert all(s["args"] == {"kernel": kernel, "bytes": raw.nbytes}
               for s in spans)
