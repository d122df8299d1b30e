"""Composable state providers: zero-copy streams, composition, ordering."""

import pickle

import numpy as np
import pytest

from repro.core.host_cache import HostCache
from repro.core.state_provider import (Chunk, CompositeStateProvider,
                                       ObjectStateProvider,
                                       TensorStateProvider)


def host_tsp(name, arr, **kw):
    return TensorStateProvider(name, dtype=str(arr.dtype), shape=arr.shape,
                               nbytes=arr.nbytes, host_array=arr, **kw)


def test_host_tensor_zero_copy_chunks():
    arr = np.arange(1000, dtype=np.float64)
    p = host_tsp("t", arr, chunk_bytes=1024)
    chunks = list(p.chunks())
    assert len(chunks) == (arr.nbytes + 1023) // 1024
    assert chunks[-1].last and not chunks[0].last
    joined = b"".join(bytes(c.data) for c in chunks)
    assert joined == arr.tobytes()
    # zero-copy: first chunk's memoryview aliases the source array
    assert chunks[0].data.obj is not None


def test_device_tensor_streams_as_staged():
    """Chunks become available incrementally as staging lands bytes."""
    cache = HostCache(1 << 20)
    p = TensorStateProvider("t", dtype="uint8", shape=(4096,), nbytes=4096,
                            chunk_bytes=1024)
    p.bind_reservation(cache.reserve(4096))
    src = np.random.default_rng(0).integers(0, 255, 4096, dtype=np.uint8)
    it = p.chunks()
    out = []
    for staged in (1024, 2048, 4096):
        dst = p.reservation.array(np.uint8, (4096,))
        dst[:staged] = src[:staged]
        p.notify_staged(staged)
        while len(out) * 1024 < staged:
            out.append(next(it))
    assert b"".join(bytes(c.data) for c in out) == src.tobytes()


def test_object_provider_lazy_serialization():
    calls = {"n": 0}

    class Tracked:
        def __reduce__(self):
            calls["n"] += 1
            return (dict, ())

    p = ObjectStateProvider("o", {"x": Tracked()})
    assert calls["n"] == 0          # nothing serialized at construction
    chunks = list(p.chunks())       # serialization happens at stream time
    assert calls["n"] == 1
    assert chunks[-1].last
    payload = b"".join(bytes(c.data) for c in chunks)
    assert pickle.loads(payload) == {"x": {}}
    assert p.serialized_nbytes == len(payload)


def test_preserialized_object_provider():
    payload = pickle.dumps([1, 2, 3])
    p = ObjectStateProvider("o", None, preserialized=payload)
    assert b"".join(bytes(c.data) for c in p.chunks()) == payload


def test_composite_orders_tensors_first_largest_first():
    a = host_tsp("small", np.zeros(10, np.uint8))
    b = host_tsp("big", np.zeros(10000, np.uint8))
    o = ObjectStateProvider("obj", {"k": 1})
    comp = CompositeStateProvider("f", [o, a, b])
    kinds = [(c.kind, c.name) for c in comp.chunks()]
    names = [n for _k, n in kinds]
    assert names.index("big") < names.index("small") < names.index("obj")


def test_composite_layout_assigns_offsets():
    a = host_tsp("a", np.zeros(100, np.uint8))
    b = host_tsp("b", np.zeros(200, np.uint8))
    comp = CompositeStateProvider("f", [a, b])
    layout = comp.plan_layout()
    assert {e.name for e in layout.tensors} == {"a", "b"}
    for c in comp.chunks():
        if c.kind == "tensor":
            assert c.offset is not None
        else:
            assert c.offset is None


def test_hierarchical_composition():
    inner = CompositeStateProvider("inner", [
        host_tsp("x", np.zeros(64, np.uint8)),
        ObjectStateProvider("io", 42)])
    outer = CompositeStateProvider("outer", [
        inner, host_tsp("y", np.zeros(128, np.uint8))])
    assert {p.name for p in outer.tensor_providers} == {"x", "y"}
    assert {p.name for p in outer.object_providers} == {"io"}


def test_xor_bytes_kernel_path_chunks_and_pads(monkeypatch):
    """The TPU branch of ``xor_bytes`` (forced here, kernels interpreted):
    a whole tensor goes through the kernel in fixed, zero-padded chunks
    and comes back bit-identical to NumPy's XOR, tail included."""
    from repro.core import state_provider as sp
    from repro.kernels import ops as kops

    monkeypatch.setattr(kops, "host_fastpath", lambda: False)
    monkeypatch.setattr(sp, "DEFAULT_CHUNK_BYTES", 1 << 18)
    rng = np.random.default_rng(5)
    cur = rng.integers(0, 256, (1 << 18) * 2 + 5, dtype=np.uint8)
    prev = rng.integers(0, 256, cur.size, dtype=np.uint8)
    out = sp.xor_bytes(cur, prev)
    assert out.dtype == np.uint8 and out.size == cur.size
    np.testing.assert_array_equal(out, np.bitwise_xor(cur, prev))
