"""Hybrid fixed-offset / log-append file layout: roundtrips + invariants."""

import os

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.layout import (ALIGN, FileLayout, FileReader, FileWriter,
                               align_up)


def _write_file(path, tensors, objects):
    specs = [(name, arr.nbytes, str(arr.dtype), arr.shape, None, None)
             for name, arr in tensors.items()]
    layout = FileLayout.plan(specs)
    w = FileWriter(path, layout)
    for entry, (name, arr) in zip(layout.tensors, tensors.items()):
        w.write_at(entry.offset, memoryview(np.ascontiguousarray(arr)).cast("B"))
    for name, obj in objects.items():
        import pickle
        w.append_object(name, pickle.dumps(obj))
    w.finalize()
    return layout


def test_roundtrip(tmp_path):
    path = str(tmp_path / "f.dsllm")
    tensors = {
        "a": np.arange(1000, dtype=np.float32).reshape(10, 100),
        "b": np.ones((3, 5, 7), dtype=np.float16),
        "c": np.array(3.14, dtype=np.float64).reshape(()),
    }
    objects = {"meta": {"step": 7, "cfg": [1, 2, 3]}, "empty": None}
    _write_file(path, tensors, objects)
    r = FileReader(path)
    for name, arr in tensors.items():
        np.testing.assert_array_equal(r.read_tensor(name), arr)
    assert r.read_object("meta") == {"step": 7, "cfg": [1, 2, 3]}
    assert r.read_object("empty") is None


def test_alignment_and_region_separation(tmp_path):
    path = str(tmp_path / "f.dsllm")
    tensors = {"a": np.zeros(17, np.uint8), "b": np.zeros(5000, np.uint8)}
    layout = _write_file(path, tensors, {"o": "x" * 10000})
    for e in layout.tensors:
        assert e.offset % ALIGN == 0
    ends = [e.offset + e.nbytes for e in layout.tensors]
    assert layout.tensor_region_end >= max(ends)
    assert layout.tensor_region_end % ALIGN == 0
    r = FileReader(path)
    for o in r.objects.values():
        assert o.offset >= layout.tensor_region_end


def test_bad_magic(tmp_path):
    path = str(tmp_path / "junk")
    with open(path, "wb") as f:
        f.write(b"\0" * 64)
    with pytest.raises(ValueError, match="magic"):
        FileReader(path)


def test_planned_offsets_do_not_overlap():
    specs = [(f"t{i}", sz, "uint8", (sz,), None, None)
             for i, sz in enumerate([1, 4095, 4096, 4097, 100, 0, 7])]
    layout = FileLayout.plan(specs)
    spans = sorted((e.offset, e.offset + e.nbytes) for e in layout.tensors)
    for (s1, e1), (s2, _e2) in zip(spans, spans[1:]):
        assert e1 <= s2


@settings(max_examples=30, deadline=None)
@given(st.lists(st.integers(min_value=0, max_value=100_000),
                min_size=1, max_size=20))
def test_property_layout_no_overlap_and_aligned(sizes):
    specs = [(f"t{i}", sz, "uint8", (sz,), None, None)
             for i, sz in enumerate(sizes)]
    layout = FileLayout.plan(specs)
    spans = sorted((e.offset, e.offset + e.nbytes) for e in layout.tensors)
    prev_end = 0
    for s, e in spans:
        assert s % ALIGN == 0
        assert s >= prev_end
        prev_end = e
    assert layout.tensor_region_end == align_up(max(e for _s, e in spans))


@settings(max_examples=20, deadline=None)
@given(st.data())
def test_property_tensor_roundtrip(tmp_path_factory, data):
    dtypes = [np.float32, np.float16, np.int32, np.uint8, np.int64]
    n = data.draw(st.integers(1, 5))
    tensors = {}
    for i in range(n):
        dt = data.draw(st.sampled_from(dtypes))
        dims = data.draw(st.lists(st.integers(1, 8), min_size=0, max_size=3))
        arr = np.random.default_rng(i).integers(0, 100, size=dims).astype(dt)
        tensors[f"t{i}"] = arr
    objects = {"o": data.draw(st.dictionaries(
        st.text(max_size=5), st.integers(), max_size=4))}
    path = str(tmp_path_factory.mktemp("prop") / "f.dsllm")
    _write_file(path, tensors, objects)
    r = FileReader(path)
    for name, arr in tensors.items():
        got = r.read_tensor(name)
        np.testing.assert_array_equal(got, arr)
        assert got.dtype == arr.dtype
    assert r.read_object("o") == objects["o"]
