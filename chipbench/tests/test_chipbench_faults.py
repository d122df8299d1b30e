"""A run with the timed path broken underneath comes out not correct:
once for each fault a one-chip cell can have."""

import dataclasses

import jax
import numpy as np
import pytest

from chipbench.tests import tiny


def test_state_left_unchanged(monkeypatch, tmp_path):
    from repro.training import loop

    monkeypatch.setattr(loop, "make_update_step",
                        lambda cfg, hp: lambda p, o, g: (p, o))
    out = tiny.run("sc2-save-raw", str(tmp_path))
    assert out["correct"] is False
    assert out["checks"]["change_gap"]["value"] == pytest.approx(1.0)


def test_half_batch_left_out(monkeypatch, tmp_path):
    from repro.training import loop

    real = loop.make_grad_step

    def half(cfg):
        step = real(cfg)
        return lambda p, b: step(p, {"tokens": b["tokens"][
            :b["tokens"].shape[0] // 2]})

    monkeypatch.setattr(loop, "make_grad_step", half)
    out = tiny.run("sc2-save-raw", str(tmp_path))
    assert out["correct"] is False


def _flip_first(chunks):
    first = True
    for c in chunks:
        if first and c.kind == "tensor" and len(c.data):
            data = bytearray(bytes(c.data))
            data[0] ^= 0x01
            c = dataclasses.replace(c, data=memoryview(bytes(data)))
            first = False
        yield c


def test_raw_checkpoint_byte_altered_where_written(monkeypatch, tmp_path):
    from repro.core import state_provider as sp

    real = sp.TensorStateProvider.chunks
    monkeypatch.setattr(sp.TensorStateProvider, "chunks",
                        lambda self: _flip_first(real(self)))
    out = tiny.run("sc2-save-raw", str(tmp_path))
    assert out["correct"] is False
    assert out["checks"]["ckpt_mismatch"]["value"] > 0


def test_delta_byte_altered_where_encoded(monkeypatch, tmp_path):
    from repro.core import state_provider as sp

    real = sp.encode_delta_chunk

    def altered(cur, prev, with_digest=False):
        delta, digest = real(cur, prev, with_digest=with_digest)
        delta = delta.copy()
        delta[0] ^= 0x01
        return delta, digest

    monkeypatch.setattr(sp, "encode_delta_chunk", altered)
    out = tiny.run("rwkv6-save-delta", str(tmp_path))
    assert out["correct"] is False
    assert out["checks"]["ckpt_mismatch"]["value"] > 0


def test_restored_answer_altered(monkeypatch, tmp_path):
    from repro.core import restore

    real = restore.RestoreEngine.restore

    def altered(self, sdir, template, *a, **kw):
        tree, stats = real(self, sdir, template, *a, **kw)
        leaves, tdef = jax.tree_util.tree_flatten(tree)
        i = next(i for i, x in enumerate(leaves) if isinstance(x, jax.Array))
        x = leaves[i]
        leaves[i] = (x.reshape(-1).at[0].add(np.ones((), x.dtype))
                     .reshape(x.shape))
        return tdef.unflatten(leaves), stats

    monkeypatch.setattr(restore.RestoreEngine, "restore", altered)
    out = tiny.run("sc2-restore", str(tmp_path))
    assert out["correct"] is False
    assert out["checks"]["ckpt_mismatch"]["value"] > 0
