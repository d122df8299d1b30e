"""A layer stack of several block kinds, through the program's normal path:
a leading dense layer, then a window-attention and a full-attention MoE
layer, twice (``data/tiny-moe.json``, with its architecture file under
``data/arch/``). A clean run is correct; a program run with the pattern's
kinds swapped, or without the router's auxiliary loss, is not."""

import dataclasses

import pytest

from chipbench import harness
from chipbench.tests import tiny


def test_a_stack_of_several_kinds_runs_correct(tmp_path):
    out = tiny.run("moe-save-raw", str(tmp_path))
    assert out["correct"] is True, out["checks"]
    assert out["checks"]["ckpt_mismatch"]["value"] == 0
    assert out["failed"] == 0 and out["attempted"] > 0


def swap_kinds(cfg):
    return dataclasses.replace(cfg, layer_groups=tuple(
        (tuple(reversed(pattern)), count)
        for pattern, count in cfg.layer_groups))


def drop_aux(cfg):
    return dataclasses.replace(cfg, router_aux_coef=0.0)


@pytest.mark.parametrize("fault,fails", [(swap_kinds, "grad_gap"),
                                         (drop_aux, "loss_gap")])
def test_a_program_at_fault_is_not_correct(fault, fails, monkeypatch,
                                           tmp_path):
    real = harness.program_config
    monkeypatch.setattr(harness, "program_config",
                        lambda config: fault(real(config)))
    out = tiny.run("moe-save-raw", str(tmp_path))
    assert out["correct"] is False
    assert out["checks"][fails]["value"] > out["checks"][fails]["limit"]
