"""BENCHMARK.json against the benchmark's naming rules and the files that
the harness finds by name; configurations against their stated sizes."""

import math
import os
import re
from types import SimpleNamespace

import jax
import pytest

from chipbench import reference, spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCH = spec.benchmark()


def test_every_name_is_legal_and_unique():
    names = {}
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        got = [e["name"] for e in BENCH[section]]
        assert len(got) == len(set(got)), section
        names[section] = got
        for n in got:
            assert NAME.match(n), n
    for w in BENCH["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for c in BENCH["configs"]:
        for key in c["reduced"]:
            assert NAME.match(key)
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])


def test_every_cell_finds_its_files_and_reports_enough():
    e2e_names = {m["name"] for m in BENCH["end_to_end"]}
    for w in BENCH["workloads"]:
        cell = spec.Cell(BENCH, w["name"])
        for fn in ("setup", "window", "end_to_end", "verify"):
            assert callable(getattr(cell.kind, fn)), (w["name"], fn)
        reported = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in reported and len(reported) >= 2
        assert cell.per_layer, w["name"]
        for m in cell.per_layer:
            assert m["moves"] in reported, (m["name"], w["name"])
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e_names
        assert os.path.exists(os.path.join(spec.METRICS_DIR,
                                           m["name"] + ".py")), m["name"]


@pytest.mark.parametrize("name,params,version", [
    ("starcoder2-7b-l1v8", 273_739_264, 3_832_404_996),
    ("rwkv6-7b-l1v8", 287_113_216, 4_019_716_100),
])
def test_config_resolves_to_its_stated_sizes(name, params, version):
    cfg = spec.load_json(os.path.join(spec.HERE, "configs", name + ".json"))
    shapes = jax.eval_shape(lambda: reference.make_state(cfg, 3))
    p, opt = shapes
    assert sum(math.prod(x.shape) for x in jax.tree_util.tree_leaves(p)) \
        == params
    assert sum(math.prod(x.shape) * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(shapes)) == version
    for key in cfg["reduced"]:
        assert cfg[key] != cfg["published"][key]


def test_a_metric_is_added_by_a_file_and_an_entry(tmp_path):
    (tmp_path / "dummy.layer_count.py").write_text(
        "def read(ctx):\n    return ctx.steps * 2 if ctx.steps else None\n")
    (tmp_path / "silent.py").write_text("def read(ctx):\n    return None\n")
    metrics = [{"name": "dummy.layer_count", "unit": "steps"},
               {"name": "silent", "unit": "%"}]
    got = spec.read_per_layer(metrics, SimpleNamespace(steps=21),
                              metrics_dir=str(tmp_path))
    # a reader that finds nothing leaves its metric out of the line
    assert got == {"dummy.layer_count": {"value": 42.0, "unit": "steps"}}


def test_a_traffic_kind_is_added_by_a_file_and_an_entry(tmp_path):
    (tmp_path / "idle_cycle.py").write_text(
        "def setup(run):\n    pass\n")
    (tmp_path / "idle.json").write_text('{"kind": "idle_cycle"}')
    bench = dict(BENCH, workloads=BENCH["workloads"] + [
        {"name": "idle-cell", "config": BENCH["configs"][0]["name"],
         "traffic": "idle", "chips": 1}])
    cell = spec.Cell(bench, "idle-cell", traffic_dir=str(tmp_path),
                     kinds_dir=str(tmp_path))
    assert cell.kind.__file__ == str(tmp_path / "idle_cycle.py")
    assert cell.traffic == {"kind": "idle_cycle"}


@pytest.mark.parametrize("name", [c["name"] for c in BENCH["configs"]])
def test_each_configuration_finds_its_architecture_file(name):
    cfg = spec.load_json(os.path.join(spec.HERE, "configs", name + ".json"))
    arch = spec.arch(cfg)
    groups = arch.layer_groups(cfg)
    assert sum(len(pattern) * count for pattern, count in groups) \
        == cfg["num_hidden_layers"]
    assert "d_ff" in arch.program_widths(cfg)
    assert arch.forward_flops(cfg, 4096) > 0
    assert arch.aux_weight(cfg) >= 0
    assert arch.final_norm_spec(cfg)
    for pattern, _count in groups:
        for kind in pattern:
            assert set(arch.block_spec(cfg, kind)) >= {"ln1", "ln2"}


def save_path_context(saves):
    from chipbench import traceutil

    spans = [{"name": "d2h.stage", "t0": 1.0, "t1": 3.0,
              "args": {"bytes": 4e9}},
             {"name": "encode.digest", "t0": 2.0, "t1": 6.0,
              "args": {"bytes": 4e9}},
             {"name": "flush", "t0": 3.0, "t1": 11.0,
              "args": {"bytes": 4e9}},
             {"name": "commit", "t0": 11.0, "t1": 11.25, "args": {}},
             {"name": "flush", "t0": 0.0, "t1": 0.5,   # before the window
              "args": {"bytes": 1e12}}]
    return SimpleNamespace(spans=spans, saves=saves, window=(0.75, 40.0),
                           traceutil=traceutil)


@pytest.mark.parametrize("base,want", [("d2h_stage_GBps", 2.0),
                                       ("digest_GBps", 1.0),
                                       ("flush_GBps", 0.5),
                                       ("commit_ms", 250.0)])
def test_a_split_save_path_metric_reads_as_its_base(base, want):
    ctx = save_path_context([])
    assert spec.load_reader(base)(ctx) == pytest.approx(want)
    assert spec.load_reader(base + ".train")(ctx) == pytest.approx(want)


def test_save_commit_per_layer_is_the_mean_of_committed_saves():
    read = spec.load_reader("save_commit_s.train")
    saves = [{"t_call": 1.0, "t_committed": 12.0},
             {"t_call": 13.0, "t_committed": 26.0},
             {"t_call": 39.0}]                    # never committed
    assert read(save_path_context(saves)) == pytest.approx(12.0)
    assert read(save_path_context([])) is None
