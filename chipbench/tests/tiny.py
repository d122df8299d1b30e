"""Tiny cells for the CPU tests: the benchmark's cells with toy-width
configurations and traffic under ``data/``, a save-free cell that shows a
traffic mix added as data alone, and a cell whose layer stack mixes dense
and MoE layers of window and full attention, with its architecture file
under ``data/arch/``."""

import os
import time

from chipbench import harness, spec

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CELLS = {"sc2-save-raw": ("tiny-dense", "tiny_save_raw"),
         "sc2-restore": ("tiny-dense", "tiny_restore"),
         "rwkv6-save-delta": ("tiny-rwkv", "tiny_save_delta"),
         "sc2-train-nosave": ("tiny-dense", "tiny_train_nosave"),
         "moe-save-raw": ("tiny-moe", "tiny_save_raw")}
ARCH_DIRS = {"tiny-moe": os.path.join(DATA, "arch")}


def cell(workload: str) -> spec.Cell:
    bench = {
        "configs": [{"name": n, "file": f"chipbench/tests/data/{n}.json"}
                    for n in ("tiny-dense", "tiny-rwkv", "tiny-moe")],
        "workloads": [{"name": w, "config": c, "traffic": t, "chips": 1}
                      for w, (c, t) in CELLS.items()],
        "end_to_end": [{k: v for k, v in m.items() if k != "workloads"}
                       for m in spec.benchmark()["end_to_end"]],
        "per_layer": []}
    return spec.Cell(bench, workload,
                     traffic_dir=os.path.join(DATA, "traffic"),
                     arch_dir=ARCH_DIRS.get(CELLS[workload][0],
                                            spec.ARCH_DIR))


def run(workload: str, run_dir: str, seed: int = 2**31 + 17,
        seconds: float = 1.5):
    """A whole run past the look for a chip: set-up, window, check."""
    return harness.run(cell(workload), seed, seconds, False,
                       time.perf_counter(), run_dir=run_dir)
