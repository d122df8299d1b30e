"""The reduction on a slice of a trace recorded on the chip
(``data/trace_rwkv6-save-delta.json``: a TPU v5 lite running
``rwkv6-save-delta --trace 1``, cut to a stretch with training steps and
fused XOR+digest kernel calls)."""

import json
import math
import os
from types import SimpleNamespace

import numpy as np

from chipbench import flops, spec, traceutil as tu

TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "trace_rwkv6-save-delta.json")


def load():
    with open(TRACE) as f:
        d = json.load(f)
    return (d["slice"], [tuple(e) for e in d["ops"]],
            [tuple(e) for e in d["modules"]])


def brute_busy(events, t0, t1, step=1e-6):
    """Busy time on a 1 us grid: the oracle for the interval union."""
    grid = np.zeros(int(math.ceil((t1 - t0) / step)) + 1, bool)
    for _n, a, b in events:
        a, b = max(a, t0), min(b, t1)
        if b > a:
            grid[int((a - t0) / step):int((b - t0) / step)] = True
    return grid.sum() * step


def test_busy_and_idle_share_match_a_grid_count():
    (t0, t1), ops, _modules = load()
    busy = tu.union_length(tu.clip([(a, b) for _n, a, b in ops], t0, t1))
    assert 0 < busy < t1 - t0
    assert math.isclose(busy, brute_busy(ops, t0, t1), abs_tol=2e-6 *
                        len(ops))
    idle = tu.idle_share([(a, b) for _n, a, b in ops], t0, t1)
    assert math.isclose(idle, 100 * (1 - busy / (t1 - t0)))
    gaps = tu.gaps([(a, b) for _n, a, b in ops], t0, t1)
    assert math.isclose(sum(b - a for a, b in gaps) + busy, t1 - t0)


def test_modules_name_the_device_time():
    (t0, t1), _ops, modules = load()
    top = dict(tu.top_ops(modules, t0, t1))
    assert "jit_grad_step" in top and "jit_fused_xor_checksum" in top
    assert all(not name.endswith(")") for name in top)


def test_kernel_roofline_from_the_recorded_calls():
    (t0, t1), ops, modules = load()
    calls = [e for e in tu.within(ops, modules, "jit_fused_xor_checksum")
             if e[0].endswith("custom-call")]
    n_modules = sum(1 for m in modules
                    if m[0] == "jit_fused_xor_checksum")
    assert len(calls) == n_modules > 0        # one kernel call per chunk
    busy = sum(b - a for _n, a, b in calls)
    chunk = 4 << 20
    spans = [{"name": "encode.delta", "t0": t0, "t1": t1,
              "args": {"bytes": chunk}}] * n_modules
    ctx = SimpleNamespace(device=ops, modules=modules, spans=spans,
                          window=(t0, t1), flops=flops, traceutil=tu,
                          peak={"hbm_bytes_per_s": 819e9})
    share = spec.load_reader("xor_checksum_roofline")(ctx)
    want = 100 * n_modules * 3 * chunk / 819e9 / busy
    assert math.isclose(share, want)
    assert 0 < share <= 100
