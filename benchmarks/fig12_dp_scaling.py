"""Figs 10–12: checkpointing under increasing data parallelism.

Runs in a subprocess with 8 virtual devices. The optimizer state is sharded
over the ``data`` axis (ZeRO-1, the paper's setup): growing DP shrinks the
per-rank checkpoint payload (minor axis of Fig 12) while adding concurrent
writers. We measure per-rank bytes and effective blocked-time throughput for
DP ∈ {1, 2, 4, 8}.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from typing import List

from .common import save_results

_CHILD = r"""
import os, json, time, tempfile, shutil
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core import CheckpointManager, CheckpointPolicy, EnginePolicy
from repro.launch.mesh import make_mesh

results = []
n_total = 8 * (1 << 20) // 4          # 8 MiB of fp32 "optimizer state"
for dp in (1, 2, 4, 8):
    mesh = make_mesh((dp, 1), ("data", "model"))
    # ZeRO-1: optimizer state sharded over data; params replicated
    opt = jax.device_put(jnp.arange(n_total, dtype=jnp.float32),
                         NamedSharding(mesh, P("data")))
    params = jax.device_put(jnp.ones((1 << 18,), jnp.bfloat16),
                            NamedSharding(mesh, P()))
    state = {"model": {"w": params}, "optimizer": {"m": opt},
             "meta": {"dp": dp}}
    d = tempfile.mkdtemp()
    mgr = CheckpointManager.from_policy(
        d, CheckpointPolicy(engine=EnginePolicy(
            host_cache_bytes=128 << 20, throttle_mbps=600.0)))
    fut = mgr.save(0, state)
    fut.wait_persisted()
    stats = fut.stats
    files = os.listdir(os.path.join(d, "global_step0"))
    per_rank = stats.bytes_tensors / max(dp, 1)
    results.append({"dp": dp, "n_files": len(files),
                    "total_mb": stats.bytes_tensors / 2**20,
                    "per_rank_mb": per_rank / 2**20,
                    "blocking_s": stats.blocking_s,
                    "persist_s": stats.persist_latency_s})
    mgr.close()
    shutil.rmtree(d, ignore_errors=True)
print(json.dumps(results))
"""


def run(quick: bool = False) -> List[dict]:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    # a rehearsal on 8 virtual CPU devices by design; it must never take
    # (or wait for) an accelerator the parent may hold
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    rows = json.loads(out.stdout.strip().splitlines()[-1])
    save_results("fig12_dp_scaling", rows)
    return rows


def summarize(rows) -> List[str]:
    return [f"fig12/dp{r['dp']},{r['blocking_s']*1e6:.0f},"
            f"per_rank={r['per_rank_mb']:.1f}MB files={r['n_files']}"
            for r in rows]
