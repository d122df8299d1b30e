"""Restore throughput: the seed's serial restore vs the parallel
RestoreEngine, across all three checkpoint formats.

Runs in a subprocess with 8 virtual devices: a synthetic ≥100M-parameter
fp32 state is sharded over an 8-way data mesh, saved by each engine, and
then restored three ways —

* ``seed-serial``  — a faithful replica of the seed's restore path
  (per-tensor whole-shard reads; the snapshot format re-loads whole rank
  files per tensor, O(files × tensors));
* ``engine-1``     — RestoreEngine with ``threads=1`` (the planning +
  ranged-read machinery, no parallelism: isolates the fan-out win);
* ``engine-8``     — RestoreEngine with ``threads=8``.

Reads are throttled per *stream* at the same ``THROTTLE_MBPS`` the save
benchmarks use: local page cache hides the PFS bandwidth that dominates
restore at scale (arXiv 2512.24511), so — exactly like the write side —
each concurrent read stream is capped at the emulated per-connection
bandwidth. Serial restore owns one stream; the parallel engine opens one
per thread (ByteCheckpoint's parallel re-sharded load). Unthrottled
wall-clock rows are recorded too so the raw local-SSD effect (ranged
``preadv`` vs per-tensor memmap faulting) is visible separately.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from typing import List

from .common import THROTTLE_MBPS, save_results

_CHILD = r"""
import glob, json, os, pickle, re, sys, tempfile, time
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ.setdefault("REPRO_NO_FSYNC", "1")
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core import (CheckpointManager, CheckpointPolicy,
                        EnginePolicy, RestoreEngine, step_dir)
from repro.core.baselines import load_snapshot_rank, load_sync_rank
from repro.core.distributed import _path_str
from repro.core.layout import FileReader
from repro.launch.mesh import make_mesh

N_TENSORS = %(n_tensors)d
SHAPE = (%(rows)d, %(cols)d)
THROTTLE = %(throttle)f

mesh = make_mesh((8,), ("data",))
shard = NamedSharding(mesh, P("data", None))
key = jax.random.PRNGKey(0)
state = {"model": {}, "meta": {"step": 0, "note": "fig_restore"}}
for i in range(N_TENSORS):
    key, sub = jax.random.split(key)
    state["model"]["w%%02d" %% i] = jax.device_put(
        jax.random.normal(sub, SHAPE, jnp.float32), shard)
payload = sum(v.nbytes for v in state["model"].values())

# host-side template: isolates the storage->host path being compared (the
# device_put cost of a sharded target is identical for every variant)
tpl = {"model": {k: np.empty(SHAPE, np.float32) for k in state["model"]},
       "meta": {"step": 0, "note": ""}}


# --- faithful replica of the seed's serial restore ------------------------
# (checkpoint.py@de9b523: _index_step_dir + _assemble), instrumented with a
# byte counter and an optional single-stream read throttle.
def seed_restore(sdir, template, throttle_mbps=None):
    read_bytes = [0]

    def throttled(nb, t0):
        read_bytes[0] += nb
        if throttle_mbps:
            target = nb / (throttle_mbps * 1e6)
            el = time.perf_counter() - t0
            if target > el:
                time.sleep(target - el)

    tensor_index, object_index = {}, {}
    dsllm = sorted(glob.glob(os.path.join(sdir, "*.dsllm")))
    manifests = sorted(glob.glob(os.path.join(sdir, "manifest_rank*.pkl")))
    if dsllm:
        for p in dsllm:
            rd = FileReader(p)
            for name, entry in rd.tensors.items():
                base = name.split("@[", 1)[0]

                def read(r=rd, n=entry.name, nb=entry.nbytes):
                    t0 = time.perf_counter()
                    out = np.array(r.read_tensor(n))   # full-shard read
                    throttled(nb, t0)
                    return out
                tensor_index.setdefault(base, []).append((entry.index, read))
            for oname in rd.objects:
                object_index[oname] = (lambda r=rd, n=oname:
                                       r.read_object(n))
    elif manifests:
        for mpath in manifests:
            with open(mpath, "rb") as f:
                manifest = pickle.load(f)
            rank = int(re.search(r"manifest_rank(\d+)", mpath).group(1))
            rank_bytes = sum(hi - lo for t in manifest["tensors"]
                             for _, lo, hi in t["chunks"])
            for t in manifest["tensors"]:
                base = t["name"].split("@[", 1)[0]

                def read(d=os.path.dirname(mpath), r=rank, n=t["name"],
                         nb=rank_bytes):
                    t0 = time.perf_counter()
                    out = load_snapshot_rank(d, r)[n]  # whole-rank re-read!
                    throttled(nb, t0)
                    return out
                tensor_index.setdefault(base, []).append(
                    (tuple(t["index"]), read))
        opath = os.path.join(sdir, "objects.pkl")
        if os.path.exists(opath):
            with open(opath, "rb") as f:
                objects = pickle.load(f)
            for oname, val in objects.items():
                object_index[oname] = (lambda v=val: v)
    else:
        for p in sorted(glob.glob(os.path.join(sdir, "*.pkl"))):
            t0 = time.perf_counter()
            graph = load_sync_rank(p)
            throttled(os.path.getsize(p), t0)
            for name, rec in graph.items():
                if name == "__objects__":
                    for oname, val in rec.items():
                        object_index[oname] = (lambda v=val: v)
                    continue
                base = name.split("@[", 1)[0]
                tensor_index.setdefault(base, []).append(
                    (tuple(rec["index"]), (lambda r=rec: r["data"])))

    leaves, treedef = jax.tree_util.tree_flatten_with_path(template)
    out = []
    for path, leaf in leaves:
        pstr = "state/" + _path_str(path)
        if isinstance(leaf, np.ndarray):
            region = tuple((0, d) for d in leaf.shape)
            buf = np.empty(leaf.shape, dtype=leaf.dtype)
            for s_idx, read in tensor_index[pstr]:
                inter = tuple((max(a, c), min(b, d))
                              for (a, b), (c, d) in zip(region, s_idx))
                if any(lo >= hi for lo, hi in inter):
                    continue
                src = read()
                src_sl = tuple(slice(lo - c, hi - c)
                               for (lo, hi), (c, _d) in zip(inter, s_idx))
                dst_sl = tuple(slice(lo - a, hi - a)
                               for (lo, hi), (a, _b) in zip(inter, region))
                buf[dst_sl] = src[src_sl]
            out.append(buf)
        else:
            out.append(object_index[pstr]() if pstr in object_index else leaf)
    return jax.tree_util.tree_unflatten(treedef, out), read_bytes[0]


def check(tree):
    ref = np.asarray(state["model"]["w00"])
    np.testing.assert_array_equal(np.asarray(tree["model"]["w00"]), ref)


rows = []
for mode in ("datastates", "snapshot", "sync"):
    d = tempfile.mkdtemp(prefix="fig_restore_")
    mgr = CheckpointManager.from_policy(
        d, CheckpointPolicy(engine=EnginePolicy(
            mode=mode, host_cache_bytes=1 << 30)))
    mgr.save(0, state, blocking=True)
    mgr.close()
    sdir = step_dir(d, 0)
    ckpt_bytes = sum(os.path.getsize(os.path.join(sdir, f))
                     for f in os.listdir(sdir))

    variants = [("seed-serial", None, True), ("engine-1", 1, True),
                ("engine-8", 8, True)]
    if mode == "datastates":
        variants += [("seed-serial", None, False), ("engine-8", 8, False)]
    for variant, threads, throttled_run in variants:
        throttle = THROTTLE if throttled_run else None
        t0 = time.perf_counter()
        if threads is None:
            tree, nbytes = seed_restore(sdir, tpl, throttle_mbps=throttle)
            n_ranges = -1
        else:
            eng = RestoreEngine(threads=threads, throttle_mbps=throttle)
            tree, stats = eng.restore(sdir, tpl)
            nbytes, n_ranges = stats.bytes_read, stats.n_ranges
        dt = time.perf_counter() - t0
        check(tree)
        rows.append({"format": mode, "variant": variant,
                     "throttled": bool(throttled_run), "seconds": dt,
                     "gbps": payload / dt / 1e9,
                     "bytes_read": int(nbytes),
                     "ckpt_bytes": int(ckpt_bytes),
                     "payload_bytes": int(payload),
                     "n_ranges": int(n_ranges)})
        del tree
    for f in os.listdir(sdir):
        os.unlink(os.path.join(sdir, f))
print("RESULT " + json.dumps(rows))
"""


def run(quick: bool = False) -> List[dict]:
    # 13 x 1024 x 7872 fp32 = 104.8M params (400 MiB); quick: 16.8M (64 MiB)
    n_tensors, rows_, cols = (8, 256, 8192) if quick else (13, 1024, 7872)
    code = _CHILD % {"n_tensors": n_tensors, "rows": rows_, "cols": cols,
                     "throttle": THROTTLE_MBPS}
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    # a rehearsal on 8 virtual CPU devices by design; it must never take
    # (or wait for) an accelerator the parent may hold
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=1800)
    if out.returncode != 0:
        raise RuntimeError(f"fig_restore child failed:\n{out.stdout}\n"
                           f"{out.stderr}")
    line = [l for l in out.stdout.splitlines() if l.startswith("RESULT ")][-1]
    rows = json.loads(line[len("RESULT "):])
    save_results("fig_restore", rows,
                 meta={"n_tensors": n_tensors,
                       "shape": [rows_, cols],
                       "read_throttle_per_stream_mbps": THROTTLE_MBPS})
    return rows


def summarize(rows) -> List[str]:
    lines = []
    by = {(r["format"], r["variant"], r["throttled"]): r for r in rows}
    for fmt in ("datastates", "snapshot", "sync"):
        seed = by.get((fmt, "seed-serial", True))
        par = by.get((fmt, "engine-8", True))
        if seed and par:
            lines.append(
                f"fig_restore/{fmt}/throttled,0,"
                f"seed={seed['seconds']:.2f}s "
                f"par={par['seconds']:.2f}s "
                f"speedup={seed['seconds'] / par['seconds']:.2f}x")
    seed_u = by.get(("datastates", "seed-serial", False))
    par_u = by.get(("datastates", "engine-8", False))
    if seed_u and par_u:
        lines.append(f"fig_restore/datastates/unthrottled,0,"
                     f"seed={seed_u['seconds']:.2f}s "
                     f"par={par_u['seconds']:.2f}s "
                     f"speedup={seed_u['seconds'] / par_u['seconds']:.2f}x")
    snap_seed = by.get(("snapshot", "seed-serial", True))
    snap_eng = by.get(("snapshot", "engine-8", True))
    if snap_seed and snap_eng:
        lines.append(
            f"fig_restore/snapshot/bytes_read,0,"
            f"seed={snap_seed['bytes_read'] / 2**30:.2f}GiB "
            f"engine={snap_eng['bytes_read'] / 2**30:.2f}GiB "
            f"ckpt={snap_eng['ckpt_bytes'] / 2**30:.2f}GiB")
    return lines
