"""One run of one cell: set-up, the measured window, the check, the result.

The program under test is driven through its public calls only: a
``Trainer`` with a ``CheckpointManager`` built from a ``CheckpointPolicy``,
``Trainer.resume`` and ``load_params_for_serving``. The benchmark brings
its own weights, token feed, timing, fingerprints and reference.

This file holds what every cell shares: the token feed, the timed proxy
of the manager, the policy built from the traffic file's ``policy``, the
set-up's first steps and the check against the reference. What a traffic
kind does in set-up, in the window, for its end-to-end metrics and for
its read-back lives in ``chipbench/traffic/<kind>.py`` (``setup``,
``window``, ``end_to_end``, ``verify``), found by the traffic file's
``kind``.
"""

from __future__ import annotations

import dataclasses
import json
import os
import resource
import shutil
import sys
import threading
import time
from types import SimpleNamespace
from typing import Any, Dict, List, Optional

import numpy as np

from chipbench import flops, spec, traceutil
from chipbench.fingerprint import array_leaves, fingerprint

RUN_DIR = os.path.join(spec.ROOT, ".chipbench_run")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class WindowClosed(Exception):
    """Raised by the feed when the trainer asks for a batch after the
    window's deadline: the trainer's state is whole at that point."""


class TokenFeed:
    """Seeded token rows, uniform over the vocabulary slice; a drop-in for
    the trainer's pipeline (``next_batch``, ``state``, ``restore``)."""

    def __init__(self, seed: int, batch: int, seq_len: int, vocab: int):
        self.seed, self.batch, self.seq_len, self.vocab = (
            int(seed), batch, seq_len, vocab)
        self.step = 0
        self.deadline: Optional[float] = None

    def tokens(self, step: int) -> np.ndarray:
        rng = np.random.default_rng(np.random.SeedSequence(
            [self.seed, step]))
        return rng.integers(0, self.vocab, (self.batch, self.seq_len),
                            dtype=np.int32)

    @property
    def state(self) -> Dict[str, int]:
        return {"seed": self.seed, "step": self.step}

    def restore(self, state: Dict[str, int]) -> None:
        self.seed, self.step = int(state["seed"]), int(state["step"])

    def next_batch(self) -> Dict[str, np.ndarray]:
        if self.deadline is not None and time.perf_counter() >= self.deadline:
            raise WindowClosed
        batch = {"tokens": self.tokens(self.step)}
        self.step += 1
        return batch


class TimedManager:
    """The program's ``CheckpointManager`` with every save timed from the
    call to its commit, and the saved state fingerprinted as it is handed
    over. Everything else passes through."""

    def __init__(self, manager):
        self._manager = manager
        self.saves: List[Dict[str, Any]] = []
        self._waiters: List[threading.Thread] = []

    def __getattr__(self, name):
        return getattr(self._manager, name)

    def save(self, step, state, blocking=False):
        rec = {"step": step, "fp": fingerprint(
                   {"model": state["model"], "optimizer": state["optimizer"]}),
               "data_state": dict(state["meta"]["data_state"])}
        rec["t_call"] = time.perf_counter()
        future = self._manager.save(step, state, blocking=blocking)
        self.saves.append(rec)

        def wait():
            self._manager.wait_for_commit(step)
            rec["t_committed"] = time.perf_counter()

        waiter = threading.Thread(target=wait, name=f"chipbench-commit-{step}")
        waiter.start()
        self._waiters.append(waiter)
        return future

    def drain(self) -> None:
        self._manager.wait_for_capture()
        self._manager.wait_for_persist()
        self._manager.wait_for_commit()
        for w in self._waiters:
            w.join()


class CompileCounter:
    """Counts backend compilations while ``active``."""

    def __init__(self):
        import jax.monitoring

        self.active = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **kw):
        if self.active and "backend_compile" in event:
            self.count += 1


# --------------------------------------------------------------- the model
def program_config(config: Dict[str, Any]):
    """The program's ``ModelConfig`` for a configuration file, with the
    architecture file's layer stack, checked against the file's sizes (the
    architecture file names every width past these three)."""
    from repro.configs import get_config

    arch = spec.arch(config)
    groups = arch.layer_groups(config)
    cfg = dataclasses.replace(get_config(config["repro_config"]),
                              **config["repro_overrides"],
                              layer_groups=groups)
    layers = sum(len(pattern) * count for pattern, count in groups)
    if layers != config["num_hidden_layers"]:
        raise SystemExit(f"chipbench: {config['arch']} stacks {layers} "
                         f"layers, {config['name']} has "
                         f"{config['num_hidden_layers']}")
    want = {"d_model": config["hidden_size"], "vocab": config["vocab_size"],
            "n_layers": config["num_hidden_layers"]}
    want.update(arch.program_widths(config))
    got = {k: getattr(cfg, k) for k in want}
    if got != want:
        raise SystemExit(f"chipbench: program config {config['repro_config']}"
                         f" differs from {config['name']}: {got} != {want}")
    return cfg


def check_layout(ours, theirs) -> None:
    import jax

    a = jax.tree_util.tree_structure(ours)
    b = jax.tree_util.tree_structure(theirs)
    if a != b:
        raise SystemExit(f"chipbench: parameter layout differs from the "
                         f"program's:\n{a}\n{b}")
    for (p, x), y in zip(jax.tree_util.tree_leaves_with_path(ours),
                         jax.tree_util.tree_leaves(theirs)):
        if x.shape != y.shape or x.dtype != y.dtype:
            raise SystemExit(f"chipbench: {jax.tree_util.keystr(p)} is "
                             f"{x.shape} {x.dtype}, the program's "
                             f"{y.shape} {y.dtype}")


def delete_arrays(tree) -> None:
    for x in array_leaves(tree):
        if not x.is_deleted():
            x.delete()


def abstract(tree):
    import jax

    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)
        if isinstance(x, jax.Array) else x, tree)


def drop_page_cache(directory: str) -> None:
    """Evict a step's files from the page cache, so a read is cold."""
    for base, _dirs, files in os.walk(directory):
        for name in files:
            fd = os.open(os.path.join(base, name), os.O_RDONLY)
            try:
                os.posix_fadvise(fd, 0, 0, os.POSIX_FADV_DONTNEED)
            finally:
                os.close(fd)


KEEP_LAST_N = 2                  # retention: the newest two versions
SETUP_STEPS = 3                  # the steps the reference follows
SNAPSHOT_PROVIDERS = ("delta",)  # keep the domain's last version in the cache


def make_policy(policy: Dict[str, Any], domain_bytes: Dict[str, int]):
    """The program's ``CheckpointPolicy`` from a traffic file's ``policy``:
    a host cache of ``host_cache_versions`` versions, one where not given
    (plus the snapshot of each domain routed to a provider that keeps
    one), a provider rule for
    each entry of ``routes`` (the rest stays on the stock tensor and object
    routes), and ``delta``, where given, as the ``DeltaPolicy``. Manifest
    checksums are on and retention keeps ``KEEP_LAST_N`` versions."""
    from repro.core import (CheckpointPolicy, DeltaPolicy, EnginePolicy,
                            StateProviderRegistry, StoragePolicy)
    from repro.storage.repository import RetentionPolicy

    routes = policy.get("routes", [])
    cache = policy.get("host_cache_versions", 1) * sum(domain_bytes.values())
    cache += sum(domain_bytes[r["domain"]] for r in routes
                 if r["provider"] in SNAPSHOT_PROVIDERS)
    registry = None
    if routes:
        registry = StateProviderRegistry()
        for r in routes:
            registry = registry.add_rule(**r)
        registry = (registry.add_rule(provider="tensor", kind="tensor")
                    .add_rule(provider="object", kind="object"))
    delta = DeltaPolicy(**policy["delta"]) if "delta" in policy else None
    return CheckpointPolicy(
        engine=EnginePolicy(host_cache_bytes=cache),
        storage=StoragePolicy(
            manifest_checksums=True,
            retention=RetentionPolicy(keep_last_n=KEEP_LAST_N)),
        delta=delta, providers=registry)


def warm_routes(policy: Dict[str, Any], state: Dict[str, Any]) -> None:
    """Run the warm-up of each route's provider that has one, on the
    leaves of its domain."""
    from repro.core import EnginePolicy

    for r in policy.get("routes", []):
        path = os.path.join(spec.WARM_DIR, r["provider"] + ".py")
        if os.path.exists(path):
            spec.load_module(spec.WARM_DIR, r["provider"]).warm(
                array_leaves(state[r["domain"]]), EnginePolicy().chunk_bytes)


# ----------------------------------------------------------------- the run
class Run:
    """Set-up, window and check of one cell; ``run`` strings them
    together. The traffic kind's module fills in ``t0``, ``t1``,
    ``steps`` and ``ops`` in its window."""

    def __init__(self, cell: spec.Cell, seed: int, seconds: float,
                 run_dir: str = RUN_DIR):
        self.seed, self.seconds = seed, seconds
        self.config, self.traffic, self.kind = (cell.config, cell.traffic,
                                                cell.kind)
        self.ckpt_dir = os.path.join(run_dir, "ckpt", cell.name)
        self.checks: Dict[str, Dict[str, float]] = {}
        self.steps, self.ops = 0, []

    # -------------------------------------------------------------- set-up
    def setup(self) -> None:
        import jax

        from repro.core import CheckpointManager
        from repro.optim.adamw import AdamWConfig
        from repro.training.loop import Trainer

        from chipbench import reference as ref

        cfg, tr = self.config, self.traffic
        pcfg = program_config(cfg)
        shutil.rmtree(self.ckpt_dir, ignore_errors=True)
        os.makedirs(self.ckpt_dir)
        params, opt = ref.make_state(cfg, self.seed)
        domain_bytes = {
            "model": sum(x.nbytes for x in array_leaves(params)),
            "optimizer": sum(x.nbytes for x in array_leaves(opt))}
        self.version_bytes = sum(domain_bytes.values())
        self.manager = TimedManager(CheckpointManager.from_policy(
            self.ckpt_dir, make_policy(tr["policy"], domain_bytes)))
        trainer = Trainer(pcfg, batch=tr["batch"], seq_len=tr["seq_len"],
                          hp=AdamWConfig(**cfg["optimizer"]),
                          manager=self.manager, seed=0)
        check_layout(params, trainer.params)
        delete_arrays((trainer.params, trainer.opt_state))
        trainer.params, trainer.opt_state = params, opt
        self.feed = TokenFeed(self.seed, tr["batch"], tr["seq_len"],
                              cfg["vocab_size"])
        trainer.pipeline = self.feed
        self.trainer = trainer
        self.n_model = len(array_leaves(params))
        del params, opt

        # the first steps, through the window's own call and feed
        b1 = cfg["optimizer"]["b1"]
        trainer.run(1)
        self.prog_grad = np.asarray(
            ref.leaf_norms(trainer.opt_state["m"])) / (1.0 - b1)
        self.setup_save_at = tr.get("setup_save_at") or 0
        trainer.run(SETUP_STEPS - 1, ckpt_interval=self.setup_save_at)
        self.prog_losses = [r.loss for r in trainer.records]
        p0 = ref.make_params(cfg, self.seed)
        self.prog_change = np.asarray(
            ref.diff_norms(trainer.opt_state["master"], p0))
        delete_arrays(p0)
        self.fp_after_setup = fingerprint(
            {"model": trainer.params, "optimizer": trainer.opt_state})
        warm_routes(tr["policy"], {"model": trainer.params,
                                   "optimizer": trainer.opt_state})
        self.kind.setup(self)
        jax.block_until_ready((trainer.params, trainer.opt_state))

    # -------------------------------------------------------------- window
    def window(self) -> None:
        self.kind.window(self)

    def window_saves(self) -> List[Dict[str, Any]]:
        return [s for s in self.manager.saves
                if self.t0 <= s["t_call"] <= self.t1]

    def end_to_end(self) -> Dict[str, float]:
        return self.kind.end_to_end(self)

    def metric_context(self, spans, ops, modules) -> SimpleNamespace:
        import jax

        peaks = spec.load_json(os.path.join(spec.HERE, "peaks.json"))
        kind = jax.devices()[0].device_kind
        tr = self.traffic
        return SimpleNamespace(
            window=(self.t0, self.t1), steps=self.steps,
            tokens=self.steps * tr["batch"] * tr["seq_len"],
            flops_per_token=flops.train_flops_per_token(self.config,
                                                        tr["seq_len"]),
            peak=peaks.get(kind), spans=spans, device=ops, modules=modules,
            saves=self.window_saves(), ops=self.ops, flops=flops,
            traceutil=traceutil)

    # ---------------------------------------------------------------- check
    def check(self) -> bool:
        from chipbench import checks, reference as ref

        t0 = time.perf_counter()
        bad = self.kind.verify(self)
        self.verify_s = time.perf_counter() - t0
        delete_arrays((self.trainer.params, self.trainer.opt_state))
        batches = [self.feed.tokens(i) for i in range(SETUP_STEPS)]
        p0 = ref.make_params(self.config, self.seed)
        want = ref.train_reference(self.config, p0, batches)
        delete_arrays(p0)
        extra = [o["loss"] for o in self.ops if "loss" in o]
        numbers = checks.compare(
            prog_losses=self.prog_losses[:SETUP_STEPS],
            extra_step3_losses=extra, prog_grad=self.prog_grad,
            prog_change=self.prog_change, ref=want)
        numbers["ckpt_mismatch"] = bad
        self.checks = checks.judge(numbers, self.config["limits"])
        return all(c["limit"] is not None and c["value"] <= c["limit"]
                   for c in self.checks.values())


def run(cell: spec.Cell, seed: int, seconds: float, trace: bool,
        t_process: float, run_dir: str = RUN_DIR) -> Dict[str, Any]:
    """One run of ``cell``, past the look for a chip; the result line."""
    import jax

    r = Run(cell, seed, seconds, run_dir)
    counter = CompileCounter()
    try:
        r.setup()
        setup_s = time.perf_counter() - t_process
        tracer = None
        trace_dir = os.path.join(run_dir, "trace", cell.name)
        if trace:
            from repro.obs import trace as obs

            shutil.rmtree(trace_dir, ignore_errors=True)
            tracer = obs.enable()
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0   # host Python frames: costly
            jax.profiler.start_trace(trace_dir, profiler_options=options)
        counter.active = True
        if trace:
            with jax.profiler.TraceAnnotation(traceutil.WINDOW_ANNOTATION):
                host_t0 = time.perf_counter()
                r.window()
        else:
            r.window()
        counter.active = False
        t_drain = time.perf_counter()
        r.manager.drain()
        t_drained = time.perf_counter()
        if trace:
            jax.profiler.stop_trace()
            obs.disable()
        log(f"set-up {setup_s:.3f} s; window {r.t1 - r.t0:.3f} s, "
            f"{r.steps} steps, {len(r.window_saves())} saves, {len(r.ops)} "
            f"ops; {counter.count} compiles in the window; drain "
            f"{t_drained - t_drain:.3f} s")
        stats = jax.devices()[0].memory_stats() or {}
        peak = stats.get("peak_bytes_in_use")
        device = {"platform": jax.devices()[0].platform,
                  "kind": jax.devices()[0].device_kind,
                  "count": len(jax.devices()),
                  "memory_peak_bytes": peak}
        result: Dict[str, Any] = {}
        if trace:
            spans = tracer.spans()
            data = traceutil.read_xplane(traceutil.find_xplane(trace_dir))
            ops, modules = [], []
            if data["ops"] and data["window"]:
                plane = sorted(data["ops"])[0]
                ops = traceutil.to_host_clock(
                    data["ops"][plane], data["window"][0], host_t0)
                modules = traceutil.to_host_clock(
                    data["modules"].get(plane, []), data["window"][0],
                    host_t0)
            ctx = r.metric_context(spans, ops, modules)
            metrics = spec.read_per_layer(cell.per_layer, ctx)
            busy = [(a, b) for _n, a, b in ops]
            device["busy_s"] = traceutil.union_length(
                traceutil.clip(busy, r.t0, r.t1))
            device["window_s"] = r.t1 - r.t0
            host = [(s["name"], s["t0"], s["t1"]) for s in spans
                    if s["lane"] == "MainThread"] + \
                [(o["op"], o["t0"], o["t1"]) for o in r.ops]
            result["breakdown"] = {
                "device_ops": traceutil.top_ops(modules, r.t0, r.t1),
                "idle_gaps": traceutil.label_gaps(
                    traceutil.gaps(busy, r.t0, r.t1), host)}
            shutil.rmtree(trace_dir, ignore_errors=True)
        else:
            e2e = r.end_to_end()
            e2e["setup_s"] = setup_s
            units = {m["name"]: m["unit"] for m in cell.end_to_end}
            metrics = {k: {"value": v, "unit": units[k]}
                       for k, v in e2e.items() if k in units}
        rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
        log(f"[observation] device peak {peak} bytes, host max RSS {rss} "
            f"bytes, one version {r.version_bytes} bytes")
        t_check = time.perf_counter()
        correct = r.check()
        log(f"read-back and reference took {time.perf_counter() - t_check:.3f}"
            f" s, read-back {r.verify_s:.3f} s")
        saves = r.window_saves()
        attempted = r.steps + len(saves) + len(r.ops)
        errors = {step for step, _err in r.manager.commit_errors}
        failed = sum(1 for s in saves if s["step"] in errors)
        for name, c in r.checks.items():
            log(f"check {name} {c['value']!r} limit {c['limit']!r}")
        out = {"correct": bool(correct), "attempted": attempted,
               "failed": failed, "metrics": metrics, "device": device}
        out.update(result)
        out["checks"] = r.checks
        return out
    finally:
        manager = getattr(r, "manager", None)
        if manager is not None:
            manager.close()
        shutil.rmtree(r.ckpt_dir, ignore_errors=True)


def main(argv=None, t_process: Optional[float] = None) -> int:
    import argparse

    t_process = time.perf_counter() if t_process is None else t_process
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = spec.Cell(spec.benchmark(), args.workload)
    sys.path.insert(0, os.path.join(spec.ROOT, "src"))
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        log(f"chipbench: JAX finds no TPU (platform "
            f"{devices[0].platform!r}); nothing was run")
        return 2
    if len(devices) < cell.chips:
        log(f"chipbench: {cell.name} needs {cell.chips} chips, JAX finds "
            f"{len(devices)}")
        return 2
    peaks = spec.load_json(os.path.join(spec.HERE, "peaks.json"))
    if devices[0].device_kind not in peaks:
        log(f"chipbench: no peaks for device kind "
            f"{devices[0].device_kind!r}")
        return 2
    result = run(cell, args.seed, args.seconds, bool(args.trace), t_process)
    print(json.dumps(result), flush=True)
    return 0
