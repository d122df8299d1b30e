"""Training launcher.

Runs a (reduced or full) config with the two-phase lazy-checkpoint loop.
On this CPU container it is used with ``--smoke`` (reduced configs) — the
end-to-end driver for examples and the checkpointing benchmarks. On a real
TPU cluster the same entrypoint runs the full configs under
``make_production_mesh()``.

    PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b --smoke \
        --steps 20 --ckpt-interval 5 --engine datastates --ckpt-dir /tmp/ckpt
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family variant (CPU-sized)")
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--ckpt-interval", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--engine", default="datastates",
                    choices=["datastates", "datastates-old", "snapshot",
                             "sync"])
    ap.add_argument("--host-cache-mb", type=int, default=512)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--json", default=None, help="write iteration records")
    args = ap.parse_args(argv)

    from repro.configs import get_config, smoke_variant
    from repro.core import CheckpointManager
    from repro.launch.compile_cache import enable_compile_cache
    from repro.training.loop import Trainer

    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_variant(cfg)

    manager = None
    if args.ckpt_interval:
        from repro.core import CheckpointPolicy, EnginePolicy
        manager = CheckpointManager.from_policy(
            args.ckpt_dir, CheckpointPolicy(engine=EnginePolicy(
                mode=args.engine,
                host_cache_bytes=args.host_cache_mb << 20)))
    trainer = Trainer(cfg, batch=args.batch, seq_len=args.seq_len,
                      manager=manager)
    if args.resume and manager is not None and manager.latest_step() is not None:
        step = trainer.resume()
        print(f"resumed from step {step}")

    t0 = time.perf_counter()
    records = trainer.run(args.steps, ckpt_interval=args.ckpt_interval)
    wall = time.perf_counter() - t0
    losses = [r.loss for r in records]
    stalls = [r.ckpt_stall_s for r in records]
    print(f"arch={cfg.name} steps={len(records)} wall={wall:.2f}s "
          f"final_loss={losses[-1]:.4f} "
          f"ckpt_stall_total={sum(stalls)*1e3:.1f}ms")
    assert all(np.isfinite(l) for l in losses), "NaN loss"
    if args.json:
        with open(args.json, "w") as f:
            json.dump([r.__dict__ for r in records], f, indent=2)
    if manager is not None:
        manager.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
