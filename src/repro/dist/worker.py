"""Child-process entry point for the process-per-rank runtime.

One spawned process per writer rank runs :func:`worker_main`: build this
rank's engine, report ``ready`` (with a ``perf_counter`` sample for the
parent's trace clock alignment), then serve ``save`` requests until
``close`` or pipe EOF. The *protocol brain* — barriers, node manifests,
watchdog, the aggregated future — stays in the parent; the child only
does the work a real rank would do locally: drain its shards through its
own engine, write its rank file, and cast its phase-1 vote. The ack is
the ``prepared`` reply itself — the parent-side proxy meets the
collective on the child's behalf.

Fault injection (:class:`~repro.dist.ipc.ProcessFaultSpec`) is fired
*here*, child-side, with ``os.kill(os.getpid(), SIGKILL)`` — uncatchable
and instant, exactly the failure mode a preempted node presents. The
``mid_file`` point tears the rank's own file first (``os.truncate`` to
half size) so the orphaned step carries real on-disk damage.

Module-top imports stay light (stdlib only): the spawn bootstrap imports
this module before the parent learns whether the child even started, and
the heavy stack (numpy/jax/engine) loads inside :func:`worker_main` where
failures are reportable over the pipe.
"""

from __future__ import annotations

import os
import signal
import time
import traceback
from typing import Any, Dict, List, Optional


def _fire_fault(fault: Any, point: str, rank: int, step: int,
                directory: str, filenames: List[str]) -> None:
    if fault is None or not fault.should_fire(point, rank, step):
        return
    if fault.action == "stall":
        time.sleep(fault.stall_s)
        return
    if point == "mid_file":
        # torn write: the file exists at a plausible-but-short size, as
        # if the node died with flush buffers in flight
        for name in filenames:
            path = os.path.join(directory, name)
            try:
                size = os.path.getsize(path)
                os.truncate(path, max(size // 2, 1))
            except OSError:
                pass
    os.kill(os.getpid(), signal.SIGKILL)


def worker_main(conn: Any, rank: int, world: int, mode: str,
                engine_kw: Dict[str, Any], checksum_files: bool,
                fault: Optional[Any] = None) -> None:
    """Serve one rank's saves over ``conn`` until close/EOF."""
    # The parent holds the accelerator (a chip belongs to one process), and
    # a rank only moves host bytes: pin JAX to the CPU before anything here
    # asks for a backend. Env vars are too late — unpickling this function
    # already imported JAX — but the platform config is read at first use.
    import jax
    jax.config.update("jax_platforms", "cpu")
    from repro.core.baselines import rank_file
    from repro.core.engine import CheckpointFuture
    from repro.dist.ipc import decode_record, encode_stats
    from repro.dist.runtime import RANK_ENGINES
    from repro.obs import trace as obs
    from repro.storage.manifest import RankManifest

    lane = f"rank{rank:05d}"
    engine = RANK_ENGINES[mode](label=lane, **engine_kw)
    conn.send(("ready", os.getpid(), time.perf_counter()))
    try:
        while True:
            try:
                msg = conn.recv()
            except EOFError:
                return
            if msg[0] == "close":
                return
            if msg[0] != "save":
                continue
            _, step, directory, payload, objects, delta, trace = msg
            tracer = obs.enable() if trace else None
            try:
                records = [decode_record(p) for p in payload]
                fut = CheckpointFuture(step, directory)
                engine.save(directory, {rank: records}, objects, fut,
                            delta=delta)
                fut.wait_captured()
                fut.wait_persisted()
                files = [os.path.basename(rank_file(directory, rank))]
                _fire_fault(fault, "mid_file", rank, step, directory,
                            files)
                _fire_fault(fault, "after_upload", rank, step, directory,
                            files)
                with obs.span("vote", lane=lane, step=step, rank=rank):
                    vote = RankManifest.build(
                        directory, rank=rank, world=world, step=step,
                        filenames=files, checksum=checksum_files,
                        precomputed=fut.stats.extra.get("file_checksums"))
                    vote.write(directory)
                _fire_fault(fault, "after_vote", rank, step, directory,
                            files)
                _fire_fault(fault, "before_ack", rank, step, directory,
                            files)
                events = tracer.events() if tracer is not None else []
                conn.send(("prepared", step, encode_stats(fut.stats),
                           events))
            except BaseException as exc:  # noqa: BLE001 — report, don't die
                events = tracer.events() if tracer is not None else []
                try:
                    conn.send(("failed", step, repr(exc),
                               traceback.format_exc(), events))
                except (OSError, ValueError, BrokenPipeError):
                    return
            finally:
                if tracer is not None:
                    obs.disable()
    finally:
        try:
            engine.close()
        except Exception:
            pass
        try:
            conn.send(("closed",))
        except (OSError, ValueError, BrokenPipeError):
            pass
        conn.close()
