"""Parallel streaming restore engine (the save path's missing twin).

The paper optimizes *capture* (lazy D2H, composable providers, streamlined
flush) but says little about resume; "Understanding LLM Checkpoint/Restore
I/O Strategies and Patterns" (arXiv 2512.24511) measures serial,
data-oblivious reload as the dominant resume cost and ByteCheckpoint
(arXiv 2407.20143) shows parallel re-sharded load is the fix. This module
applies the same discipline to restore that the engine applies to save:

1. **Index once** — every checkpoint file in the step directory is opened
   exactly once and its shard directory (name, global region, byte layout)
   is extracted, whatever the format (native ``.dsllm`` footers,
   TorchSnapshot-style chunk manifests, sync pickled object graphs).
2. **Plan up front** — for every template leaf, the target regions (one per
   unique device shard of the requested sharding — elastic, so the target
   mesh need not match the stored one) are intersected with the stored
   shard regions, producing an explicit list of byte ranges *before* any
   data is read. Coverage is validated at plan time.
3. **Fan out ranged reads** — the byte ranges become positional
   ``os.preadv`` calls over a thread pool, reading *only* intersecting
   bytes (the fixed-offset aligned tensor region of ``layout.py`` makes
   every range computable from the footer alone) directly into
   preallocated destination buffers. ``preadv`` releases the GIL, so
   ranges overlap both each other and the throttled-PFS latency.

Formats without byte-addressable tensors degrade gracefully: sync pickle
graphs are loaded once per *file* per restore (never once per tensor — the
seed's snapshot path re-read whole rank files O(files × tensors) times)
and sliced in memory.

Per-restore :class:`RestoreStats` record the phase split (index / read /
assemble), bytes actually read, and the number of ranged reads issued —
``bytes_read`` is the paper-style evidence that a sub-tree or re-sharded
restore touches only the bytes it needs.
"""

from __future__ import annotations

import concurrent.futures
import dataclasses
import glob
import itertools
import os
import pickle
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.obs import trace as obs

from .codecs import is_chained_codec
from .distributed import normalize_index, _path_str
from .layout import FileReader

Region = Tuple[Tuple[int, int], ...]  # ((start, stop), ...) per dim


class RestoreError(RuntimeError):
    """A checkpoint could not be indexed or did not cover a request."""


@dataclasses.dataclass
class RestoreStats:
    """Phase timings + I/O accounting for one restore."""

    index_s: float = 0.0      # footer/manifest indexing
    plan_s: float = 0.0       # intersection planning
    read_s: float = 0.0       # parallel ranged-read fan-out (wall clock)
    assemble_s: float = 0.0   # host-to-device copies enqueued (not done)
    bytes_read: int = 0       # bytes actually fetched from storage
    n_ranges: int = 0         # ranged reads issued
    n_files: int = 0          # checkpoint files indexed
    n_leaves: int = 0         # template leaves restored
    threads: int = 0          # fan-out width used

    @property
    def total_s(self) -> float:
        return self.index_s + self.plan_s + self.read_s + self.assemble_s


# --------------------------------------------------------------------------
# Byte-range math for C-contiguous stored shards.

def _volume(region: Region) -> int:
    v = 1
    for lo, hi in region:
        v *= max(0, hi - lo)
    return v


def _contiguous_runs(local_region: Region, shape: Tuple[int, ...],
                     itemsize: int):
    """Yield ``(byte_offset, nbytes)`` contiguous runs of ``local_region``
    within a C-contiguous array of ``shape``, in C order.

    Runs are maximal: a suffix of dims fully covered by the region folds
    into its predecessor, so a full-array region is a single run.
    """
    nd = len(shape)
    if nd == 0:
        yield 0, itemsize
        return
    if any(hi <= lo for lo, hi in local_region):
        return
    k = nd
    while k > 0 and local_region[k - 1] == (0, shape[k - 1]):
        k -= 1
    inner = itemsize
    for d in range(k, nd):
        inner *= shape[d]
    if k == 0:
        yield 0, inner
        return
    run_lo, run_hi = local_region[k - 1]
    run_bytes = (run_hi - run_lo) * inner
    # byte strides of the outer (partially covered) dims 0..k-2
    strides = [0] * (k - 1)
    acc = inner * shape[k - 1]
    for d in range(k - 2, -1, -1):
        strides[d] = acc
        acc *= shape[d]
    base = run_lo * inner
    for coords in itertools.product(
            *[range(lo, hi) for lo, hi in local_region[:k - 1]]):
        yield base + sum(c * strides[d] for d, c in enumerate(coords)), \
            run_bytes


def plan_ranged_slices(nbytes: int, slice_bytes: int = 16 << 20
                       ) -> List[Tuple[int, int]]:
    """``[(offset, nbytes), ...]`` fixed-cap slices covering ``[0, nbytes)``.

    The ranged-read splitting discipline shared by the restore engine
    (``_emit_tasks`` splits giant runs so they parallelize across the
    thread pool) and the fleet's peer exchange (which deals the same
    disjoint slices to concurrent replicas so each remote byte is read by
    exactly one of them)."""
    cap = max(1, int(slice_bytes))
    return [(lo, min(cap, nbytes - lo)) for lo in range(0, nbytes, cap)]


def _preadv_full(fd: int, mv: memoryview, offset: int) -> None:
    pos = 0
    end = len(mv)
    while pos < end:
        n = os.preadv(fd, [mv[pos:]], offset + pos)
        if n <= 0:
            raise RestoreError(
                f"short read at offset {offset + pos} (wanted {end - pos} "
                f"more bytes) — truncated checkpoint file?")
        pos += n


class _FDCache:
    """Positional-read fd per file, shared across reader threads."""

    def __init__(self) -> None:
        self._fds: Dict[str, int] = {}
        self._lock = threading.Lock()

    def get(self, path: str) -> int:
        with self._lock:
            fd = self._fds.get(path)
            if fd is None:
                fd = os.open(path, os.O_RDONLY)
                self._fds[path] = fd
            return fd

    def close(self) -> None:
        with self._lock:
            for fd in self._fds.values():
                os.close(fd)
            self._fds.clear()


# --------------------------------------------------------------------------
# Shard sources: one stored shard of a logical array, format-specific.

class _ShardSource:
    """Base: a stored shard covering ``index`` of the global array."""

    __slots__ = ("index", "shape", "dtype")

    def __init__(self, index: Region, shape: Tuple[int, ...], dtype):
        self.index = tuple(tuple(p) for p in index)
        self.shape = tuple(shape)
        self.dtype = np.dtype(dtype)

    def byte_ranges(self, local_region: Region):
        """(file_path, file_offset, nbytes) pieces for ``local_region``,
        in C order of the region. None for non-byte-addressable formats."""
        raise NotImplementedError

    def read_fallback(self, local_region: Region) -> np.ndarray:
        """Materialize ``local_region`` without ranged reads."""
        raise NotImplementedError


class _DsllmShard(_ShardSource):
    """Fixed-offset aligned tensor region in a native ``.dsllm`` file."""

    __slots__ = ("path", "offset")

    def __init__(self, path: str, entry):
        index = entry.index if entry.index is not None \
            else tuple((0, d) for d in entry.shape)
        super().__init__(index, entry.shape, entry.dtype)
        self.path = path
        self.offset = entry.offset

    def byte_ranges(self, local_region: Region):
        for off, nb in _contiguous_runs(local_region, self.shape,
                                        self.dtype.itemsize):
            yield self.path, self.offset + off, nb


class _SnapshotShard(_ShardSource):
    """One tensor spread over TorchSnapshot-style chunk files."""

    __slots__ = ("chunks",)

    def __init__(self, index: Region, shape, dtype,
                 chunks: Sequence[Tuple[str, int, int]]):
        super().__init__(index, shape, dtype)
        # (path, lo, hi): byte interval of the flattened tensor per file
        self.chunks = sorted(chunks, key=lambda c: c[1])

    def byte_ranges(self, local_region: Region):
        for off, nb in _contiguous_runs(local_region, self.shape,
                                        self.dtype.itemsize):
            run_lo, run_hi = off, off + nb
            for path, lo, hi in self.chunks:
                a, b = max(run_lo, lo), min(run_hi, hi)
                if a < b:
                    yield path, a - lo, b - a


class _EncodedShard(_ShardSource):
    """A self-contained encoded tensor (e.g. an int8-quantized optimizer
    moment) in a native file: its compressed log chunks decode without a
    chain base, so it restores standalone — decoded at most once per
    restore (thread-safe), then sliced in memory."""

    __slots__ = ("loader",)

    def __init__(self, index: Region, shape, dtype,
                 loader: Callable[[], np.ndarray]):
        super().__init__(index, shape, dtype)
        self.loader = loader

    def byte_ranges(self, local_region: Region):
        return None

    def read_fallback(self, local_region: Region) -> np.ndarray:
        arr = self.loader()
        return arr[tuple(slice(lo, hi) for lo, hi in local_region)]


class _GraphShard(_ShardSource):
    """A shard inside a pickled object graph (sync format): the graph is
    loaded at most once per restore; slicing happens in memory."""

    __slots__ = ("loader", "name")

    def __init__(self, index: Region, shape, dtype,
                 loader: Callable[[], Dict[str, Any]], name: str):
        super().__init__(index, shape, dtype)
        self.loader = loader
        self.name = name

    def byte_ranges(self, local_region: Region):
        return None

    def read_fallback(self, local_region: Region) -> np.ndarray:
        arr = np.asarray(self.loader()[self.name]["data"])
        return arr[tuple(slice(lo, hi) for lo, hi in local_region)]


class _OnceLoader:
    """Thread-safe load-once wrapper around an expensive whole-file read."""

    def __init__(self, fn: Callable[[], Any], nbytes: int,
                 stats: "RestoreStats", stats_lock: threading.Lock):
        self._fn = fn
        self._nbytes = nbytes
        self._stats = stats
        self._stats_lock = stats_lock
        self._lock = threading.Lock()
        self._value: Any = None
        self._loaded = False

    def __call__(self) -> Any:
        with self._lock:
            if not self._loaded:
                self._value = self._fn()
                self._loaded = True
                with self._stats_lock:
                    self._stats.bytes_read += self._nbytes
                    self._stats.n_ranges += 1
        return self._value


# --------------------------------------------------------------------------

class RestoreIndex:
    """Everything learned from one pass over a step directory."""

    def __init__(self, sdir: str):
        self.sdir = sdir
        self.tensors: Dict[str, List[_ShardSource]] = {}
        # Differential steps: encoded (XOR-domain) shards, keyed like
        # ``tensors`` but holding ``(FileReader, TensorEntry)`` pairs —
        # their payloads are compressed log chunks, not byte-addressable
        # regions, and their values only exist relative to a chain base.
        self.delta_tensors: Dict[str, List[Tuple[Any, Any]]] = {}
        self.objects: Dict[str, Callable[[], Any]] = {}
        self.n_files = 0


class _Run:
    """Per-restore mutable state, so one engine instance (e.g. the manager's
    default) can serve concurrent restores without sharing fd caches."""

    __slots__ = ("stats", "lock", "fds", "flow")

    def __init__(self, stats: RestoreStats):
        self.stats = stats
        self.lock = threading.Lock()
        self.fds = _FDCache()
        # flow-link id tying this restore's index→plan→read→assemble spans
        self.flow = obs.flow_id("restore", id(self) & 0xFFFFFF)


class RestoreEngine:
    """Plans and executes parallel ranged restores from any engine format.

    ``threads`` is the ranged-read fan-out width (``1`` gives a serial
    engine with identical results — used by tests and the restore
    benchmark's ablation). ``throttle_mbps`` emulates per-stream storage
    bandwidth exactly like the save-side engines do, so benchmarks can model
    a bandwidth-limited PFS where read parallelism is the paper-world win.
    ``read_chunk_bytes`` caps a single ``preadv`` so large tensors split
    across the pool instead of serializing behind one thread.
    """

    def __init__(self, threads: Optional[int] = None,
                 throttle_mbps: Optional[float] = None,
                 read_chunk_bytes: int = 16 << 20):
        if threads is None:
            threads = min(16, 4 * (os.cpu_count() or 1))
        self.threads = max(1, int(threads))
        self.throttle_mbps = throttle_mbps
        self.read_chunk_bytes = int(read_chunk_bytes)

    # ------------------------------------------------------------- indexing
    def index(self, sdir: str, stats: Optional[RestoreStats] = None,
              stats_lock: Optional[threading.Lock] = None) -> RestoreIndex:
        """One pass over ``sdir``: build the shard directory for whatever
        checkpoint format lives there (same precedence as the writers:
        native ``.dsllm``, then snapshot manifests, then sync pickles)."""
        stats = stats if stats is not None else RestoreStats()
        stats_lock = stats_lock or threading.Lock()
        idx = RestoreIndex(sdir)

        dsllm = sorted(glob.glob(os.path.join(sdir, "*.dsllm")))
        if dsllm:
            for p in dsllm:
                try:
                    rd = FileReader(p)
                except Exception as exc:
                    raise RestoreError(
                        f"corrupt or truncated checkpoint file {p!r}: {exc} "
                        f"(footer unreadable — was the save interrupted?)"
                    ) from exc
                idx.n_files += 1
                for entry in rd.tensors.values():
                    base = entry.name.split("@[", 1)[0]
                    if entry.codec != "raw" and is_chained_codec(entry.codec):
                        idx.delta_tensors.setdefault(base, []).append(
                            (rd, entry))
                    elif entry.codec != "raw":
                        # self-contained encoding (quantized): restorable
                        # standalone through a decode-once shard source
                        region = entry.index if entry.index is not None \
                            else tuple((0, d) for d in entry.shape)
                        comp_nb = sum(c[1] for c in entry.enc_chunks or ())
                        loader = _OnceLoader(
                            (lambda r=rd, e=entry:
                             r.read_encoded_tensor(e.name)
                             .view(np.dtype(e.dtype)).reshape(e.shape)),
                            comp_nb, stats, stats_lock)
                        idx.tensors.setdefault(base, []).append(
                            _EncodedShard(tuple(map(tuple, region)),
                                          entry.shape, entry.dtype, loader))
                    else:
                        idx.tensors.setdefault(base, []).append(
                            _DsllmShard(p, entry))
                for oname, oe in rd.objects.items():
                    idx.objects[oname] = _OnceLoader(
                        (lambda r=rd, n=oname: r.read_object(n)),
                        oe.nbytes, stats, stats_lock)
            return idx

        manifests = sorted(glob.glob(os.path.join(sdir, "manifest_rank*.pkl")))
        snapshot_objects = os.path.join(sdir, "objects.pkl")
        if manifests or os.path.exists(snapshot_objects):
            for mpath in manifests:
                try:
                    with open(mpath, "rb") as f:
                        manifest = pickle.load(f)
                except Exception as exc:
                    raise RestoreError(
                        f"corrupt or truncated manifest {mpath!r}: {exc}"
                    ) from exc
                idx.n_files += 1
                for t in manifest["tensors"]:
                    base = t["name"].split("@[", 1)[0]
                    chunks = []
                    for cpath, lo, hi in t["chunks"]:
                        if not os.path.exists(cpath):  # step dir was moved
                            cpath = os.path.join(sdir,
                                                 os.path.basename(cpath))
                        chunks.append((cpath, lo, hi))
                        idx.n_files += 1
                    index = t["index"] if t["index"] is not None \
                        else tuple((0, d) for d in t["shape"])
                    idx.tensors.setdefault(base, []).append(_SnapshotShard(
                        tuple(map(tuple, index)), t["shape"], t["dtype"],
                        chunks))
            if os.path.exists(snapshot_objects):
                idx.n_files += 1
                nb = os.path.getsize(snapshot_objects)
                try:
                    with open(snapshot_objects, "rb") as f:
                        objs = pickle.load(f)
                except Exception as exc:
                    raise RestoreError(
                        f"corrupt or truncated object file "
                        f"{snapshot_objects!r}: {exc}") from exc
                with stats_lock:
                    stats.bytes_read += nb
                    stats.n_ranges += 1
                for oname, val in objs.items():
                    idx.objects[oname] = (lambda v=val: v)
            return idx

        pkls = sorted(glob.glob(os.path.join(sdir, "*.pkl")))
        if pkls:
            from .baselines import load_sync_rank
            for p in pkls:
                try:
                    with open(p, "rb") as f:
                        graph = pickle.load(f)
                except Exception as exc:
                    raise RestoreError(
                        f"corrupt or truncated checkpoint file {p!r}: {exc}"
                    ) from exc
                nb = os.path.getsize(p)
                idx.n_files += 1
                # count the (unavoidable) whole-graph load once, at index
                # time — the graph is then sliced in memory, never re-read.
                with stats_lock:
                    stats.bytes_read += nb
                    stats.n_ranges += 1
                loader = (lambda g=graph: g)
                for name, rec in graph.items():
                    if name == "__objects__":
                        for oname, val in rec.items():
                            idx.objects[oname] = (lambda v=val: v)
                        continue
                    base = name.split("@[", 1)[0]
                    arr = np.asarray(rec["data"])
                    index = rec["index"] if rec["index"] is not None \
                        else tuple((0, d) for d in arr.shape)
                    idx.tensors.setdefault(base, []).append(_GraphShard(
                        tuple(map(tuple, index)), arr.shape, arr.dtype,
                        loader, name))
            return idx

        raise FileNotFoundError(f"no checkpoint files in {sdir}")

    # ------------------------------------------------------------- planning
    @staticmethod
    def _leaf_regions(leaf) -> Tuple[List[Region], str]:
        """Target regions for one template leaf: one per unique device
        shard of the requested sharding (elastic), or the full array."""
        shape = tuple(leaf.shape)
        full = tuple((0, d) for d in shape)
        if isinstance(leaf, np.ndarray):
            return [full], "numpy"
        sharding = getattr(leaf, "sharding", None)
        if sharding is None:
            return [full], "jax_full"
        try:
            imap = sharding.addressable_devices_indices_map(shape)
        except (AttributeError, TypeError):
            return [full], "jax_full"
        regions: List[Region] = []
        seen = set()
        for index in imap.values():
            region = normalize_index(index, shape)
            if region not in seen:
                seen.add(region)
                regions.append(region)
        return regions or [full], "jax_sharded"

    def _plan_region(self, run: _Run, sources: List[_ShardSource],
                     region: Region, buf: np.ndarray,
                     tasks: List[Callable[[], Tuple[int, int]]],
                     leaf_name: str) -> None:
        """Intersect ``region`` with the stored shards; append read tasks
        that fill ``buf`` (shaped like ``region``) in place."""
        covered = 0
        for src in sources:
            inter = tuple((max(a, c), min(b, d))
                          for (a, b), (c, d) in zip(region, src.index))
            if any(lo >= hi for lo, hi in inter):
                continue
            covered += _volume(inter)
            src_local = tuple((lo - c, hi - c)
                              for (lo, hi), (c, _d) in zip(inter, src.index))
            dst_sl = tuple(slice(lo - a, hi - a)
                           for (lo, hi), (a, _b) in zip(inter, region))
            dst_view = buf[dst_sl] if dst_sl else buf[...]
            self._emit_tasks(run, src, src_local, dst_view, tasks)
        if covered < _volume(region):
            raise RestoreError(
                f"checkpoint does not cover requested region {region} of "
                f"{leaf_name!r} (stored shards cover {covered} of "
                f"{_volume(region)} elements — wrong template shape, or a "
                f"partially written checkpoint?)")

    def _emit_tasks(self, run: _Run, src: _ShardSource, src_local: Region,
                    dst_view: np.ndarray,
                    tasks: List[Callable[[], Tuple[int, int]]]) -> None:
        ranges = src.byte_ranges(src_local)
        if ranges is None or dst_view.dtype != src.dtype \
                or not dst_view.flags["C_CONTIGUOUS"]:
            # Non-byte-addressable source, a dtype-converting restore
            # (template dtype != stored dtype — raw bytes must not land in
            # the destination; numpy assignment casts values), or a
            # destination view whose memory layout differs from the C order
            # of the ranges: read through a scratch intersection buffer.
            def copy_task(src=src, src_local=src_local, dst_view=dst_view):
                arr = self._read_intersection(run, src, src_local)
                dst_view[...] = arr
                return 0, 0  # byte accounting happens inside the source
            tasks.append(copy_task)
            return
        out = dst_view.reshape(-1).view(np.uint8)
        pos = 0
        cap = self.read_chunk_bytes
        for path, off, nb in ranges:
            # split giant runs so they parallelize
            for lo, piece in plan_ranged_slices(nb, cap):
                mv = memoryview(out[pos + lo:pos + lo + piece])
                tasks.append(self._make_pread_task(run, path, off + lo, mv))
            pos += nb

    def _make_pread_task(self, run: _Run, path: str, offset: int,
                         mv: memoryview) -> Callable[[], Tuple[int, int]]:
        def task():
            t0 = time.perf_counter()
            fd = run.fds.get(path)
            _preadv_full(fd, mv, offset)
            if self.throttle_mbps:  # emulate per-stream PFS bandwidth
                target = len(mv) / (self.throttle_mbps * 1e6)
                elapsed = time.perf_counter() - t0
                if target > elapsed:
                    time.sleep(target - elapsed)
            return len(mv), 1
        return task

    def _read_intersection(self, run: _Run, src: _ShardSource,
                           src_local: Region) -> np.ndarray:
        """Scratch-buffer path for non-contiguous destinations."""
        shape = tuple(hi - lo for lo, hi in src_local)
        ranges = src.byte_ranges(src_local)
        if ranges is None:
            return src.read_fallback(src_local)
        tmp = np.empty(shape, dtype=src.dtype)
        out = tmp.reshape(-1).view(np.uint8)
        pos = 0
        nbytes = 0
        n = 0
        t0 = time.perf_counter()
        for path, off, nb in ranges:
            _preadv_full(run.fds.get(path), memoryview(out[pos:pos + nb]),
                         off)
            pos += nb
            nbytes += nb
            n += 1
        with run.lock:
            run.stats.bytes_read += nbytes
            run.stats.n_ranges += n
        if self.throttle_mbps and nbytes:
            target = nbytes / (self.throttle_mbps * 1e6)
            elapsed = time.perf_counter() - t0
            if target > elapsed:
                time.sleep(target - elapsed)
        return tmp

    # ------------------------------------------------------------- restore
    def _run_tasks(self, run: _Run,
                   tasks: List[Callable[[], Tuple[int, int]]]) -> None:
        """Fan the read/apply tasks over the pool; fold I/O accounting."""
        stats = run.stats
        t0 = time.perf_counter()
        if tasks:
            if self.threads == 1:
                for t in tasks:
                    nb, nr = t()
                    stats.bytes_read += nb
                    stats.n_ranges += nr
            else:
                with concurrent.futures.ThreadPoolExecutor(
                        self.threads) as pool:
                    for nb, nr in pool.map(lambda t: t(), tasks):
                        stats.bytes_read += nb
                        stats.n_ranges += nr
        t1 = time.perf_counter()
        stats.read_s += t1 - t0
        if tasks:
            obs.add_span("restore.read", t0, t1, tasks=len(tasks),
                         flow=run.flow)

    def _read_step(self, run: _Run, sdir: str, template: Any):
        """Index ``sdir``, plan per-leaf regions/buffers, execute the
        ranged-read fan-out. Returns ``(treedef, assembled, idx)`` with
        the host buffers filled but not yet assembled into leaves."""
        stats = run.stats
        t0 = time.perf_counter()
        idx = self.index(sdir, stats, run.lock)
        t1 = time.perf_counter()
        stats.index_s += t1 - t0
        obs.add_span("restore.index", t0, t1, dir=os.path.basename(sdir),
                     flow=run.flow, flow_phase="start")
        stats.n_files += idx.n_files

        # ---- plan: regions, buffers, and the full read-task list
        t0 = time.perf_counter()
        leaves, treedef = jax.tree_util.tree_flatten_with_path(template)
        tasks: List[Callable[[], Tuple[int, int]]] = []
        # (kind, leaf, aux, pstr) per template leaf
        assembled: List[Tuple[str, Any, Any, str]] = []
        for path, leaf in leaves:
            pstr = f"state/{_path_str(path)}"
            if isinstance(leaf, (jax.Array, jax.ShapeDtypeStruct,
                                 np.ndarray)):
                if pstr not in idx.tensors:
                    if pstr in idx.delta_tensors:
                        raise RestoreError(
                            f"tensor {pstr!r} is delta-encoded in {sdir!r} "
                            f"— a differential step cannot be restored "
                            f"alone; replay its chain (restore_chain / "
                            f"CheckpointManager.restore)")
                    raise KeyError(
                        f"tensor {pstr!r} not found in checkpoint "
                        f"(have {sorted(idx.tensors)[:5]}...)")
                stats.n_leaves += 1
                regions, kind = self._leaf_regions(leaf)
                dtype = np.dtype(leaf.dtype)
                buffers: Dict[Region, np.ndarray] = {}
                for region in regions:
                    buf = np.empty(
                        tuple(hi - lo for lo, hi in region), dtype)
                    buffers[region] = buf
                    self._plan_region(run, idx.tensors[pstr], region,
                                      buf, tasks, pstr)
                assembled.append((kind, leaf, buffers, pstr))
            else:
                assembled.append(("object", leaf, None, pstr))
        t1 = time.perf_counter()
        stats.plan_s += t1 - t0
        obs.add_span("restore.plan", t0, t1, leaves=len(assembled),
                     tasks=len(tasks), flow=run.flow)

        self._run_tasks(run, tasks)
        return treedef, assembled, idx

    def _assemble(self, run: _Run, treedef, assembled,
                  idx: RestoreIndex) -> Any:
        """Host buffers -> leaves; objects resolved from ``idx`` (for a
        chain restore: the newest step's object log).

        ``restore.assemble`` and ``RestoreStats.assemble_s`` time the
        enqueue of the host-to-device copies, not their completion. Traced,
        ``restore.h2d`` runs from the same start until the device leaves
        are ready, with their ``bytes``; the caller's next step waits for
        them anyway."""
        stats = run.stats
        t0 = time.perf_counter()
        out = []
        for kind, leaf, aux, pstr in assembled:
            if kind == "object":
                out.append(idx.objects[pstr]()
                           if pstr in idx.objects else leaf)
            elif kind == "numpy":
                out.append(next(iter(aux.values())))
            elif kind == "jax_full":
                out.append(jax.numpy.asarray(next(iter(aux.values()))))
            else:  # jax_sharded
                shape = tuple(leaf.shape)
                buffers = aux

                def cb(index, shape=shape, buffers=buffers):
                    return buffers[normalize_index(index, shape)]
                out.append(jax.make_array_from_callback(
                    shape, leaf.sharding, cb))
        tree = jax.tree_util.tree_unflatten(treedef, out)
        t1 = time.perf_counter()
        stats.assemble_s += t1 - t0
        obs.add_span("restore.assemble", t0, t1, flow=run.flow,
                     flow_phase="end")
        if obs.enabled():
            leaves = jax.block_until_ready(
                [x for x in out if isinstance(x, jax.Array)])
            obs.add_span("restore.h2d", t0, time.perf_counter(),
                         bytes=sum(x.nbytes for x in leaves))
        return tree

    def restore(self, sdir: str, template: Any
                ) -> Tuple[Any, RestoreStats]:
        """Rebuild a ``template``-shaped pytree from ``sdir``.

        Array leaves (``jax.Array``/``ShapeDtypeStruct``/``np.ndarray``)
        are reassembled from whichever stored shards intersect each target
        region; non-array leaves come from the object log (or keep their
        template value). Returns ``(tree, stats)``.
        """
        run = _Run(RestoreStats(threads=self.threads))
        try:
            treedef, assembled, idx = self._read_step(run, sdir, template)
            tree = self._assemble(run, treedef, assembled, idx)
            return tree, run.stats
        finally:
            run.fds.close()

    # ------------------------------------------------------- chain restore
    def restore_chain(self, sdirs: Sequence[str], template: Any
                      ) -> Tuple[Any, RestoreStats]:
        """Replay a differential chain: ``sdirs[0]`` is the keyframe step
        directory, ``sdirs[1:]`` the delta steps in chain order.

        The keyframe restores exactly like a full snapshot (same planned
        ranged-read fan-out, elastic across target shardings); each delta
        step's compressed XOR payloads are then decompressed (once per
        stored shard, whatever the target sharding) and folded into the
        in-place host buffers (kernel-backed XOR). Steps apply strictly
        in chain order, and within a step any raw re-saved tensors
        overwrite *before* XOR folds run, so mixed raw/encoded steps are
        deterministic. Objects (RNG state, data-pipeline cursors, step
        metadata) always come from the *newest* step — every save
        persists its objects in full.
        """
        if not sdirs:
            raise ValueError("restore_chain needs at least one step dir")
        run = _Run(RestoreStats(threads=self.threads))
        try:
            treedef, assembled, idx = self._read_step(run, sdirs[0],
                                                      template)
            for sdir in sdirs[1:]:
                idx = self._apply_delta_dir(run, sdir, assembled)
            tree = self._assemble(run, treedef, assembled, idx)
            return tree, run.stats
        finally:
            run.fds.close()

    def _apply_delta_dir(self, run: _Run, sdir: str,
                         assembled) -> RestoreIndex:
        """Fold one delta step's encoded shards into the leaf buffers."""
        stats = run.stats
        t0 = time.perf_counter()
        idx = self.index(sdir, stats, run.lock)
        t1 = time.perf_counter()
        stats.index_s += t1 - t0
        obs.add_span("restore.index", t0, t1, dir=os.path.basename(sdir),
                     delta=True, flow=run.flow)
        stats.n_files += idx.n_files
        xor_tasks: List[Callable[[], Tuple[int, int]]] = []
        raw_tasks: List[Callable[[], Tuple[int, int]]] = []
        t0 = time.perf_counter()
        for kind, leaf, aux, pstr in assembled:
            if kind == "object":
                continue
            enc = idx.delta_tensors.get(pstr, ())
            raw = idx.tensors.get(pstr, ())
            if not enc and not raw:
                raise RestoreError(
                    f"delta step {sdir!r} does not cover tensor {pstr!r} "
                    f"— the chain was built across a reshard without a "
                    f"keyframe?")
            # one task per stored shard: the payload is decompressed once
            # and folded into every intersecting target region
            for rd, entry in enc:
                xor_tasks.append(self._make_delta_task(run, rd, entry,
                                                       aux, pstr))
            if raw:
                # a raw tensor inside a delta step (re-saved whole):
                # overwrite semantics via the normal ranged-read path —
                # executed as a separate batch *before* the XOR folds so
                # mixed raw/encoded steps stay deterministic
                for region, buf in aux.items():
                    self._plan_region(run, list(raw), region, buf,
                                      raw_tasks, pstr)
        t1 = time.perf_counter()
        stats.plan_s += t1 - t0
        obs.add_span("restore.plan", t0, t1, delta=True, flow=run.flow)
        self._run_tasks(run, raw_tasks)
        self._run_tasks(run, xor_tasks)
        return idx

    def _make_delta_task(self, run: _Run, rd, entry,
                         buffers: Dict[Region, np.ndarray], pstr: str
                         ) -> Callable[[], Tuple[int, int]]:
        def task():
            src_index = entry.index if entry.index is not None \
                else tuple((0, d) for d in entry.shape)
            inters = []
            for region, buf in buffers.items():
                inter = tuple((max(a, c), min(b, d))
                              for (a, b), (c, d) in zip(region, src_index))
                if not any(lo >= hi for lo, hi in inter):
                    inters.append((region, buf, inter))
            if not inters:
                return 0, 0
            dtype = np.dtype(entry.dtype)
            if any(dtype != buf.dtype for _r, buf, _i in inters):
                raise RestoreError(
                    f"{pstr!r}: template dtype != stored dtype {dtype} — "
                    f"dtype-converting restore is not defined for XOR "
                    f"delta chains")
            from .state_provider import xor_bytes
            comp_nb = sum(c[1] for c in entry.enc_chunks or ())
            delta = rd.read_encoded_delta(entry.name) \
                .view(dtype).reshape(entry.shape)
            for region, buf, inter in inters:
                src_sl = tuple(slice(lo - c, hi - c)
                               for (lo, hi), (c, _d) in zip(inter,
                                                            src_index))
                dst_sl = tuple(slice(lo - a, hi - a)
                               for (lo, hi), (a, _b) in zip(inter, region))
                dst_view = buf[dst_sl] if dst_sl else buf[...]
                sub = delta[src_sl] if src_sl else delta[...]
                cur = np.ascontiguousarray(dst_view)
                cur_b = cur.reshape(-1).view(np.uint8)
                sub_b = np.ascontiguousarray(sub).reshape(-1).view(np.uint8)
                folded = xor_bytes(cur_b, sub_b) \
                    .view(cur.dtype).reshape(cur.shape)
                if dst_sl:
                    buf[dst_sl] = folded
                else:
                    buf[...] = folded
            return comp_nb, len(entry.enc_chunks or ())
        return task
