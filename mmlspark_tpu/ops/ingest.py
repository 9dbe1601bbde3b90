"""Host -> device ingest pipeline.

The reference streams rows into the native dataset in micro-batches
(StreamingPartitionTask.scala:203-277, pushDenseMicroBatches) so JVM
marshaling overlaps native ingestion. The TPU analog: ``device_put`` is
asynchronous, so chunking a large host array overlaps the host-side
prep of chunk i+1 (dtype narrowing, contiguity copy) with the wire
transfer of chunk i. For an ndarray that is double buffering without
threads; a column of separate row arrays (:class:`RowSource`) is laid
out a chunk at a time by a few worker threads, into staging buffers
that are reused, while the calling thread puts the chunk before.
Binned GBDT matrices additionally narrow to uint8 (max_bin <= 256),
cutting bytes on the wire 4x vs int32; XLA's implicit integer promotion
makes the narrow dtype free on device (gathers/adds fuse the widening).
"""

from __future__ import annotations

import copy
import functools
import json
import os
import struct
import time
import zlib
from typing import Any, Iterator, List, Optional, Sequence, Set

import numpy as np

from mmlspark_tpu.core.faults import FaultInjected, fault_point
from mmlspark_tpu.core.serialize import DiskFull
from mmlspark_tpu.core.timer import span


# what one ``device_put`` of a chunk carries
CHUNK_BYTES = 64 << 20
# staging buffers a row source's chunks take in turn: one being put, one
# being filled, one whose transfer may not have landed yet
STAGING_DEPTH = 3


class RowSource:
    """A column whose rows are separate arrays (a DataFrame's object
    column) standing for the ``(n,) + row shape`` array they would
    stack to, which is never made on the host in one piece.

    ``shape``, ``dtype`` and ``nbytes`` are that array's;
    ``lay_out(a, b)`` gives its rows ``[a, b)``, C-contiguous and cast
    to ``dtype`` as ``astype`` would. Rows of unequal shape, or no rows,
    raise here what ``np.stack`` raises, before anything is put.
    ``span_name`` is the span each lay-out is timed under: the front end
    that owns the column names it.
    """

    def __init__(self, rows, span_name: str, dtype: Optional[Any] = None):
        rows = [np.asarray(r) for r in rows]
        if not rows:
            raise ValueError("need at least one array to stack")
        if len({r.shape for r in rows}) != 1:
            raise ValueError("all input arrays must have the same shape")
        if dtype is None:
            dtype = np.result_type(*{r.dtype for r in rows})
        self._rows, self.span_name = rows, span_name
        self.shape = (len(rows),) + rows[0].shape
        self.dtype = np.dtype(dtype)

    @property
    def ndim(self) -> int:
        return len(self.shape)

    @property
    def nbytes(self) -> int:
        return int(np.prod(self.shape, dtype=np.int64)) * self.dtype.itemsize

    def __len__(self) -> int:
        return self.shape[0]

    def astype(self, dtype) -> "RowSource":
        """The same rows, to be laid out in ``dtype``."""
        new = copy.copy(self)
        new.dtype = np.dtype(dtype)
        return new

    def window(self, start: int, rows: int) -> "RowSource":
        """``rows`` rows from ``start`` on: past the last row, rows of
        zeros (a scorer's padding of a short last group)."""
        new = copy.copy(self)
        new._rows = self._rows[start:start + rows]
        new.shape = (rows,) + self.shape[1:]
        return new

    def lay_out(self, a: int, b: int, out: Optional[np.ndarray] = None):
        """Rows ``[a, b)`` as one array: ``out`` if given, so that no
        memory is touched for the first time. One copy a row, for each
        of which numpy releases the GIL: a worker thread can run it."""
        if out is None:
            out = np.empty((b - a,) + self.shape[1:], self.dtype)
        real = self._rows[a:b]
        for k, row in enumerate(real):
            out[k] = row
        out[len(real):] = 0
        return out


def _writable(buf: np.ndarray, left) -> bool:
    """May ``buf`` be written again, ``left`` being the device array of
    the chunk that last left it? Not before the transfer has landed; and
    never where the runtime took the host buffer itself for the array
    (XLA:CPU does, given an aligned one): the array then reads ``buf``
    until the concatenate has run."""
    left.block_until_ready()
    lo = buf.ctypes.data
    return not any(lo <= s.data.unsafe_buffer_pointer() < lo + buf.nbytes
                   for s in left.addressable_shards)


def _layout_threads() -> int:
    """Threads that lay one chunk out, each a share of its rows. A
    616 MB image column on the chip's 13-core host took 0.098, 0.076,
    0.075 and 0.086 s with 1, 2, 4 and 8 (PERF.md section 6, PR 32):
    from two on the transfer sets the pace, and the copies are bound by
    memory, not by cores."""
    cores = (len(os.sched_getaffinity(0))
             if hasattr(os, "sched_getaffinity") else os.cpu_count() or 1)
    return min(4, cores)


def _staged_chunks(source: RowSource, bounds, staging: list, parts: list):
    """Yield ``source``'s rows for each of ``bounds`` in turn, laid out
    in one of ``staging``'s buffers by worker threads while the caller
    puts the chunk before (it appends each chunk's device array to
    ``parts``). The wait for the workers, and for a buffer to become
    writable, is the source's span, on the calling thread."""
    from concurrent.futures import ThreadPoolExecutor

    row_shape, depth = source.shape[1:], len(staging)
    most = max(b - a for a, b in bounds)
    threads = _layout_threads()

    def start(j):
        a, b = bounds[j]
        buf = staging[j % depth]
        if (buf is None or buf.shape[1:] != row_shape
                or buf.dtype != source.dtype or len(buf) < b - a
                or (j >= depth and not _writable(buf, parts[j - depth]))):
            buf = staging[j % depth] = np.empty((most,) + row_shape,
                                                source.dtype)
        cuts = np.linspace(0, b - a, threads + 1).astype(int)
        return buf[:b - a], [
            pool.submit(source.lay_out, a + s, a + e, buf[s:e])
            for s, e in zip(cuts, cuts[1:]) if e > s]

    with ThreadPoolExecutor(max_workers=threads) as pool:
        ahead = []
        for i in range(len(bounds)):
            with span(source.span_name):
                # this chunk and the next: the one after would wait for
                # the last put to land before this one is on the wire
                while len(ahead) < min(i + depth - 1, len(bounds)):
                    ahead.append(start(len(ahead)))
                part, shares = ahead[i]
                for share in shares:
                    share.result()
            yield part


@functools.lru_cache(maxsize=None)
def _concatenate(sharding):
    """The jitted concatenate of a put's chunks, one a result sharding:
    made once, so JAX caches its programs by the chunks' shapes."""
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda *p: jnp.concatenate(p, axis=0),
                   out_shardings=sharding)


def chunked_device_put(arr, sharding=None,
                       dtype: Optional[Any] = None,
                       chunk_bytes: int = CHUNK_BYTES,
                       row_multiple: int = 1,
                       span_name: str = "dataPreparation.transfer",
                       staging: Optional[list] = None):
    """Transfer ``arr``, an ndarray or a :class:`RowSource`, to device
    in async chunks; returns the device array (concatenated under one
    jit so the result carries ``sharding``).

    ``row_multiple``: chunk row counts stay multiples of this (the mesh
    dp axis size when sharded). An array of one chunk or less falls
    through to one put, a row source being laid out whole first.

    Each chunk's put is a span ``span_name`` (the chunk's bytes on the
    wire, and ``chunks``, how many the whole put has): the host's share
    of the transfer, which is an ndarray's narrowing copy and the
    enqueue. A row source's chunks are laid out under its own span, by
    worker threads, into ``staging`` (``STAGING_DEPTH`` buffers that
    the caller keeps from call to call, so that no lay-out touches
    fresh memory; a list of ``None`` to begin with).

    An ndarray's put waits for nothing, so the copies still in flight at
    the end are paid for by whoever first needs the array. A row
    source's waits, in the last chunk's span, until the concatenated
    array is there: the staging buffers are then the caller's again.
    """
    import jax
    import jax.numpy as jnp

    source = arr if isinstance(arr, RowSource) else None
    row_nbytes = int(np.dtype(dtype if dtype is not None
                              else arr.dtype).itemsize
                     * np.prod(arr.shape[1:], dtype=np.int64))
    n = arr.shape[0]
    chunk_rows = max(chunk_bytes // max(row_nbytes, 1), 1)
    chunk_rows = max(chunk_rows // row_multiple, 1) * row_multiple

    def prep(part):
        part = np.ascontiguousarray(part)
        if dtype is not None:
            part = part.astype(dtype, copy=False)
        return part

    if chunk_rows >= n:
        if source is not None:
            with span(source.span_name):
                arr = source.lay_out(0, n)
        with span(span_name, bytes=n * row_nbytes, chunks=1):
            full = prep(arr)
            return (jax.device_put(full, sharding) if sharding is not None
                    else jnp.asarray(full))

    bounds = [(s, min(s + chunk_rows, n)) for s in range(0, n, chunk_rows)]
    parts = []
    if source is None:
        laid = (arr[a:b] for a, b in bounds)
    else:
        laid = _staged_chunks(source, bounds, staging if staging is not None
                              else [None] * STAGING_DEPTH, parts)
    for part in laid:
        with span(span_name, bytes=len(part) * row_nbytes,
                  chunks=len(bounds)):
            # device_put returns immediately: the next chunk's host prep
            # overlaps this chunk's transfer. Each chunk carries the final
            # sharding (chunk rows are row_multiple-aligned), so shards go
            # straight to their devices — no single-device staging
            part = prep(part)
            parts.append(jax.device_put(part, sharding)
                         if sharding is not None
                         and len(part) % row_multiple == 0
                         else jax.device_put(part))
            if len(parts) == len(bounds):
                whole = _concatenate(sharding)(*parts)
                if source is not None:
                    whole.block_until_ready()
    return whole


def binned_ingest_dtype(total_bins: int):
    """Narrowest integer dtype holding bin ids in [0, total_bins).

    The single source of truth for bin-id dtype selection (binned
    scoring gathers run in the input dtype, so narrower moves fewer
    bytes): uint8 for the common <=256-bin configs, uint16 up to 65536
    (derived binnings from deep imported models can exceed 256
    thresholds per feature), int32 beyond."""
    if total_bins <= 256:
        return np.uint8
    if total_bins <= 65536:
        return np.uint16
    return np.int32


# -- spill-directory chunk store (out-of-core training plane) ---------------
#
# The out-of-core GBDT fit streams pre-binned row chunks from disk instead
# of holding the (N, F) binned matrix resident. The format is deliberately
# dumb: one framed file per chunk plus a JSON manifest, written append-only
# and sealed by an atomic manifest rename, so a partially written spill is
# never mistaken for a complete one.
#
# Chunk frame (since v2): MAGIC | header-len (uint32 LE) | JSON header
# {version, dtype, shape, nbytes, crc32} | raw C-order payload bytes.
# The crc32 (stdlib zlib) turns silent disk bit-rot into an attributed
# SpillCorrupt instead of wrong trees: the filesystem is NOT trusted
# (arXiv:1605.08695 treats checksummed persistence I/O as table stakes).
# Verification policy comes from MMLSPARK_TPU_SPILL_VERIFY
# (see resolve_spill_verify); the cost is accounted per reader/store so
# hist_stats can stamp it.

_SPILL_MANIFEST = "spill_meta.json"
_FRAME_MAGIC = b"MMSC"        # "mmlspark spill chunk"
_FRAME_VERSION = 1
_VERIFY_MODES = ("auto", "off", "on")


class SpillCorrupt(RuntimeError):
    """An on-disk chunk failed structural or checksum validation
    (truncation, bad magic, crc32 mismatch, missing file). Carries
    ``chunk`` (index, when known) and ``path`` so OOC failures are
    attributable to one artifact."""

    def __init__(self, message: str, *, chunk: Optional[int] = None,
                 path: Optional[str] = None) -> None:
        super().__init__(message)
        self.chunk = chunk
        self.path = path


def resolve_spill_verify() -> str:
    """MMLSPARK_TPU_SPILL_VERIFY policy: ``auto`` (default — always
    verify checkpoint payload digests, verify each spill chunk's crc32
    on its first read), ``on`` (verify every read), ``off`` (trust the
    disk). A bad value warns once and falls back to auto."""
    from mmlspark_tpu.core.env import env_str
    from mmlspark_tpu.core.logging_utils import warn_once
    v = (env_str("MMLSPARK_TPU_SPILL_VERIFY", "auto") or "auto")
    v = v.strip().lower() or "auto"
    if v not in _VERIFY_MODES:
        warn_once("spill.verify.mode",
                  "MMLSPARK_TPU_SPILL_VERIFY=%r is not one of %s; "
                  "using 'auto'", v, "|".join(_VERIFY_MODES))
        v = "auto"
    return v


def pack_frame(arr: np.ndarray) -> bytes:
    """Serialize one array to the framed chunk format (header + crc32
    over the payload bytes)."""
    c = np.ascontiguousarray(arr)
    payload = c.tobytes()
    header = json.dumps({
        "version": _FRAME_VERSION, "dtype": c.dtype.name,
        "shape": list(c.shape), "nbytes": len(payload),
        "crc32": zlib.crc32(payload) & 0xFFFFFFFF,
    }, separators=(",", ":")).encode()
    return (_FRAME_MAGIC + struct.pack("<I", len(header))
            + header + payload)


def write_chunk(path: str, arr: np.ndarray) -> None:
    """Atomically persist one framed chunk (tmp + ``os.replace``).

    Every spill-plane write funnels through the ``io.disk_full`` fault
    boundary: a real ENOSPC/quota OSError — or an armed fault — comes
    back as the attributed :class:`~mmlspark_tpu.core.serialize.
    DiskFull` so callers can degrade (OOC falls back in-core) instead
    of surfacing a bare write error."""
    frame = pack_frame(arr)
    tmp = path + ".tmp"
    try:
        fault_point("io.disk_full")
        with open(tmp, "wb") as fh:
            fh.write(frame)
        os.replace(tmp, path)
    except (OSError, FaultInjected) as e:
        raise DiskFull(
            f"[io.disk_full] spill chunk write failed for {path} "
            f"({type(e).__name__}: {e})") from e


def read_chunk(path: str, *, verify: bool = True,
               chunk: Optional[int] = None,
               label: str = "spill") -> tuple:
    """Load one framed chunk; returns ``(array, verify_seconds)``.

    Structural damage (missing file, truncation, bad magic/header) and
    — when ``verify`` — a crc32 mismatch raise :class:`SpillCorrupt`
    with expected/actual byte counts. The payload passes through the
    ``spill.read`` fault point before the checksum, so an armed
    ``corrupt`` action is caught exactly like real bit-rot."""
    where = f"{label} chunk {chunk}" if chunk is not None else label
    try:
        with open(path, "rb") as fh:
            blob = fh.read()
    except OSError as e:
        raise SpillCorrupt(
            f"{where}: chunk file missing or unreadable at {path} "
            f"({type(e).__name__}: {e})", chunk=chunk, path=path) from e
    if len(blob) < 8 or blob[:4] != _FRAME_MAGIC:
        raise SpillCorrupt(
            f"{where}: {path} is not a framed spill chunk (expected "
            f"magic {_FRAME_MAGIC!r} + header, found {len(blob)} "
            f"bytes)", chunk=chunk, path=path)
    (hlen,) = struct.unpack("<I", blob[4:8])
    try:
        header = json.loads(blob[8:8 + hlen])
        expected = int(header["nbytes"])
        stored_crc = int(header["crc32"])
        dtype = np.dtype(header["dtype"])
        shape = tuple(int(s) for s in header["shape"])
    except Exception as e:
        raise SpillCorrupt(
            f"{where}: torn frame header in {path} "
            f"({type(e).__name__}: {e})", chunk=chunk, path=path) from e
    payload = blob[8 + hlen:]
    if len(payload) != expected:
        raise SpillCorrupt(
            f"{where}: truncated payload in {path} — expected "
            f"{expected} bytes, found {len(payload)}",
            chunk=chunk, path=path)
    payload = fault_point("spill.read", payload)
    verify_s = 0.0
    if verify:
        t0 = time.perf_counter()
        crc = zlib.crc32(payload) & 0xFFFFFFFF
        verify_s = time.perf_counter() - t0
        if crc != stored_crc:
            raise SpillCorrupt(
                f"{where}: crc32 mismatch in {path} (stored "
                f"{stored_crc:#010x}, found {crc:#010x}) — disk "
                f"bit-rot or tampering", chunk=chunk, path=path)
    try:
        arr = np.frombuffer(payload, dtype=dtype).reshape(shape)
    except ValueError as e:
        raise SpillCorrupt(
            f"{where}: payload in {path} does not reshape to "
            f"{shape} {dtype} ({e})", chunk=chunk, path=path) from e
    return arr, verify_s


class SpillWriter:
    """Append-only writer for a binned row-chunk spill directory.

    ``append`` writes each chunk as a framed ``chunk_{i:06d}.bin``
    (narrowed to ``dtype``, crc32-stamped); ``finalize`` atomically
    publishes the manifest and returns a :class:`SpillReader`. Chunks
    may have uneven row counts; the feature count and dtype must stay
    fixed.
    """

    def __init__(self, path: str, dtype: Any = np.uint8) -> None:
        self.path = path
        self.dtype = np.dtype(dtype)
        self.chunk_rows: List[int] = []
        self.n_features: Optional[int] = None
        self._sealed = False
        os.makedirs(path, exist_ok=True)

    def append(self, chunk: np.ndarray) -> None:
        if self._sealed:
            raise RuntimeError("SpillWriter already finalized")
        c = np.ascontiguousarray(chunk)
        if c.ndim != 2:
            raise ValueError(f"spill chunks must be 2-d, got {c.shape}")
        if self.n_features is None:
            self.n_features = int(c.shape[1])
        elif c.shape[1] != self.n_features:
            raise ValueError(
                f"chunk has {c.shape[1]} features, expected {self.n_features}")
        i = len(self.chunk_rows)
        write_chunk(os.path.join(self.path, f"chunk_{i:06d}.bin"),
                    c.astype(self.dtype, copy=False))
        self.chunk_rows.append(int(c.shape[0]))

    def finalize(self) -> "SpillReader":
        from mmlspark_tpu.core.serialize import atomic_write

        if self.n_features is None:
            raise ValueError("spill has no chunks")
        meta = {
            "version": 2,
            "dtype": self.dtype.name,
            "n_features": self.n_features,
            "chunk_rows": self.chunk_rows,
            "total_rows": int(sum(self.chunk_rows)),
        }
        atomic_write(os.path.join(self.path, _SPILL_MANIFEST),
                     json.dumps(meta, indent=1))
        self._sealed = True
        return SpillReader(self.path)


class SpillReader:
    """Reader over a sealed spill directory (see :class:`SpillWriter`).

    ``read`` verifies chunk checksums per :func:`resolve_spill_verify`
    (auto = first read of each chunk); the cumulative cost lands in
    ``verify_s`` / ``verify_chunks`` for hist_stats accounting.
    ``repair`` rewrites one chunk from trusted source bytes after a
    detected corruption."""

    def __init__(self, path: str) -> None:
        self.path = path
        meta_path = os.path.join(path, _SPILL_MANIFEST)
        try:
            with open(meta_path) as fh:
                meta = json.load(fh)
        except (OSError, json.JSONDecodeError) as e:
            raise SpillCorrupt(
                f"spill manifest missing or unreadable at {meta_path} "
                f"({type(e).__name__}: {e}) — the spill was never "
                "sealed or the directory is damaged",
                path=meta_path) from e
        self.dtype = np.dtype(meta["dtype"])
        self.n_features = int(meta["n_features"])
        self.chunk_rows: List[int] = [int(r) for r in meta["chunk_rows"]]
        self.total_rows = int(meta["total_rows"])
        self.offsets: List[int] = []
        off = 0
        for r in self.chunk_rows:
            self.offsets.append(off)
            off += r
        self.verify_mode = resolve_spill_verify()
        self.verify_s = 0.0
        self.verify_chunks = 0
        self.repairs = 0
        self._verified: Set[int] = set()

    @property
    def num_chunks(self) -> int:
        return len(self.chunk_rows)

    def _chunk_path(self, i: int) -> str:
        return os.path.join(self.path, f"chunk_{i:06d}.bin")

    def read(self, i: int) -> np.ndarray:
        check = (self.verify_mode == "on"
                 or (self.verify_mode == "auto"
                     and i not in self._verified))
        arr, vs = read_chunk(self._chunk_path(i), verify=check, chunk=i)
        if check:
            self.verify_s += vs
            self.verify_chunks += 1
            self._verified.add(i)
        if (arr.dtype != self.dtype
                or arr.shape != (self.chunk_rows[i], self.n_features)):
            raise SpillCorrupt(
                f"spill chunk {i}: {self._chunk_path(i)} holds "
                f"{arr.shape} {arr.dtype}, manifest says "
                f"({self.chunk_rows[i]}, {self.n_features}) "
                f"{self.dtype}", chunk=i, path=self._chunk_path(i))
        return arr

    def repair(self, i: int, chunk: np.ndarray) -> None:
        """Rewrite chunk ``i`` from re-derived source data (binning is
        deterministic on fixed sketch edges, so the bytes are the
        originals)."""
        c = np.ascontiguousarray(chunk).astype(self.dtype, copy=False)
        if c.shape != (self.chunk_rows[i], self.n_features):
            raise ValueError(
                f"repair chunk {i}: source produced {c.shape}, spill "
                f"expects ({self.chunk_rows[i]}, {self.n_features})")
        write_chunk(self._chunk_path(i), c)
        self.repairs += 1
        # the frame was just built from trusted bytes: first-read
        # verification is already discharged
        self._verified.add(i)

    def __iter__(self) -> Iterator[np.ndarray]:
        for i in range(self.num_chunks):
            yield self.read(i)


class ChunkStore:
    """Per-chunk array store for out-of-core per-row state (raw score
    carry, quantized grad/hess, node ids). Same chunking as the
    companion spill; overwritten in place each iteration via tmp +
    ``os.replace`` so a torn write never corrupts a chunk (resume
    rebuilds this state from checkpoints anyway — the atomicity just
    keeps same-process retries honest). Entries carry the same framed
    crc32 as spill chunks; under SPILL_VERIFY=auto each entry is
    re-verified on its first read after every ``put``."""

    def __init__(self, path: str, name: str) -> None:
        self.path = path
        self.name = name
        self.verify_mode = resolve_spill_verify()
        self.verify_s = 0.0
        self.verify_chunks = 0
        self._verified: Set[int] = set()
        os.makedirs(path, exist_ok=True)

    def _file(self, i: int) -> str:
        return os.path.join(self.path, f"{self.name}_{i:06d}.bin")

    def put(self, i: int, arr: np.ndarray) -> None:
        write_chunk(self._file(i), np.ascontiguousarray(arr))
        self._verified.discard(i)

    def get(self, i: int) -> np.ndarray:
        path = self._file(i)
        check = (self.verify_mode == "on"
                 or (self.verify_mode == "auto"
                     and i not in self._verified))
        arr, vs = read_chunk(path, verify=check, chunk=i,
                             label=f"chunk store {self.name!r}")
        if check:
            self.verify_s += vs
            self.verify_chunks += 1
            self._verified.add(i)
        return arr
