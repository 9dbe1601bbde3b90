"""Host -> device ingest pipeline.

The reference streams rows into the native dataset in micro-batches
(StreamingPartitionTask.scala:203-277, pushDenseMicroBatches) so JVM
marshaling overlaps native ingestion. The TPU analog: ``device_put`` is
asynchronous, so chunking a large host array overlaps the host-side
prep of chunk i+1 (dtype narrowing, contiguity copy) with the wire
transfer of chunk i — double buffering without threads. Binned GBDT
matrices additionally narrow to uint8 (max_bin <= 256), cutting bytes
on the wire 4x vs int32; XLA's implicit integer promotion makes the
narrow dtype free on device (gathers/adds fuse the widening).
"""

from __future__ import annotations

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


def chunked_device_put(arr: np.ndarray, sharding=None,
                       dtype: Optional[Any] = None,
                       chunk_bytes: int = 64 << 20,
                       row_multiple: int = 1):
    """Transfer ``arr`` to device in async chunks; returns the device
    array (concatenated under one jit so the result carries
    ``sharding``).

    ``row_multiple``: chunk row counts stay multiples of this (the mesh
    dp axis size when sharded). Small arrays fall through to one put.

    The whole of it is the span ``dataPreparation.transfer`` (bytes on
    the wire, chunks): the host's share of the transfer, which is the
    narrowing copies and the enqueues. Nothing here waits for the
    device, so the copies still in flight at the end are paid for by
    whoever first needs the array.
    """
    import jax
    import jax.numpy as jnp

    if dtype is not None and arr.dtype != dtype:
        row_nbytes = int(np.dtype(dtype).itemsize * np.prod(arr.shape[1:],
                                                            dtype=np.int64))
    else:
        row_nbytes = int(arr.dtype.itemsize * np.prod(arr.shape[1:],
                                                      dtype=np.int64))
    n = arr.shape[0]
    chunk_rows = max(chunk_bytes // max(row_nbytes, 1), 1)
    chunk_rows = max(chunk_rows // row_multiple, 1) * row_multiple

    def prep(part):
        part = np.ascontiguousarray(part)
        if dtype is not None:
            part = part.astype(dtype, copy=False)
        return part

    with span("dataPreparation.transfer", bytes=n * row_nbytes,
              chunks=-(-n // chunk_rows)):
        if chunk_rows >= n:
            full = prep(arr)
            return (jax.device_put(full, sharding) if sharding is not None
                    else jnp.asarray(full))

        parts = []
        for s in range(0, n, chunk_rows):
            # device_put returns immediately: the next chunk's host prep
            # overlaps this chunk's transfer. Each chunk carries the final
            # sharding (chunk rows are row_multiple-aligned), so shards go
            # straight to their devices — no single-device staging
            part = prep(arr[s:s + chunk_rows])
            parts.append(jax.device_put(part, sharding)
                         if sharding is not None
                         and len(part) % row_multiple == 0
                         else jax.device_put(part))
        concat = jax.jit(lambda *p: jnp.concatenate(p, axis=0),
                         out_shardings=sharding)
        return concat(*parts)


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
