"""ctypes bindings for the native runtime (libuda_tpu_native.so).

Gracefully degrades: when the shared library hasn't been built (``make
-C uda_tpu/native``) or ``uda.tpu.use.native`` is off, callers fall back
to the pure-Python implementations in uda_tpu.utils.ifile. The Python
and native codecs are parity-tested against each other
(tests/test_native.py) — the Python side is the semantic reference, the
C++ side is the hot path (the reference's equivalent split: Java plugin
logic vs libuda.so, SURVEY §1 L4/L5).
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import Optional

import numpy as np

from uda_tpu.utils.errors import MergeError, StorageError
from uda_tpu.utils.ifile import RecordBatch
from uda_tpu.utils.logging import get_logger

__all__ = ["available", "build", "crack_native", "crack_partial_native",
           "decode_vlongs_native", "write_records_native", "frame_batch",
           "iter_framed_chunks", "ReadPool", "kway_supported",
           "kway_merge_paths", "SegmentTable", "gather_slab_native",
           "RunTable", "gather_runs_native", "stage_segment_native"]

log = get_logger()

_DIR = os.path.dirname(os.path.abspath(__file__))
_SO = os.path.join(_DIR, "libuda_tpu_native.so")
_lib = None
_lib_stale = False  # cached "old .so lacks newer symbols" outcome
_lib_lock = threading.RLock()


def _load():
    global _lib, _lib_stale
    with _lib_lock:
        if _lib is not None:
            return _lib
        if _lib_stale or not os.path.exists(_SO):
            return None
        try:
            lib = _bind(ctypes.CDLL(_SO))
        except AttributeError as e:
            # a stale .so from an older build lacks newer symbols; fall
            # back to pure Python rather than poisoning every caller.
            # Cached (and cleared by a successful build()) so hot paths
            # don't re-dlopen + re-warn per call.
            log.warn(f"native library is stale ({e}); rebuild with "
                     f"`make -C uda_tpu/native` — using pure Python")
            _lib_stale = True
            return None
        _lib = lib
        return lib


def _bind(lib):
    i64p = ctypes.POINTER(ctypes.c_int64)
    u8p = ctypes.POINTER(ctypes.c_uint8)
    lib.uda_crack.restype = ctypes.c_int64
    lib.uda_crack.argtypes = [u8p, ctypes.c_int64, i64p, i64p, i64p,
                              i64p, ctypes.c_int64, i64p,
                              ctypes.POINTER(ctypes.c_int32)]
    lib.uda_decode_vlongs.restype = ctypes.c_int64
    lib.uda_decode_vlongs.argtypes = [u8p, ctypes.c_int64, i64p,
                                      ctypes.c_int64]
    lib.uda_pool_create.restype = ctypes.c_void_p
    lib.uda_pool_create.argtypes = [ctypes.c_int]
    lib.uda_pool_destroy.argtypes = [ctypes.c_void_p]
    lib.uda_pool_submit.restype = ctypes.c_int
    lib.uda_pool_submit.argtypes = [ctypes.c_void_p, ctypes.c_int,
                                    ctypes.c_int64, ctypes.c_int64,
                                    u8p, ctypes.c_uint64]
    lib.uda_pool_get_events.restype = ctypes.c_int
    lib.uda_pool_get_events.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_uint64), i64p,
        ctypes.c_int, ctypes.c_int, ctypes.c_double]
    lib.uda_pool_backend.restype = ctypes.c_int
    lib.uda_pool_backend.argtypes = [ctypes.c_void_p]
    lib.uda_pool_submit_batch.restype = ctypes.c_int
    lib.uda_pool_submit_batch.argtypes = [
        ctypes.c_void_p, ctypes.c_int, ctypes.POINTER(ctypes.c_int32),
        i64p, i64p, ctypes.POINTER(ctypes.POINTER(ctypes.c_uint8)),
        ctypes.POINTER(ctypes.c_uint64)]
    lib.uda_write_records.restype = ctypes.c_int64
    lib.uda_write_records.argtypes = [u8p, i64p, i64p, i64p, i64p,
                                      ctypes.c_int64, u8p,
                                      ctypes.c_int64, ctypes.c_int32]
    lib.uda_kway_create.restype = ctypes.c_void_p
    lib.uda_kway_create.argtypes = [
        ctypes.POINTER(ctypes.c_char_p), ctypes.c_int32,
        ctypes.c_int32, ctypes.c_int32, ctypes.c_int64, i64p]
    lib.uda_kway_next_block.restype = ctypes.c_int64
    lib.uda_kway_next_block.argtypes = [ctypes.c_void_p, u8p,
                                        ctypes.c_int64, i64p]
    lib.uda_kway_destroy.argtypes = [ctypes.c_void_p]
    szp = ctypes.POINTER(ctypes.c_size_t)
    lib.uda_lzo1x_decompress_safe.restype = ctypes.c_int
    lib.uda_lzo1x_decompress_safe.argtypes = [u8p, ctypes.c_size_t,
                                              u8p, szp]
    lib.uda_lzo1x_1_compress.restype = ctypes.c_int
    lib.uda_lzo1x_1_compress.argtypes = [u8p, ctypes.c_size_t, u8p, szp]
    u32p = ctypes.POINTER(ctypes.c_uint32)
    lib.uda_merge_rows.restype = None
    lib.uda_merge_rows.argtypes = [u32p, ctypes.c_int64, u32p,
                                   ctypes.c_int64, ctypes.c_int32, u32p]
    lib.uda_gather_spans.restype = None
    lib.uda_gather_spans.argtypes = [u8p, i64p, i64p, ctypes.c_int64,
                                     u8p, i64p]
    # the table and the (seg, row) slab columns go as raw addresses:
    # SegmentTable / gather_slab_native own their layout checks
    addr = ctypes.c_void_p
    lib.uda_slab_lengths.restype = ctypes.c_int64
    slab = [addr, addr, ctypes.c_int64, addr, ctypes.c_int64,
            ctypes.c_int64]  # table, seg + stride, row + stride, n
    lib.uda_slab_lengths.restype = ctypes.c_int64
    lib.uda_slab_lengths.argtypes = slab + [ctypes.c_int64, i64p, i64p,
                                            i64p]
    lib.uda_slab_copy.restype = None
    lib.uda_slab_copy.argtypes = slab + [i64p, i64p, ctypes.c_int64, u8p,
                                         i64p, i64p]
    # RunTable / gather_runs_native own the layout checks
    lib.uda_runs_open.restype = ctypes.c_void_p
    lib.uda_runs_open.argtypes = [ctypes.POINTER(ctypes.c_char_p), i64p,
                                  i64p, ctypes.c_int64, ctypes.c_int64,
                                  ctypes.c_int32]
    lib.uda_runs_gather.restype = ctypes.c_int64
    lib.uda_runs_gather.argtypes = [addr, addr, ctypes.c_int64,
                                    ctypes.c_int64, addr, ctypes.c_int64,
                                    i64p]
    lib.uda_runs_consumed.restype = ctypes.c_int64
    lib.uda_runs_consumed.argtypes = [addr, ctypes.c_int64]
    lib.uda_runs_close.restype = None
    lib.uda_runs_close.argtypes = [addr]
    # data + size, key_off, key_len, val_len, n, key mode, key words,
    # segment index, rows + capacity, out: stage_segment_native owns the
    # layout checks
    lib.uda_stage_segment.restype = ctypes.c_int64
    lib.uda_stage_segment.argtypes = [
        addr, ctypes.c_int64, addr, addr, addr, ctypes.c_int64,
        ctypes.c_int32, ctypes.c_int32, ctypes.c_uint32, addr,
        ctypes.c_int64, i64p]
    return lib


def available() -> bool:
    return _load() is not None


_build_attempted = False
_build_ok = False


def build(quiet: bool = True) -> bool:
    """Best-effort build of the shared library (g++ via make), run at
    most once per process — even when the .so already exists, so a
    STALE library (older than its sources, e.g. after a pull) is
    rebuilt instead of crashing symbol binds. The outcome (either way)
    is remembered so later callers don't re-spawn make per DataEngine
    construction. Thread-safe via the lib lock; concurrent PROCESSES
    are safe because the Makefile links to a temp file and renames
    (dlopen never sees a half-written .so) and make itself no-ops when
    the library is current."""
    global _build_attempted, _build_ok, _lib, _lib_stale
    with _lib_lock:
        if _build_attempted:
            return _build_ok
        _build_attempted = True
        try:
            subprocess.run(["make", "-C", _DIR],
                           check=True, capture_output=quiet)
            _lib = None       # rebind in case make refreshed a stale .so
            _lib_stale = False
        except (subprocess.CalledProcessError, FileNotFoundError) as e:
            if os.path.exists(_SO):
                log.warn(f"native rebuild failed; keeping the existing "
                         f"library: {e}")
                _build_ok = available()
                return _build_ok
            log.warn(f"native build failed, using pure-Python codec: {e}")
            _build_ok = False
            return False
        _build_ok = available()
        return _build_ok


def _u8ptr(arr: np.ndarray):
    return arr.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))


def _i64ptr(arr: np.ndarray):
    return arr.ctypes.data_as(ctypes.POINTER(ctypes.c_int64))


def crack_partial_native(data) -> tuple[RecordBatch, int, bool]:
    """Native twin of ifile.crack_partial (same return contract)."""
    lib = _load()
    arr = (np.frombuffer(data, np.uint8) if not isinstance(data, np.ndarray)
           else np.ascontiguousarray(data, np.uint8))
    n = len(arr)
    cap = max(16, n // 2 + 1)  # a record is >= 2 bytes of framing
    ko = np.empty(cap, np.int64)
    kl = np.empty(cap, np.int64)
    vo = np.empty(cap, np.int64)
    vl = np.empty(cap, np.int64)
    consumed = ctypes.c_int64(0)
    saw_eof = ctypes.c_int32(0)
    count = lib.uda_crack(_u8ptr(arr), n, _i64ptr(ko), _i64ptr(kl),
                          _i64ptr(vo), _i64ptr(vl), cap,
                          ctypes.byref(consumed), ctypes.byref(saw_eof))
    if count == -1:
        raise StorageError("corrupt record framing (native crack)")
    if count == -2:  # capacity overflow: cannot happen with cap >= n/2+1
        raise StorageError("native crack capacity overflow")
    c = int(count)
    batch = RecordBatch(arr, ko[:c].copy(), kl[:c].copy(), vo[:c].copy(),
                        vl[:c].copy())
    return batch, int(consumed.value), bool(saw_eof.value)


def crack_native(data, expect_eof: bool = True) -> RecordBatch:
    """Native twin of ifile.crack."""
    batch, consumed, saw_eof = crack_partial_native(data)
    n = len(data)
    if expect_eof and not saw_eof:
        raise StorageError("IFile segment missing EOF marker (native)")
    if not saw_eof and consumed != n:
        raise StorageError(f"truncated IFile segment at offset {consumed}")
    return batch


def decode_vlongs_native(data, count: int = -1) -> np.ndarray:
    lib = _load()
    arr = (np.frombuffer(data, np.uint8) if not isinstance(data, np.ndarray)
           else np.ascontiguousarray(data, np.uint8))
    cap = len(arr) if count < 0 else count
    out = np.empty(max(cap, 1), np.int64)
    n = lib.uda_decode_vlongs(_u8ptr(arr), len(arr), _i64ptr(out), cap)
    if count >= 0 and n < count:
        raise IndexError("truncated VLong stream (native)")
    return out[:n].copy()


def write_records_native(batch: RecordBatch, write_eof: bool = True) -> bytes:
    """Native twin of ifile.write_records over a RecordBatch: re-frames
    the batch's records as one IFile byte stream (the emit hot path)."""
    lib = _load()
    n = batch.num_records
    # worst case: 20 framing bytes per record (two max-width VLongs)
    cap = int(batch.key_len.sum() + batch.val_len.sum()) + 20 * n + 2
    out = np.empty(cap, np.uint8)
    data = np.ascontiguousarray(batch.data, np.uint8)
    wrote = lib.uda_write_records(
        _u8ptr(data),
        _i64ptr(np.ascontiguousarray(batch.key_off)),
        _i64ptr(np.ascontiguousarray(batch.key_len)),
        _i64ptr(np.ascontiguousarray(batch.val_off)),
        _i64ptr(np.ascontiguousarray(batch.val_len)),
        n, _u8ptr(out), cap, 1 if write_eof else 0)
    if wrote < 0:
        raise StorageError("native write_records capacity overflow")
    return out[:wrote].tobytes()


def frame_batch(batch: RecordBatch, write_eof: bool = True) -> bytes:
    """Frame a whole RecordBatch as one IFile byte stream, native when
    enabled+built (one C pass over the columns — the emit/spill hot path
    the reference runs in C++, reference src/Merger/StreamRW.cc:151-225),
    pure Python otherwise. The two produce identical bytes
    (parity-tested in tests/test_native.py). Honors the
    ``uda.tpu.use.native`` kill switch (ifile.set_native_enabled), like
    every other native dispatch."""
    from uda_tpu.utils.ifile import native_enabled

    if native_enabled() and build():
        return write_records_native(batch, write_eof=write_eof)
    import io

    from uda_tpu.utils.ifile import IFileWriter

    out = io.BytesIO()
    w = IFileWriter(out)
    for k, v in batch.iter_records():
        w.append(k, v)
    if write_eof:
        w.close()
    return out.getvalue()


def iter_framed_chunks(batch: RecordBatch, chunk_records: int = 1 << 16,
                       write_eof: bool = True):
    """Frame a RecordBatch in bounded chunks: yields IFile byte pieces
    whose concatenation equals ``frame_batch(batch)``. Peak transient
    memory is one chunk's framed bytes, so multi-GB spills stream to
    their file instead of materializing wholesale."""
    n = batch.num_records
    for start in range(0, n, max(1, chunk_records)):
        stop = min(start + chunk_records, n)
        sub = RecordBatch(batch.data, batch.key_off[start:stop],
                          batch.key_len[start:stop],
                          batch.val_off[start:stop],
                          batch.val_len[start:stop])
        yield frame_batch(sub, write_eof=False)
    if write_eof:
        from uda_tpu.utils.ifile import EOF_MARKER

        yield EOF_MARKER


# KeyType.name -> (key_mode, key_param) for the native loser-tree merge
# (merge.cc). The mode family is exactly the reference CompareFunc
# dispatch (CompareFunc.cc:70-113): identity memcmp, Text VInt-skip,
# BytesWritable 4-byte skip, plus this framework's sign-flip numeric
# variants. Comparators outside this table (user-registered) fall back
# to the Python heap merge — the reference's unsupported-comparator
# posture (CompareFunc.cc:95-113).
_KWAY_MODES = {
    "raw": (0, 0), "boolean": (0, 0), "byte": (0, 0), "short": (0, 0),
    "int": (0, 0), "long": (0, 0),
    "text": (1, 0),
    "bytes": (2, 0), "ibytes": (2, 0),
    "int_numeric": (3, 4), "long_numeric": (3, 8),
}

_KWAY_ERRORS = {-1: "corrupt record framing / missing EOF marker",
                -4: "read failure"}


def kway_supported(kt) -> bool:
    """Whether the native merge implements this KeyType's comparator."""
    return kt.name in _KWAY_MODES


def kway_merge_paths(paths, kt, block_bytes: int = 1 << 20,
                     buffer_size: int = 1 << 20, write_eof: bool = True):
    """Streaming k-way merge of sorted IFile spill files: yields framed
    byte blocks whose concatenation is the merged record stream
    (+ EOF marker when ``write_eof``) — byte-identical to
    ``ops.merge.merge_record_streams`` over the same files re-framed.
    The C++ loser tree (merge.cc, the reference MergeQueue.h:276-427
    analogue) does all comparator and framing work; peak memory is one
    read buffer per file + one output block."""
    from uda_tpu.utils.ifile import EOF_MARKER

    mode, param = _KWAY_MODES[kt.name]
    if not paths:
        if write_eof:
            yield EOF_MARKER
        return
    lib = _load()
    if lib is None:
        raise StorageError("native library not built")
    arr = (ctypes.c_char_p * len(paths))(
        *[os.fsencode(p) for p in paths])
    err = ctypes.c_int64(0)
    h = lib.uda_kway_create(arr, len(paths), mode, param, buffer_size,
                            ctypes.byref(err))
    if not h:
        reason = _KWAY_ERRORS.get(int(err.value), "open failed")
        raise StorageError(f"native kway merge over {list(paths)}: "
                           f"{reason}")
    try:
        cap = block_bytes
        out = np.empty(cap, np.uint8)
        need = ctypes.c_int64(0)
        while True:
            n = lib.uda_kway_next_block(h, _u8ptr(out), cap,
                                        ctypes.byref(need))
            if n == -3:  # one record larger than the block: grow
                cap = max(cap * 2, int(need.value))
                out = np.empty(cap, np.uint8)
                continue
            if n < 0:
                raise StorageError(
                    f"native kway merge: "
                    f"{_KWAY_ERRORS.get(int(n), f'error {n}')}")
            if n == 0:
                break
            yield out[:n].tobytes()
        if write_eof:
            yield EOF_MARKER
    finally:
        lib.uda_kway_destroy(h)


def gather_spans_native(src: np.ndarray, src_off: np.ndarray,
                        lens: np.ndarray, dst: np.ndarray,
                        dst_off: np.ndarray) -> bool:
    """Per-record memcpy gather: dst[dst_off_i:+len_i] = src[src_off_i:
    +len_i]. The byte-movement core of the streaming interleave / slab
    gather (the numpy expand-index fallback moves 8 bytes of index per
    byte of payload). Returns False when the library isn't available."""
    lib = _load()
    if lib is None:
        return False
    # dst is written through its raw pointer: coercion would write into
    # a discarded copy, so demand the right layout outright; the C loop
    # is bounds-unchecked, so offset arrays must agree on n
    if dst.dtype != np.uint8 or not dst.flags["C_CONTIGUOUS"]:
        raise ValueError("gather destination must be contiguous uint8")
    n = src_off.shape[0]
    if lens.shape[0] != n or dst_off.shape[0] != n:
        raise ValueError(f"span arrays disagree: {n} offsets, "
                         f"{lens.shape[0]} lengths, "
                         f"{dst_off.shape[0]} destinations")
    # the C loop is a bounds-unchecked memcpy: corrupt spans (e.g. a
    # non-monotonic run offset sidecar producing negative lengths) must
    # fail HERE like the numpy fallback would, not scribble memory
    if n and (int(lens.min()) < 0
              or int((src_off + lens).max()) > src.size
              or int(src_off.min()) < 0 or int(dst_off.min()) < 0
              or int((dst_off + lens).max()) > dst.size):
        raise ValueError("gather spans out of bounds")
    src = np.ascontiguousarray(src, np.uint8)
    lib.uda_gather_spans(
        _u8ptr(src), _i64ptr(np.ascontiguousarray(src_off, np.int64)),
        _i64ptr(np.ascontiguousarray(lens, np.int64)), n,
        _u8ptr(dst), _i64ptr(np.ascontiguousarray(dst_off, np.int64)))
    return True


class SegmentTable:
    """One task's per-segment lookup table for :func:`gather_slab_native`:
    row ``s`` of a contiguous ``int64[segments, 7]`` array holds batch
    ``s``'s ``data``, ``key_off``, ``key_len``, ``val_off`` and
    ``val_len`` base addresses, its record count and its data size (the
    C side's ``UdaSegment``). Built once per task in O(segments):
    nothing of the shuffle is concatenated or copied (a column that is
    not already contiguous int64 is, once, and the copy kept). The
    arrays the addresses point into are kept alive for the table's
    life."""

    __slots__ = ("segments", "_rows", "_keep")

    def __init__(self, batches):
        self.segments = len(batches)
        self._rows = np.empty((self.segments, 7), np.int64)
        self._keep = []
        for s, b in enumerate(batches):
            data = np.ascontiguousarray(b.data, np.uint8)
            cols = [np.ascontiguousarray(c, np.int64)
                    for c in (b.key_off, b.key_len, b.val_off, b.val_len)]
            n = cols[0].shape[0]
            if data.ndim != 1 or any(c.shape != (n,) for c in cols):
                raise ValueError(f"segment {s}: ragged record columns")
            self._keep.append((data, cols))
            self._rows[s] = (data.ctypes.data, *(c.ctypes.data for c in cols),
                             n, data.size)


_SLAB_ERRORS = {1: "segment index out of range",
                2: "row index out of range",
                3: "record span outside its segment's data"}


def _u32_column(col: np.ndarray, what: str) -> np.ndarray:
    """A slab's segment or row column as the C loop reads it: 1-D
    uint32 at any stride (the row-major slab's column is taken in
    place). Other integer dtypes are converted, checked first — a
    negative or oversized index must not wrap into range."""
    col = np.asarray(col)
    if col.ndim != 1 or col.dtype.kind not in "iu":
        raise ValueError(f"slab {what} column must be a 1-D integer array")
    if col.dtype == np.uint32:
        return col
    if col.size and (int(col.min()) < 0 or int(col.max()) > 0xFFFFFFFF):
        raise MergeError(f"slab gather: {what} index out of range")
    return col.astype(np.uint32)


def gather_slab_native(table: SegmentTable, seg: np.ndarray,
                       row: np.ndarray) -> Optional[RecordBatch]:
    """One output slab's records, looked up through ``table`` by
    ``(seg[i], row[i])`` and written as a compact RecordBatch — its own
    buffer with all keys then all values — in two C passes over the
    slab, whatever the segment count (the native twin of
    ``merger/streaming.py:slab_batch``'s numpy path, byte-identical).
    The C loop checks every record; an index or span out of range
    raises MergeError. Returns None when the library isn't available."""
    lib = _load()
    if lib is None:
        return None
    seg = _u32_column(seg, "segment")
    row = _u32_column(row, "row")
    n = seg.shape[0]
    if row.shape[0] != n:
        raise ValueError(f"slab columns disagree: {n} segment indices, "
                         f"{row.shape[0]} rows")
    slab = (table._rows.ctypes.data, seg.ctypes.data, seg.strides[0],
            row.ctypes.data, row.strides[0], n)
    k_len = np.empty(n, np.int64)
    v_len = np.empty(n, np.int64)
    out = (ctypes.c_int64 * 3)()  # key total, value total, bad record
    rc = lib.uda_slab_lengths(*slab, table.segments, _i64ptr(k_len),
                              _i64ptr(v_len), out)
    if rc:
        raise MergeError(f"slab gather: {_SLAB_ERRORS.get(rc, rc)} at slab "
                         f"record {out[2]} (segment {int(seg[out[2]])}, "
                         f"row {int(row[out[2]])})")
    buf = np.empty(out[0] + out[1], np.uint8)
    k_off = np.empty(n, np.int64)
    v_off = np.empty(n, np.int64)
    lib.uda_slab_copy(*slab, _i64ptr(k_len), _i64ptr(v_len), out[0],
                      _u8ptr(buf), _i64ptr(k_off), _i64ptr(v_off))
    return RecordBatch(buf, k_off, k_len, v_off, v_len)


class RunTable:
    """One task's table of run cursors for :func:`gather_runs_native`
    (the C side's ``RunTable``): per run its path, record count and
    framed size, one read buffer (``buffer_size`` bytes at most, filled
    by ``pread``) and how far it has read. Built once per task in
    O(runs); each run file is checked against the size the store
    recorded when it wrote it. ``keep_open`` holds a run's descriptor
    between fills — for few runs; otherwise a fill opens and closes,
    and the run count is not held to the fd limit. ``runs`` maps
    segment index to ``(run_path, records, framed_bytes)``; an index
    nobody staged stays empty. The gathered slab lives in one output
    buffer the table reuses, slab after slab."""

    __slots__ = ("runs", "_h", "_out")

    def __init__(self, runs: dict, keep_open: bool = False,
                 buffer_size: int = 1 << 20):
        from uda_tpu.utils.ifile import EOF_MARKER

        lib = _load()
        if lib is None:
            raise StorageError("native library unavailable")
        self.runs = max(runs, default=-1) + 1
        paths = (ctypes.c_char_p * self.runs)()
        records = np.zeros(self.runs, np.int64)
        sizes = np.zeros(self.runs, np.int64)
        for s, (run_path, n, nbytes) in runs.items():
            if n <= 0:
                continue
            have = os.path.getsize(run_path)
            if have != nbytes + len(EOF_MARKER):
                raise MergeError(f"run {s}: {run_path} holds {have} bytes, "
                                 f"the store wrote "
                                 f"{nbytes + len(EOF_MARKER)}")
            paths[s] = os.fsencode(run_path)
            records[s], sizes[s] = n, nbytes
        self._out = np.empty(buffer_size, np.uint8)
        self._h = lib.uda_runs_open(paths, _i64ptr(records), _i64ptr(sizes),
                                    self.runs, int(buffer_size),
                                    int(bool(keep_open)))
        if not self._h:
            raise MemoryError("run table allocation failed")

    def consumed(self, s: int) -> int:
        """Records of run ``s`` gathered so far."""
        return int(_load().uda_runs_consumed(self._h, s)) if self._h else 0

    def close(self) -> None:
        h, self._h = self._h, None
        if h:
            _load().uda_runs_close(h)


_RUNS_ERRORS = {-1: "merged rows reference an unstaged segment",
                -2: "merged rows ask a run for more records than it holds",
                -3: "run framing runs past the run file's records",
                -4: "run file read failure"}


def gather_runs_native(table: RunTable, seg: np.ndarray) -> memoryview:
    """One output slab's framed bytes: each record the next unread one
    of the run ``seg[i]`` names, copied verbatim, in one C pass over
    the slab whatever the run count (the native twin of
    ``merger/streaming.py:interleave_runs``' numpy gather,
    byte-identical). The view is of the table's own output buffer: it
    holds until the next call. The C loop checks every record; a bad
    index, an exhausted run, framing past the file or a failed read
    raises MergeError."""
    lib = _load()
    seg = _u32_column(seg, "segment")
    n = seg.shape[0]
    done = written = 0
    out = (ctypes.c_int64 * 3)()
    while True:
        got = lib.uda_runs_gather(
            table._h, seg.ctypes.data + done * seg.strides[0],
            seg.strides[0], n - done,
            table._out.ctypes.data + written, table._out.size - written, out)
        if got < 0:
            at = done + out[2]
            raise MergeError(f"run gather: {_RUNS_ERRORS.get(got, got)} at "
                             f"slab record {at} (segment {int(seg[at])})")
        done += got
        written += out[0]
        if done == n:
            return memoryview(table._out)[:written]
        # the next record did not fit: at least double the buffer
        grown = np.empty(max(2 * table._out.size, written + out[1]),
                         np.uint8)
        grown[:written] = table._out[:written]
        table._out = grown


_STAGE_ERRORS = {1: "empty serialized Text key",
                 2: "BytesWritable key shorter than its length field",
                 3: "key content outside the segment's data",
                 4: "out of memory sorting the segment"}


def stage_segment_native(batch: RecordBatch, kt, width: int, seg_index: int,
                         rows: np.ndarray) -> Optional[tuple[bool, int, int]]:
    """Stage one cracked segment in ONE C pass: ``rows`` (uint32
    ``[cap >= n, width/4 + 3]``, written in place) gets the sorted
    composite-key rows ``ops.merge.fill_run_rows`` builds from
    ``pack_keys`` + ``run_row_order`` — byte-identical — and its tail
    ``PAD_WORD``. Returns ``(presorted, longest content length,
    key + value bytes)``: whether the segment arrived in (words, len)
    order (if not, the rows were sorted and their row-index column is
    the stable order vector), the oversize test's operand, and
    ``stage.bytes``. A key longer than ``width`` is reported
    (``longest``) and gets its row like any other — its first ``width``
    bytes as words, its whole content length — so a segment with
    several of equal words is not in (words, len) order where their
    lengths fall, and is sorted here; the caller keeps the rows on the
    forest and restores the comparator's order inside such a block at
    emit, or takes its fallback (merger/overlap.py). A key type outside ``_KWAY_MODES`` packs its
    serialized bytes, as ``packing.content_spans`` does. A malformed
    key raises MergeError. Returns None when the library isn't
    available."""
    lib = _load()
    if lib is None:
        return None
    if width % 4 != 0 or width <= 0:
        raise MergeError(f"key width must be a positive multiple of 4, "
                         f"got {width}")
    data = np.ascontiguousarray(batch.data, np.uint8)
    key_off, key_len, val_len = (
        np.ascontiguousarray(c, np.int64)
        for c in (batch.key_off, batch.key_len, batch.val_len))
    n = key_off.shape[0]
    if data.ndim != 1 or key_len.shape != (n,) or val_len.shape != (n,):
        raise ValueError("ragged record columns")
    # rows is written through its raw pointer: demand the layout outright
    if (rows.dtype != np.uint32 or not rows.flags["C_CONTIGUOUS"]
            or rows.ndim != 2 or rows.shape[1] != width // 4 + 3
            or rows.shape[0] < n):
        raise ValueError(f"row matrix must be contiguous uint32 "
                         f"[>= {n}, {width // 4 + 3}], got {rows.dtype}"
                         f"{list(rows.shape)}")
    out = (ctypes.c_int64 * 4)()
    rc = lib.uda_stage_segment(
        data.ctypes.data, data.size, key_off.ctypes.data,
        key_len.ctypes.data, val_len.ctypes.data, n,
        _KWAY_MODES.get(kt.name, (0, 0))[0], width // 4, seg_index,
        rows.ctypes.data, rows.shape[0], out)
    if rc:
        raise MergeError(f"segment {seg_index}: {_STAGE_ERRORS.get(rc, rc)} "
                         f"at record {out[3]}")
    return bool(out[0]), int(out[1]), int(out[2])


def merge_rows_native(a: np.ndarray, b: np.ndarray) -> Optional[np.ndarray]:
    """Linear lexicographic merge of two sorted uint32 row matrices
    (ties to ``a``): the host-engine twin of the Pallas merge-path
    kernel, used by the overlap run forest's CPU fallback. Returns None
    when the native library isn't available (caller re-lexsorts)."""
    lib = _load()
    if lib is None:
        return None
    a = np.ascontiguousarray(a, np.uint32)
    b = np.ascontiguousarray(b, np.uint32)
    assert a.ndim == 2 and b.ndim == 2 and a.shape[1] == b.shape[1]
    out = np.empty((a.shape[0] + b.shape[0], a.shape[1]), np.uint32)

    def u32(arr):
        return arr.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32))

    lib.uda_merge_rows(u32(a), a.shape[0], u32(b), b.shape[0],
                       a.shape[1], u32(out))
    return out


def merge_rows_native_into(a: np.ndarray, b: np.ndarray,
                           out: np.ndarray) -> bool:
    """merge_rows_native writing into a caller-owned ``out`` buffer
    (must be C-contiguous uint32 with a.shape[0]+b.shape[0] rows).
    Reusing merge outputs matters on this path: the overlap forest's
    merge traffic is k*log2(k) segment-loads, and a fresh np.empty per
    merge page-faults every output byte (the PR 6 large-alloc lesson) —
    the staging pipeline leases outputs from a buffer pool instead.
    Returns False when the native library isn't available."""
    lib = _load()
    if lib is None:
        return False
    assert a.flags.c_contiguous and b.flags.c_contiguous \
        and out.flags.c_contiguous
    assert out.shape[0] == a.shape[0] + b.shape[0] \
        and out.shape[1] == a.shape[1] == b.shape[1]

    def u32(arr):
        return arr.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32))

    lib.uda_merge_rows(u32(a), a.shape[0], u32(b), b.shape[0],
                       a.shape[1], u32(out))
    return True


class ReadPool:
    """Async read pool over the native worker threads — the AIOHandler
    submit/get_events contract (reference AIOHandler.cc:122-235)."""

    def __init__(self, threads: int = 2):
        lib = _load()
        if lib is None:
            raise StorageError("native library not built")
        self._lib = lib
        self._pool = lib.uda_pool_create(threads)
        self._lock = threading.Lock()
        self._next_tag = 0
        self._pending: dict[int, tuple[np.ndarray, object]] = {}

    def backend(self) -> str:
        """Which PARITY C15 rung this pool runs: "io_uring" when the
        ring backend was compiled in AND the running kernel accepted
        io_uring_setup, else "pool" (pread worker threads)."""
        if not self._pool:
            return "pool"
        return ("io_uring"
                if self._lib.uda_pool_backend(self._pool) == 1
                else "pool")

    def submit(self, fd: int, offset: int, length: int):
        """Returns a tag; the destination buffer is allocated here and
        returned by poll() with the completion."""
        buf = np.empty(length, np.uint8)
        with self._lock:
            tag = self._next_tag
            self._next_tag += 1
            self._pending[tag] = (buf, None)
        rc = self._lib.uda_pool_submit(self._pool, fd, offset, length,
                                       _u8ptr(buf), tag)
        if rc != 0:
            with self._lock:
                del self._pending[tag]
            raise StorageError("submit on stopped native pool")
        return tag

    def submit_batch(self, jobs) -> list:
        """Batched submission (the C15 submit_batch half): every
        ``(fd, offset, length)`` job enters the native pool in ONE
        call — one lock round / ring doorbell for the whole burst.
        Returns the tags in job order; completions ride poll() like
        single submits (per-tag isolation)."""
        n = len(jobs)
        if n == 0:
            return []
        bufs = [np.empty(length, np.uint8) for _, _, length in jobs]
        fds = (ctypes.c_int32 * n)(*[fd for fd, _, _ in jobs])
        offs = (ctypes.c_int64 * n)(*[off for _, off, _ in jobs])
        lens = (ctypes.c_int64 * n)(*[length for _, _, length in jobs])
        dsts = (ctypes.POINTER(ctypes.c_uint8) * n)(
            *[_u8ptr(b) for b in bufs])
        with self._lock:
            tags = list(range(self._next_tag, self._next_tag + n))
            self._next_tag += n
            for tag, buf in zip(tags, bufs):
                self._pending[tag] = (buf, None)
        ctags = (ctypes.c_uint64 * n)(*tags)
        rc = self._lib.uda_pool_submit_batch(self._pool, n, fds, offs,
                                             lens, dsts, ctags)
        if rc != 0:
            with self._lock:
                for tag in tags:
                    self._pending.pop(tag, None)
            raise StorageError("submit_batch on stopped native pool")
        return tags

    def poll(self, min_events: int = 1, timeout: float = 5.0
             ) -> list[tuple[int, object]]:
        """Drain completions: [(tag, result)] where result is the data
        sliced to the bytes actually read, or a StorageError for a failed
        read (per-tag: one bad read never poisons other requests)."""
        max_events = 256
        tags = (ctypes.c_uint64 * max_events)()
        results = (ctypes.c_int64 * max_events)()
        n = self._lib.uda_pool_get_events(self._pool, tags, results,
                                          max_events, min_events, timeout)
        out: list[tuple[int, object]] = []
        for i in range(n):
            tag = int(tags[i])
            res = int(results[i])
            with self._lock:
                ent = self._pending.pop(tag, None)
            if ent is None:
                # duplicate/stale completion (a tag already settled by
                # an error path): dropping it beats killing the router
                # thread that every native read in the process shares
                continue
            buf, _ = ent
            if res < 0:
                out.append((tag, StorageError(
                    f"native read failed: errno {-res}")))
            else:
                out.append((tag, buf[:res]))
        return out

    def close(self) -> None:
        if self._pool:
            self._lib.uda_pool_destroy(self._pool)
            self._pool = None

    def __enter__(self) -> "ReadPool":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
