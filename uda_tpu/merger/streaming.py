"""Streaming bounded-memory emission for the online merge.

The reference's online merge never materialized the shuffle on the host:
records flowed RDMA chunk buffers -> k-way heap -> 2 x 1 MB staging
buffers -> consumer (reference src/Merger/MergeManager.cc:155-182,
src/Merger/StreamRW.cc:151-225), so host memory stayed at
O(fetch window), independent of shuffle size. The TPU-native online path
computes the global sort permutation on device instead of running a
comparison heap — which is faster, but naively needs every segment's
bytes resident for the final gather. This module restores the
reference's memory model around the device permutation:

- **Sorted run spooling** (:class:`RunStore`): as each segment's fetch
  completes, its records are written to local disk *in per-segment
  sorted order* as an IFile-framed run plus an ``.off`` sidecar of
  cumulative framed-record end offsets; the raw fetched bytes are then
  released. Host memory during fetch = the in-flight window.
- **Permutation-driven interleave** (:func:`interleave_runs`): the
  merged device rows already encode, for every output position, which
  segment supplies the next record. Because each run is sorted, every
  run is consumed strictly *sequentially* — the emit phase is k
  file cursors and one output slab, no comparisons, no random
  access ever (the property that let the reference emit from 1 MB
  staging buffers, MergeQueue.h:276-427). With the native library a
  slab is one C pass through a per-task table of run cursors, one
  pread buffer a run (O(records), whatever the run count); the
  numpy path (buffered cursors, two masked passes per run per slab) is
  the fallback and the reference the native one is parity-tested
  against.
- **Slab gather** (:func:`slab_batch`): the in-memory twin used when
  streaming is off — gathers each output slab's bytes directly from the
  per-segment batches, so even the memory-resident path never
  concatenates the whole shuffle a second time. With the native library
  it is two C passes per slab over a per-task :func:`segment_table`
  (O(records), whatever the segment count); the
  numpy path (two masked passes per segment per slab) is the fallback
  and the reference the native one is parity-tested against.

The rest is vectorized numpy; its only per-record work is done by the
native framer when runs are written and by the native span gather.
"""

from __future__ import annotations

import os
import tempfile
import threading
import zlib
from typing import Iterator, Optional, Sequence

import numpy as np

from uda_tpu import native
from uda_tpu.utils.errors import MergeError, StorageError
from uda_tpu.utils.ifile import EOF_MARKER, RecordBatch, native_enabled
from uda_tpu.utils.logging import get_logger
from uda_tpu.utils.metrics import metrics

__all__ = ["RunStore", "framed_lengths", "interleave_runs", "slab_batch",
           "segment_table", "iter_row_slabs", "SLAB_RECORDS"]

log = get_logger()

# records per emission slab: bounds transient host memory at emit to one
# slab's bytes (the streaming analogue of the reference's staging loop)
SLAB_RECORDS = 1 << 16


def _vlong_sizes(values: np.ndarray) -> np.ndarray:
    """Vectorized ``vint.vlong_size`` for non-negative lengths."""
    v = np.asarray(values, dtype=np.int64)
    if np.any(v < 0):
        raise MergeError("negative record length")
    # 1 byte for <=127; else 1 tag byte + minimal big-endian body
    nbits = np.zeros_like(v)
    nz = v > 0
    # number of bits via log2 on float64 is exact for lengths < 2^53
    nbits[nz] = np.floor(np.log2(v[nz])).astype(np.int64) + 1
    body = (nbits + 7) // 8
    return np.where(v <= 127, 1, body + 1)


def framed_lengths(key_len: np.ndarray, val_len: np.ndarray) -> np.ndarray:
    """Per-record IFile framed byte length: VInt(klen) VInt(vlen) key
    value (the ``write_kv_to_stream`` framing, StreamRW.cc:151-225)."""
    return (_vlong_sizes(key_len) + _vlong_sizes(val_len)
            + np.asarray(key_len, np.int64) + np.asarray(val_len, np.int64))


def _expand_spans(off: np.ndarray, length: np.ndarray) -> np.ndarray:
    """Flat int64 indices covering [off_i, off_i + length_i) for every i,
    concatenated in order — the vectorized byte-gather index (the
    pure-numpy fallback of :func:`_gather_spans`)."""
    length = np.asarray(length, np.int64)
    total = int(length.sum())
    if total == 0:
        return np.empty(0, np.int64)
    ends = np.cumsum(length)
    starts = ends - length
    return np.repeat(np.asarray(off, np.int64) - starts, length) + np.arange(
        total, dtype=np.int64)


_native_built = None  # resolved build/availability, cached per process


def _native_ready() -> bool:
    """Whether this call may take a native gather: library availability
    is resolved once per process; the ``uda.tpu.use.native`` kill switch
    stays LIVE (re-read per call, like frame_batch)."""
    global _native_built
    if not native_enabled():
        return False
    if _native_built is None:
        _native_built = bool(native.build() and native.available())
    return _native_built


def _gather_spans(src: np.ndarray, src_off: np.ndarray, lens: np.ndarray,
                  dst: np.ndarray, dst_off: np.ndarray) -> None:
    """dst[dst_off_i : +len_i] = src[src_off_i : +len_i] per record —
    native memcpy loop when built (8x less memory traffic than the
    expand-index fallback, the streaming emit hot path)."""
    if (_native_ready()
            and native.gather_spans_native(src, src_off, lens, dst, dst_off)):
        return
    dst[_expand_spans(dst_off, lens)] = src[_expand_spans(src_off, lens)]


def _group_ranks(seg: np.ndarray) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """For a slab's segment-index column, return (unique_segs,
    per-record rank within its segment group, per-seg counts) — the
    sequential-cursor positions each record consumes."""
    unique, inverse, counts = np.unique(seg, return_inverse=True,
                                        return_counts=True)
    # rank of each occurrence within its group, preserving slab order
    order = np.argsort(inverse, kind="stable")
    ranks_sorted = np.arange(seg.shape[0], dtype=np.int64) - np.repeat(
        np.cumsum(counts) - counts, counts)
    ranks = np.empty(seg.shape[0], np.int64)
    ranks[order] = ranks_sorted
    return unique, ranks, counts


def spill_dirs(cfg) -> list[str]:
    """Parse ``uda.tpu.spill.dirs`` into a rotation list (shared by the
    hybrid LPQ spiller and the streaming run store); empty = system
    tmp."""
    dirs = [d for d in str(cfg.get("uda.tpu.spill.dirs")).split(",") if d]
    return dirs or [tempfile.gettempdir()]


class RunStore:
    """Per-segment sorted run files + offset sidecars in scratch dirs.

    One run per staged segment: ``run-SSSSS.ifile`` holds the segment's
    records in sorted order with the EOF marker (a complete, valid IFile
    stream — so the comparator-level k-way merge can consume runs
    directly on the overflow fallback), and ``run-SSSSS.off`` holds
    int64 cumulative end offsets of each framed record (EOF excluded),
    letting the interleave slice records without parsing framing.
    Multiple base dirs rotate per segment (the reference's local-dir
    rotation the hybrid spiller also follows). Thread-safe: a staging
    pool may spool different segments concurrently.
    """

    def __init__(self, base_dirs=None, tag: str = "online",
                 fixed_dir: Optional[str] = None):
        # fixed_dir (checkpointing, merger/checkpoint.py): run files
        # live at a STABLE path that survives the process, so a
        # restarted attempt finds them where the manifest says; the
        # checkpoint owns the directory's lifetime (cleanup() keeps the
        # files — they ARE the durable state; TaskCheckpoint.discard
        # removes them on task success)
        self.fixed = fixed_dir is not None
        if self.fixed:
            os.makedirs(fixed_dir, exist_ok=True)
            self.dirs = [fixed_dir]
        else:
            if isinstance(base_dirs, str):
                base_dirs = [base_dirs]
            roots = (list(base_dirs) if base_dirs
                     else [tempfile.gettempdir()])
            self.dirs = []
            for root in roots:
                os.makedirs(root, exist_ok=True)
                self.dirs.append(
                    tempfile.mkdtemp(prefix=f"uda.{tag}.runs.", dir=root))
        self.counts: dict[int, int] = {}   # seg index -> record count
        self.bytes: dict[int, int] = {}    # seg index -> framed bytes (no EOF)
        self.crcs: dict[int, int] = {}     # seg index -> crc32 of the
        # whole run file including the EOF marker (the checkpoint
        # manifest's torn-spool detector)
        self._lock = threading.Lock()
        self._closed = False

    @property
    def dir(self) -> str:
        """Primary scratch dir (single-dir stores; tests)."""
        return self.dirs[0]

    def _paths(self, seg_index: int) -> tuple[str, str]:
        stem = os.path.join(self.dirs[seg_index % len(self.dirs)],
                            f"run-{seg_index:05d}")
        return stem + ".ifile", stem + ".off"

    def run_path(self, seg_index: int) -> str:
        return self._paths(seg_index)[0]

    @property
    def total_records(self) -> int:
        return sum(self.counts.values())

    @staticmethod
    def _contiguous_framed_span(batch: RecordBatch,
                                lens: np.ndarray) -> Optional[tuple]:
        """When the batch's records sit back-to-back in its data buffer
        in their original framing (the shape every cracked segment has),
        return the (start, end) byte span — the run file can then be
        written straight from the fetched bytes, skipping re-framing."""
        n = batch.num_records
        if n == 0:
            return None
        head = framed_lengths(batch.key_len, batch.val_len) \
            - batch.key_len - batch.val_len  # both VInt header bytes
        starts = batch.key_off - head
        ends = batch.val_off + batch.val_len
        if (int(starts[0]) >= 0 and np.all(starts[1:] == ends[:-1])
                and np.array_equal(lens, ends - starts)):
            return int(starts[0]), int(ends[-1])
        return None

    def write_run(self, seg_index: int, batch: RecordBatch,
                  order: np.ndarray) -> None:
        """Spool ``batch`` in ``order`` as this segment's sorted run.
        Streams framed chunks (native framer) — peak memory is one
        chunk, never the whole segment twice. Identity order over a
        contiguously framed batch (the already-sorted Hadoop MOF case)
        writes the fetched bytes verbatim."""
        with self._lock:
            if seg_index in self.counts:
                raise MergeError(f"segment {seg_index} staged twice")
            self.counts[seg_index] = -1  # reserve (pool-safe)
        sub = batch.take(order)
        run_path, off_path = self._paths(seg_index)
        lens = framed_lengths(sub.key_len, sub.val_len)
        ends = np.cumsum(lens)
        total = int(ends[-1]) if len(ends) else 0
        identity = (order.shape[0] > 0
                    and np.array_equal(order,
                                       np.arange(order.shape[0])))
        span = self._contiguous_framed_span(batch, lens) \
            if identity else None
        # CRC accumulated while writing (whole file incl. EOF): the
        # checkpoint manifest's torn-spool detector costs one pass over
        # bytes already in cache, no re-read
        crc = 0
        with metrics.timer("run_spool"):
            with open(run_path, "wb") as f:
                if span is not None:
                    piece = memoryview(batch.data[span[0]:span[1]])
                    f.write(piece)
                    crc = zlib.crc32(piece)
                    f.write(EOF_MARKER)
                    crc = zlib.crc32(EOF_MARKER, crc)
                else:
                    for piece in native.iter_framed_chunks(
                            sub, write_eof=True):
                        f.write(piece)
                        crc = zlib.crc32(piece, crc)
                if self.fixed:
                    f.flush()
                    os.fsync(f.fileno())
            wrote = os.path.getsize(run_path)
            if wrote != total + len(EOF_MARKER):
                raise StorageError(
                    f"run {seg_index}: framed {wrote} bytes, offsets "
                    f"predict {total + len(EOF_MARKER)}")
            with open(off_path, "wb") as f:
                ends.astype("<i8").tofile(f)
                f.flush()
                if self.fixed:
                    # checkpoint mode: the sidecar must be durable
                    # before a manifest can reference this run
                    os.fsync(f.fileno())
        with self._lock:
            self.counts[seg_index] = sub.num_records
            self.bytes[seg_index] = total
            self.crcs[seg_index] = crc & 0xFFFFFFFF
        metrics.add("spool.bytes", total)

    def adopt(self, seg_index: int, records: int, nbytes: int,
              crc: int) -> None:
        """Register an already-on-disk run (checkpoint resume: the file
        was written — and validated against the manifest — by a prior
        attempt). Accounting only; no bytes move."""
        with self._lock:
            if seg_index in self.counts:
                raise MergeError(f"segment {seg_index} staged twice")
            self.counts[seg_index] = int(records)
            self.bytes[seg_index] = int(nbytes)
            self.crcs[seg_index] = int(crc) & 0xFFFFFFFF

    def discard(self, seg_index: int) -> None:
        """Unlink an UNREGISTERED run's files (a checkpoint adoption
        that failed revalidation — the segment re-fetches and write_run
        later rewrites the path)."""
        for p in self._paths(seg_index):
            try:
                os.unlink(p)
            except OSError:
                pass  # udalint: disable=UDA006 - cleanup best effort

    def manifest(self) -> dict[int, tuple[int, int, int]]:
        """Snapshot of COMPLETED runs for the checkpoint writer:
        {seg_index: (records, framed_bytes, crc)} — reserved-but-
        unfinished spools (count -1) are excluded; they will appear in
        a later snapshot once durable."""
        with self._lock:
            return {s: (n, self.bytes[s], self.crcs[s])
                    for s, n in self.counts.items() if n >= 0}

    def cleanup(self) -> None:
        with self._lock:
            if self._closed:
                return
            self._closed = True
            segs = list(self.counts)
        if self.fixed:
            # checkpoint-owned directory: the run files ARE the durable
            # resume state — a failed attempt must leave them for the
            # next one; TaskCheckpoint.discard removes the whole task
            # dir once the merge output is delivered
            return
        for seg in segs:
            for p in self._paths(seg):
                try:
                    os.unlink(p)
                except OSError:
                    pass
        for d in self.dirs:
            try:
                os.rmdir(d)
            except OSError:
                pass


# open-cursor cap for the interleave: 2 fds per open cursor, kept well
# under common ulimits however many segments the shuffle has (evicted
# cursors reopen + seek — reads stay strictly sequential either way)
MAX_OPEN_CURSORS = 256


class _RunCursor:
    """Sequential reader over one run: hands out the byte span covering
    the next ``count`` records. Suspendable: ``suspend()`` closes both
    file handles and a later read transparently reopens at the consumed
    position, so an interleave over thousands of runs stays within the
    process fd limit."""

    __slots__ = ("run_path", "off_path", "run_f", "off_f",
                 "consumed_bytes", "consumed_records")

    def __init__(self, run_path: str, off_path: str):
        self.run_path = run_path
        self.off_path = off_path
        self.run_f = None
        self.off_f = None
        self.consumed_bytes = 0
        self.consumed_records = 0

    @property
    def is_open(self) -> bool:
        return self.run_f is not None

    def _ensure_open(self) -> None:
        if self.run_f is None:
            self.run_f = open(self.run_path, "rb")
            self.off_f = open(self.off_path, "rb")
            self.run_f.seek(self.consumed_bytes)
            self.off_f.seek(self.consumed_records * 8)

    def next_span(self, count: int) -> tuple[np.ndarray, np.ndarray]:
        """Returns (span_bytes, record_lengths) for the next ``count``
        records."""
        self._ensure_open()
        ends = np.fromfile(self.off_f, dtype="<i8", count=count)
        if ends.shape[0] != count:
            raise StorageError("run offset sidecar truncated")
        lens = np.diff(ends, prepend=np.int64(self.consumed_bytes))
        span = np.fromfile(self.run_f, dtype=np.uint8,
                           count=int(ends[-1]) - self.consumed_bytes)
        if span.shape[0] != int(ends[-1]) - self.consumed_bytes:
            raise StorageError("run file truncated")
        self.consumed_bytes = int(ends[-1])
        self.consumed_records += count
        return span, lens

    def suspend(self) -> None:
        if self.run_f is not None:
            self.run_f.close()
            self.off_f.close()
            self.run_f = self.off_f = None

    def close(self) -> None:
        self.suspend()


def iter_row_slabs(rows, valid: int,
                   slab: int = SLAB_RECORDS) -> Iterator[np.ndarray]:
    """Yield the merged composite-key rows in bounded host slabs (the
    rows may be device-resident; each slice transfers one slab, timed
    as ``emit_readback``)."""
    for start in range(0, valid, slab):
        stop = min(start + slab, valid)
        with metrics.timer("emit_readback"):
            host = np.asarray(rows[start:stop])
        yield host


def interleave_runs(slabs: Iterator[np.ndarray], store: RunStore,
                    num_key_words: int) -> Iterator[bytes]:
    """Permutation-driven k-way interleave of the sorted runs.

    ``slabs`` yields merged rows whose column ``num_key_words + 1`` is
    the segment index (the OverlappedMerger row layout). Each slab
    becomes one framed output piece; runs are read strictly
    sequentially. The concatenation of the yielded pieces plus the
    EOF marker is the complete merged IFile stream.

    With the native library each slab is gathered by
    ``native.gather_runs_native`` through a per-task ``native.RunTable``
    (built before the first slab, under no span: one read buffer a run,
    descriptors held between fills only for ``MAX_OPEN_CURSORS`` runs
    or fewer) and counted in ``emit.gather.native_slabs``; such a piece
    is a view of the table's output buffer and holds until the next
    piece is asked for. Without the library, or with
    ``uda.tpu.use.native`` off as the task starts to emit, the numpy
    cursors below run (2 file handles per open segment, at most
    ``MAX_OPEN_CURSORS`` open at a time) — the plain reference, same
    bytes. A task takes one path from its first slab to its last: the
    two keep their cursors apart.
    """
    table = None
    if _native_ready():
        table = native.RunTable(
            {s: (store.run_path(s), n, store.bytes[s])
             for s, n in store.counts.items()},
            keep_open=len(store.counts) <= MAX_OPEN_CURSORS)
    cursors: dict[int, _RunCursor] = {}
    open_lru: dict[int, None] = {}  # insertion-ordered set of open segs

    def _touch(s: int, cur: _RunCursor) -> None:
        open_lru.pop(s, None)
        open_lru[s] = None
        while len(open_lru) > MAX_OPEN_CURSORS:
            victim, _ = next(iter(open_lru.items()))
            del open_lru[victim]
            cursors[victim].suspend()

    def gather_slab(rows: np.ndarray) -> bytes:
        """One slab's framed bytes, gathered from the runs' next
        spans in merged order (the runs are framed already, so this
        route has no ``emit_frame`` stage)."""
        seg = rows[:, num_key_words + 1].astype(np.int64)
        unique, ranks, counts = _group_ranks(seg)
        spans: dict[int, np.ndarray] = {}
        starts: dict[int, np.ndarray] = {}
        lens: dict[int, np.ndarray] = {}
        for s, c in zip(unique.tolist(), counts.tolist()):
            cur = cursors.get(s)
            if cur is None:
                if s not in store.counts:
                    raise MergeError(
                        f"merged rows reference unstaged segment {s}")
                cur = cursors[s] = _RunCursor(*store._paths(s))
            span, ln = cur.next_span(c)
            _touch(s, cur)
            spans[s] = span
            lens[s] = ln
            starts[s] = np.cumsum(ln) - ln
        # per-record framed length and source offset in its span
        rec_len = np.empty(seg.shape[0], np.int64)
        src_off = np.empty(seg.shape[0], np.int64)
        for s in unique.tolist():
            m = seg == s
            rec_len[m] = lens[s][ranks[m]]
            src_off[m] = starts[s][ranks[m]]
        out = np.empty(int(rec_len.sum()), np.uint8)
        dst_end = np.cumsum(rec_len)
        dst_start = dst_end - rec_len
        for s in unique.tolist():
            m = seg == s
            _gather_spans(spans[s], src_off[m], rec_len[m],
                          out, dst_start[m])
        return out.tobytes()

    try:
        for rows in slabs:
            if rows.shape[0] == 0:
                continue
            with metrics.timer("emit_gather"):
                if table is not None:
                    # a view of the table's output buffer: consumed
                    # before the next slab is asked for
                    piece = native.gather_runs_native(
                        table, rows[:, num_key_words + 1])
                    metrics.add("emit.gather.native_slabs")
                else:
                    piece = gather_slab(rows)
            yield piece
        # verify every run was fully consumed (lost-records guard)
        for s, n in store.counts.items():
            if table is not None:
                done = table.consumed(s)
            else:
                done = cursors[s].consumed_records if s in cursors else 0
            if done != n:
                raise MergeError(
                    f"run {s}: merged rows consumed {done} of {n} records")
    finally:
        for cur in cursors.values():
            cur.close()
        if table is not None:
            table.close()
    yield EOF_MARKER


def segment_table(batches: Sequence[RecordBatch]
                  ) -> Optional["native.SegmentTable"]:
    """The per-task table :func:`slab_batch`'s native path looks records
    up through (O(segments) to build, nothing concatenated), or None
    when the native library is off or unavailable — every slab then
    takes the numpy path."""
    return native.SegmentTable(batches) if _native_ready() else None


def slab_batch(batches: Sequence[RecordBatch], seg: np.ndarray,
               row: np.ndarray,
               table: Optional["native.SegmentTable"] = None) -> RecordBatch:
    """Gather one output slab's records from per-segment batches into a
    compact RecordBatch (its own small data buffer: all keys, then all
    values) — the in-memory emission path's bounded gather, replacing
    whole-shuffle concat. ``seg`` / ``row`` name each record's batch and
    its row in it; the uint32 slab's columns may be passed as they are.

    With ``table`` (:func:`segment_table` over the same ``batches``) the
    slab is gathered by the native routine in O(records) and counted in
    ``emit.gather.native_slabs``; an index or span out of range raises
    MergeError. Without it, or with ``uda.tpu.use.native`` switched off
    since, the numpy path below runs: two masked passes per segment
    present in the slab — the plain reference, same bytes."""
    if table is not None and native_enabled():
        sub = native.gather_slab_native(table, seg, row)
        if sub is not None:
            metrics.add("emit.gather.native_slabs")
            return sub
    seg = np.asarray(seg).astype(np.int64, copy=False)
    row = np.asarray(row).astype(np.int64, copy=False)
    m = seg.shape[0]
    k_len = np.empty(m, np.int64)
    v_len = np.empty(m, np.int64)
    for s in np.unique(seg).tolist():
        msk = seg == s
        b = batches[s]
        r = row[msk]
        k_len[msk] = b.key_len[r]
        v_len[msk] = b.val_len[r]
    k_total = int(k_len.sum())
    buf = np.empty(k_total + int(v_len.sum()), np.uint8)
    k_off = np.cumsum(k_len) - k_len
    v_off = k_total + np.cumsum(v_len) - v_len
    for s in np.unique(seg).tolist():
        msk = seg == s
        b = batches[s]
        r = row[msk]
        _gather_spans(b.data, b.key_off[r], k_len[msk], buf, k_off[msk])
        _gather_spans(b.data, b.val_off[r], v_len[msk], buf, v_off[msk])
    return RecordBatch(buf, k_off, k_len, v_off, v_len)
