"""Record-level merge APIs + host fallback.

``merge_batches`` is the framework's equivalent of the reference's
network-levitated merge core (MergeManager's PQ over Segments, reference
src/Merger/MergeManager.cc:155-182 + MergeQueue.h:276-427): take k sorted
segments, produce the globally sorted record stream. Here the comparator
work happens on device (uda_tpu.ops.sort); the host only packs columns
and gathers bytes at emission.

``merge_batches_host`` is the pure-host fallback, kept (a) as the
correctness oracle the device path is diffed against, and (b) as the
actual merge path when no accelerator is present — mirroring the
reference's fallback-to-vanilla philosophy (SURVEY §5) inside the engine.

The run-row helpers here (``stage_run_rows``, ``merge_row_pair``,
``RowBufferPool``, ...) build and merge the sorted runs of the
overlapped merger's forest (uda_tpu.merger.overlap): each run is
partially sorted on its own (usually just the monotonicity check: Hadoop
map outputs arrive comparator-sorted) and the runs fold through a
pairwise merge tree (the O(n log k) merge-path kernel / native linear
merge), so every record moves through at most log2(k) merges.
"""

from __future__ import annotations

import functools
import heapq
import threading
from typing import Iterator, Optional, Sequence, Tuple

import jax
import numpy as np

from uda_tpu import native
from uda_tpu.ops import packing, sort
from uda_tpu.ops.pallas_merge import merge_sorted_pair
from uda_tpu.utils.comparators import KeyType
from uda_tpu.utils.ifile import RecordBatch, native_enabled
from uda_tpu.utils.metrics import metrics
from uda_tpu.utils.resledger import resledger


def _buf_key(flat: np.ndarray) -> int:
    """Ledger identity of a pool buffer: its base data pointer (stable
    across the lease's view reshapes; cheap on both sides)."""
    return int(flat.__array_interface__["data"][0])

__all__ = ["merge_batches", "merge_batches_host", "merge_iter_host",
           "merge_record_streams", "sorted_batch_order",
           "resolve_run_engine", "resolve_native_rows_merge",
           "lex_cols_sorted", "run_row_order", "fill_run_rows",
           "stage_run_rows",
           "merge_row_pair", "merge_split_point", "merge_rows_split_into",
           "RowBufferPool", "next_run_capacity", "pad_rows_to",
           "PAD_WORD", "MIN_RUN_CAPACITY", "ROW_EXTRA_COLS"]

# -- run-row machinery (the overlap forest) ---------------------------------

# Padding word for device runs: all-0xFFFFFFFF rows sort strictly after
# every real row (a real row's length column is a content length < 2^31),
# so valid rows stay a prefix through any merge.
PAD_WORD = np.uint32(0xFFFFFFFF)

MIN_RUN_CAPACITY = 512  # smallest padded run (= default merge tile)

# composite-key columns appended after the key words:
# (content length, segment index, row index)
ROW_EXTRA_COLS = 3


def next_run_capacity(n: int) -> int:
    """Smallest power-of-two run capacity >= n (>= MIN_RUN_CAPACITY):
    bounds the set of pallas merge-kernel shapes to O(log) per job."""
    p = MIN_RUN_CAPACITY
    while p < n:
        p *= 2
    return p


def resolve_run_engine(engine: str) -> str:
    """Resolve the pairwise run-merge backend: "pallas" (the device
    merge-path kernel), "host" (vectorized numpy/native merge — the
    correctness twin, and the fast choice on the XLA CPU backend), or
    "auto" (host on CPU, pallas elsewhere)."""
    if engine == "auto":
        return "host" if jax.default_backend() == "cpu" else "pallas"
    if engine not in ("host", "pallas"):
        from uda_tpu.utils.errors import MergeError

        raise MergeError(f"unknown run merge engine {engine!r}")
    return engine


def resolve_native_rows_merge():
    """The native linear two-pointer row merge when built, else None.
    Resolved ONCE per consumer so a cold .so compiles before any merge
    runs under a forest lock (a make inside the lock would stall the
    whole staging pool)."""
    if native_enabled() and native.build():
        return native.merge_rows_native
    return None


def merge_split_point(a_rows: np.ndarray, b_rows: np.ndarray,
                      m: int) -> int:
    """Merge-path partition with the ties-to-``a`` rule: the unique
    ``ia`` (with ``ib = m - ia``) such that the first ``m`` rows of the
    stable merge are exactly ``merge(a[:ia], b[:ib])`` — i.e.
    ``a[ia-1] <= b[ib]`` (a tie sends the ``a`` row first, so equality
    keeps it in the prefix) and ``b[ib-1] < a[ia]`` (an equal ``a`` row
    would precede, so the ``b`` prefix row must be strictly smaller).
    O(log n) full-row lexicographic compares; used to split one large
    pairwise merge across threads without breaking stability."""
    na, nb = int(a_rows.shape[0]), int(b_rows.shape[0])
    lo, hi = max(0, m - nb), min(na, m)
    while lo < hi:
        ia = (lo + hi) // 2
        ib = m - ia
        # a[ia] <= b[ib-1]: that a row ties-or-precedes the b prefix
        # row, so it belongs in the prefix too -> ia is too small
        if ia < na and ib > 0 and tuple(a_rows[ia]) <= tuple(b_rows[ib - 1]):
            lo = ia + 1
        else:
            hi = ia
    return lo


def merge_rows_split_into(a_rows: np.ndarray, b_rows: np.ndarray,
                          out: np.ndarray, parts: int = 2) -> bool:
    """Native linear merge of two sorted row runs into a caller-owned
    ``out`` buffer, split across ``parts`` threads at merge-path
    partition points (each part is an independent contiguous-slice
    merge; the native call releases the GIL, so parts genuinely run in
    parallel). Stability (ties to ``a``) is preserved by construction —
    see :func:`merge_split_point`. Returns False when the native
    library isn't built (caller falls back); single-part calls degrade
    to one plain native merge."""
    na, nb = int(a_rows.shape[0]), int(b_rows.shape[0])
    total = na + nb
    parts = max(1, min(int(parts), max(1, total)))
    if parts == 1:
        return native.merge_rows_native_into(a_rows, b_rows, out)
    if not native.available():
        return False
    cuts_a = [0]
    for p in range(1, parts):
        cuts_a.append(merge_split_point(a_rows, b_rows, total * p // parts))
    cuts_a.append(na)
    # every part reports into ok: a part whose native call refuses
    # (e.g. the .so momentarily unloaded by a concurrent rebuild) left
    # stale pool-lease bytes in its out slice — the caller MUST fall
    # back, so a single False fails the whole split
    ok = [False] * parts

    def _part(idx: int, a: np.ndarray, b: np.ndarray, o: np.ndarray):
        ok[idx] = bool(native.merge_rows_native_into(a, b, o))

    threads = []
    for p in range(parts):
        mlo = total * p // parts if p else 0
        mhi = total * (p + 1) // parts if p < parts - 1 else total
        alo, ahi = cuts_a[p], cuts_a[p + 1]
        blo, bhi = mlo - alo, mhi - ahi
        args = (p, a_rows[alo:ahi], b_rows[blo:bhi], out[mlo:mhi])
        if p < parts - 1:
            t = threading.Thread(target=_part, args=args, daemon=True)
            t.start()
            threads.append(t)
        else:
            _part(*args)  # last part inline
    for t in threads:
        t.join()
    return all(ok)


class RowBufferPool:
    """Reusable pre-allocated host uint32 row buffers.

    Two hot paths lease from it: stage workers building device-bound
    row matrices (recycled once the jax.device_put transfer completes)
    and the host-engine pipeline's merge outputs (recycled when the run
    merges into a larger one) — the forest's merge traffic is
    k*log2(k) segment-loads, and a fresh np.empty per merge would
    page-fault every output byte (the PR 6 large-alloc lesson).
    Buffers are flat uint32 arrays reshaped per lease, so one big
    early buffer serves every later exact-size request; the free list
    is bounded so a pathological size spread cannot hoard host
    memory."""

    MAX_FREE = 8

    def __init__(self, lock_class: str = "stage.bufpool"):
        from uda_tpu.utils.locks import TrackedLock

        self._lock = TrackedLock(lock_class)
        self._free: list[np.ndarray] = []

    def lease(self, rows: int, cols: int) -> np.ndarray:
        need = rows * cols
        got = None
        with self._lock:
            for i, buf in enumerate(self._free):
                if buf.size >= need:
                    got = self._free.pop(i)
                    metrics.add("stage.buffer.reuses")
                    break
        if got is None:
            got = np.empty(need, np.uint32)
        # ledger key = the base buffer's data pointer: release() walks
        # any view back to the same base, so both sides reproduce it
        resledger.acquire("pool.lease", key=_buf_key(got),
                          owner=id(self), amount=need * 4)
        return got[:need].reshape(rows, cols)

    def release(self, view: Optional[np.ndarray]) -> None:
        if view is None:
            return
        base = view
        while base.base is not None:
            base = base.base
        flat = np.asarray(base, np.uint32).reshape(-1)
        resledger.settle("pool.lease", key=_buf_key(flat), owner=id(self))
        with self._lock:
            self._free.append(flat)
            self._free.sort(key=lambda b: b.size)
            del self._free[self.MAX_FREE:]


def lex_cols_sorted(cols: Sequence[np.ndarray]) -> bool:
    """Vectorized lexicographic monotonicity over parallel uint columns:
    True when every adjacent pair is non-decreasing under first-column
    priority (O(n·k) — the already-sorted fast path that replaces an
    O(n log n) lexsort for Hadoop's map-side-sorted segments)."""
    n = cols[0].shape[0]
    if n < 2:
        return True
    lt = cols[0][:-1] < cols[0][1:]
    eq = cols[0][:-1] == cols[0][1:]
    for c in cols[1:]:
        lt = lt | (eq & (c[:-1] < c[1:]))
        eq = eq & (c[:-1] == c[1:])
    return bool(np.all(lt | eq))


def run_row_order(packed: packing.PackedKeys) -> Optional[np.ndarray]:
    """Per-run sort order under (words, len) — which equals comparator
    order for within-width keys. Returns None when the run is already
    sorted (identity order; the map-side sort contract the reference's
    merge leaned on — it never re-sorted segments, MergeManager.cc:
    47-63), else the int64 lexsort permutation. Stable: equal keys keep
    arrival order."""
    kw = packed.key_words.shape[1]
    cols = [packed.key_words[:, c] for c in range(kw)] \
        + [packed.key_lens.astype(np.uint32)]
    if lex_cols_sorted(cols):
        return None
    # np.lexsort: LAST key is primary -> reversed column priority
    return np.lexsort(tuple(reversed(cols))).astype(np.int64)


def fill_run_rows(rows: np.ndarray, packed: packing.PackedKeys,
                  order: Optional[np.ndarray], seg_index: int) -> None:
    """Fill a (cap >= n, kw+3) uint32 row matrix with the sorted
    composite-key rows (words..., content length, segment index,
    ORIGINAL row index) and PAD_WORD tail. Writes the sorted rows
    directly (no build-then-permute copy); ``order=None`` = identity."""
    n = packed.num_records
    kw = packed.key_words.shape[1]
    if order is None:
        rows[:n, :kw] = packed.key_words
        rows[:n, kw] = packed.key_lens.astype(np.uint32)
        rows[:n, kw + 2] = np.arange(n, dtype=np.uint32)
    else:
        rows[:n, :kw] = packed.key_words[order]
        rows[:n, kw] = packed.key_lens[order].astype(np.uint32)
        rows[:n, kw + 2] = order.astype(np.uint32)
    rows[:n, kw + 1] = np.uint32(seg_index)
    if rows.shape[0] > n:
        rows[n:] = PAD_WORD


def stage_run_rows(rows: np.ndarray, batch: RecordBatch, kt: KeyType,
                   width: int, seg_index: int) -> Tuple[bool, int, int]:
    """One segment's staging work on its keys: fill ``rows`` (cap >= n,
    width/4 + 3) with the segment's sorted composite-key rows and
    PAD_WORD tail. Returns ``(presorted, longest, nbytes)``: whether
    the segment arrived in (words, len) order — if not, the rows'
    row-index column is the stable order vector — the longest key
    content, and the key + value bytes.

    With the native library (and ``uda.tpu.use.native`` on) this is ONE
    C pass, ``native.stage_segment_native``, counted in
    ``stage.native_segments``: a stage worker gives up the interpreter
    lock once a segment, where the numpy path below — ``pack_keys``,
    ``run_row_order``, ``fill_run_rows``, the fallback and the plain
    reference, same bytes — gives it up ~50 times, and a pool of
    workers staging small segments then spends its time handing the
    lock around. A key longer than ``width`` gets a row like any other
    on either path — its first ``width`` bytes as words, its whole
    content length — and the rows are sorted by (words, length, row):
    inside a block of oversize keys with equal words that is not the
    comparator's order, which the caller restores at emit
    (merger/overlap.py) or replaces by a fallback; ``presorted`` says
    nothing about such a block."""
    if native_enabled():
        staged = native.stage_segment_native(batch, kt, width, seg_index,
                                             rows)
        if staged is not None:
            metrics.add("stage.native_segments")
            return staged
    packed = packing.pack_keys(batch, kt, width, ranks=False)
    nbytes = int(batch.key_len.sum() + batch.val_len.sum())
    longest = int(np.max(packed.key_lens, initial=0))
    order = run_row_order(packed)
    fill_run_rows(rows, packed, order, seg_index)
    return order is None, longest, nbytes


def merge_row_pair(a_rows, b_rows, a_valid: int, b_valid: int,
                   engine: str, interpret: bool = False,
                   native_merge=None):
    """Merge two sorted composite-key row runs into one. Host engine:
    linear two-pointer native merge when built (ties to ``a`` = the
    earlier run, preserving the composite-key stability); lexsort of
    the concatenation otherwise. Pallas engine: the O(n) merge-path
    kernel — every column is part of the composite key (words, len,
    seg, row), rows are totally ordered, so the kernel's internal
    tie-break never decides anything."""
    if engine == "host":
        if native_merge is not None:
            merged = native_merge(np.asarray(a_rows[:a_valid]),
                                  np.asarray(b_rows[:b_valid]))
            if merged is not None:
                return merged
        rows = np.concatenate([a_rows[:a_valid], b_rows[:b_valid]])
        order = np.lexsort(tuple(rows[:, c]
                                 for c in range(rows.shape[1] - 1, -1, -1)))
        return rows[order]
    return merge_sorted_pair(a_rows, b_rows,
                             num_keys=int(a_rows.shape[1]),
                             interpret=interpret)


def sorted_batch_order(batch: RecordBatch, kt: KeyType, width: int) -> np.ndarray:
    """Device-computed stable sort permutation for one batch."""
    with metrics.timer("pack"):
        packed = packing.pack_keys(batch, kt, width)
    with metrics.timer("device_sort"):
        return sort.sort_permutation(packed)


def merge_batches(batches: Sequence[RecordBatch], kt: KeyType,
                  width: int) -> RecordBatch:
    """Merge k sorted (or unsorted — the sort is total) segments on device.

    Overflow ranks are computed across the *concatenation* so they are
    globally consistent (see merge_runs caveat in uda_tpu.ops.sort).
    This is also where a task with keys longer than ``width`` ends up
    (merger.overlap's overflow fallback), so each stage has a timer of
    its own: ``overflow_concat``, ``pack`` (``overflow_rank`` inside
    it), ``device_sort``, ``overflow_take``.
    """
    with metrics.timer("overflow_concat"):
        cat = RecordBatch.concat(list(batches))
    order = sorted_batch_order(cat, kt, width)
    with metrics.timer("overflow_take"):
        return cat.take(order)


def merge_batches_host(batches: Sequence[RecordBatch], kt: KeyType) -> RecordBatch:
    """Host oracle: stable sort of the concatenation by comparator order.

    Equal keys keep (segment, record) arrival order — the same contract
    the device path's stable sort provides.
    """
    cat = RecordBatch.concat(list(batches))
    idx = list(range(cat.num_records))
    keys = [cat.key(i) for i in idx]
    cmp = kt.compare
    order = sorted(idx, key=functools.cmp_to_key(
        lambda i, j: cmp(keys[i], keys[j])))
    return cat.take(np.asarray(order, dtype=np.int64))


def merge_record_streams(streams: Sequence[Iterator[Tuple[bytes, bytes]]],
                         kt: KeyType) -> Iterator[Tuple[bytes, bytes]]:
    """Streaming k-way heap merge over record iterators — the literal
    analogue of the reference's MergeQueue::next (MergeQueue.h:276-427).
    Memory held = one record per stream, so file-backed runs (the RPQ
    phase over SuperSegments) merge with bounded memory."""

    cmp = kt.compare

    class _Cursor:
        __slots__ = ("it", "seq", "head")

        def __init__(self, it: Iterator[Tuple[bytes, bytes]], seq: int):
            self.it = it
            self.seq = seq
            self.head: Optional[Tuple[bytes, bytes]] = next(it, None)

        def advance(self) -> None:
            self.head = next(self.it, None)

        def __lt__(self, other: "_Cursor") -> bool:
            c = cmp(self.head[0], other.head[0])
            if c != 0:
                return c < 0
            return self.seq < other.seq  # stable by segment order

    heap = [c for c in (_Cursor(iter(s), i) for i, s in enumerate(streams))
            if c.head is not None]
    heapq.heapify(heap)
    while heap:
        cur = heap[0]
        yield cur.head
        cur.advance()
        if cur.head is not None:
            heapq.heapreplace(heap, cur)
        else:
            heapq.heappop(heap)


def merge_iter_host(batches: Sequence[RecordBatch],
                    kt: KeyType) -> Iterator[Tuple[bytes, bytes]]:
    """merge_record_streams over in-memory batches."""
    return merge_record_streams([b.iter_records() for b in batches], kt)


def pad_rows_to(rows, capacity: int):
    """Pad a device run up to ``capacity`` rows with PAD_WORD rows.
    Padding rows sort strictly last, so the validity prefix is
    preserved; capacities stay powers of two, keeping pallas kernel
    shapes in the O(log) compiled set (the overlap forest's leftover
    merge, merger.overlap)."""
    cur = int(rows.shape[0])
    if cur >= capacity:
        return rows
    pad = np.full((capacity - cur, int(rows.shape[1])), PAD_WORD,
                  np.uint32)
    return jax.numpy.concatenate([rows, jax.device_put(pad)], axis=0)
