"""Overlapped fetch/merge: the network-levitated property itself.

The reference's entire reason to exist is that the merge runs WHILE
fetches stream in (reference src/Merger/MergeManager.cc:47-182: arriving
MOFs join the k-way heap; src/Merger/StreamRW.cc:462-590: the merge loop
re-issues each segment's next chunk), so by the time the last map output
lands, almost all comparison work is already done. The TPU-native shape
of that property is NOT a record-at-a-time heap (which cannot use the
VPU) but a **log-structured run forest**:

- as each segment's fetch completes it is packed (host, vectorized) and
  staged to the device as a sorted run, while later fetches are still
  in flight;
- runs merge pairwise on device with the O(n) Pallas merge-path kernel
  (uda_tpu.ops.pallas_merge.merge_sorted_pair) under a binary-counter
  policy: each run is padded to a power-of-two capacity and two runs of
  equal capacity merge immediately into one of twice the capacity —
  every record therefore moves through at most log2(k) merges, total
  work O(n log k), and only O(log) distinct kernel shapes ever compile
  (pallas_call executables are shape-specialized; unconstrained segment
  sizes would compile a fresh kernel per (na, nb) pair);
- the forest's SMALL size classes never reach the device: a run whose
  class is below ``DEVICE_MIN_BUCKET`` rows stays a host array and
  carries with the native linear row merge; a carry whose output
  reaches that class is transferred once and continues on the device.
  Asking the device for a merge costs the host milliseconds whatever
  the run's size, so a 1,024-map task puts tens of runs on the device,
  not 1,024. Rows are totally ordered (every column is key), so the
  merged run is the same bytes whichever engine merged a class;
- the finish (``emit_stream`` in memory, ``finish_streaming`` over a
  run store) merges the O(log k) leftover runs, largest-capacity last,
  and gathers the output a read-back slab at a time on the host;
- a partition the chip cannot hold whole (``group_rows``, streaming
  mode: what admission reserved holds one GROUP of that many rows of
  run capacity, utils/budget.py) is merged on the device a group at a
  time: when the next run would not fit, the forest is folded into one
  sorted run, its rows are read back to a host row run and the device
  memory is released; after the last segment the few group runs are
  joined on the host with the native row merge into the one row order
  the streaming emit consumes. Rows are totally ordered — equal keys
  by (segment, row) — so the joined order is the order one forest
  would have given, whichever group a record went through.

**Staging pipeline**: fetch→pack→stage. A bounded pool of
stage workers (``uda.tpu.stage.pool``) runs the host-side work — segment
materialization (the join and crack of a segment whose chunks were kept;
a compressed segment's blocks are inflated before it gets here, by
``compress.DecompressingClient`` on the thread that completes each
inner fetch), vint-decode/pack, row-matrix build on reusable
pre-allocated host buffers, run spooling — concurrently across
DIFFERENT segments, while ONE merge consumer drains the staged-run
queue: it dispatches ``jax.device_put`` of the next run while the
device merges of the previous run are still executing (JAX dispatch is
async; the consumer blocks only at accounting points — the host-buffer
recycle after a transfer completes, and the finish drain). In-flight
bytes are budgeted (``uda.tpu.stage.inflight.mb``): ``feed()`` blocks
while fed-but-unmerged bytes would exceed the cap, which is the same
credit-flow backpressure posture the bounded queue gives streaming mode
(the reference's RDMA credit flow, MergeManager.cc:47-63).

``merge.wait_ms`` measures how long the merge waited for each run to
become mergeable: feed()-to-staged latency (queue wait + materialize +
pack + spool). Its complement is the ``feed()`` backpressure block
(``stage.backpressure_events``) — together they say whether the device
is starved by the host (high wait) or the host is throttled by the
device (backpressure).

Stability contract (identical to ops.merge.merge_batches): the device
rows carry (key words, content length, segment index, row index) as the
composite sort key, so equal comparator keys order by original (segment,
row) arrival — independent of fetch COMPLETION order, which under a
randomized fetch schedule is nondeterministic. The order in which the
pool's workers hand their runs to the consumer decides nothing either,
for the same reason: forest insertion order never decides anything.

Oversize keys: a key whose content exceeds the carried width is staged
like any other — its first ``width`` bytes as words, its whole content
length, segment, row (``ops.merge.stage_run_rows``) — and merges on the
forest under the same total row order. That order is the comparator's
everywhere but inside a BLOCK: the oversize keys (length > width) of
one set of key words, contiguous in the merged order because keys of
those words that fit the width sort before them (a key equal to the
block's ``width``-byte stem is a proper prefix of every member and
already comes first). The emit re-orders each block's (segment, row)
pairs by whole content — memcmp, shorter first, ties by (segment, row):
the comparator's order and the stable order of the reference —
before it gathers a slab (``_fix_oversize_blocks``); a block that
straddles a read-back slab is held back until it closes. The scan runs
only in a task that staged an oversize key (``_oversize``, a one-way
flag): 0.3 % of an inverted index's postings are such keys, in blocks
of tens, and the fix-up is hundredths of a second where the fallback
below was 14 of the task's 16 (benchmark cell ``reduce_invindex``,
PERF.md §5). ``merge.overflow.keys`` counts the oversize keys as they
are staged, ``merge.oversize.blocks`` the blocks of two or more rows
re-ordered, the ``oversize_fixup`` timer the scan and the re-order.

Overflow fallback, for what the forest cannot order: a key type whose
``compare`` is its own (``not uses_default_bytewise``: the prefix says
nothing about its order) latches ``_overflow`` at its first oversize
key; staging stops and ``emit_stream()`` falls back to
the global device re-sort with host-side ranks
(``ops.merge.merge_batches``: a concatenation, a pack, one device sort
and a take over the whole partition). The streaming route (a run
store) latches too, for every key type: its oversize segments spool in
full-comparator order and ``finish_streaming`` merges the run FILES
k-way. ``merge.overflow.fallbacks`` counts such tasks and the
``overflow_resort`` timer (around ``merge_batches``'
``overflow_concat``, ``pack`` with ``overflow_rank`` inside it,
``device_sort``, ``overflow_take``) what the in-memory ones paid.
Correctness never depends on the fast path applying.
"""

from __future__ import annotations

import contextlib
import functools
import os
import queue
import threading
import time
from collections import deque
from typing import Callable, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from uda_tpu.ops import merge as merge_ops
from uda_tpu.utils.budget import FOREST_FACTOR, RECORD_BYTES_DEFAULT
from uda_tpu.utils.comparators import KeyType, uses_default_bytewise
from uda_tpu.utils.errors import MergeError
from uda_tpu.utils.ifile import EOF_MARKER, RecordBatch
from uda_tpu.utils.locks import TrackedCondition, TrackedLock
from uda_tpu.utils.logging import get_logger
from uda_tpu.utils.metrics import metrics
from uda_tpu.utils.resledger import resledger

__all__ = ["OverlappedMerger", "MIN_RUN_CAPACITY", "DEVICE_MIN_BUCKET"]

log = get_logger()

MIN_RUN_CAPACITY = merge_ops.MIN_RUN_CAPACITY

_PAD_WORD = merge_ops.PAD_WORD

_next_pow2 = merge_ops.next_run_capacity

# Pallas engine: the smallest size class (``_Run.bucket``, rows) that
# lives on the device; smaller classes carry on the host with the native
# row merge (module docstring). It sits where asking the device for a
# merge stops costing the host more than doing the merge. Both costs as
# host seconds on the chip's host, 7-column rows, runs filled to 62.5 %
# (TPU v5 lite machine, chip runs of PR 27, scripts/forest_class_costs.py;
# PERF.md §3):
#   native row merge   8.4 ns an output row from class 2^15 up (19 ns at
#                      2^11): 0.34 ms for a class-2^16 output, 0.69 ms at
#                      2^17, 1.40 ms at 2^18
#   one device merge   a device_put + wait of the run that joins (0.57 ms
#                      at a capacity of 2^10 rows, 0.89 at 2^15, 1.15 at
#                      2^16, 1.79 at 2^17: half the output's class) +
#                      0.27-0.33 ms to dispatch the merge: 0.85 ms
#                      at class 2^11, 1.47 at 2^17, 2.1 at 2^18 alone in a
#                      process; 2.7 ms whatever the class inside a task
#                      whose stage pool contends for the interpreter
#                      (ledger, PR 26: 2.81 s in 1,023 merges + 1,024 puts)
# The host merge wins at every class up to 2^18 by these numbers; the
# constant is held at 2^17 because a 16 MB map output (164,062 rows,
# class 2^18) must go to the device as it always has.
DEVICE_MIN_BUCKET = 1 << 17

# widest per-key content the vectorized overflow lexsort materializes
# as an n-by-width matrix; rarer/wider keys keep the comparator loop
_LEXSORT_MAX_KEY = 4096


class _Run:
    """One sorted run of the forest.

    Rows are uint32[cap, C] with C = key words + 3: the composite key
    (words..., content length, segment index, row index). Device
    (pallas-engine) runs are padded to a power-of-two capacity with
    all-0xFFFFFFFF rows, which sort strictly after every real row (a
    real row's length column is a content length < 2^31), so valid rows
    stay a prefix through any merge; host runs are exact-sized.

    ``bucket`` is the binary-counter size class: staging assigns
    next_pow2(valid), each merge doubles it — so every record passes
    through at most log2(k) merges regardless of engine. ``lease`` is
    the pool-owned host buffer backing a host run's ``rows``, recycled
    when this run merges into a larger one or moves to the device.
    """

    __slots__ = ("rows", "valid", "bucket", "lease")

    def __init__(self, rows, valid: int, bucket: int, lease=None):
        self.rows = rows
        self.valid = valid
        self.bucket = bucket
        self.lease = lease

    @property
    def capacity(self) -> int:
        return int(self.rows.shape[0])

    @property
    def on_host(self) -> bool:
        return isinstance(self.rows, np.ndarray)


class _StagedRun:
    """A stage worker's output awaiting the merge consumer: sorted host
    rows (possibly a leased pool buffer), fed timestamp (the
    merge.wait_ms anchor) and the in-flight byte charge it releases
    once merged."""

    __slots__ = ("seg_index", "rows", "valid", "lease", "fed_t", "charge")

    def __init__(self, seg_index: int, rows, valid: int, lease,
                 fed_t: float, charge: int):
        self.seg_index = seg_index
        self.rows = rows
        self.valid = valid
        self.lease = lease
        self.fed_t = fed_t
        self.charge = charge


# Reusable pre-allocated host row buffers (ops.merge.RowBufferPool).
# Pallas engine: stage workers lease, the merge consumer recycles once
# the jax.device_put transfer completes. Host runs (the host engine's,
# and the pallas engine's small classes): staged runs
# AND merge outputs lease, each buffer recycled when its run merges
# into a larger one — killing the per-merge large-alloc page-fault
# churn that would otherwise dominate k*log2(k) merge traffic on this
# class of host.
_RowBufferPool = merge_ops.RowBufferPool

# host-engine merges at/above this many output rows split across
# threads at merge-path partition points (ops.merge.merge_rows_split_into)
# — below it the split/join overhead beats the win
_MERGE_SPLIT_MIN_ROWS = 1 << 18


def _auto_width() -> int:
    """A few threads: the stage pool's auto width and the parts a large
    host row merge is split into (the native calls release the GIL, so
    width ~ cores, held to four)."""
    return max(2, min(4, os.cpu_count() or 2))


class OverlappedMerger:
    """Consumes completed segments during the fetch phase; produces the
    final permutation over the concatenated batches.

    ``engine`` selects the pairwise merge backend: "pallas" (the device
    merge-path kernel; the real TPU path), "host" (vectorized numpy
    lexsort merge — the correctness twin, and the fast choice where the
    only accelerator is the XLA CPU backend, whose interpret-mode Pallas
    emulation compiles an unrolled grid per shape), or "auto" (host on
    CPU, pallas elsewhere).

    Staging is a bounded pool of ``stagers`` stage workers (0 = a few,
    ``_auto_width()``) and one merge consumer (see module docstring).
    ``inflight_bytes`` > 0 bounds the fed-but-unmerged bytes (feed()
    blocks — the credit-flow backpressure).
    """

    def __init__(self, key_type: KeyType, width: int, engine: str = "auto",
                 run_store=None, max_pending: int = 0, stagers: int = 0,
                 inflight_bytes: int = 0, on_spool=None,
                 group_rows: int = 0, on_record_bytes=None):
        self.key_type = key_type
        self.width = width
        # run-spool boundary hook (merger/checkpoint.py): called with the
        # segment index right after its sorted run file is durable — the
        # natural crash-consistent snapshot trigger. Contract: the hook
        # never raises (TaskCheckpoint.maybe_save catches internally).
        self._on_spool = on_spool
        # admission reckoned the partition's rows from its bytes at
        # RECORD_BYTES_DEFAULT a record; staging sees the records. The
        # hook (MemoryBudget.rebook_device through MergeManager; never
        # waits) is told the framed bytes a record so far, whenever
        # they project more rows than the last figure did (the three
        # counters below: under _state_lock, _observe_records).
        self._on_record_bytes = on_record_bytes
        self._seen_records = 0
        self._seen_bytes = 0
        self._booked_record_bytes = float(RECORD_BYTES_DEFAULT)
        # group_rows > 0 (streaming mode only): the forest may hold
        # that many rows of run capacity at a time (module docstring);
        # _group_held counts what it holds, _group_runs are the host
        # row runs of the groups flushed so far.
        # udarace: lockfree=_group_held,_group_runs,_groups - confined
        # to _group_lock holders and, after _drain() has joined every
        # stage thread, to the finish path
        self._group_rows = max(0, int(group_rows))
        if self._group_rows and run_store is None:
            raise MergeError("group_rows requires streaming mode "
                             "(a run store)")
        self._group_lock = threading.Lock()
        self._group_held = 0
        self._group_runs: list = []
        self._groups = 0                  # groups flushed (stats)
        self.engine = merge_ops.resolve_run_engine(engine)
        # off-TPU, a forced pallas engine runs in interpret mode
        self.interpret = jax.default_backend() == "cpu"
        # streaming mode (uda.tpu.online.streaming): segments spool to
        # sorted run files and release their bytes after staging; the
        # bounded queue is the credit backpressure that keeps
        # completed-but-unstaged segments at O(window)
        self.run_store = run_store
        # staging threads adopt the constructing thread's span (the
        # reduce-task root) so their pack/stage/merge timers land in the
        # right trace subtree
        self._parent_span = metrics.current_span()
        # udarace: lockfree=_q,_staged_q - queue.Queue is internally
        # locked; cross-thread put/get rides the Queue's own mutex
        self._q: "queue.Queue" = queue.Queue(maxsize=max_pending)
        # udarace: lockfree=_aborted,_overflow,_oversize - one-way bool
        # latches (GIL-atomic store; readers may lag one item, by design)
        self._aborted = False
        self._forest: dict[int, _Run] = {}   # capacity -> run
        self._forest_lock = threading.Lock()
        self._state_lock = threading.Lock()  # counters/overflow flag
        self._overflow = False
        # oversize keys stay on the forest, their blocks fixed up at
        # emit (module docstring), where the row order decides all but
        # that: the in-memory route under the stock bytewise compare
        self._forest_orders_oversize = (run_store is None
                                        and uses_default_bytewise(key_type))
        self._oversize = False            # such a key was staged
        # udarace: lockfree=_error - first-error latch: a lagging racer
        # overwrites with its own exception, either surfaces at the finish
        self._error: Optional[Exception] = None
        self._merges = 0
        self._staged = 0
        # device engine: how far merge dispatch may run ahead of the
        # device (see _await_device_room).
        # udarace: lockfree=_device_staged_bytes - confined to carries
        # udarace: lockfree=_device_pending - confined to carries
        # udarace: lockfree=_device_pending_bytes - confined to carries:
        # touched only inside _insert's _forest_lock (_merge,
        # _merge_rows, _await_device_room run under it) or by the finish's
        # _merge_leftovers after _drain() has joined every stage thread
        self._device_staged_bytes = 0    # every run staged so far
        self._device_pending: deque = deque()   # (merge output, bytes)
        self._device_pending_bytes = 0
        # in-flight bytes budget: feed() charges, the merge consumer
        # (or the spool/drop path) releases; 0 = unbounded
        self._inflight_cap = max(0, int(inflight_bytes))
        self._inflight = 0
        self._inflight_cv = TrackedCondition(TrackedLock("stage.inflight"))
        # the host merges dispatch to the native row merge; resolve it
        # ONCE here so a cold .so compiles before any carry runs under
        # _forest_lock (a make inside the lock would stall the whole
        # staging pool) and the per-merge hot path pays no imports
        native_rows_merge = merge_ops.resolve_native_rows_merge()
        self._native_rows_merge = None
        # pallas engine: size classes below this carry on the host. 0 =
        # every run goes to the device (no native merge to carry with:
        # the numpy lexsort would cost more than the calls it saves)
        self._device_min_bucket = 0
        if self.engine == "host":
            self._native_rows_merge = native_rows_merge
        elif native_rows_merge is not None:
            self._device_min_bucket = DEVICE_MIN_BUCKET
        # a task with none of these (the host engine; every run large)
        # reads 0, where a program without host classes reads nothing
        metrics.add("merge.device_runs", 0)
        metrics.add("merge.host_merges", 0)
        metrics.add("stage.native_segments", 0)
        metrics.add("merge.device_groups", 0)
        metrics.add("merge.overflow.fallbacks", 0)
        metrics.add("merge.overflow.keys", 0)
        metrics.add("merge.oversize.blocks", 0)
        metrics.add("fetch.crack.deferred_segments", 0)
        for timer in ("merge_host_batch", "merge_group_flush",
                      "merge_group_join", "run_spool", "fetch_crack",
                      "fetch_feed_wait", "overflow_resort",
                      "overflow_rank", "oversize_fixup"):
            metrics.declare_timer(timer)
        # bounded stage pool + single merge consumer. Pool width:
        # explicit ``stagers`` wins; auto = a few workers (staging
        # is numpy-heavy and releases the GIL, so width ~ cores).
        nworkers = stagers if stagers > 0 else _auto_width()
        # staged-run queue is bounded: a slow device consumer
        # backpressures the workers (and, through the in-flight
        # budget, the transports feeding feed())
        self._staged_q: "queue.Queue" = queue.Queue(maxsize=nworkers + 2)
        # host-buffer reuse where ownership hands off cleanly:
        # pallas = rows are COPIED to the device (recycle after the
        # transfer; interpret-mode device_put may alias numpy memory,
        # so it owns its arrays), host+native = staged runs AND
        # merge outputs lease (recycle when a run merges away), and
        # large host merges split across threads at merge-path
        # partition points — the merge half of the pipeline uses
        # the cores the stage half leaves idle
        self._buf_pool = None
        self._merge_parts = 1
        if self.engine == "pallas" and not self.interpret:
            self._buf_pool = _RowBufferPool()
        elif (self.engine == "host"
              and self._native_rows_merge is not None):
            self._buf_pool = _RowBufferPool()
            self._merge_parts = _auto_width()
        self._workers = [
            threading.Thread(target=self._worker_loop, daemon=True,
                             name=f"uda-stage-w{i}")
            for i in range(nworkers)]
        self._consumer_thread = threading.Thread(
            target=self._consumer_loop, daemon=True,
            name="uda-overlap-merge")
        self._threads = self._workers + [self._consumer_thread]
        for t in self._threads:
            t.start()

    # -- producer side (fetch completion callbacks, any thread) -------------

    def feed(self, seg_index: int, source) -> None:
        """Stage one completed segment's records (safe to call from a
        transport completion thread). ``source`` is either a RecordBatch
        or an object with a ``record_batch()`` method (a Segment) —
        materialization happens on a stage thread, and for a Segment
        whose crack was deferred that is where its bytes are cracked
        (Segment.record_batch), so a corrupt stream fails the task from
        there, through ``_error`` at the finish. This call BLOCKS when
        staging lags — on the bounded queue (streaming mode) and on the
        in-flight bytes budget (``uda.tpu.stage.inflight.mb``) — which
        is the intended backpressure: the transport thread holds off
        until host memory frees (the reference's RDMA credit-flow
        posture, MergeManager.cc:47-63). Both blocking places run under
        the ``fetch_feed_wait`` timer, entered only when they actually
        block: it holds the process's one upcall thread, so one task's
        wait here is every task's dispatch-queue wait."""
        charge = self._charge(source)
        if charge < 0:
            return  # aborted while waiting on the budget
        item = (seg_index, source, time.perf_counter(), charge)
        try:
            self._q.put_nowait(item)
        except queue.Full:
            with self._feed_wait(source):
                while True:
                    if self._aborted:
                        self._release_charge(charge)
                        return
                    try:
                        self._q.put(item, timeout=0.1)
                        break
                    except queue.Full:
                        continue
        if self._aborted:
            # the put may have raced abort(): _charge() saw the flag
            # unset, abort() then drained _q (threads already joined)
            # before our item landed — nothing would ever release its
            # charge. Re-drain: either a still-live worker consumed the
            # item (drain is a no-op) or we reap it here; a queue item
            # is consumed exactly once, so the charge releases exactly
            # once either way.
            self._reap_input_queue()

    @staticmethod
    @contextlib.contextmanager
    def _feed_wait(source):
        """The ``fetch_feed_wait`` timer under the fed segment's own
        ``fetch.segment`` span (ended already, still the parent: the
        upcall thread has no ambient span, and a span outside the
        task's trace is one critpath never sees)."""
        with metrics.use_span(getattr(source, "trace_span", None)), \
                metrics.timer("fetch_feed_wait"):
            yield

    @staticmethod
    def _source_bytes(source) -> int:
        """Best-effort byte size of a fed segment for the in-flight
        budget: a Segment's raw_length (uncompressed record bytes), a
        RecordBatch's buffer size."""
        raw = getattr(source, "raw_length", None)
        if raw:
            return int(raw)
        data = getattr(source, "data", None)
        if data is not None:
            return int(len(data))
        return 0

    def _charge(self, source) -> int:
        """Charge the segment against the in-flight budget, blocking
        (abort-responsive) while over it. Returns the charged bytes, or
        -1 when the merger aborted during the wait. A single oversized
        segment is admitted when nothing else is in flight (the same
        escape the supplier read budget has) — the budget bounds
        concurrency, it never wedges progress."""
        if self._inflight_cap <= 0:
            return 0
        charge = self._source_bytes(source)
        if charge <= 0:
            return 0
        with self._inflight_cv:
            if self._over_budget(charge):
                metrics.add("stage.backpressure_events")
                with self._feed_wait(source):
                    while self._over_budget(charge):
                        self._inflight_cv.wait(timeout=0.1)
            if self._aborted:
                return -1
            self._inflight += charge
        # the +charge rides the returned int: feed() pairs every
        # non-negative _charge() with exactly one _release_charge()
        # (consumer dispatch, abort drain, or its own unwind)
        metrics.gauge_add("stage.inflight.bytes", charge)  # udalint: disable=UDA101
        return charge

    def _over_budget(self, charge: int) -> bool:
        """_inflight_cv held: would ``charge`` more bytes pass the cap
        (never true with nothing in flight, or once aborted)."""
        return (not self._aborted and self._inflight > 0
                and self._inflight + charge > self._inflight_cap)

    def _release_charge(self, charge: int) -> None:
        if charge <= 0:
            return
        with self._inflight_cv:
            self._inflight -= charge
            self._inflight_cv.notify_all()
        metrics.gauge_add("stage.inflight.bytes", -charge)

    # -- staging: the stage pool and the merge consumer --------------------

    def _worker_loop(self) -> None:
        """Stage worker: materialize + pack + row build +
        spool for ONE segment at a time, concurrently across workers;
        finished runs queue for the merge consumer."""
        with metrics.use_span(self._parent_span):
            while True:
                try:
                    item = self._q.get(timeout=0.25)
                except queue.Empty:
                    if self._aborted:
                        return
                    continue
                if item is None:
                    return
                seg_index, source, fed_t, charge = item
                if self._error is not None or self._aborted:
                    self._release_charge(charge)
                    continue
                try:
                    staged = self._prepare(seg_index, source, fed_t)
                except Exception as e:  # surfaced at the finish
                    self._error = e
                    self._release_charge(charge)
                    continue
                if staged is None:
                    self._release_charge(charge)
                    continue
                staged.charge = charge
                self._put_staged(staged)

    def _put_staged(self, staged: _StagedRun) -> None:
        while not self._aborted:
            try:
                self._staged_q.put(staged, timeout=0.1)
                return
            except queue.Full:
                continue
        self._discard(staged)

    def _consumer_loop(self) -> None:
        """The merge loop as a consumer of staged runs: device_put of
        the next run is dispatched while the previous run's merges are
        still executing (async dispatch); the forest carry serializes
        here, which also makes _forest_lock uncontended."""
        with metrics.use_span(self._parent_span):
            # merge.wait spans: the consumer's blocked-on-staging time
            # as a first-class trace lane (the span twin of the
            # merge.wait_ms histogram, critpath's "wait" bucket). One
            # span covers each contiguous wait; a no-op while spans
            # are disabled
            wait = metrics.start_span("merge.wait")
            while True:
                try:
                    staged = self._staged_q.get(timeout=0.25)
                except queue.Empty:
                    if self._aborted:
                        wait.end(aborted=True)
                        return
                    continue
                wait.end()
                if staged is None:
                    return
                if self._error is not None or self._aborted:
                    self._discard(staged)
                    wait = metrics.start_span("merge.wait")
                    continue
                try:
                    self._observe_wait(staged.fed_t)
                    self._consume_run(staged)
                    metrics.add("merge.pipeline.runs")
                except Exception as e:  # surfaced at the finish
                    self._error = e
                    self._recycle(staged)
                finally:
                    self._release_charge(staged.charge)
                    staged.charge = 0
                wait = metrics.start_span("merge.wait")

    def _discard(self, staged: _StagedRun) -> None:
        """Drop a staged run without merging (abort/error drain):
        release its budget charge and recycle its buffer lease."""
        self._release_charge(staged.charge)
        staged.charge = 0
        self._recycle(staged)

    def _recycle(self, staged: _StagedRun) -> None:
        if staged.lease is not None and self._buf_pool is not None:
            self._buf_pool.release(staged.lease)
        staged.lease = None

    @staticmethod
    def _observe_wait(fed_t: float) -> None:
        # merge-wait: how long the merge waited for this run to become
        # mergeable after its segment was fed (queue wait + materialize
        # + pack + spool). Its complement is the feed()
        # backpressure block (stage.backpressure_events): high wait =
        # the device is starved by the host, backpressure = the host is
        # throttled by the device.
        metrics.observe("merge.wait_ms",
                        (time.perf_counter() - fed_t) * 1e3)

    # -- staging ------------------------------------------------------------

    @staticmethod
    def _release(source) -> None:
        """Free a staged segment's raw bytes (streaming mode only: the
        sorted run on disk is now the record source of truth)."""
        release = getattr(source, "release", None)
        if release is not None:
            release()

    def _notify_spool(self, seg_index: int) -> None:
        """Fire the run-spool boundary hook (checkpoint trigger) outside
        every merger lock — the hook fsyncs."""
        hook = self._on_spool
        if hook is not None:
            hook(seg_index)

    def adopt_run(self, seg_index: int, batch: RecordBatch) -> None:
        """Resume path (merger/checkpoint.py): account a run file that a
        PREVIOUS attempt already spooled — the re-cracked, already-sorted
        batch joins the forest without re-spooling. Single-threaded by
        contract: called before any feed(), so no staging worker races
        the forest. Byte-identity with the uninterrupted run holds
        because the run file is in sorted order, so the identity order
        (row index = file position) reproduces exactly the rows the
        original ``_prepare`` built."""
        n = batch.num_records
        if n == 0:
            return
        rows, lease, _, longest, _ = self._stage_rows(seg_index, batch)
        if longest > self.width:
            # oversize keys: same posture as _prepare — on a streaming
            # resume the fast path is disabled and finish_streaming's
            # comparator k-way file merge (which reads this adopted run
            # file) is the correctness fallback
            self._keep_oversize(rows, n)
        with self._state_lock:
            self._staged += 1
        metrics.add("merge.records", n)
        if self._overflow:
            self._release_rows(lease)
            return
        self._consume_run(_StagedRun(seg_index, rows, n, lease,
                                     time.perf_counter(), 0))

    def _stage_rows(self, seg_index: int, batch: RecordBatch):
        """The key work of staging one non-empty segment, under the
        ``overlap_pack`` timer: a row buffer (pool-leased when there is
        a pool) filled by ``ops.merge.stage_run_rows`` — one native
        pass, or the numpy passes. Returns ``(rows, lease, presorted,
        longest, nbytes)``; the caller owns the lease, which goes home
        here if the fill raises (a leaked lease pins staging budget
        forever, and the abort drain asserts the pool is whole)."""
        cap = self._staged_capacity(batch.num_records)
        rows, lease = self._host_rows(
            cap, self.width // 4 + merge_ops.ROW_EXTRA_COLS)
        try:
            with metrics.timer("overlap_pack"):
                return (rows, lease) + merge_ops.stage_run_rows(
                    rows, batch, self.key_type, self.width, seg_index)
        except BaseException:
            self._release_rows(lease)
            raise

    def _release_rows(self, lease) -> None:
        if lease is not None:
            self._buf_pool.release(lease)

    @staticmethod
    def _batch_of(source) -> RecordBatch:
        """A fed source's records: itself, or a Segment's
        ``record_batch()`` (whose first call cracks a deferred
        segment, on this thread)."""
        return (source if isinstance(source, RecordBatch)
                else source.record_batch())

    def _prepare(self, seg_index: int, source,
                 fed_t: float) -> Optional[_StagedRun]:
        """The host half of staging: materialize (a Segment whose
        crack was deferred joins and cracks its chunks here, under its
        own ``fetch_crack`` timer), pack, per-run sort, spool.
        Returns the device-bound staged run, or None when nothing needs
        the forest (empty segment, overflow)."""
        streaming = self.run_store is not None
        if self._overflow and not streaming:
            return None  # fast path already disabled; the emit re-sorts
        batch = self._batch_of(source)
        n = batch.num_records
        if n == 0:
            if streaming:
                self._release(source)
            return None
        rows, lease, presorted, longest, nbytes = self._stage_rows(
            seg_index, batch)
        kept = False  # whether the forest takes the rows (and the lease)
        try:
            metrics.add("stage.bytes", nbytes)
            self._observe_records(n, nbytes)
            if longest > self.width and not self._keep_oversize(rows, n):
                # the forest cannot order these keys: the fast path is
                # disabled (see module docstring)
                if not streaming:
                    return None
                # streaming keeps spooling: this run is ordered by the
                # FULL comparator, so finish falls back to the
                # comparator-level k-way merge over the run files —
                # still O(window) host memory
                order = self._overflow_order(batch, n)
                self.run_store.write_run(seg_index, batch, order)
                with self._state_lock:
                    self._staged += 1
                metrics.add("merge.records", n)
                self._notify_spool(seg_index)
                self._observe_wait(fed_t)
                self._release(source)
                return None
            # per-segment sort on host key order: Hadoop map outputs
            # arrive ALREADY comparator-sorted (the map-side sort
            # contract), and for within-width keys comparator order ==
            # (words, len) order, so the O(n·k) monotonicity check
            # usually replaces the O(n log n) sort — the staging hot
            # path collapses to pack+spool at memory bandwidth. Unsorted
            # input (exchange-path buckets, foreign writers) was sorted
            # as its rows were filled: their row-index column is the
            # order.
            if streaming:
                spool_order = (np.arange(n, dtype=np.int64) if presorted
                               else rows[:n, -1].astype(np.int64))
                self.run_store.write_run(seg_index, batch, spool_order)
                self._release(source)
                self._notify_spool(seg_index)
            with self._state_lock:
                self._staged += 1
            metrics.add("merge.records", n)
            if self._overflow:
                self._observe_wait(fed_t)
                return None  # forest output won't be consumed; runs suffice
            kept = True
            return _StagedRun(seg_index, rows, n, lease, fed_t, 0)
        finally:
            if not kept:
                self._release_rows(lease)

    def _observe_records(self, n: int, nbytes: int) -> None:
        """Tell the admission hook the task's record size once staging
        knows it, and again only if later segments bring it down by
        more than a thirty-second: a frame is its key and value bytes
        and two length VInts, of a byte each at least. TeraSort's 102
        never pass the model's 100, so such a task never calls."""
        if self._on_record_bytes is None:
            return
        with self._state_lock:
            self._seen_records += n
            self._seen_bytes += nbytes + 2 * n
            observed = self._seen_bytes / self._seen_records
            if observed * 32 >= self._booked_record_bytes * 31:
                return
            self._booked_record_bytes = observed
        self._on_record_bytes(observed)

    def _keep_oversize(self, rows: np.ndarray, n: int) -> bool:
        """A staged segment holds a key longer than the carried width.
        True: its rows stay on the forest and the emit fixes the
        equal-prefix blocks up (the task flag ``_oversize``). False:
        the task latches the overflow fallback — a key type with a
        ``compare`` of its own, or the streaming route. Counts the
        keys in ``merge.overflow.keys``, except where the in-memory
        fallback will (``packing.overflow_ranks``, as it ranks the
        whole partition)."""
        keep = self._forest_orders_oversize
        if keep:
            self._oversize = True
        else:
            self._overflow = True
            if self.run_store is None:
                return False
        kw = rows.shape[1] - merge_ops.ROW_EXTRA_COLS
        metrics.add("merge.overflow.keys",
                    int(np.count_nonzero(rows[:n, kw] > self.width)))
        return keep

    def _overflow_order(self, batch: RecordBatch, n: int) -> np.ndarray:
        """Full-comparator sort order for an oversize-key run. Default
        bytewise comparators vectorize: memcmp-with-shorter-is-smaller
        order == lexsort over (zero-padded content bytes, content
        length) — no O(n log n) interpreter-level compares on the hot
        path. A custom ``compare`` override (or pathologically wide
        keys) keeps the comparator-faithful cmp_to_key path."""
        kt = self.key_type
        if uses_default_bytewise(kt):
            contents = [kt.content(batch.key(i)) for i in range(n)]
            lens = np.fromiter((len(c) for c in contents),
                               np.int64, count=n)
            width = int(lens.max(initial=0))
            if 0 < width <= _LEXSORT_MAX_KEY:
                mat = np.zeros((n, width), np.uint8)
                for i, c in enumerate(contents):
                    mat[i, :len(c)] = np.frombuffer(c, np.uint8)
                cols = [mat[:, j] for j in range(width)] + [lens]
                # np.lexsort is stable -> ties keep arrival order, the
                # same (i - j) tiebreak the comparator path applies
                return np.lexsort(tuple(reversed(cols))).astype(np.int64)
        cmp = kt.compare
        keys = [batch.key(i) for i in range(n)]
        return np.asarray(sorted(range(n), key=functools.cmp_to_key(
            lambda i, j: cmp(keys[i], keys[j]) or (i - j))), np.int64)

    def _device_class(self, bucket: int) -> bool:
        """Whether a run of this size class lives on the device."""
        return self.engine == "pallas" and bucket >= self._device_min_bucket

    def _staged_capacity(self, n: int) -> int:
        """Rows of the buffer an ``n``-row run is staged in: device-bound
        runs pad to a power-of-two capacity (bounded set of kernel
        shapes); host runs stay exact-sized."""
        bucket = _next_pow2(n)
        return bucket if self._device_class(bucket) else n

    def _consume_run(self, staged: _StagedRun) -> None:
        """The device half of staging: transfer + forest insert. The
        merges this triggers dispatch asynchronously; the only block is
        the transfer completion that frees a leased host buffer. A run
        of a host class skips the transfer."""
        bucket = _next_pow2(staged.valid)
        if self._group_rows:
            # one run at a time makes its way into a grouped forest: the
            # room made for it must still be there when it lands
            with self._group_lock:
                if self._make_group_room(staged, bucket):
                    self._stage_run(staged, bucket)
        else:
            self._stage_run(staged, bucket)

    def _stage_run(self, staged: _StagedRun, bucket: int) -> None:
        with metrics.timer("overlap_stage"):
            # the run takes the pool lease: a device run's recycles once
            # its transfer is done, a host run keeps it until it merges
            # away; either way an error-path _recycle can never
            # double-release it
            lease, staged.lease = staged.lease, None
            if self._device_class(bucket):
                run = self._put_on_device(staged.rows, staged.valid, bucket,
                                          lease)
            else:
                run = _Run(staged.rows, staged.valid, bucket, lease)
            self._insert(run)

    # -- device groups (group_rows > 0; callers hold _group_lock) -----------

    def _make_group_room(self, staged: _StagedRun, bucket: int) -> bool:
        """Book ``bucket`` rows of run capacity in the current group,
        flushing the group first when they would not fit. False when
        the run is larger than any group: it is a sorted run already,
        so its rows become a group run as they are and never see the
        device."""
        if bucket > self._group_rows:
            self._group_runs.append(
                np.array(staged.rows[:staged.valid], np.uint32))
            self._recycle(staged)
            return False
        if self._group_held + bucket > self._group_rows:
            self._flush_group()
        self._group_held += bucket
        return True

    def _flush_group(self) -> None:
        """Fold the forest into one sorted run, take its rows off the
        device into a host row run, release the device memory."""
        acc = None
        with metrics.timer("merge_group_flush"):
            try:
                acc = self._merge_leftovers()
                if acc is not None:
                    self._group_runs.append(self._rows_on_host(acc))
            finally:
                self._release_run(acc)
                self._release_forest()
                self._device_staged_bytes = 0
                self._group_held = 0
        if acc is not None:
            self._groups += 1
            metrics.add("merge.device_groups")

    @staticmethod
    def _rows_on_host(run: _Run) -> np.ndarray:
        """A run's valid rows as a host array of its own; a device
        run's are read back in slabs (the first one waits for the
        fold)."""
        from uda_tpu.merger.streaming import SLAB_RECORDS

        if run.on_host:
            return np.array(run.rows[:run.valid], np.uint32)
        out = np.empty((run.valid, int(run.rows.shape[1])), np.uint32)
        for start in range(0, run.valid, SLAB_RECORDS):
            stop = min(start + SLAB_RECORDS, run.valid)
            out[start:stop] = np.asarray(run.rows[start:stop])
        return out

    def _join_groups(self) -> Optional[_Run]:
        """After the last segment: flush what the forest still holds,
        then join the group runs, smallest two first, with the native
        row merge split across threads (the numpy lexsort where the
        library is missing). Returns the one merged row run, on the
        host, or None when nothing was staged."""
        with self._group_lock:
            self._flush_group()
            runs, self._group_runs = self._group_runs, []
        if not runs:
            return None
        parts = _auto_width()
        with metrics.timer("merge_group_join"):
            while len(runs) > 1:
                runs.sort(key=len, reverse=True)
                b, a = runs.pop(), runs.pop()
                out = np.empty((len(a) + len(b), a.shape[1]), np.uint32)
                if not merge_ops.merge_rows_split_into(a, b, out, parts):
                    out = merge_ops.merge_row_pair(a, b, len(a), len(b),
                                                   "host")
                runs.append(out)
        rows = runs[0]
        return _Run(rows, len(rows), _next_pow2(len(rows)))

    def _put_on_device(self, rows: np.ndarray, valid: int, bucket: int,
                       lease) -> _Run:
        """ONE ``jax.device_put`` of a padded host run; its pool lease,
        if it has one, goes home whether the transfer succeeds or not."""
        try:
            with metrics.span("merge.device_put", rows=valid):
                dev = jax.device_put(rows)
                if lease is not None:
                    # accounting point: the host buffer may only be
                    # reused once the transfer is done. Merges of the
                    # PREVIOUS run keep executing under this wait.
                    t0 = time.perf_counter()
                    jax.block_until_ready(dev)
                    metrics.observe("merge.pipeline.put_ms",
                                    (time.perf_counter() - t0) * 1e3)
        finally:
            self._release_rows(lease)
        metrics.add("merge.device_runs")
        return _Run(dev, valid, bucket)

    def _host_rows(self, rows: int, cols: int):
        """A host row buffer and its pool lease. No pool in this mode
        (interpret-mode pallas, the host engine without the native
        merge): a fresh array that nobody reuses, lease None."""
        if self._buf_pool is None:
            return np.empty((rows, cols), np.uint32), None
        buf = self._buf_pool.lease(rows, cols)
        return buf, buf

    def _promote(self, run: _Run, capacity: int) -> _Run:
        """Move a host-class run of the pallas engine to the device:
        padded to ``capacity`` and transferred once; from here on it is
        a device run like any staged one. A merge output already has
        its padded capacity (_merge_rows_host); a run still as staged
        is copied into a padded buffer first."""
        if run.capacity != capacity:
            rows, lease = self._host_rows(capacity, int(run.rows.shape[1]))
            rows[:run.valid] = run.rows[:run.valid]
            self._release_run(run)
        else:
            rows, lease, run.lease = run.rows, run.lease, None
        rows[run.valid:] = _PAD_WORD
        dev = self._put_on_device(rows, run.valid, run.bucket, lease)
        self._device_staged_bytes += int(dev.rows.nbytes)
        return dev

    def _insert(self, run: _Run) -> None:
        # binary-counter carry: equal size classes merge immediately.
        # Carries run on the one merge consumer (and in adopt_run, before
        # any feed) while the stage workers pack/sort/spool other
        # segments; the lock keeps them and the finish's reads apart.
        with self._forest_lock:
            if not run.on_host:
                self._device_staged_bytes += int(run.rows.nbytes)
            other = None
            try:
                while run.bucket in self._forest:
                    other = self._forest.pop(run.bucket)
                    # the transitive join() is the split merge waiting
                    # on its OWN compute workers — bounded work on data
                    # already in hand, not a wait on external progress;
                    # serializing carries under the lock is the forest
                    # design
                    run = self._merge(other, run)  # udalint: disable=UDA102
                    if run.on_host and self._device_class(run.bucket):
                        # the carry left the host classes: the capacity
                        # today's all-device forest holds at this class
                        run = self._promote(run, run.bucket)
            except BaseException:
                # a failed carry: neither run is in the forest any more,
                # so no later drain would find their leases
                self._release_run(other)
                self._release_run(run)
                raise
            self._forest[run.bucket] = run

    def _merge(self, a: _Run, b: _Run) -> _Run:
        bucket = 2 * max(a.bucket, b.bucket)
        with metrics.timer("overlap_device_merge"):
            merged, lease = self._merge_rows(a, b)
        with self._state_lock:
            self._merges += 1
        return _Run(merged, a.valid + b.valid, bucket, lease)

    def _merge_rows(self, a: _Run, b: _Run):
        """One pairwise run merge. The host engine with a pool merges
        into a pool-leased output buffer (no per-merge large-alloc
        page faults) and splits large merges across threads at
        merge-path partition points (the native call releases the GIL);
        the inputs' leases recycle immediately. The pallas engine's
        host classes: _merge_rows_host. The rest takes the plain
        merge_row_pair path."""
        if self.engine == "host" and self._buf_pool is not None:
            total = a.valid + b.valid
            out = self._buf_pool.lease(total, int(a.rows.shape[1]))
            parts = (self._merge_parts
                     if total >= _MERGE_SPLIT_MIN_ROWS else 1)
            try:
                ok = merge_ops.merge_rows_split_into(
                    a.rows[:a.valid], b.rows[:b.valid], out, parts)
            except BaseException:
                # a failed native merge fails the segment upstream; the
                # output lease must go back to the pool on that path
                # too, or every retry shrinks the staging budget
                self._buf_pool.release(out)
                raise
            if ok:
                self._buf_pool.release(a.lease)
                self._buf_pool.release(b.lease)
                a.lease = b.lease = None
                return out, out
            self._buf_pool.release(out)  # native .so went missing
        on_device = self.engine == "pallas"
        if on_device and a.on_host:
            return self._merge_rows_host(a, b)
        if on_device:
            nbytes = int(a.rows.nbytes) + int(b.rows.nbytes)
            self._await_device_room(nbytes)
        merged = merge_ops.merge_row_pair(
            a.rows, b.rows, a.valid, b.valid, self.engine,
            interpret=self.interpret,
            native_merge=self._native_rows_merge)
        if on_device:
            self._device_pending.append((merged, nbytes))
            self._device_pending_bytes += nbytes
        return merged, None

    def _merge_rows_host(self, a: _Run, b: _Run):
        """One carry of the pallas engine's host classes: the native
        linear merge into a buffer of the output's padded capacity, so
        that moving it to the device later is a tail fill, not a copy
        (the pages behind ``total`` are never touched until then). The
        inputs' leases recycle at once."""
        total = a.valid + b.valid
        out, lease = self._host_rows(_next_pow2(total), int(a.rows.shape[1]))
        try:
            with metrics.timer("merge_host_batch"):
                ok = merge_ops.merge_rows_split_into(
                    a.rows[:a.valid], b.rows[:b.valid], out[:total], 1)
            if not ok:
                # resolved at construction and gone since: fail the
                # task rather than sort in numpy
                raise MergeError("native row merge went missing mid-task")
        except BaseException:
            self._release_rows(lease)
            raise
        self._release_run(a)
        self._release_run(b)
        metrics.add("merge.host_merges")
        return out, lease

    def _await_device_room(self, nbytes: int) -> None:
        """Bound how far merge dispatch runs ahead of the device. A
        dispatched merge's output is allocated at once and its inputs
        stay allocated until it has executed, so a host that outruns
        the device holds every level of the forest at the same time
        (measured: 4.5x the staged rows in a warm task; 7x at worst
        with 64 runs). A record's row lives in ONE executed run (the
        forest, or the input of a pending merge) plus one copy per
        pending output above it; so with the outputs of merges that may
        not have executed held to ``FOREST_FACTOR - 1`` times the bytes
        staged so far, the task's device rows stay within
        ``FOREST_FACTOR`` times its staged rows — what
        ``utils.budget.device_bytes_estimate`` reserves for it in the
        chip-wide ledger. Twice the staged bytes is what one carry
        chain's outputs add up to, so a chain never waits for itself;
        the wait, when there is one, is for the OLDEST pending merge,
        which the device runs first anyway: it costs a dispatch
        latency, not device time."""
        pending = self._device_pending
        room = (FOREST_FACTOR - 1) * self._device_staged_bytes
        while pending:
            out, n = pending[0]
            if not out.is_ready():
                if self._device_pending_bytes + nbytes <= room:
                    return
                jax.block_until_ready(out)
            pending.popleft()
            self._device_pending_bytes -= n

    # -- consumer side -------------------------------------------------------

    @property
    def stats(self) -> dict:
        """Counters for observability/tests: merges that have completed
        and segments staged so far (both monotone)."""
        pending = self._q.qsize() + self._staged_q.qsize()
        return {"device_merges": self._merges, "staged_runs": self._staged,
                "device_groups": self._groups,
                "pending": pending, "overflow": self._overflow,
                "oversize": self._oversize,
                "inflight_bytes": self._inflight}

    def _reap_input_queue(self) -> None:
        """Release the budget charge of every item still in the input
        queue. Safe concurrently with live workers (each item is
        consumed exactly once — by a worker or by this drain, never
        both)."""
        while True:
            try:
                item = self._q.get_nowait()
            except queue.Empty:
                break
            if item is not None:
                self._release_charge(item[3])

    def _reap_pending(self) -> None:
        """With every stage thread stopped, anything still queued holds
        budget charges (and possibly buffer leases): release them so an
        abort/error drain never leaks in-flight bytes (the gauge must
        return to zero)."""
        self._reap_input_queue()
        while True:
            try:
                staged = self._staged_q.get_nowait()
            except queue.Empty:
                break
            if staged is not None:
                self._discard(staged)

    def _drain(self) -> None:
        """Signal end of input and wait for staging to finish."""
        for _ in self._workers:
            self._q.put(None)
        for t in self._workers:
            t.join()
        self._staged_q.put(None)
        self._consumer_thread.join()
        # error paths drop their items without consuming them
        self._reap_pending()
        if self._error is not None:
            raise self._error

    def _release_run(self, run) -> None:
        """Recycle a run's pool lease (idempotent: lease goes to None)."""
        if run is not None and run.lease is not None \
                and self._buf_pool is not None:
            self._buf_pool.release(run.lease)
            run.lease = None

    def _release_forest(self) -> None:
        """Recycle every forest run's pool lease (the abort / overflow-
        fallback paths abandon the forest without merging it — the
        leases must still go home or the drain point reports them)."""
        with self._forest_lock:
            runs, self._forest = list(self._forest.values()), {}
            self._device_pending.clear()     # the last device references
            self._device_pending_bytes = 0
        for run in runs:
            self._release_run(run)

    def _finish_cleanup(self, acc) -> None:
        """THE finish-path cleanup contract, shared by every finish
        flavor's ``finally``: the final accumulated run's lease and any
        abandoned forest runs' leases go home, then the drain point
        asserts this merger's pool books are empty."""
        self._release_run(acc)
        self._release_forest()
        self._group_runs = []     # an overflow fallback leaves them unjoined
        self._ledger_drain("merger.finish")

    def _ledger_drain(self, point: str) -> None:
        """ResourceLedger drain point (UDA_TPU_RESLEDGER=1): with this
        merger finished or aborted, its pool leases must all be
        settled — anything open is the lost-worker-buffer leak shape,
        reported with its acquire stack. Drained under this merger's
        pool OWNER scope, so a concurrent merger's legitimately-open
        leases are untouched. The staging GAUGES are deliberately not
        drained here: their ledger records are process-global
        (owner-less), so a per-merger drain would confiscate a
        concurrent merger's live charges — and abort() additionally
        races in-flight feed() calls whose charges the PR 9 re-drain
        settles only after abort returns. Gauge obligations are
        asserted at the genuinely quiescent points instead: the
        per-test conftest teardown and the bridge-EXIT full drain."""
        if not resledger.enabled:
            return
        if self._buf_pool is not None:
            resledger.drain(point, pairs=("pool.lease",),
                            owner=id(self._buf_pool))

    def _merge_leftovers(self) -> Optional[_Run]:
        """Merge the O(log k) leftover forest runs, smallest-first; on
        the pallas engine, pad the smaller run up to the larger capacity
        first (padding rows sort last, so the validity prefix is
        preserved) — capacities stay powers of two, so kernel shapes
        stay in the O(log) compiled set. A task whose every run stayed
        in the host classes ends with one transfer and no device merge.
        Returns None when nothing was staged."""
        # UDA202 (udarace): _insert writes the forest under
        # _forest_lock; take it here too — the leftover merge runs
        # after the stage pool quiesces, but "after join" is an
        # ordering argument the lock makes unnecessary (uncontended)
        with self._forest_lock:
            if not self._forest:
                return None
            runs = [self._forest[c] for c in sorted(self._forest)]
            self._forest = {}  # release device-resident runs when done
        if self.engine == "pallas" and runs[0].on_host:
            # the host classes (all below the device ones) fold on the
            # host into one run, which goes to the device once, however
            # small: one path from here on
            nhost = sum(r.on_host for r in runs)
            acc = runs[0]
            try:
                for nxt in runs[1:nhost]:
                    acc = self._merge(acc, nxt)
                runs[:nhost] = [self._promote(acc, _next_pow2(acc.valid))]
            except BaseException:
                # the forest is already empty: these are the only
                # references to the host runs' leases
                for run in (acc, *runs[:nhost]):
                    self._release_run(run)
                raise
        acc = runs[0]
        for nxt in runs[1:]:
            if self.engine == "pallas" and acc.capacity < nxt.capacity:
                acc = _Run(merge_ops.pad_rows_to(acc.rows, nxt.capacity),
                           acc.valid, acc.bucket)
            acc = self._merge(acc, nxt)
        return acc

    def _note_overflow_fallback(self, fallback: str) -> None:
        """A task's merge leaves the forest because of oversize keys:
        counted once a task, whichever fallback it takes."""
        metrics.add("merge.overflow.fallbacks")
        log.warn(f"overlap fast path disabled (oversize keys); "
                 f"falling back to {fallback}")

    def _overflow_resort(self, batches: Sequence[RecordBatch]) -> RecordBatch:
        """The in-memory routes' overflow fallback: the whole partition
        re-sorted by ``ops.merge.merge_batches``, under a timer of its
        own so that a trace says what of the ``merge`` seconds it is."""
        self._note_overflow_fallback("global device re-sort")
        with metrics.timer("overflow_resort"):
            return merge_ops.merge_batches(batches, self.key_type,
                                           self.width)

    def _check_accounting(self, acc: Optional[_Run], total: int) -> bool:
        """Lost-records guard shared by every finish variant. Returns
        False when nothing was staged AND nothing should have been (the
        all-empty case); raises when records went missing — silently
        emitting an incomplete or unsorted merge result is the one
        unforgivable failure mode."""
        if acc is None:
            if total:
                raise MergeError(
                    f"overlap merge fed 0 of {total} records")
            return False
        if acc.valid != total:
            raise MergeError(
                f"overlap merge lost records: {acc.valid} of {total} "
                f"(segments fed != segments finished?)")
        return True

    # -- oversize blocks (the task staged a key longer than the width) ------

    @staticmethod
    def _row_pairs(rows: np.ndarray) -> np.ndarray:
        """The (segment, row) columns of merged rows: a view."""
        return rows[:, -2:]

    def _fix_oversize_blocks(self, rows: np.ndarray,
                             batches: Sequence[RecordBatch],
                             last: bool) -> tuple:
        """The (segment, row) pairs of merged ``rows`` in the
        comparator's order: every block of oversize keys with equal key
        words (module docstring) re-ordered by whole content, the rest
        as the rows have them. The rows are sorted by (words, length,
        segment, row) and the sort below is stable, so equal contents
        keep (segment, row) order. Returns ``(pairs, done)``: a block
        that reaches the end of ``rows`` may go on in the next slab, so
        unless these are the ``last`` rows it is left alone and
        ``done`` is where it starts — ``pairs[:done]`` is final, the
        caller brings ``rows[done:]`` back in front of the next slab."""
        kw = rows.shape[1] - merge_ops.ROW_EXTRA_COLS
        pairs = self._row_pairs(rows)
        over = np.flatnonzero(rows[:, kw] > self.width)
        if over.size == 0:
            return pairs, len(rows)
        words = rows[over, :kw]
        first = np.ones(over.size, bool)    # a block starts at this row
        first[1:] = ((over[1:] != over[:-1] + 1)
                     | np.any(words[1:] != words[:-1], axis=1))
        at = np.flatnonzero(first)
        starts = over[at]
        stops = np.append(over[at[1:] - 1], over[-1]) + 1
        done = len(rows)
        if not last and stops[-1] == done:
            done = int(starts[-1])
            starts, stops = starts[:-1], stops[:-1]
        blocks = [(a, b) for a, b in zip(starts.tolist(), stops.tolist())
                  if b - a > 1]
        if blocks:
            content = self.key_type.content
            # the slab itself stays as read; a whole-row copy is a
            # memcpy, where the two strided columns alone cost twice it
            pairs = self._row_pairs(np.array(rows))
            for a, b in blocks:
                member = pairs[a:b].tolist()
                keys = [content(batches[s].key(r)) for s, r in member]
                order = sorted(range(b - a), key=keys.__getitem__)
                pairs[a:b] = [member[i] for i in order]
            metrics.add("merge.oversize.blocks", len(blocks))
        return pairs, done

    def _oversize_fixed(self, slabs, batches: Sequence[RecordBatch]):
        """``emit_stream``'s read-back slabs as (segment, row) pairs
        with the oversize blocks fixed up; a slab's open trailing block
        is held back and fixed as one with the slab that closes it."""
        held = None
        for rows in slabs:
            with metrics.timer("oversize_fixup"):
                if held is not None:
                    rows = np.concatenate([held, rows])
                pairs, done = self._fix_oversize_blocks(rows, batches, False)
                held = rows[done:] if done < len(rows) else None
            if done:
                yield pairs[:done]
        if held is not None:
            with metrics.timer("oversize_fixup"):
                pairs, _ = self._fix_oversize_blocks(held, batches, True)
            yield pairs

    def emit_stream(self, sources: Sequence, emitter, consumer) -> int:
        """In-memory streaming emission: drain staging, merge the
        leftover forest and emit the merged stream without ever
        concatenating the shuffle — each output slab's bytes are
        gathered straight from the per-segment batches and framed
        natively, so transient host memory is one slab (the reference's
        staging-loop memory model over memory-resident segments).
        ``sources`` are what was fed, in segment-index order: batches,
        or Segments — asked for their batch only once staging has
        drained, so that the stage workers crack the deferred ones and
        a corrupt one fails the task through ``_error``, not from this
        thread with the workers still live."""
        from uda_tpu.merger import streaming as stream_mod

        acc = None
        try:
            with metrics.timer("merge"):
                self._drain()
                batches = [self._batch_of(s) for s in sources]
                merged = None
                if self._overflow:
                    merged = self._overflow_resort(batches)
                else:
                    total = sum(b.num_records for b in batches)
                    acc = self._merge_leftovers()
            if merged is not None:
                return emitter.emit_batch(merged, consumer)
            if not self._check_accounting(acc, total):
                return emitter.emit_framed(iter([EOF_MARKER]), consumer)
            table = stream_mod.segment_table(batches)
            slabs = stream_mod.iter_row_slabs(acc.rows, acc.valid)
            slab_pairs = (self._oversize_fixed(slabs, batches)
                          if self._oversize else map(self._row_pairs, slabs))

            def pieces():
                from uda_tpu import native

                for pairs in slab_pairs:
                    with metrics.timer("emit_gather"):
                        sub = stream_mod.slab_batch(
                            batches, pairs[:, 0], pairs[:, 1], table)
                    with metrics.timer("emit_frame"):
                        piece = native.frame_batch(sub, write_eof=False)
                    yield piece
                yield EOF_MARKER

            return emitter.emit_framed(pieces(), consumer)
        finally:
            # emit_framed fully consumes pieces() before returning, so
            # the lease recycle here never races the emission
            self._finish_cleanup(acc)

    def finish_streaming(self, emitter, consumer,
                         expected_records: Union[int, Callable[[], int],
                                                 None] = None) -> int:
        """Streaming-mode finish: drain staging, then emit the merged
        stream straight from the sorted run files — the permutation-
        driven k-way interleave (uda_tpu.merger.streaming). Host memory
        is one slab + one read buffer per run; no shuffle-sized
        allocation exists on this path. Cleans up the run store.
        ``expected_records`` is the count the runs must add up to, or a
        callable that gives it, called once staging has drained (a
        Segment whose crack was deferred knows its records only then)."""
        from uda_tpu import native
        from uda_tpu.merger import streaming as stream_mod
        from uda_tpu.utils.ifile import iter_file_records, native_enabled

        store = self.run_store
        if store is None:
            raise MergeError("finish_streaming without a run store")
        acc = None
        try:
            with metrics.timer("merge"):
                self._drain()
                # read the latch only now: a segment still being staged
                # when finish was called may be the one that sets it
                no_forest = self._overflow
                if not no_forest:
                    acc = (self._join_groups() if self._group_rows
                           else self._merge_leftovers())
            total = store.total_records
            if callable(expected_records):
                expected_records = expected_records()
            if expected_records is not None and total != expected_records:
                raise MergeError(
                    f"staged {total} of {expected_records} records")
            if total == 0:
                return emitter.emit_framed(iter([EOF_MARKER]), consumer)
            if no_forest:
                # every run is comparator-sorted (oversize segments were
                # ordered by the full comparator at staging; in-width
                # runs by (words, len) == comparator order), so the
                # fallback is a comparator-level k-way merge over the
                # run FILES — bounded memory, like the hybrid RPQ
                self._note_overflow_fallback("k-way merge over run files")
                paths = [store.run_path(s) for s in sorted(store.counts)]
                if (native_enabled() and native.kway_supported(self.key_type)
                        and native.build()):
                    return emitter.emit_framed(
                        native.kway_merge_paths(paths, self.key_type),
                        consumer)
                streams = [iter_file_records(p) for p in paths]
                return emitter.emit(
                    merge_ops.merge_record_streams(streams, self.key_type),
                    consumer)
            self._check_accounting(acc, total)  # total>0: raises on loss
            kw = int(acc.rows.shape[1]) - 3
            slabs = stream_mod.iter_row_slabs(acc.rows, acc.valid)
            return emitter.emit_framed(
                stream_mod.interleave_runs(slabs, store, kw), consumer)
        finally:
            # the spool's other end: removing hundreds of large run
            # files is seconds of a 10 GB task, not nothing
            with metrics.timer("run_spool"):
                store.cleanup()
            self._finish_cleanup(acc)

    def abort(self) -> None:
        """Stop the staging threads without producing output. Safe with
        a bounded queue: ``_aborted`` unblocks any transport thread
        waiting in feed() (queue OR in-flight budget) and makes the
        stage loops drain-and-exit even if no poison pill can land (they
        poll the flag on an empty queue). Queued items' budget charges
        and buffer leases are reaped once every thread has stopped — an
        abort never leaks in-flight bytes. The run store is only cleaned
        once every stager has stopped — never under a concurrent
        write_run."""
        self._aborted = True
        # black-box state transition: an abort is the merge half of
        # almost every failure post-mortem (utils/flightrec.py)
        from uda_tpu.utils.flightrec import flightrec
        flightrec.record("overlap.abort",
                         staged_runs=self.stats.get("staged_runs", 0),
                         pending=self.stats.get("pending", 0))
        try:
            self._q.put_nowait(None)  # best effort: wake one instantly
        except queue.Full:
            pass
        try:
            self._staged_q.put_nowait(None)
        except queue.Full:
            pass
        with self._inflight_cv:
            self._inflight_cv.notify_all()  # wake budget-blocked feeds
        deadline = 10.0
        for t in self._threads:
            t0 = time.monotonic()
            t.join(timeout=max(0.1, deadline))
            deadline -= time.monotonic() - t0
        stragglers = any(t.is_alive() for t in self._threads)
        if not stragglers:
            self._reap_pending()
        if self.run_store is not None:
            if stragglers:
                log.warn("overlap abort: stager still running; leaving "
                         "scratch runs for it to fail safely")
            else:
                self.run_store.cleanup()
        if not stragglers:
            # the abandoned forest's leases go home, then the drain
            # point asserts nothing else is still open (a straggler
            # thread may still legitimately hold leases — no drain)
            self._release_forest()
            self._group_runs = []
            self._ledger_drain("merger.abort")
