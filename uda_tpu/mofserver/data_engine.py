"""Supplier data engine: bounded chunk pool + threaded segment reads.

TPU-native rebuild of the reference's DataEngine (reference
src/MOFServer/IndexInfo.cc:97-376): the libaio O_DIRECT read loop with a
1000-chunk pool becomes a pread thread pool (one pool per local dir,
``mapred.uda.provider.blocked.threads.per.disk`` threads each — the
capability of the orphaned AsyncIO/ reader, reference
src/AsyncIO/AsyncReaderManager.cc:16-50, now actually wired in).

Backpressure: the reference bounded supplier memory with a 1000-chunk
free list (occupy_chunk blocking when empty, IndexInfo.cc:276-292). Here
in-flight memory is bounded structurally instead: every Segment keeps at
most ONE outstanding request (uda_tpu.merger.segment), and the
MergeManager's fetch window caps concurrently-active segments
(``mapred.rdma.wqe.per.conn``), so in-flight bytes <= window x
chunk_size. A blocking budget inside ``submit`` is deliberately avoided:
chained fetches are re-issued from worker-thread completion callbacks,
and blocking there can deadlock the pool.

A fetch request asks for up to ``chunk_size`` bytes of one partition at
``offset`` within the partition; the reply carries (raw_length,
part_length, actual bytes, mof_offset) — the fields of the reference's
RDMA ACK message ("rawLen:partLen:sentSize:mofOffset:path",
src/DataNet/RDMAServer.cc:537-631). Refcounted fd reuse mirrors the
reference's fd_counter map (IndexInfo.cc:195-233).

The batched host-I/O plane (``submit_batch``; PARITY C15 consumed)
amortizes the per-op costs this host measured in PR 6 (~20 us
syscalls, ~100 us pool handoffs): one pool handoff per request burst,
per-fd grouping + gap-threshold range coalescing, and vectored reads
down the io_uring -> preadv -> pread backend ladder
(``uda.tpu.read.backend``; README "Host I/O & self-tuning").
"""

from __future__ import annotations

import dataclasses
import os
import sys
import threading
import time
import zlib
from concurrent.futures import Future, ThreadPoolExecutor
from concurrent.futures import TimeoutError as FutureTimeout
from typing import Dict, List, Optional, Sequence

from uda_tpu.mofserver.index import IndexResolver
from uda_tpu.utils.config import Config
from uda_tpu.utils.errors import ConfigError, StorageError
from uda_tpu.utils.failpoints import failpoint, failpoints
from uda_tpu.utils.logging import get_logger
from uda_tpu.utils.metrics import metrics
from uda_tpu.utils.resledger import resledger

__all__ = ["ShuffleRequest", "FetchResult", "FdSlice", "DataEngine",
           "plan_coalesced", "BATCH_BACKENDS"]

log = get_logger()

# The batched-read backend ladder, best rung first (the RDMAbox lesson,
# arXiv:2104.12197: amortize per-op syscall/handoff cost by batching
# submissions). "io_uring" = the native ReadPool's kernel ring (PARITY
# C15's reserved slot, compiled in when the build host has the uapi
# header, selected only when the RUNNING kernel accepts
# io_uring_setup); "preadv" = one os.preadv per coalesced run;
# "pread" = per-request os.pread on the batch worker (one pool handoff
# per batch — the floor every host has).
BATCH_BACKENDS = ("io_uring", "preadv", "pread")

# the native-reader-unavailable fallback is warned ONCE per process
# (a fleet of engines must not spam the log; every occurrence still
# counts io.native.unavailable — the errors.swallowed posture)
_native_warn_lock = threading.Lock()
_native_warned = False


def _warn_native_unavailable(cause: Exception) -> None:
    global _native_warned
    metrics.add("io.native.unavailable")
    with _native_warn_lock:
        first = not _native_warned
        _native_warned = True
    if first:
        log.warn(f"native reader unavailable, using os.pread: {cause}")
    else:
        log.debug(f"native reader unavailable (counted): {cause}")


@dataclasses.dataclass(frozen=True)
class ShuffleRequest:
    """One chunk fetch (reference shuffle_req_t, src/MOFServer/
    IndexInfo.h:64-77: jobid, map, reduceID, map_offset, chunk_size).

    ``host`` identifies the supplier serving this map output (the
    reference addresses fetches per supplier host, RDMAClient.cc:
    498-527); single-host transports ignore it.

    ``tenant`` is the multi-tenant service plane's in-process stamp:
    the ShuffleServer copies its connection's MSG_JOB binding here
    before submitting, so the engine's per-tenant admission partitions
    and metric labels key on it. It never rides the wire (the REQ
    frame carries job identity; the TENANT identity is the
    connection's authenticated binding — a client cannot spoof a
    neighbor's tenant per request). Empty = untenanted (the
    single-job default, exact PR 1-13 behavior)."""

    job_id: str
    map_id: str
    reduce_id: int
    offset: int          # offset within the partition's record bytes
    chunk_size: int
    host: str = ""
    tenant: str = ""


@dataclasses.dataclass
class FetchResult:
    """Reply payload (reference ACK fields, RDMAServer.cc:597-607).

    ``raw_length`` is the partition's uncompressed record-byte size and
    ``part_length`` its on-disk size (they differ under compression,
    matching Hadoop's spill-index semantics); ``last`` is set by the
    producer in whatever domain it serves (DataEngine: on-disk bytes;
    DecompressingClient: uncompressed stream).

    ``data`` is bytes-LIKE, not necessarily bytes: the event-loop
    client donates its per-frame receive bytearray straight into this
    field (zero-copy receive), so consumers must stay buffer-agnostic
    (len/crc32/decompress/``bytes + data`` concatenation all are).
    """

    data: bytes  # bytes-like (bytes or bytearray); see docstring
    raw_length: int      # total uncompressed record bytes of the partition
    part_length: int     # total on-disk bytes of the partition
    offset: int          # echo of the request offset
    path: str
    last: bool           # required: a defaulted value silently truncated
                         # multi-chunk streams once; producers must decide
    crc: Optional[int] = None  # CRC32 of the chunk as read from disk
                               # (uda.tpu.fetch.crc); None = unchecked
    timing: Optional[tuple] = None  # (park_us, serve_us) a supplier
                                    # reported in the DATA head (net/
                                    # wire.py, _FLAG_TIMING); None = not
                                    # reported (spans off, local fetch)

    @property
    def is_last(self) -> bool:
        return self.last


@dataclasses.dataclass
class FdSlice:
    """A zero-copy serve plan: one chunk of a MOF described as
    ``(fd, offset, length)`` instead of bytes — the event-loop server
    streams it with ``os.sendfile`` so the chunk never transits the
    Python heap (the reference's RDMA-WRITE-from-registered-MOF-memory
    shape, RDMAServer.cc:537-631, minus the NIC).

    Holds one fd-cache reference AND the request's admission charge
    until :meth:`release` — bytes on their way to the wire stay inside
    the supplier read budget exactly like bytes sitting in a
    FetchResult would. ``release()`` is idempotent and MUST be called
    exactly-once-effective on every path (written, torn, dropped)."""

    fd: int
    file_offset: int     # absolute offset in the MOF file
    length: int          # chunk bytes to serve
    raw_length: int      # the FetchResult ACK fields, verbatim
    part_length: int
    offset: int          # echo of the request offset
    path: str
    last: bool
    _engine: "DataEngine" = dataclasses.field(repr=False, default=None)
    _admitted: int = 0
    _released: bool = False
    _tenant: str = ""    # the admission charge's tenant partition

    def release(self) -> None:
        if self._released:
            return
        self._released = True
        self._engine._fds.release(self.path)
        if self._admitted:
            self._engine._unadmit(self._admitted, self._tenant)

    def view(self):
        """A memoryview of the chunk inside the MOF's cached whole-file
        mmap (the serve path's mmap mode: sent with ``sendmsg``, the
        bytes go page-cache -> socket without a Python-heap object).
        None when the file cannot be mapped — caller falls back to
        sendfile. Only valid while this slice is unreleased; callers
        must drop the view before (or with) release()."""
        if self._released:
            return None
        mm = self._engine._fds.mmap_for(self.path)
        if mm is None:
            return None
        return memoryview(mm)[self.file_offset:
                              self.file_offset + self.length]


class _FdCache:
    """Refcounted fd reuse across in-flight requests for the same MOF
    (reference fd_counter, IndexInfo.cc:195-233), with an optional
    per-entry read-only ``mmap`` of the whole file — the registered-
    memory analogue the zero-copy serve path's mmap mode slices
    memoryviews out of (one map per MOF, zero per-chunk syscalls).

    Entries whose refcount hits zero are RETAINED idle (LRU, up to
    ``_IDLE_CAP``) instead of closed: the serve path acquires/releases
    once per chunk, and paying an open+close (+ mmap/munmap) syscall
    round trip per chunk dominated the serve critical path on
    emulated-syscall kernels — this is the reference's registered-
    memory-stays-registered property. Eviction and close_all() still
    close for real."""

    _IDLE_CAP = 128

    def __init__(self) -> None:
        self._lock = threading.Lock()
        # path -> [fd, refs, mmap|None]
        self._fds: Dict[str, list] = {}
        self._idle: list = []  # LRU order of refs==0 paths (front=oldest)

    def acquire(self, path: str) -> int:
        with self._lock:
            ent = self._fds.get(path)
            if ent:
                if ent[1] == 0:
                    self._idle.remove(path)
                ent[1] += 1
                resledger.acquire("engine.fd", key=path, owner=id(self))
                return ent[0]
        fd = os.open(path, os.O_RDONLY)
        with self._lock:
            ent = self._fds.get(path)
            if ent:  # raced: keep the existing one
                if ent[1] == 0:
                    self._idle.remove(path)
                ent[1] += 1
                os.close(fd)
                resledger.acquire("engine.fd", key=path, owner=id(self))
                return ent[0]
            self._fds[path] = [fd, 1, None]
            resledger.acquire("engine.fd", key=path, owner=id(self))
            return fd

    def mmap_for(self, path: str):
        """The whole-file read-only map for an entry the caller holds a
        reference on (lazily created, cached with the fd). None when
        the file cannot be mapped (empty file, exotic fs) — the caller
        falls back to sendfile/pread."""
        import mmap as mmap_mod

        with self._lock:
            ent = self._fds.get(path)
            if ent is None:
                return None
            if ent[2] is not None:
                return ent[2]
            fd = ent[0]
        try:
            mm = mmap_mod.mmap(fd, 0, prot=mmap_mod.PROT_READ)
        except (ValueError, OSError):
            return None
        with self._lock:
            ent = self._fds.get(path)
            if ent is None or ent[2] is not None:
                mm.close()
                return ent[2] if ent else None
            ent[2] = mm
            return mm

    @staticmethod
    def _close_entry(fd: int, mm) -> None:
        if mm is not None:
            try:
                mm.close()
            except BufferError:
                # a serve-path memoryview still points into the map
                # (abandoned mid-write item not yet collected): leaking
                # the map until process exit beats a crash
                log.warn("mmap still exported at fd-cache release; "
                         "leaking the mapping")
        os.close(fd)

    def release(self, path: str) -> None:
        evicted = None
        with self._lock:
            ent = self._fds.get(path)
            if not ent:
                return
            ent[1] -= 1
            # one ref settled (the ledger ignores an over-release's
            # unmatched settle, same as the refcount clamp below)
            resledger.settle("engine.fd", key=path, owner=id(self))
            if ent[1] > 0:
                return
            ent[1] = 0
            if path in self._idle:
                return  # defensive: an over-release must not double-add
            # keep the entry idle (fd + mmap stay warm); evict the
            # oldest idle entry beyond the cap
            self._idle.append(path)
            if len(self._idle) > self._IDLE_CAP:
                victim = self._idle.pop(0)
                evicted = self._fds.pop(victim, None)
        if evicted is not None:
            self._close_entry(evicted[0], evicted[2])

    def close_all(self) -> None:
        with self._lock:
            ents = list(self._fds.values())
            self._fds.clear()
            self._idle.clear()
        for fd, _, mm in ents:
            self._close_entry(fd, mm)


# one coalesced run never exceeds this many entries: each entry costs
# up to two iovecs (its buffer + a gap scratch view), and preadv
# rejects more than IOV_MAX (1024) buffers per call with EINVAL — a
# config/tuning-cache batch_max above 512 must split runs, not turn a
# whole burst's reads into errors
_MAX_RUN_ITEMS = 511


def plan_coalesced(ranges: Sequence[tuple], gap_bytes: int,
                   max_run_bytes: int,
                   max_items: int = _MAX_RUN_ITEMS) -> List[list]:
    """Group ``(item, file_off, length)`` triples into coalesced runs:
    within a run, ranges ascend, never overlap, successive ranges are
    at most ``gap_bytes`` apart, the whole read span stays under
    ``max_run_bytes`` and the run holds at most ``max_items`` entries
    (the IOV_MAX bound) — each run becomes ONE vectored read (the gaps
    are read into scratch and discarded). Overlapping or duplicate
    ranges start a fresh run: a scatter list cannot write the same
    disk bytes into two buffers in one preadv. Pure planning (no IO),
    unit-tested directly."""
    if not ranges:
        return []
    ordered = sorted(ranges, key=lambda r: (r[1], r[2]))
    runs: List[list] = []
    run: list = [ordered[0]]
    run_start = ordered[0][1]
    run_end = ordered[0][1] + ordered[0][2]
    for item in ordered[1:]:
        _, off, length = item
        if (off >= run_end and off - run_end <= gap_bytes
                and (off + length) - run_start <= max_run_bytes
                and len(run) < max_items):
            run.append(item)
            run_end = off + length
        else:
            runs.append(run)
            run = [item]
            run_start, run_end = off, off + length
    runs.append(run)
    return runs


def _preadv_full(fd: int, bufs: Sequence, offset: int) -> tuple:
    """os.preadv until every buffer is full or EOF: one scatter read
    for the common case, continuation reads re-sliced past the filled
    prefix when the kernel returns short (pipe-sized transfers,
    signals). Returns (bytes_read, syscalls)."""
    views = [memoryview(b) for b in bufs]
    lens = [len(v) for v in views]
    total = sum(lens)
    got = 0
    syscalls = 0
    while got < total:
        acc = 0
        i = 0
        while i < len(views) and acc + lens[i] <= got:
            acc += lens[i]
            i += 1
        iov = [views[i][got - acc:]] + views[i + 1:]
        n = os.preadv(fd, iov, offset + got)
        syscalls += 1
        if n <= 0:
            break  # EOF mid-run: callers fail the unfilled ranges
        got += n
    return got, syscalls


class _BatchEntry:
    """One request's slot in a submitted batch: the future the caller
    holds, the accounting it owes, and the per-request state the batch
    worker fills in as the stages (resolve -> read -> finish) run.
    ``err`` short-circuits later stages — one failing request never
    touches its batch-mates (per-request error isolation)."""

    __slots__ = ("req", "want_admit", "fut", "parent_span", "rec",
                 "want", "file_off", "fd", "buf", "got", "err")

    def __init__(self, req: ShuffleRequest, want_admit: int, fut: Future,
                 parent_span=None):
        self.req = req
        self.want_admit = want_admit
        self.fut = fut
        self.parent_span = parent_span
        self.rec = None
        self.want = 0          # actual chunk bytes (clamped to the MOF)
        self.file_off = 0
        self.fd = -1
        self.buf = None        # per-request read buffer (bytearray)
        self.got = 0           # bytes actually landed in buf
        self.err: Optional[Exception] = None


class _NativeReads:
    """Routes blocking reads through the native ReadPool: a router thread
    drains the pool's completion queue (the io_getevents analogue) and
    wakes the submitting thread by tag. Submit and waiter registration
    are atomic under the same lock the router needs to deliver, so a
    completion can never beat its waiter's registration."""

    def __init__(self, pool):
        self.pool = pool
        self._lock = threading.Lock()
        self._waiters: dict[int, list] = {}   # tag -> [Event, data|None]
        self._stop = False
        self._router = threading.Thread(target=self._route, daemon=True,
                                        name="uda-native-router")
        self._router.start()

    def _route(self) -> None:
        while not self._stop:
            events = self.pool.poll(min_events=1, timeout=0.2)
            with self._lock:
                for tag, result in events:
                    w = self._waiters.pop(tag, None)
                    if w is not None:
                        w[1] = result
                        w[0].set()

    def read(self, fd: int, offset: int, length: int) -> bytes:
        waiter = [threading.Event(), None]
        with self._lock:
            tag = self.pool.submit(fd, offset, length)
            self._waiters[tag] = waiter
        if not waiter[0].wait(timeout=60.0):
            with self._lock:
                self._waiters.pop(tag, None)  # don't leak the entry
            raise StorageError("native read timed out")
        result = waiter[1]
        if isinstance(result, Exception):
            raise result
        return result.tobytes()

    def read_batch(self, jobs: Sequence[tuple]) -> list:
        """Batched reads: submit every ``(fd, offset, length)`` job in
        ONE native call (uda_pool_submit_batch — one lock round/ring
        doorbell for the whole burst), then wait for all completions.
        Returns results in job order; a failed read is its job's
        StorageError, never its batch-mates' (per-tag isolation, the
        same contract as poll())."""
        waiters = []
        with self._lock:
            tags = self.pool.submit_batch(jobs)
            for tag in tags:
                w = [threading.Event(), None]
                self._waiters[tag] = w
                waiters.append((tag, w))
        deadline = time.monotonic() + 60.0
        out = []
        for tag, w in waiters:
            if not w[0].wait(timeout=max(0.0,
                                         deadline - time.monotonic())):
                with self._lock:
                    self._waiters.pop(tag, None)
                out.append(StorageError("native batch read timed out"))
                continue
            result = w[1]
            out.append(result if isinstance(result, Exception)
                       else result.tobytes())
        return out

    def close(self) -> None:
        self._stop = True
        self._router.join(timeout=2.0)
        if self._router.is_alive():
            # the router may still be inside pool.poll; destroying the
            # native pool under it would be a use-after-free of the
            # whole process — leaking the pool is the safe failure mode
            log.warn("native read router did not exit in 2s; "
                     "leaking the native pool instead of freeing it")
            return
        self.pool.close()


class DataEngine:
    """Threaded chunk server over local map-output files."""

    def __init__(self, resolver: IndexResolver, config: Optional[Config] = None,
                 num_disks: int = 1):
        cfg = config or Config()
        threads = max(1, cfg.get("mapred.uda.provider.blocked.threads.per.disk")) \
            * max(1, num_disks)
        self.chunk_size_default = cfg.get("mapred.rdma.buf.size") * 1024
        self._crc = bool(cfg.get("uda.tpu.fetch.crc"))
        # read-pool admission (the reference's 1000-chunk pool bound,
        # IndexInfo.cc:276-292, minus the blocking: submit() must stay
        # non-blocking — see the module docstring — so over-budget
        # requests are REJECTED with StorageError and the reduce side's
        # retry/backoff machinery absorbs the push-back). The budget
        # covers bytes queued or being read; 0 = a 256 MB floor scaled
        # by the reader thread count.
        budget_mb = int(cfg.get("uda.tpu.supplier.read.budget.mb"))
        if budget_mb <= 0:
            budget_mb = max(256, threads * 32)
        self.read_budget_bytes = budget_mb * (1 << 20)
        # the synchronous path's wait bound (fetch()): derived from the
        # reduce side's retry knobs so the two paths give up on a
        # wedged completion on the same schedule; both unset -> 60 s
        # (no caller means "forever" by leaving a knob at 0)
        attempt_ms = int(cfg.get("mapred.rdma.fetch.attempt.timeout.ms"))
        deadline_ms = int(cfg.get("mapred.rdma.fetch.deadline.ms"))
        self.sync_fetch_timeout_s = (
            (attempt_ms or deadline_ms) / 1e3
            if (attempt_ms or deadline_ms) else 60.0)
        self._admitted_bytes = 0
        self._admit_lock = threading.Lock()
        # multi-tenant read-budget partitions (uda_tpu/tenant/): when a
        # TenantRegistry is attached, tenant-stamped requests are
        # additionally admitted against that tenant's weighted SHARE of
        # the budget — one abusive job exhausts its slice and only its
        # own clients see the push-back (the isolation contract).
        self._tenant_registry = None
        self._tenant_admitted: Dict[str, int] = {}
        spec = cfg.get("uda.tpu.failpoints")
        if spec:
            failpoints.arm_spec(spec)
        self.resolver = resolver
        # the elastic disaggregated store (mofserver/store.py): when
        # attached, reads of store-MANAGED partitions (blob primaries,
        # twin-holding locals) route through its failover router;
        # unmanaged partitions keep the classic fd path untouched —
        # zero-copy FdSlice serve included
        self.store = None
        self._pool = ThreadPoolExecutor(max_workers=threads,
                                        thread_name_prefix="uda-data-engine")
        self._fds = _FdCache()
        self._stopped = False
        # native read path (the AIOHandler-equivalent worker pool,
        # uda_tpu/native/reader.cc), flag-gated with graceful fallback.
        # The flag also gates the process-wide native IFile codec — but
        # only when EXPLICITLY set, so a default-config engine never
        # silently reconfigures other jobs in the process.
        if cfg.is_set("uda.tpu.use.native"):
            from uda_tpu.utils.ifile import set_native_enabled
            set_native_enabled(bool(cfg.get("uda.tpu.use.native")))
        self._native = None
        if cfg.get("uda.tpu.use.native"):
            try:
                from uda_tpu import native
                if native.available() or native.build():
                    self._native = _NativeReads(native.ReadPool(threads))
            except Exception as e:  # pragma: no cover - best effort
                _warn_native_unavailable(e)
        self._resolve_batch_plane(cfg)

    def _resolve_batch_plane(self, cfg: Config) -> None:
        """Resolve the batched host-I/O plane's parameters. Precedence
        per knob: explicit config > tuning-cache winner > built-in
        default (utils/tuncache.py — env/config winners always beat
        the cache, and a cold/corrupt cache is exactly the defaults).
        The backend ladder walks io_uring -> preadv -> pread downward
        from whatever the winner/knob requests, constrained by what
        this process actually has; the selected rung is recorded as
        the ``io.backend`` metric label and the ``io_backend``
        attribute every stats provider can read."""
        winner: dict = {}
        explicit = cfg.is_set("uda.tpu.tune.cache.path")
        tc_path = (str(cfg.get("uda.tpu.tune.cache.path")) if explicit
                   else "")
        if not tc_path:
            from uda_tpu.utils.tuncache import cache_path_from_env
            tc_path = cache_path_from_env()
        if tc_path:
            from uda_tpu.utils.tuncache import TuneCache, tune_cache
            cache = TuneCache(tc_path) if explicit else tune_cache
            rec = cache.lookup("io.read", sys.platform)
            if rec is not None and isinstance(rec.get("winner"), dict):
                winner = rec["winner"]
        mode = str(cfg.get("uda.tpu.read.batch")).strip().lower()
        if mode not in ("on", "off", "auto"):
            raise ConfigError(f"uda.tpu.read.batch={mode!r} is not "
                              f"on/off/auto")
        if mode == "auto" and winner.get("batch") in ("on", "off"):
            mode = winner["batch"]
        self.batch_enabled = mode != "off"
        gap_kb = int(cfg.get("uda.tpu.read.coalesce.gap.kb"))
        if not cfg.is_set("uda.tpu.read.coalesce.gap.kb") \
                and isinstance(winner.get("gap_kb"), int) \
                and winner["gap_kb"] >= 0:
            gap_kb = winner["gap_kb"]
        self.coalesce_gap_bytes = max(0, gap_kb) << 10
        bmax = int(cfg.get("uda.tpu.read.batch.max"))
        if not cfg.is_set("uda.tpu.read.batch.max") \
                and isinstance(winner.get("batch_max"), int) \
                and winner["batch_max"] > 0:
            bmax = winner["batch_max"]
        self.batch_max = max(1, bmax)
        # one coalesced run's read span stays bounded so gap scratch +
        # per-request buffers cannot balloon past the admission budget
        self.max_run_bytes = self.batch_max * (64 << 10)
        want_backend = str(cfg.get("uda.tpu.read.backend")).strip().lower()
        if want_backend not in BATCH_BACKENDS + ("auto",):
            # typo'd deploy values fail loudly, never silently serve
            # the slow rung
            raise ConfigError(f"uda.tpu.read.backend={want_backend!r} "
                              f"is not one of {BATCH_BACKENDS + ('auto',)}")
        if want_backend == "auto" and winner.get("backend") \
                in BATCH_BACKENDS:
            want_backend = winner["backend"]
        self.io_backend = self._walk_backend_ladder(want_backend)
        metrics.add("io.backend", backend=self.io_backend)

    def _walk_backend_ladder(self, want: str) -> str:
        """The io_uring -> preadv -> pread fallback ladder, entered at
        ``want`` ("auto" = the top): each rung is taken only when this
        process can actually drive it — io_uring needs the native pool
        built WITH the ring backend and a kernel that accepted
        io_uring_setup (a 4.4-class host lands on preadv; the ABI is
        the drop-in for real hosts), preadv needs os.preadv."""
        start = 0 if want == "auto" else BATCH_BACKENDS.index(want)
        for rung in BATCH_BACKENDS[start:]:
            if rung == "io_uring":
                native = self._native
                if native is not None and \
                        getattr(native.pool, "backend", lambda: "pool")() \
                        == "io_uring":
                    return rung
            elif rung == "preadv":
                if hasattr(os, "preadv"):
                    return rung
            else:
                return rung
        return "pread"

    def submit(self, req: ShuffleRequest) -> Future:
        """Async fetch; the Future resolves to a FetchResult. Never
        blocks (see module docstring on backpressure); safe to call from
        completion callbacks. Blocking IN a completion callback can
        still deadlock the pool — chained fetch re-issue must stay
        non-blocking (regression-tested under a delay failpoint:
        tests/test_mofserver.py::test_chained_fetches_under_delay_
        failpoint_no_deadlock)."""
        if self._stopped:
            raise StorageError("DataEngine is stopped")
        want = req.chunk_size or self.chunk_size_default
        self._admit_bytes(want, req.tenant)
        # the +1 rides the returned Future: _serve's finally owns the
        # -1 on every outcome; the except below covers the one path
        # where the pool never ran it
        metrics.gauge_add("supplier.reads.on_air", 1)  # udalint: disable=UDA101
        try:
            # span adoption across the pool handoff: the submitting
            # thread's current span (a net.serve span on the wire path,
            # a fetch.segment span in-process) becomes the worker-side
            # engine.pread span's parent — the contextvar does not
            # cross threads, so the parent rides the work item
            return self._pool.submit(self._serve, req, want,
                                     metrics.current_span())
        except BaseException:  # pool shutdown race: undo the accounting
            self._unadmit(want, req.tenant)
            metrics.gauge_add("supplier.reads.on_air", -1)
            raise

    def attach_store(self, store) -> None:
        """Attach a :class:`~uda_tpu.mofserver.store.StoreManager`:
        the engine consults ``store.manages(path)`` per resolved record
        and routes managed reads through the store's failover router
        (``read``/``read_ranges``). Byte semantics are identical —
        short-read checks, CRC stamping and the ``data_engine.pread``
        failpoint all run on the routed bytes exactly as on the fd
        path."""
        self.store = store

    def _store_managed(self, rec) -> bool:
        store = self.store
        return store is not None and store.manages(rec.path)

    def set_tenant_registry(self, registry) -> None:
        """Attach the multi-tenant registry: tenant-stamped requests
        are admitted against per-tenant budget shares
        (``registry.share_bytes``), and a retiring job's obligation
        books are drained — any admission bytes it never released are
        reported with the tenant as the leak's attribution."""
        self._tenant_registry = registry
        if registry is not None:
            registry.on_retire(lambda tenant, job:
                               self.drain_tenant(tenant))

    def drain_tenant(self, tenant: str) -> None:
        """ResourceLedger drain of one tenant's admission books (retire
        hook). Only when the tenant is quiescent — bytes still in
        flight are live obligations, not leaks; the engine-stop drain
        owns the final sweep."""
        with self._admit_lock:
            quiescent = self._tenant_admitted.get(tenant, 0) <= 0
        if quiescent:
            resledger.drain(f"tenant.retire[{tenant}]",
                            pairs=("tenant.admit",), owner=id(self))

    def _admit_bytes(self, want: int, tenant: str = "") -> None:
        """THE read-budget admission gate (the occupy_chunk pool bound,
        IndexInfo.cc:276-292, minus the blocking): every serve path —
        submit, submit_serve, try_plan, submit_batch — charges through
        here, and every non-serving outcome must pair the charge with
        :meth:`_unadmit` (budget-critical logic lives exactly once).
        Raises StorageError on rejection. An oversized single request
        is admitted when the pool is otherwise idle: progress beats the
        bound (a request larger than the whole budget could never be
        served at all, which would turn push-back into a permanent
        dead end) — and the idle escape is PER TENANT on the tenant
        gate, so one tenant's giant request rides its own idle slice,
        never a neighbor's headroom."""
        reg = self._tenant_registry
        with self._admit_lock:
            if self._admitted_bytes > 0 and \
                    self._admitted_bytes + want > self.read_budget_bytes:
                metrics.add("supplier.admission.rejections")
                raise StorageError(
                    f"supplier read pool exhausted: {self._admitted_bytes}"
                    f" B in flight + {want} B > budget "
                    f"{self.read_budget_bytes} B (retry with backoff, or "
                    f"raise uda.tpu.supplier.read.budget.mb)")
            if tenant and reg is not None:
                mine = self._tenant_admitted.get(tenant, 0)
                share = reg.share_bytes(tenant, self.read_budget_bytes)
                if mine > 0 and mine + want > share:
                    metrics.add("supplier.admission.rejections")
                    metrics.add("tenant.admission.rejections",
                                tenant=tenant)
                    raise StorageError(
                        f"tenant {tenant!r} read share exhausted: "
                        f"{mine} B in flight + {want} B > share "
                        f"{share} B of the supplier budget (this "
                        f"tenant's clients pace; others are unaffected)")
            self._admitted_bytes += want
            if tenant:
                self._tenant_admitted[tenant] = \
                    self._tenant_admitted.get(tenant, 0) + want
        metrics.gauge_add("supplier.read.bytes.on_air", want)
        if tenant:
            metrics.gauge_add("tenant.read.bytes.on_air", want)
            metrics.gauge_add("tenant.read.bytes.on_air", want,
                              tenant=tenant)
            resledger.acquire("tenant.admit", key=tenant, amount=want,
                              owner=id(self), detail=f"tenant={tenant}")

    def _unadmit(self, want: int, tenant: str = "") -> None:
        with self._admit_lock:
            self._admitted_bytes -= want
            if tenant:
                left = self._tenant_admitted.get(tenant, 0) - want
                if left > 0:
                    self._tenant_admitted[tenant] = left
                else:
                    self._tenant_admitted.pop(tenant, None)
        metrics.gauge_add("supplier.read.bytes.on_air", -want)
        if tenant:
            metrics.gauge_add("tenant.read.bytes.on_air", -want)
            metrics.gauge_add("tenant.read.bytes.on_air", -want,
                              tenant=tenant)
            resledger.settle("tenant.admit", key=tenant, amount=want,
                             owner=id(self))

    def submit_serve(self, req: ShuffleRequest) -> Future:
        """Like :meth:`submit`, but the Future may resolve to an
        :class:`FdSlice` (the zero-copy plan: chunk described as
        fd+offset+length with the fd pinned in the cache) instead of a
        FetchResult. The byte path is taken — transparently, same
        Future type — whenever the chunk cannot be served straight off
        the fd: CRC stamping is on (the checksum needs the bytes), or
        the ``data_engine.pread`` failpoint is armed (injected
        truncation/corruption must keep mangling real bytes, or chaos
        would silently stop testing anything on the zero-copy plane).
        Identical admission, backpressure and error semantics to
        submit(); callers that receive an FdSlice own its release()."""
        if self._stopped:
            raise StorageError("DataEngine is stopped")
        want = req.chunk_size or self.chunk_size_default
        self._admit_bytes(want, req.tenant)
        # same handoff as submit(): _serve_plan's finally owns the -1
        metrics.gauge_add("supplier.reads.on_air", 1)  # udalint: disable=UDA101
        try:
            return self._pool.submit(self._serve_plan, req, want,
                                     metrics.current_span())
        except BaseException:  # pool shutdown race: undo the accounting
            self._unadmit(want, req.tenant)
            metrics.gauge_add("supplier.reads.on_air", -1)
            raise

    def _slice_eligible(self) -> bool:
        return not self._crc \
            and not failpoints.is_armed("data_engine.pread")

    def slice_eligible(self) -> bool:
        """Whether zero-copy FdSlice planning is currently possible
        (CRC off, pread failpoint disarmed). The event-loop server
        consults this to route: slice-eligible requests keep the
        zero-copy plane, everything else rides the batched byte path
        when batching is on."""
        return self._slice_eligible()

    # -- the batched host-I/O plane ------------------------------------------

    def submit_batch(self, reqs: Sequence[ShuffleRequest],
                     parent_spans: Optional[Sequence] = None
                     ) -> List[Future]:
        """Batch submission front (the RDMAbox batched-submission
        lesson; PARITY C15): the whole request burst rides ONE pool
        handoff, the worker groups per fd, coalesces adjacent/
        near-adjacent ranges (``uda.tpu.read.coalesce.gap.kb``) and
        issues vectored reads — a burst against one hot MOF is
        O(files) syscalls, not O(chunks). Returns one Future per
        request, resolving to FetchResults exactly like submit()'s.

        Semantics vs submit(): admission is PER REQUEST (an over-
        budget request fails only its own future with StorageError —
        its batch-mates proceed), and this method never raises — a
        stopped engine or pool-shutdown race fails the futures, so a
        caller iterating a burst cannot half-attach callbacks. Error
        isolation holds all the way down: one failing range in a
        coalesced batch (bad offset, short read, injected
        data_engine.preadv fault) fails only its request."""
        futs: List[Future] = []
        entries: List[_BatchEntry] = []
        parents = parent_spans or ()
        stopped = self._stopped
        for i, req in enumerate(reqs):
            fut = Future()
            futs.append(fut)
            if stopped:
                fut.set_exception(StorageError("DataEngine is stopped"))
                continue
            want = req.chunk_size or self.chunk_size_default
            try:
                # obligation hand-off, the submit()/submit_serve()
                # shape: the charge rides the _BatchEntry into
                # _serve_batch, whose finally settles every entry on
                # every outcome (the except below covers the one path
                # where the pool never ran it)
                self._admit_bytes(want, req.tenant)  # udalint: disable=UDA101
            except StorageError as e:
                fut.set_exception(e)
                continue
            # both +1s ride the batch entry: _serve_batch's finally
            # owns every -1 (or the except below when the pool never
            # ran it)
            metrics.gauge_add("supplier.reads.on_air", 1)  # udalint: disable=UDA101
            metrics.gauge_add("io.batch.inflight", 1)  # udalint: disable=UDA101
            entries.append(_BatchEntry(
                req, want, fut,
                parents[i] if i < len(parents) else None))
        if not entries:
            return futs
        metrics.add("io.batch.submits")
        # per-tenant labels advance the total AND the tenant series;
        # untenanted entries keep the plain total-only add
        plain = sum(1 for e in entries if not e.req.tenant)
        if plain:
            metrics.add("io.batch.requests", plain)
        by_tenant: Dict[str, int] = {}
        for e in entries:
            if e.req.tenant:
                by_tenant[e.req.tenant] = by_tenant.get(e.req.tenant,
                                                        0) + 1
        for tenant, n in by_tenant.items():
            metrics.add("io.batch.requests", n, tenant=tenant)
        try:
            self._pool.submit(self._serve_batch, entries)
        except BaseException as exc:  # pool shutdown race: undo + fail
            # every future (the error is FORWARDED there, chained —
            # never leave a caller holding futures nobody resolves)
            for e in entries:
                self._settle_batch_entry(e, 0.0, observe=False)
                err = StorageError("DataEngine is stopped")
                err.__cause__ = exc
                e.fut.set_exception(err)
        return futs

    def _settle_batch_entry(self, e: _BatchEntry, t0: float,
                            observe: bool = True) -> None:
        """The one settlement point for a batch entry's accounting
        (admission bytes + both paired gauges), run exactly once per
        entry on every outcome."""
        self._unadmit(e.want_admit, e.req.tenant)
        metrics.gauge_add("supplier.reads.on_air", -1)
        metrics.gauge_add("io.batch.inflight", -1)
        if observe:
            if e.req.tenant:
                metrics.observe("supplier.read.latency_ms",
                                (time.perf_counter() - t0) * 1e3,
                                tenant=e.req.tenant)
            else:
                metrics.observe("supplier.read.latency_ms",
                                (time.perf_counter() - t0) * 1e3)

    def _serve_batch(self, entries: List[_BatchEntry]) -> None:
        """Worker-side body of submit_batch, on ONE pool thread for
        the whole batch: resolve each request (the resolver may be an
        embedder upcall — pool thread, never a loop), read per the
        backend rung, then finish every entry (CRC, failpoints,
        FetchResult) — completions fire inline on this thread, one
        dispatch per batch."""
        t0 = time.perf_counter()
        try:
            with metrics.span("engine.read_batch", n=len(entries),
                              backend=self.io_backend):
                self._batch_resolve(entries)
                live = [e for e in entries if e.err is None]
                if live:
                    if self.io_backend == "io_uring" \
                            and self._native is not None:
                        self._read_batch_native(live)
                    else:
                        self._read_batch_runs(live)
                self._batch_finish(entries)
        except BaseException as exc:  # defensive: a worker bug must
            # still resolve every future (callers block on them)
            for e in entries:
                if not e.fut.done():
                    e.fut.set_exception(
                        exc if isinstance(exc, StorageError)
                        else StorageError(f"batch serve failed: {exc}"))
        finally:
            for e in entries:
                self._settle_batch_entry(e, t0)
                if not e.fut.done():  # belt and braces: no caller may
                    # wait forever on a future the stages skipped
                    e.fut.set_exception(
                        StorageError("batch entry never served"))

    def _batch_resolve(self, entries: List[_BatchEntry]) -> None:
        for e in entries:
            req = e.req
            try:
                rec = self.resolver.resolve(req.job_id, req.map_id,
                                            req.reduce_id)
                served = rec.part_length
                if req.offset < 0 or req.offset >= max(served, 1):
                    raise StorageError(
                        f"offset {req.offset} outside partition "
                        f"(on-disk {served}) for {req.map_id}/"
                        f"{req.reduce_id}")
                e.rec = rec
                e.want = min(req.chunk_size or self.chunk_size_default,
                             served - req.offset)
                e.file_off = rec.start_offset + req.offset
            except Exception as exc:  # noqa: BLE001 - per-request
                # isolation: a missing MOF fails one future, not the
                # batch (the error lands on the future below)
                e.err = exc

    def _read_batch_runs(self, live: List[_BatchEntry]) -> None:
        """The preadv/pread rungs: group per MOF (one fd pin per file
        across the whole batch), coalesce, read."""
        by_path: Dict[str, List[_BatchEntry]] = {}
        for e in live:
            by_path.setdefault(e.rec.path, []).append(e)
        for path, group in by_path.items():
            if self.store is not None and self.store.manages(path):
                self._read_batch_store(path, group)
                continue
            try:
                fd = self._fds.acquire(path)
            except OSError as exc:
                for e in group:
                    e.err = StorageError(f"cannot open {path}: {exc}")
                continue
            try:
                for e in group:
                    e.fd = fd
                if self.io_backend == "preadv":
                    runs = plan_coalesced(
                        [(e, e.file_off, e.want) for e in group],
                        self.coalesce_gap_bytes, self.max_run_bytes)
                    for run in runs:
                        self._read_run_preadv(fd, run)
                else:  # the pread floor: per-request reads, still one
                    # pool handoff for the batch
                    for e in group:
                        try:
                            data = os.pread(fd, e.want, e.file_off)
                            metrics.add("io.batch.reads",
                                        backend="pread")
                            e.buf = bytearray(data)
                            e.got = len(data)
                        except OSError as exc:
                            e.err = StorageError(
                                f"read failed at {path}:{e.file_off}: "
                                f"{exc}")
            finally:
                self._fds.release(path)

    def _read_batch_store(self, path: str,
                          group: List[_BatchEntry]) -> None:
        """One store-managed path group of a batch: the router's
        vectored read (the blob tier rides the same ``plan_coalesced``
        planner), per-request error isolation preserved — a failed
        range fails ONE future, its batch-mates complete untouched."""
        results = self.store.read_ranges(
            path, [(e.file_off, e.want) for e in group],
            keys=[f"{e.req.map_id}/{e.req.reduce_id}" for e in group])
        for e, res in zip(group, results):
            if isinstance(res, Exception):
                e.err = res
            else:
                e.buf = bytearray(res)
                e.got = len(res)

    def _read_run_preadv(self, fd: int, run: List[tuple]) -> None:
        """One coalesced run -> one vectored read: per-request
        bytearrays (these BECOME FetchResult.data — no scatter copy)
        interleaved with scratch views covering the gaps. A short read
        (truncated MOF) fails only the requests whose ranges the
        kernel didn't fill."""
        entries = [item[0] for item in run]
        run_start = run[0][1]
        run_end = run[-1][1] + run[-1][2]
        gap_total = (run_end - run_start) - sum(e.want for e in entries)
        metrics.add("io.coalesce.runs")
        if gap_total > 0:
            metrics.add("io.coalesce.gap.bytes", gap_total)
        scratch = memoryview(bytearray(gap_total)) if gap_total else None
        iov: list = []
        spans: List[tuple] = []  # (entry, start-in-run, end-in-run)
        pos = run_start
        scratch_used = 0
        for e in entries:
            if e.file_off > pos:
                gap = e.file_off - pos
                iov.append(scratch[scratch_used:scratch_used + gap])
                scratch_used += gap
                pos = e.file_off
            e.buf = bytearray(e.want)
            iov.append(e.buf)
            spans.append((e, pos - run_start, pos - run_start + e.want))
            pos += e.want
        try:
            got, syscalls = _preadv_full(fd, iov, run_start)
        except OSError as exc:
            for e in entries:
                e.err = StorageError(
                    f"vectored read failed at {e.rec.path}:"
                    f"{run_start}: {exc}")
            return
        metrics.add("io.batch.reads", syscalls, backend="preadv")
        for e, lo, hi in spans:
            e.got = max(0, min(got - lo, e.want)) if got > lo else 0

    def _read_batch_native(self, live: List[_BatchEntry]) -> None:
        """The io_uring rung: per-request ranges go straight into the
        native ring (no gap reads — the SQE array IS the batch), fds
        pinned per MOF for the duration."""
        by_path: Dict[str, List[_BatchEntry]] = {}
        for e in live:
            by_path.setdefault(e.rec.path, []).append(e)
        pinned: List[str] = []
        order: List[_BatchEntry] = []
        jobs: List[tuple] = []
        try:
            for path, group in by_path.items():
                if self.store is not None and self.store.manages(path):
                    # store-managed groups keep the router semantics
                    # (failpoints, health, failover) on every backend
                    # rung — the ring never bypasses the store
                    self._read_batch_store(path, group)
                    continue
                try:
                    # released by the pinned-list sweep in THIS
                    # function's finally (list-mediated hand-off the
                    # static rule cannot follow)
                    fd = self._fds.acquire(path)  # udalint: disable=UDA101
                except OSError as exc:
                    for e in group:
                        e.err = StorageError(
                            f"cannot open {path}: {exc}")
                    continue
                pinned.append(path)
                for e in group:
                    e.fd = fd
                    order.append(e)
                    jobs.append((fd, e.file_off, e.want))
            if not jobs:
                return
            results = self._native.read_batch(jobs)
            metrics.add("io.batch.reads", len(jobs), backend="io_uring")
            for e, res in zip(order, results):
                if isinstance(res, Exception):
                    e.err = res
                else:
                    e.buf = res
                    e.got = len(res)
        finally:
            for path in pinned:
                self._fds.release(path)

    def _batch_finish(self, entries: List[_BatchEntry]) -> None:
        """Per-entry completion: short-read check, CRC from the bytes
        as read (before any failpoint can mangle them — wire-damage
        realism, same as _serve_inner), the two injection sites, the
        FetchResult. Each entry's work runs under its own engine.pread
        span adopting ITS request's serve span, so batch-served chunks
        land in the same trace shape as single-served ones."""
        for e in entries:
            req = e.req
            if e.err is None and e.got != e.want:
                e.err = StorageError(
                    f"short read {e.got}/{e.want} at {e.rec.path}:"
                    f"{e.file_off}")
            if e.err is not None:
                e.fut.set_exception(e.err)
                continue
            try:
                with metrics.use_span(e.parent_span), \
                        metrics.span("engine.pread", map=req.map_id,
                                     reduce=req.reduce_id,
                                     offset=req.offset, batched=True):
                    data = e.buf
                    crc = (zlib.crc32(data) & 0xFFFFFFFF
                           if self._crc else None)
                    data = failpoint("data_engine.preadv", data=data,
                                     key=f"{e.fd}@{e.file_off}")
                    data = failpoint("data_engine.pread", data=data,
                                     key=f"{req.map_id}/{req.reduce_id}")
                    served = e.rec.part_length
                    if req.tenant:
                        metrics.add("supplier.bytes", len(data),
                                    tenant=req.tenant)
                    else:
                        metrics.add("supplier.bytes", len(data))
                    e.fut.set_result(FetchResult(
                        data, e.rec.raw_length, e.rec.part_length,
                        req.offset, e.rec.path,
                        last=req.offset + len(data) >= served,
                        crc=crc))
            except Exception as exc:  # noqa: BLE001 - injected faults
                # (and any finish bug) stay per-request: the error is
                # THIS future's result, batch-mates complete untouched
                e.err = exc
                e.fut.set_exception(exc)

    def try_plan(self, req: ShuffleRequest) -> Optional[FdSlice]:
        """The synchronous zero-copy fast path: an FdSlice built INLINE
        from the index cache — the (fd, offset, len) triple for a cache
        hit, no pool handoff, no IO, no upcall. Returns None whenever
        planning would need blocking work (cold index entry, CRC
        stamping on, armed pread failpoint, stopped engine) and the
        caller falls back to :meth:`submit_serve`. Admission semantics
        are submit()'s exactly: an over-budget request raises
        StorageError (typed ERR to the wire), and the slice holds its
        admission charge until release(). This is what lets the
        event-loop server serve a hot chunk entirely on the loop
        thread — read, plan, sendfile — the RDMA-WRITE-from-registered-
        memory critical path with zero thread handoffs."""
        if self._stopped or not self._slice_eligible():
            return None
        resolve_cached = getattr(self.resolver, "resolve_cached", None)
        if resolve_cached is None:
            return None
        rec = resolve_cached(req.job_id, req.map_id, req.reduce_id)
        if rec is None or self._store_managed(rec):
            # store-managed partitions (blob tier / failover twins)
            # need the router's health/failover logic — no zero-copy
            # slice can express a mid-read tier switch
            return None
        want_admit = req.chunk_size or self.chunk_size_default
        self._admit_bytes(want_admit, req.tenant)
        try:
            return self._build_slice(rec, req, want_admit)
        except BaseException:
            # bad offset / fd-open failure (MOF deleted under a cached
            # index entry): the charge MUST unwind or the budget leaks
            # permanently and eventually wedges the supplier
            self._unadmit(want_admit, req.tenant)
            raise

    def _serve_plan(self, req: ShuffleRequest, admitted: int = 0,
                    parent_span=None):
        """Worker-side body of submit_serve: resolve on the pool thread
        (the resolver may be an embedder upcall — never run it on the
        event loop), then either pin an FdSlice or fall through to the
        byte serve. An FdSlice KEEPS its admission charge until
        release(); every other outcome settles here. ``parent_span``
        is the submitting thread's span (see submit): the worker's
        engine.pread span adopts it."""
        t0 = time.perf_counter()
        sliced = False
        try:
            with metrics.use_span(parent_span), \
                    metrics.span("engine.pread", map=req.map_id,
                                 reduce=req.reduce_id, offset=req.offset):
                if self._slice_eligible():
                    plan = self._plan_inner(req, admitted)
                    if plan is not None:
                        sliced = True
                        return plan
                return self._serve_inner(req)
        finally:
            if admitted and not sliced:
                self._unadmit(admitted, req.tenant)
            metrics.gauge_add("supplier.reads.on_air", -1)
            if req.tenant:
                metrics.observe("supplier.read.latency_ms",
                                (time.perf_counter() - t0) * 1e3,
                                tenant=req.tenant)
            else:
                metrics.observe("supplier.read.latency_ms",
                                (time.perf_counter() - t0) * 1e3)

    def _plan_inner(self, req: ShuffleRequest,
                    admitted: int) -> Optional[FdSlice]:
        rec = self.resolver.resolve(req.job_id, req.map_id, req.reduce_id)
        if self._store_managed(rec):
            return None  # the caller falls through to the byte serve
        return self._build_slice(rec, req, admitted)

    def _build_slice(self, rec, req: ShuffleRequest,
                     admitted: int) -> FdSlice:
        """The one slice constructor both plan paths (pool + inline)
        share: offset validation, chunk sizing, fd pin."""
        served = rec.part_length  # the on-disk domain
        if req.offset < 0 or req.offset >= max(served, 1):
            raise StorageError(
                f"offset {req.offset} outside partition (on-disk "
                f"{served}) for {req.map_id}/{req.reduce_id}")
        want = min(req.chunk_size or self.chunk_size_default,
                   served - req.offset)
        fd = self._fds.acquire(rec.path)
        try:
            if req.tenant:
                metrics.add("supplier.bytes", want, tenant=req.tenant)
            else:
                metrics.add("supplier.bytes", want)
            return FdSlice(fd=fd, file_offset=rec.start_offset + req.offset,
                           length=want, raw_length=rec.raw_length,
                           part_length=rec.part_length, offset=req.offset,
                           path=rec.path, last=req.offset + want >= served,
                           _engine=self, _admitted=admitted,
                           _tenant=req.tenant)
        except BaseException:
            # the slice never existed, so its release() never runs: the
            # fd pin must unwind here or the cache entry's refcount rots
            # and the MOF's fd outlives every request (refcount-rot is
            # the RDMAbox-class failure the ledger exists to catch)
            self._fds.release(rec.path)
            raise

    def fetch(self, req: ShuffleRequest) -> FetchResult:
        """Synchronous fetch with a deadline. A wedged read (native pool
        stall, failpoint delay storm, dead disk) must not hang the
        caller forever: the wait is bounded by the fetch retry knobs —
        the per-attempt timeout when set, else the per-segment deadline,
        else a 60 s default — and a timeout surfaces as StorageError
        (the same class a dead disk would raise), so sync callers share
        the async path's failure semantics."""
        fut = self.submit(req)
        try:
            return fut.result(timeout=self.sync_fetch_timeout_s)
        except FutureTimeout as e:
            if fut.cancel():
                # cancelled while still QUEUED: _serve never runs, so
                # its finally-block accounting never fires — undo the
                # admission charge here or timeouts would pin the read
                # budget until submit() rejects an idle engine
                self._unadmit(req.chunk_size or self.chunk_size_default,
                              req.tenant)
                metrics.gauge_add("supplier.reads.on_air", -1)
            # else: the read is running; _serve's finally settles it
            raise StorageError(
                f"synchronous fetch of {req.map_id}/{req.reduce_id} at "
                f"offset {req.offset} did not complete within "
                f"{self.sync_fetch_timeout_s:g} s (bounded by the "
                f"mapred.rdma.fetch.* knobs)") from e

    def _serve(self, req: ShuffleRequest, admitted: int = 0,
               parent_span=None) -> FetchResult:
        t0 = time.perf_counter()
        try:
            with metrics.use_span(parent_span), \
                    metrics.span("engine.pread", map=req.map_id,
                                 reduce=req.reduce_id, offset=req.offset):
                return self._serve_inner(req)
        finally:
            if admitted:
                self._unadmit(admitted, req.tenant)
            metrics.gauge_add("supplier.reads.on_air", -1)
            if req.tenant:
                metrics.observe("supplier.read.latency_ms",
                                (time.perf_counter() - t0) * 1e3,
                                tenant=req.tenant)
            else:
                metrics.observe("supplier.read.latency_ms",
                                (time.perf_counter() - t0) * 1e3)

    def _serve_inner(self, req: ShuffleRequest) -> FetchResult:
        with metrics.timer("supplier_read"):
            rec = self.resolver.resolve(req.job_id, req.map_id, req.reduce_id)
            served = rec.part_length  # the on-disk domain
            if req.offset < 0 or req.offset >= max(served, 1):
                raise StorageError(
                    f"offset {req.offset} outside partition (on-disk "
                    f"{served}) for {req.map_id}/{req.reduce_id}")
            want = min(req.chunk_size or self.chunk_size_default,
                       served - req.offset)
            if self._store_managed(rec):
                # the disaggregated-store router: tier health, the
                # store.get failpoint site and twin failover live
                # there; the bytes come back through the same CRC/
                # failpoint/accounting tail as the fd path below
                data = self.store.read(
                    rec.path, rec.start_offset + req.offset, want,
                    key=f"{req.map_id}/{req.reduce_id}")
            else:
                fd = self._fds.acquire(rec.path)
                try:
                    if self._native is not None:
                        data = self._native.read(
                            fd, rec.start_offset + req.offset, want)
                    else:
                        data = os.pread(fd, want,
                                        rec.start_offset + req.offset)
                finally:
                    self._fds.release(rec.path)
            if len(data) != want:
                raise StorageError(
                    f"short read {len(data)}/{want} at {rec.path}:"
                    f"{rec.start_offset + req.offset}")
            # CRC stamped from the bytes as read, BEFORE the failpoint
            # can mangle them — injected truncation/corruption then looks
            # exactly like wire damage to the validating Segment
            crc = zlib.crc32(data) & 0xFFFFFFFF if self._crc else None
            data = failpoint("data_engine.pread", data=data,
                             key=f"{req.map_id}/{req.reduce_id}")
            if req.tenant:
                metrics.add("supplier.bytes", len(data),
                            tenant=req.tenant)
            else:
                metrics.add("supplier.bytes", len(data))
            return FetchResult(data, rec.raw_length, rec.part_length,
                               req.offset, rec.path,
                               last=req.offset + len(data) >= served,
                               crc=crc)

    def stop(self) -> None:
        self._stopped = True
        self._pool.shutdown(wait=True)
        if self._native is not None:
            self._native.close()
        self._fds.close_all()
        # ResourceLedger drain point (UDA_TPU_RESLEDGER=1): with the
        # pool drained and the fd cache closed, every fd pin handed out
        # by THIS engine's cache (owner scope: a concurrently-live
        # peer engine's pins are untouched — the killed-supplier chaos
        # shape) must have been released; an open one is an FdSlice
        # that never ran release() — the refcount-rot leak class
        resledger.drain("data_engine.stop", pairs=("engine.fd",),
                        owner=id(self._fds))
        # the tenant partition books: with the pool drained, every
        # tenant-stamped admission charge must have settled — an open
        # one is attributed (key=tenant) to the job that leaked it
        resledger.drain("data_engine.stop", pairs=("tenant.admit",),
                        owner=id(self))

    def __enter__(self) -> "DataEngine":
        return self

    def __exit__(self, *exc) -> None:
        self.stop()
