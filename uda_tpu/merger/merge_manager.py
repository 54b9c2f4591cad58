"""Merge manager: fetch scheduling + merge orchestration.

Equivalent of the reference's MergeManager (reference
src/Merger/MergeManager.cc): the fetch phase issues per-map fetch
requests in randomized order with a bounded in-flight window (the
reference shuffles its fetch list to spread load across supplier hosts,
MergeManager.cc:58-63 / UdaUtil.h:99-103, and bounds in-flight fetches
with RDMA credits); the merge phase produces the globally sorted stream
and hands it to the consumer in staging-buffer-sized IFile-framed blocks
(the reference fills 2 x 1 MB DirectByteBuffers and up-calls
``dataFromUda`` per block, MergeManager.cc:155-182, NetlevComm.h:33).

Differences by design (TPU-first):

- no priority queue: whole runs are sorted/merged on device
  (uda_tpu.ops); the "network-levitated" property — merge overlapping
  fetch — survives as: segments crack+pack while later fetches are in
  flight, and device sorts of earlier runs overlap later fetching.
- progress: the reference reports every 20 merged segments
  (``fetchOverMessage``, MergeManager.cc:44, 124-130); we keep the same
  cadence through the ``progress`` callback.

Online mode (everything HBM/host-memory resident) is implemented here;
hybrid LPQ/RPQ spilling lives in uda_tpu.merger.hybrid.
"""

from __future__ import annotations

import functools
import random
import threading
import time
from collections import deque
from typing import Callable, Optional, Sequence

from uda_tpu.coding import parse_scheme
from uda_tpu.merger.emitter import FramedEmitter
from uda_tpu.merger.recovery import RecoveryLedger
from uda_tpu.merger.segment import InputClient, Segment
from uda_tpu.ops import merge as merge_ops
from uda_tpu.utils import compile_cache
from uda_tpu.utils.budget import MemoryBudget, stage_inflight_cap
from uda_tpu.utils.comparators import KeyType, get_key_type
from uda_tpu.utils.config import Config
from uda_tpu.utils.errors import (FallbackSignal, MergeError, StorageError,
                                  UdaError)
from uda_tpu.utils.failpoints import failpoints
from uda_tpu.utils.flightrec import flightrec
from uda_tpu.utils.locks import TrackedLock
from uda_tpu.utils.ifile import RecordBatch
from uda_tpu.utils.logging import get_logger
from uda_tpu.utils.metrics import metrics
from uda_tpu.utils.retry import RetryPolicy, SpeculationPolicy
from uda_tpu.utils.watchdog import StallError, StallWatchdog

__all__ = ["MergeManager", "PenaltyBox", "PROGRESS_INTERVAL"]

log = get_logger()

PROGRESS_INTERVAL = 20  # segments per progress report (MergeManager.cc:44)


class PenaltyBox:
    """Per-supplier fault tracker: a supplier whose fetches keep failing
    is *deprioritized* — its remaining maps rotate to the back of the
    fetch schedule instead of burning the window on a sick host (the
    dynamic counterpart of the reference's randomized fetch list, which
    only spread load statically, MergeManager.cc:58-63). Suppliers leave
    the box when the penalty expires or through forgiveness; boxing is
    never exclusion — when every pending supplier is boxed the scheduler
    proceeds anyway (progress beats politeness).

    Forgiveness DECAYS rather than resets: one success takes one fault
    off the record; only ``reset_successes`` CONSECUTIVE successes (a
    fault restarts the streak) clear it outright — a flapping supplier
    that alternates success and fault can no longer oscillate out of
    the box on every lucky fetch."""

    def __init__(self, threshold: int = 2, penalty_s: float = 1.0,
                 reset_successes: int = 3, tenant: str = ""):
        self.tenant = tenant  # labels fetch.penalties (the task's own)
        self.threshold = max(1, threshold)
        self.penalty_s = penalty_s
        self.reset_successes = max(1, reset_successes)
        self._lock = TrackedLock("penalty_box")
        self._faults: dict[str, int] = {}
        self._until: dict[str, float] = {}
        self._streak: dict[str, int] = {}  # consecutive successes

    def punish(self, key: str) -> bool:
        """Record one fault; returns True when this fault boxed the
        supplier (crossing the threshold, or extending an active box)."""
        with self._lock:
            self._streak.pop(key, None)  # a fault breaks the streak
            n = self._faults.get(key, 0) + 1
            self._faults[key] = n
            if n < self.threshold:
                return False
            self._until[key] = time.monotonic() + self.penalty_s
        tenant = self.tenant
        if tenant:
            metrics.add("fetch.penalties", supplier=key, tenant=tenant)
        else:
            metrics.add("fetch.penalties", supplier=key)
        return True

    def forgive(self, key: str) -> None:
        """One success decays the fault record one step (and unboxes a
        supplier that dropped below the threshold); the record clears
        entirely only after ``reset_successes`` consecutive
        successes."""
        with self._lock:
            n = self._faults.get(key)
            if n is None:
                return
            streak = self._streak.get(key, 0) + 1
            n = max(0, n - 1)
            if streak >= self.reset_successes or n == 0:
                self._faults.pop(key, None)
                self._until.pop(key, None)
                self._streak.pop(key, None)
                return
            self._streak[key] = streak
            self._faults[key] = n
            if n < self.threshold:
                self._until.pop(key, None)

    def faults(self, key: str) -> int:
        with self._lock:
            return self._faults.get(key, 0)

    def rank(self, keys) -> list:
        """``keys`` healthiest-first: unboxed before boxed, fewer
        faults before more, stable otherwise (the caller's preference
        order breaks ties). Read-only — no parole side effects."""
        with self._lock:
            now = time.monotonic()

            def score(k):
                t = self._until.get(k)
                return (1 if (t is not None and t > now) else 0,
                        self._faults.get(k, 0))

            return sorted(keys, key=score)

    def penalized(self, key: str) -> bool:
        with self._lock:
            t = self._until.get(key)
            if t is None:
                return False
            if time.monotonic() >= t:
                # parole: out of the box, but one more fault re-boxes
                del self._until[key]
                self._faults[key] = self.threshold - 1
                return False
            return True

    @property
    def boxed(self) -> list[str]:
        with self._lock:
            now = time.monotonic()
            return [k for k, t in self._until.items() if t > now]

    def snapshot(self) -> dict:
        """Introspection view (the MSG_STATS scrape surface and the
        final stats record's recovery block): fault counts, success
        streaks and who is boxed right now."""
        with self._lock:
            now = time.monotonic()
            return {"faults": dict(self._faults),
                    "streaks": dict(self._streak),
                    "boxed": [k for k, t in self._until.items()
                              if t > now]}

    def restore(self, snap: dict) -> None:
        """Re-seed fault/streak records from a checkpoint manifest
        (resume path). Active box TIMERS are deliberately NOT restored —
        ``_until`` holds monotonic deadlines that do not survive process
        death; a supplier at/over the threshold re-boxes on its next
        fault anyway (the parole posture in :meth:`penalized`)."""
        with self._lock:
            for k, v in (snap.get("faults") or {}).items():
                self._faults[str(k)] = int(v)
            for k, v in (snap.get("streaks") or {}).items():
                self._streak[str(k)] = int(v)


class MergeManager:
    """Orchestrates fetch -> pack -> device merge -> framed emission for
    one reduce task."""

    def __init__(self, client: InputClient, key_type: KeyType | str,
                 config: Optional[Config] = None,
                 progress: Optional[Callable[[int, int], None]] = None,
                 seed: int = 0):
        compile_cache.enable()
        self.cfg = config or Config()
        self.client = client
        self.key_type = (get_key_type(key_type) if isinstance(key_type, str)
                         else key_type)
        self.key_width = self.cfg.get("uda.tpu.key.width")
        self.chunk_size = self.cfg.get("mapred.rdma.buf.size") * 1024
        self.window = max(1, self.cfg.get("mapred.rdma.wqe.per.conn"))
        self.progress = progress
        self.seed = seed
        self.emitter = FramedEmitter(self.chunk_size)
        self.retry_policy = RetryPolicy.from_config(self.cfg)
        # the task's tenant identity labels its hot-path fetch counters
        # (fetch.bytes{tenant=}); task-local, never process-global:
        # reduce tasks of several tenants may share this process
        self.tenant = str(self.cfg.get("uda.tpu.tenant.id") or "")
        self.penalty_box = PenaltyBox(
            threshold=self.cfg.get("uda.tpu.fetch.penalty.threshold"),
            penalty_s=self.cfg.get("uda.tpu.fetch.penalty.ms") / 1e3,
            tenant=self.tenant)
        # the survivable-shuffle layer (ISSUE 8): speculation, resume
        # and k-of-n reconstruction all share ONE recovery ledger
        self.ledger = RecoveryLedger(self.penalty_box)
        self.speculation = SpeculationPolicy.from_config(self.cfg)
        self.resume_fetch = bool(self.cfg.get("uda.tpu.fetch.resume"))
        self.coding_scheme = parse_scheme(
            self.cfg.get("uda.tpu.coding.scheme"))
        spec = self.cfg.get("uda.tpu.failpoints")
        if spec:
            failpoints.arm_spec(spec)
        if self.cfg.get("uda.tpu.stats.enable"):
            metrics.enable_stats()
        # the black box rides every task (utils/flightrec.py): config
        # knobs AND the env kill switch must both say on
        from uda_tpu.utils.flightrec import flightrec_enabled_from_env
        flightrec.configure(
            enabled=(bool(self.cfg.get("uda.tpu.flightrec.enable"))
                     and flightrec_enabled_from_env()),
            capacity=int(self.cfg.get("uda.tpu.flightrec.events")),
            dump_dir=str(self.cfg.get("uda.tpu.flightrec.dir")))
        # the time-accounting plane (utils/profiler + utils/critpath):
        # arm the sampling profiler when asked (config wins, env
        # otherwise; arming is sticky — a later manager with the 0
        # default never disarms a profiler the operator turned on) and
        # expose the where-time-goes block over MSG_STATS
        from uda_tpu.utils.critpath import install_stats_provider
        from uda_tpu.utils.profiler import profile_hz_from_env, profiler
        install_stats_provider()
        prof_hz = (float(self.cfg.get("uda.tpu.profile.hz"))
                   or profile_hz_from_env())
        if prof_hz > 0:
            profiler.start(prof_hz)
        self._stop = threading.Event()
        # admission control + liveness (uda_tpu.utils.budget/.watchdog):
        # the budget is built lazily (platform detection must not run
        # for explicitly-configured approaches), the watchdog per run()
        self._budget_obj: Optional[MemoryBudget] = None
        self.last_admission = None     # routing decision (tests/diag)
        self._live_segments: list[Optional[Segment]] = []
        self._active_overlap = None
        # crash-consistent checkpointing (merger/checkpoint.py): live
        # only while a run() with uda.tpu.ckpt.dir set is in flight
        self._ckpt = None
        self._watchdog: Optional[StallWatchdog] = None
        self._stall_error: Optional[StallError] = None
        self._emit_progress = 0
        self._admit_ticks = 0  # polls of a wait for the chip's HBM ledger
        # push plane (ISSUE 19): reduce-side staging, armed by
        # arm_push() — ideally by the embedder the moment the reduce
        # task is SCHEDULED (pushes then overlap the entire map phase);
        # fetch_all arms it lazily otherwise
        self._push_staging = None

    def arm_push(self, job_id: str, reduce_id: int, hosts=None):
        """Arm reduce-side push staging for this task and subscribe the
        supplier fleet (``uda.tpu.push.enable``). Idempotent; returns
        the staging or None when the plane stays pull-only: flag off,
        a transport without a push plane (LocalFetchClient, custom
        connects), or a byte-domain-transforming wrapper
        (DecompressingClient — pushed bytes are the on-disk compressed
        stream, the Segment ledger's domain is the decompressed one).

        Call it BEFORE the map phase finishes to win overlap: pushes
        land while maps are still running, and the fetch wave then
        starts from the staged offsets instead of zero."""
        if self._push_staging is not None:
            return self._push_staging
        if not bool(self.cfg.get("uda.tpu.push.enable")):
            return None
        if getattr(self.client, "inner", None) is not None:
            return None
        reg = getattr(self.client, "push_register", None)
        if not callable(reg):
            return None
        from uda_tpu.net.push import PushStaging

        staging = PushStaging(job_id, int(reduce_id), cfg=self.cfg,
                              budget=self.budget())
        reg(job_id, int(reduce_id), staging, hosts=hosts)
        self._push_staging = staging
        return staging

    def _release_push(self) -> None:
        """Unsubscribe and discard unclaimed staged bytes (idempotent;
        run()'s finally). Late pushes after this draw
        PUSH_NACK(UNKNOWN) and the supplier goes pull-only — no frame
        is ever left unanswered."""
        staging, self._push_staging = self._push_staging, None
        if staging is None:
            return
        unreg = getattr(self.client, "push_unregister", None)
        if callable(unreg):
            unreg(staging.job_id, staging.reduce_id)
        staging.close()

    def _push_adopt(self, seg: Segment) -> None:
        """Right before a segment starts: claim its map in staging and
        arm the staged prefix as a resumed fetch (Segment.ckpt_preload
        — the PUSHED bytes land in the offset ledger exactly like a
        checkpoint's, so retry/speculation/reconstruction compose
        unchanged). The claim stands even when nothing usable is
        staged: from here the fetch is in flight, and later pushes for
        this map are refused CLAIMED (dedup)."""
        staging = self._push_staging
        if staging is None:
            return
        kw = staging.take(seg.map_id)
        if kw is None:
            return
        if seg._next_offset or seg.batches:
            return  # a checkpoint ledger is further along; keep it
        try:
            seg.ckpt_preload(**kw)
        except UdaError as e:
            metrics.add("push.invalidated")
            log.warn(f"pushed prefix of map {seg.map_id} rejected, "
                     f"fetching from zero: {e}")
            return
        metrics.add("push.adopted")
        metrics.add("push.adopted.bytes", int(kw["next_offset"]))

    def budget(self) -> MemoryBudget:
        if self._budget_obj is None:
            self._budget_obj = MemoryBudget.from_config(self.cfg)
        return self._budget_obj

    # -- elastic membership (ISSUE 18) --------------------------------------

    def notify_join(self, host: str) -> int:
        """A supplier joined mid-job: widen every in-flight segment's
        candidate list so the joiner becomes eligible at the next
        ledger-ranked decision point (retry re-pick, speculation
        alternate, reconstruction anchor), and fold the host into the
        routing client's membership ring (so its transport re-dials and
        observes the joiner's CAP_ELASTIC banner). Returns the number
        of segments widened. Already-completed segments and segments
        that already know the host are untouched — join is advisory,
        never a re-route of live attempts."""
        notify = getattr(self.client, "notify_join", None)
        if callable(notify):
            notify(host)
        else:
            metrics.add("elastic.joins", supplier=host)
        widened = 0
        for seg in list(self._live_segments):
            if seg is not None and seg.add_host(host):
                widened += 1
        self.ledger.record("join", supplier=host)
        flightrec.record("elastic.join", supplier=host,
                         widened=widened)
        log.info(f"elastic: supplier {host!r} joined mid-job; "
                 f"{widened} in-flight segment(s) widened")
        return widened

    def notify_drain(self, host: str) -> None:
        """The symmetric departure: demote the host in routing (no new
        placements; in-flight fetches against it complete normally —
        its MOFs migrate to the blob tier via StoreManager.drain, so
        fetch-after-departure resolves there, migrated not
        reconstructed)."""
        notify = getattr(self.client, "notify_drain", None)
        if callable(notify):
            notify(host)
        self.ledger.record("drain", supplier=host)
        flightrec.record("elastic.drain", supplier=host)

    # -- fetch phase --------------------------------------------------------

    def fetch_all(self, job_id: str, map_ids: Sequence,
                  reduce_id: int,
                  on_segment: Optional[Callable[[int, Segment], None]] = None,
                  skip=None, preload: Optional[dict] = None
                  ) -> list:
        """Fetch every map's partition, randomized order, sliding window.

        Resume hooks (merger/checkpoint.py): ``skip`` holds indexes
        whose run files a previous attempt already spooled — no segment
        is built (the returned list holds None there) and no byte is
        refetched; ``preload`` maps index -> a checkpointed offset
        ledger, applied via Segment.ckpt_preload before start() so the
        fetch resumes mid-stream (an invalid ledger degrades to a fresh
        fetch from zero, never an error).

        The window refills as individual segments complete (true
        credit-flow semantics: in-flight count stays at ``window`` until
        the tail, rather than draining at batch boundaries). Returns
        segments in the *original* map order (merge stability and
        reproducibility do not depend on fetch completion order).

        ``on_segment(index, segment)`` fires on each successful segment
        completion, from the transport's completion thread — the hook
        the overlapped merge uses to stage runs while later fetches are
        still in flight.

        Fault feedback: every transport fault reports the segment's
        supplier to the penalty box; maps of a boxed supplier rotate to
        the back of the pending schedule (see :class:`PenaltyBox`).
        """
        # entries are "map_id", ("host", "map_id"), or
        # (["host", ...], "map_id") — hosts route through a per-host
        # transport (HostRoutingClient); a host LIST means replicas
        # (every listed supplier holds the map output) and must lead
        # with the map WRITER's host (the stripe placement anchor):
        # fetching opens against the best PenaltyBox-ranked replica and
        # speculation duplicates to the alternates
        def _norm(m):
            if isinstance(m, tuple):
                host, mid = m
                hosts = (list(host) if isinstance(host, (list, tuple))
                         else [host])
            else:
                hosts, mid = [""], m
            return hosts or [""], mid

        entries = [_norm(m) for m in map_ids]
        # push plane: arm lazily if the embedder did not (no overlap
        # won at this point — the map phase may already be over — but
        # pushes still beat pulls for any map that commits during this
        # fetch wave)
        self.arm_push(job_id, reduce_id,
                      hosts={h for hosts, _ in entries for h in hosts
                             if h})
        stripe_ctx = None
        if self.coding_scheme is not None:
            from uda_tpu.coding.recovery import StripeContext

            # the placement domain: the job's canonically-ordered
            # supplier universe (sorted unique hosts — writers derive
            # the identical order; see uda_tpu.coding). Host-less local
            # entries ("") are NOT suppliers: mixed in with real hosts
            # they would shift the ring against the writer's
            # supplier_roots; the all-local degenerate keeps [""]
            universe = sorted({h for hosts, _ in entries
                               for h in hosts if h}) or [""]
            from uda_tpu.coding import parse_domains

            stripe_ctx = StripeContext(
                self.coding_scheme, universe, ledger=self.ledger,
                domains=parse_domains(
                    str(self.cfg.get("uda.tpu.coding.domains"))))
        skip = frozenset(skip or ())
        segs = [None if i in skip else
                Segment(self.client, job_id, mid, reduce_id,
                        self.chunk_size, host=hosts[0],
                        policy=self.retry_policy, hosts=hosts,
                        ledger=self.ledger,
                        speculation=self.speculation,
                        resume=self.resume_fetch, stripe=stripe_ctx,
                        tenant=self.tenant)
                for i, (hosts, mid) in enumerate(entries)]
        for i, kw in (preload or {}).items():
            if segs[i] is None:
                continue
            try:
                segs[i].ckpt_preload(**kw)
            except UdaError as e:
                # a ledger that fails revalidation degrades to a fresh
                # fetch from zero — resume is an optimization, never a
                # correctness dependency
                metrics.add("ckpt.invalidated", cause="ledger")
                log.warn(f"checkpointed ledger of map "
                         f"{segs[i].map_id} rejected, refetching: {e}")
        index_of = {id(s): i for i, s in enumerate(segs) if s is not None}
        order = [i for i in range(len(segs)) if i not in skip]
        random.Random(self.seed).shuffle(order)  # MergeManager.cc:58-63
        nskip = len(segs) - len(order)
        live_total = len(order)
        credits = threading.Semaphore(self.window)
        done_lock = TrackedLock("merge.fetch_done")
        done = 0
        all_notified = threading.Event()  # ALL on_done callbacks returned
        cb_errors: list[Exception] = []
        box = self.penalty_box

        def supplier_of(seg) -> str:
            # single-host transports (host == "") degrade to per-map
            return seg.supplier

        def on_fault(seg, exc) -> None:
            # the STRUCTURED cause wins over the segment's current
            # source: a speculation loser's fault must punish the host
            # whose attempt failed, not whichever source the segment
            # switched to (UDA005: attribute, never reason-string)
            sup = getattr(exc, "supplier", None) or supplier_of(seg)
            self.ledger.record("fault", supplier=sup, map_id=seg.map_id,
                               error=exc)
            if box.punish(sup):
                log.warn(f"supplier {sup!r} penalized "
                         f"after repeated fetch faults ({exc})")

        def on_done(seg) -> None:
            nonlocal done
            if seg.ready:
                box.forgive(supplier_of(seg))
            credits.release()
            try:
                if on_segment is not None and seg.ready:
                    on_segment(index_of[id(seg)], seg)
            except Exception as e:  # surfaced after the waits below
                cb_errors.append(e)
            finally:
                with done_lock:
                    done += 1
                    d = done
                if d == live_total:
                    all_notified.set()
            if self.progress and (d + nskip) % PROGRESS_INTERVAL == 0:
                self.progress(d + nskip, len(segs))

        started: list[Segment] = []

        def drained() -> bool:
            with done_lock:
                return done >= len(started)

        def stop_drain() -> None:
            """The stop path must not abandon in-flight segments: abort
            the overlapped merger first (a completion thread blocked in
            its bounded feed() would otherwise never deliver on_done),
            administratively fail every started segment (idempotent —
            already-finished ones keep their outcome), then wait for the
            on_done callbacks so credits/progress are fully accounted
            before the caller sees the error."""
            om = (self._active_overlap if on_segment is not None
                  else None)
            if om is not None:
                om.abort()
            error = self._stall_error or MergeError(
                "merge manager stopped during fetch")
            for s in started:
                s.fail(error)
            deadline = time.monotonic() + 10.0
            while not drained() and time.monotonic() < deadline:
                time.sleep(0.01)
            if not drained():
                log.warn("stop drain: some fetch completions did not "
                         "deliver within 10 s; proceeding")

        self._live_segments = segs
        with metrics.timer("fetch"):
            pending = deque(order)
            while pending:
                # stop-responsive credit wait: stop() (watchdog rescue,
                # reduce_exit) must break a fetch loop that is blocked
                # on credits held by wedged segments
                while not credits.acquire(timeout=0.25):
                    if self._stop.is_set():
                        break
                if self._stop.is_set():
                    stop_drain()
                    raise (self._stall_error
                           or MergeError("merge manager stopped during "
                                         "fetch"))
                i = self._next_fetch_index(pending, segs, supplier_of)
                segs[i].on_done = on_done
                segs[i].on_fault = on_fault
                started.append(segs[i])
                # adopt the staged push prefix AT START TIME, not at
                # construction: maps that committed while earlier
                # segments held the window get their pushed bytes in
                self._push_adopt(segs[i])
                segs[i].start()
            for s in segs:
                if s is not None:
                    s.wait()
            # a segment's _done fires BEFORE its on_done callback runs:
            # wait for the callbacks too, or a caller could finalize its
            # on_segment consumer (e.g. the overlapped merger) while the
            # last completion is still being delivered. Stop-aware: a
            # completion thread can be wedged INSIDE an on_segment
            # consumer (e.g. blocked in the overlapped merger's bounded
            # feed) — a watchdog/stop() must be able to break this wait
            # too, not only the credit wait above
            if live_total:
                while not all_notified.wait(timeout=0.25):
                    if self._stop.is_set():
                        stop_drain()
                        raise (self._stall_error
                               or MergeError("merge manager stopped "
                                             "during fetch"))
        if cb_errors:
            raise cb_errors[0]
        if self.progress:
            self.progress(len(segs), len(segs))
        return segs

    def _next_fetch_index(self, pending: deque, segs, supplier_of) -> int:
        """Penalty-box-aware pick: the first pending segment whose
        supplier is not boxed; boxed ones rotate to the back. When every
        pending supplier is boxed, take the head anyway — the box
        deprioritizes, it never starves."""
        for _ in range(len(pending) - 1):
            if not self.penalty_box.penalized(supplier_of(segs[pending[0]])):
                break
            pending.rotate(-1)
            metrics.add("fetch.deprioritized")
        return pending.popleft()

    # -- merge phase --------------------------------------------------------

    def merge_segments(self, segments: Sequence[Segment]) -> RecordBatch:
        """Device-merge all fetched segments into one sorted batch (the
        hybrid route's LPQ merge): one device sort of the
        concatenation."""
        batches = [s.record_batch() for s in segments]
        metrics.add("merge.records", sum(b.num_records for b in batches))
        with metrics.timer("merge"):
            return merge_ops.merge_batches(batches, self.key_type,
                                           self.key_width)

    def run(self, job_id: str, map_ids: Sequence, reduce_id: int,
            consumer: Callable[[memoryview], None]) -> int:
        """The full online merge: fetch overlapped with device merge ->
        emit (reference merge_online, MergeManager.cc:184-193; the
        overlap restores the network-levitated property — see
        uda_tpu.merger.overlap).

        Failure contract: a terminal engine error (retries exhausted,
        merge invariant violation, spill failure — any ``UdaError``)
        is re-raised as :class:`FallbackSignal` carrying the root cause,
        so the consumer falls back to its vanilla path instead of
        crashing on an internal type (the reference's ``failureInUda``
        flip, UdaBridge.cc:506-530). Non-UdaError exceptions (embedder
        bugs, injected foreign errors) propagate unwrapped.

        Liveness contract (``uda.tpu.watchdog.stall.s`` > 0): a stall
        watchdog samples the task's progress counters; when nothing
        advances for the deadline it dumps every thread stack + the span
        tree and (``uda.tpu.watchdog.fallback``, default on) fails the
        in-flight segments so this call terminates with a
        ``FallbackSignal(StallError)`` instead of hanging forever."""
        # task-local emit progress (the watchdog token must not read
        # process-global counters — another task's emission would mask
        # this one's wedge); counted AFTER delivery so a consumer that
        # never returns reads as a stall
        self._emit_progress = 0

        def tracked_consumer(block: memoryview) -> None:
            consumer(block)
            self._emit_progress += len(block)

        wd = self._start_watchdog(reduce_id)
        # the MSG_STATS / final-stats-record scrape surface for THIS
        # task: penalty box, recovery ledger and the last admission
        # decision, live for the run's duration
        from uda_tpu.utils.stats import (register_stats_provider,
                                         unregister_stats_provider)

        def _recovery_provider() -> dict:
            adm = self.last_admission
            return {"penalty_box": self.penalty_box.snapshot(),
                    "ledger": self.ledger.snapshot(),
                    "admission": ({"decision": adm.decision,
                                   "cause": adm.cause,
                                   "reason": adm.reason}
                                  if adm is not None else None)}

        provider_name = f"recovery.r{reduce_id}"
        register_stats_provider(provider_name, _recovery_provider)
        try:
            # the trace root: every phase timer and per-segment fetch
            # span below hangs off this reduce-task span
            with metrics.span("reduce_task", job=job_id, reduce=reduce_id,
                              maps=len(map_ids)):
                return self._run(job_id, map_ids, reduce_id,
                                 tracked_consumer)
        except FallbackSignal as e:
            # a lower layer already chose fallback: the black box still
            # owes the post-mortem (run() is the one dump point, so a
            # task failure produces exactly ONE dump)
            flightrec.dump("fallback", extra={
                "job": job_id, "reduce": reduce_id,
                "error": type(e.cause).__name__})
            raise
        except UdaError as e:
            # a watchdog rescue surfaces through whichever waiter woke
            # first (a failed segment's wait, the stopped fetch loop);
            # report the STALL as the root cause, not the wake artifact
            stall = self._stall_error
            if stall is not None and not isinstance(e, StallError):
                e = stall
            metrics.add("fallback.signals")
            log.error(f"merge failed terminally, requesting fallback: {e}")
            # the flight-recorder post-mortem: the event stream behind
            # this fallback (injected faults, segment transitions,
            # recovery events) plus the terminal cause, dumped before
            # the signal leaves the engine
            flightrec.dump("fallback", extra={
                "job": job_id, "reduce": reduce_id,
                "error": type(e).__name__,
                "supplier": getattr(e, "supplier", None)})
            raise FallbackSignal(e) from e
        finally:
            unregister_stats_provider(provider_name, _recovery_provider)
            self._release_push()
            if wd is not None:
                wd.stop()
                self._watchdog = None

    def _revalidate_spilled(self, job_id: str) -> None:
        """Resume-side locator revalidation: reachable only when the
        transport is in-process (a LocalFetchClient — possibly behind a
        DecompressingClient — over an engine with an attached
        StoreManager); remote suppliers run the same check on their own
        resume path. Raises the store's typed error on damage."""
        client = self.client
        inner = getattr(client, "inner", None)
        if inner is not None:
            client = inner
        engine = getattr(client, "engine", None)
        store_mgr = getattr(engine, "store", None)
        if store_mgr is None:
            return
        n = store_mgr.validate_spilled(job_id)
        if n:
            log.info(f"ckpt: revalidated {n} spilled blob object(s) of "
                     f"job {job_id} before resume")

    # -- liveness -----------------------------------------------------------

    def _progress_token(self) -> tuple:
        """THIS task's progress signature, sampled by the watchdog.
        Deliberately task-local — built from this manager's own
        segments, overlapped merger and emit counter, never the
        process-global metrics hub: a co-located task's counters
        advancing must not mask this one's wedge. Any component
        changing (bytes fetched, retries consumed, segments finishing,
        runs staged/merged/pending, bytes delivered) counts as alive."""
        segs = self._live_segments
        ndone = nrec = noff = nret = 0
        for s in segs:
            if s is None:  # checkpoint-adopted slot: nothing to sample
                continue
            nrec += s.num_records
            noff += s._next_offset
            nret += s._retries_left
            if s._done.is_set():
                ndone += 1
        om = self._active_overlap
        om_sig = ((om.stats["staged_runs"], om.stats["device_merges"],
                   om.stats["pending"]) if om is not None else ())
        # the ledger version makes RECOVERY progress visible: a
        # reconstruction fetching stripe shards advances nothing on the
        # segment itself, but it is progress, not a stall. Same for the
        # checkpoint version: a long fsync/snapshot quiesces the
        # counters above, yet each completed save IS progress — without
        # it the watchdog would administratively fail a task for being
        # durable (the ISSUE 16 watchdog fix)
        ckpt = self._ckpt
        return (len(segs), ndone, nrec, noff, nret, om_sig,
                self.ledger.version, getattr(self, "_emit_progress", 0),
                self._admit_ticks, ckpt.version if ckpt is not None else 0)

    def _start_watchdog(self, reduce_id: int) -> Optional[StallWatchdog]:
        stall_s = float(self.cfg.get("uda.tpu.watchdog.stall.s"))
        if stall_s <= 0:
            return None
        on_stall = (self._on_stall
                    if self.cfg.get("uda.tpu.watchdog.fallback") else None)
        wd = StallWatchdog(stall_s, self._progress_token,
                           on_stall=on_stall,
                           name=f"uda-watchdog-r{reduce_id}")
        self._watchdog = wd
        return wd.start()

    def _on_stall(self, err: StallError) -> None:
        """Watchdog rescue (runs on the watchdog thread): record the
        stall, stop the manager (breaks the fetch loop's credit and
        all-notified waits), abort the overlapped merger (unblocks
        completion threads wedged in its bounded feed / stager loops),
        and administratively fail every live segment so blocked waiters
        wake — the failure then flows through the normal FallbackSignal
        contract. A wedge inside the embedder's consumer callback itself
        cannot be interrupted from here; it still gets the diagnostic
        dump."""
        self._stall_error = err
        self._stop.set()
        try:
            self.client.stop()
        except Exception as e:  # noqa: BLE001 - rescue must not die here
            log.warn(f"watchdog: client stop failed: {e}")
        om = self._active_overlap
        if om is not None:
            try:
                om.abort()
            except Exception as e:  # noqa: BLE001
                log.warn(f"watchdog: overlap abort failed: {e}")
        for seg in list(self._live_segments):
            if seg is None:
                continue
            try:
                seg.fail(err)
            except Exception as e:  # noqa: BLE001
                log.warn(f"watchdog: failing segment "
                         f"{seg.map_id} raised: {e}")

    # -- crash-consistent checkpointing (merger/checkpoint.py) ---------------

    def _ckpt_state(self, job_id: str, reduce_id: int, mids: list,
                    store) -> tuple:
        """The snapshot collector handed to TaskCheckpoint: one
        crash-consistent view of everything the task would lose to a
        kill — spooled run files (already durable; recorded with
        length+CRC so a torn one is detected), in-flight fetch offset
        ledgers (Segment.ckpt_export), the recovery journal, penalty-box
        state and the merge-forest watermark. Returns
        ``(payload, parts)`` per the TaskCheckpoint.save contract."""
        from uda_tpu.merger import checkpoint

        runs: dict = {}
        for i, (n, nbytes, crc) in store.manifest().items():
            runs[str(i)] = {"map": mids[i], "records": int(n),
                            "bytes": int(nbytes),
                            "length": int(nbytes) + checkpoint.RUN_EOF_LEN,
                            "crc": int(crc)}
        ledgers: dict = {}
        parts: dict = {}
        for i, seg in enumerate(self._live_segments):
            if seg is None or str(i) in runs:
                continue
            ex = seg.ckpt_export()
            if ex is None:
                continue
            parts[i] = ex.pop("data")
            host = seg.supplier
            ex.update(map=seg.map_id, host=host,
                      generation=self.client.generation(host))
            ledgers[str(i)] = ex
        om = self._active_overlap
        payload = {"job": job_id, "reduce": int(reduce_id),
                   "maps": list(mids), "runs": runs, "ledgers": ledgers,
                   "journal": self.ledger.snapshot()["events"],
                   "penalty": self.penalty_box.snapshot(),
                   "forest": dict(om.stats) if om is not None else {}}
        return payload, parts

    def _resume_from_manifest(self, man: dict, mids: list, store, om,
                              ckpt) -> tuple:
        """Revalidate a loaded manifest and adopt what survives the
        ladder (generation -> epoch [at load] -> length+CRC ->
        drop-and-refetch). Returns ``(adopted, preload,
        adopted_records)``: indexes whose run files re-join the merge
        forest without refetching, and per-index ckpt_preload kwargs
        for mid-fetch offset-ledger resume. Anything that fails a check
        degrades to a fresh fetch of that segment — never an error."""
        from uda_tpu.merger import checkpoint

        if list(man.get("maps") or []) != list(mids):
            # a different map list is a different shuffle: nothing in
            # this manifest is addressable by index
            metrics.add("ckpt.invalidated", cause="maps")
            log.warn(f"checkpoint manifest for {ckpt.task} lists a "
                     f"different map set; starting fresh")
            return set(), {}, 0
        adopted: set = set()
        preload: dict = {}
        adopted_records = 0
        for key, rec in (man.get("runs") or {}).items():
            try:
                i = int(key)
                if not (0 <= i < len(mids)) or rec.get("map") != mids[i]:
                    raise StorageError(f"run index {key} does not map")
                run_path, off_path = store._paths(i)
                batch = checkpoint.read_run(run_path, off_path, rec)
            except (OSError, UdaError, ValueError, KeyError) as e:
                metrics.add("ckpt.invalidated", cause="crc")
                log.warn(f"checkpointed run {key} failed revalidation, "
                         f"refetching: {e}")
                try:
                    store.discard(int(key))
                except (ValueError, OSError):
                    pass  # udalint: disable=UDA006 - cleanup best effort
                continue
            store.adopt(i, int(rec["records"]), int(rec["bytes"]),
                        int(rec["crc"]))
            om.adopt_run(i, batch)
            adopted.add(i)
            adopted_records += batch.num_records
        for key, rec in (man.get("ledgers") or {}).items():
            try:
                i = int(key)
            except ValueError:
                continue
            if i in adopted or not (0 <= i < len(mids)) \
                    or rec.get("map") != mids[i]:
                continue
            host = str(rec.get("host") or "")
            gen_then = rec.get("generation")
            gen_now = self.client.generation(host)
            if (gen_then is not None and gen_now is not None
                    and int(gen_then) != int(gen_now)) \
                    or not self.client.resume_ok(host):
                # cold supplier restart: its map output was rebuilt, so
                # mid-stream offsets no longer address the same bytes
                metrics.add("ckpt.invalidated", cause="generation")
                log.warn(f"supplier {host!r} restarted since the "
                         f"checkpoint; refetching map {rec.get('map')} "
                         f"from zero")
                continue
            try:
                data = ckpt.part_bytes(rec)
            except StorageError as e:
                metrics.add("ckpt.invalidated", cause="ledger")
                log.warn(f"checkpointed ledger part of map "
                         f"{rec.get('map')} rejected, refetching: {e}")
                continue
            preload[i] = {"data": data,
                          "carry_len": int(rec.get("carry_len", 0)),
                          "next_offset": int(rec.get("next_offset", 0)),
                          "raw_length": rec.get("raw_length"),
                          "num_records": int(rec.get("num_records", 0))}
        self.ledger.restore(man.get("journal") or [])
        self.penalty_box.restore(man.get("penalty") or {})
        metrics.add("ckpt.resumed")
        metrics.add("ckpt.runs.adopted", len(adopted))
        log.info(f"resuming {ckpt.task} from checkpoint seq "
                 f"{man.get('seq')}: {len(adopted)} run(s) adopted, "
                 f"{len(preload)} in-flight ledger(s), "
                 f"{len(mids) - len(adopted)} map(s) to fetch")
        flightrec.record("ckpt.resume", task=ckpt.task,
                         seq=man.get("seq"), adopted=len(adopted),
                         ledgers=len(preload))
        return adopted, preload, adopted_records

    def _run(self, job_id: str, map_ids: Sequence, reduce_id: int,
             consumer: Callable[[memoryview], None]) -> int:
        approach = self.cfg.get("mapred.netmerger.merge.approach")
        streaming = bool(self.cfg.get("uda.tpu.online.streaming"))
        self.last_admission = None  # per-run routing record
        if approach == 0:
            # Auto policy (beyond the reference, which made the user
            # pick via mapred.netmerger.merge.approach), now budget-
            # aware (uda_tpu.utils.budget): the transport's size
            # estimate routes through MemoryBudget.route —
            #   in budget + small -> hybrid LPQ/RPQ;
            #   in budget + large -> streaming online (O(window) host
            #     memory); crossover not measured on the chip (ROADMAP
            #     D2c);
            #   over the HBM budget -> streaming, merged on the device
            #     in budget-sized groups (never an OOM); over the host
            #     budget -> streaming;
            #   over the hard ceiling (uda.tpu.budget.hard.mb) ->
            #     FallbackSignal BEFORE any fetch or allocation;
            #   unknown size -> streaming: bounded memory is the only
            #     safe default for an unbounded input.
            est = self.client.estimate_partition_bytes(
                job_id, map_ids, reduce_id)
            threshold = (self.cfg.get("uda.tpu.auto.approach.threshold.mb")
                         * (1 << 20))
            # checkpointing needs the run-spool (streaming) path: the
            # sorted run files ARE the durable half of the snapshot, and
            # hybrid's LPQ/RPQ state has no resume story — so an armed
            # ckpt dir steers the auto policy away from hybrid
            adm = self.budget().route(
                est, threshold,
                prefer_streaming=bool(str(self.cfg.get("uda.tpu.ckpt.dir"))),
                segments=len(map_ids))
            self.last_admission = adm
            # admission decisions carry their STRUCTURED cause into the
            # black box — a post-mortem reads why the task took the
            # path it did, not just that it failed on it
            flightrec.record("admission", decision=adm.decision,
                             cause=adm.cause, rejected=adm.rejected,
                             estimate=est)
            if adm.rejected:
                raise UdaError(
                    f"partition refused by admission control: "
                    f"{adm.reason} — falling back to the vanilla path "
                    f"(raise uda.tpu.budget.hard.mb to admit)")
            if adm.decision == "hybrid":
                approach = 2
            else:
                approach, streaming = 1, True
            log.info(f"auto merge approach: estimate="
                     f"{'unknown' if est is None else est} bytes -> "
                     f"{'hybrid' if approach == 2 else 'streaming online'}"
                     f" ({adm.reason})")
        if approach == 2:
            from uda_tpu.merger.hybrid import run_hybrid
            return run_hybrid(self, job_id, map_ids, reduce_id, consumer)
        # the overlapped route builds the device row forest. The chip
        # is shared with every other live reduce task of this process
        # (a node's reduce slots): reserve this task's device need in
        # the chip-wide ledger BEFORE anything is staged — waiting here
        # while the live tasks leave no room, like the reference's
        # occupy_chunk. At every merge approach: the auto policy's
        # route() sized the task against the whole chip, the ledger
        # sizes it against what the live tasks leave
        adm = self.last_admission
        # the in-flight staging cap clamps to a budget only when the
        # auto policy built one (stage_inflight_cap); the ledger's
        # budget below must not move it
        cap_budget = self._budget_obj
        if adm is not None:
            est = adm.estimate_bytes
        else:
            # a transport that cannot say (or a duck-typed one that has
            # no such method) leaves the size unknown
            probe = getattr(self.client, "estimate_partition_bytes", None)
            est = probe(job_id, map_ids, reduce_id) if callable(probe) \
                else None
        hold, reroute = self.budget().admit_device(
            est, segments=len(map_ids), stopped=self._admit_poll,
            counted=adm is not None and adm.cause == "hbm")
        group_rows = 0
        if reroute is not None:
            # the chip cannot hold this task whole: the streaming route,
            # its forest folded and taken off the device a group at a
            # time — never an OOM that takes the live tasks along
            self.last_admission = reroute
            streaming, group_rows = True, reroute.group_rows
            flightrec.record("admission", decision=reroute.decision,
                             cause=reroute.cause, rejected=False,
                             estimate=est)
        rebook = None
        if est is not None and reroute is None:
            # the hold was sized for RECORD_BYTES_DEFAULT-byte records;
            # staging says what they are (a grouped task's hold is the
            # group's, a task of unknown size has the whole budget)
            rebook = functools.partial(self.budget().rebook_device, hold,
                                       est, len(map_ids))
        with hold:
            return self._run_overlapped(job_id, map_ids, reduce_id,
                                        consumer, streaming, group_rows,
                                        cap_budget, rebook)

    def _admit_poll(self) -> bool:
        """Polled by the HBM ledger while this task waits: whether the
        task is being torn down. A task parked behind the live tasks'
        reservations is waiting, not wedged (each holder's own watchdog
        guards its liveness): every poll counts as progress."""
        self._admit_ticks += 1
        return self._stop.is_set()

    def _run_overlapped(self, job_id: str, map_ids: Sequence,
                        reduce_id: int,
                        consumer: Callable[[memoryview], None],
                        streaming: bool, group_rows: int,
                        cap_budget: Optional[MemoryBudget],
                        rebook: Optional[Callable[[float], bool]] = None
                        ) -> int:
        """The overlapped fetch/merge route (streaming or in-memory),
        run while the task holds its reservation of the chip's HBM;
        ``group_rows`` > 0: the reservation holds a group of that many
        rows of run capacity, not the task (streaming only).
        ``rebook`` grows the reservation once staging has seen the
        record size (``MemoryBudget.rebook_device``)."""
        from uda_tpu.merger.overlap import OverlappedMerger

        store = None
        ckpt = None
        manifest = None
        collect = None
        if streaming:
            # bounded-host-memory online mode (uda.tpu.online.streaming):
            # segments spool to sorted runs + release their bytes; the
            # bounded feed queue keeps pending segments at O(window);
            # emission interleaves the runs with sequential cursors —
            # no shuffle-sized host allocation anywhere (the reference's
            # staging-loop memory model, StreamRW.cc:151-225)
            from uda_tpu.merger.streaming import RunStore, spill_dirs

            ckpt_dir = str(self.cfg.get("uda.tpu.ckpt.dir"))
            if ckpt_dir:
                # crash-consistent checkpointing (merger/checkpoint.py):
                # run files spool into the checkpoint's FIXED dir (they
                # are the durable half of every snapshot; a tmpdir would
                # die with the process) and each spool boundary offers a
                # manifest save
                from uda_tpu.merger.checkpoint import TaskCheckpoint

                ckpt = TaskCheckpoint(
                    ckpt_dir, job_id, reduce_id,
                    interval_s=float(
                        self.cfg.get("uda.tpu.ckpt.interval.s")),
                    keep=int(self.cfg.get("uda.tpu.ckpt.keep")),
                    epoch=int(self.cfg.get("uda.tpu.tenant.epoch")))
                self._ckpt = ckpt
                manifest = ckpt.load()
                store = RunStore(tag=f"{job_id}.r{reduce_id}",
                                 fixed_dir=ckpt.runs_dir)
            else:
                store = RunStore(spill_dirs(self.cfg),
                                 tag=f"{job_id}.r{reduce_id}")
        if ckpt is not None:
            mids = [m[1] if isinstance(m, tuple) else m for m in map_ids]
            collect = functools.partial(self._ckpt_state, job_id,
                                        reduce_id, mids, store)
        om = OverlappedMerger(
            self.key_type, self.key_width, run_store=store,
            max_pending=self.window if streaming else 0,
            stagers=int(self.cfg.get("uda.tpu.stage.pool")),
            group_rows=group_rows,
            inflight_bytes=stage_inflight_cap(
                self.cfg, self.window, self.chunk_size,
                budget=cap_budget),
            on_spool=((lambda i: ckpt.maybe_save(collect))
                      if ckpt is not None else None),
            on_record_bytes=rebook)
        self._active_overlap = om  # observability (tests/diagnostics)
        adopted: set = set()
        preload: dict = {}
        adopted_records = 0
        self._live_segments = []
        if manifest is not None:
            # elastic-store interaction (ISSUE 18): partitions may have
            # SPILLED to the blob tier while this task was down — before
            # trusting the manifest's run files and offset ledgers,
            # re-verify every spilled object's CRC so damage surfaces
            # here as a typed StoreError, not later as a Segment CRC
            # mismatch blamed on the wire
            self._revalidate_spilled(job_id)
            adopted, preload, adopted_records = self._resume_from_manifest(
                manifest, mids, store, om, ckpt)
            # snapshot #0: the loaded manifest was consumed-on-load
            # (zombie fencing), so re-persist the adopted state before
            # fetching — a crash during THIS attempt's fetch phase must
            # still find a manifest (older retained generations back it
            # up, but re-persisting keeps the walk short)
            ckpt.maybe_save(collect, force=True)
        try:
            # feed the Segment itself: record_batch() (the join and the
            # one crack of a deferred segment's chunks) then runs on a
            # stage worker, not on the transport's completion thread
            segments = self.fetch_all(job_id, map_ids, reduce_id,
                                      on_segment=om.feed,
                                      skip=adopted, preload=preload)
        except Exception:
            # the abort (which also cleans up the run store) must never
            # MASK the fetch error that got us here: a failing cleanup
            # replacing the root cause is how errors get dropped on the
            # floor mid-unwind. In checkpoint mode nothing here discards
            # the manifest or the fixed-dir run files — they ARE the
            # next attempt's resume state (RunStore.cleanup is a no-op
            # for a fixed dir)
            try:
                om.abort()
            except Exception as cleanup_err:  # noqa: BLE001
                metrics.add("errors.swallowed")
                log.warn(f"overlap abort during failure unwind itself "
                         f"failed: {cleanup_err}")
            raise
        # the "merge" timer covers drain + forest carry inside the
        # finish paths; emission stays under the emitter's "emit" timer
        if streaming:
            out = om.finish_streaming(
                self.emitter, consumer,
                # asked after the drain: a deferred segment counts its
                # records where a stage worker cracks it, and one that
                # no worker was handed is cracked for the count
                expected_records=lambda: (
                    sum(s.fetched_records() for s in segments
                        if s is not None)
                    + adopted_records))
            if ckpt is not None:
                # the emitted output is the durable artifact now; a
                # retained checkpoint would resume a FINISHED task
                ckpt.discard()
                self._ckpt = None
            return out
        try:
            return om.emit_stream(segments, self.emitter, consumer)
        finally:
            # the stream is out (or the task failed): drop the
            # partition's bytes now. A finished task's segments sit in
            # reference cycles (their callbacks), full collections are
            # rare in a process this size, and what lingers until one —
            # a partition a task, more tasks a minute now that they are
            # shorter — is memory the node's live tasks are refused
            for seg in segments:
                seg.release()

    def stop(self) -> None:
        self._stop.set()
        self._release_push()
        self.client.stop()
