"""Segments: streaming views of one map-output partition.

Equivalent of the reference's Segment/BaseSegment (reference
src/Merger/StreamRW.cc:334-590): a segment pulls its partition's bytes
chunk by chunk through an InputClient, handling records that break across
chunk boundaries. The reference does this with double-buffered RDMA
fetches and a cond-wait ``switch_mem`` that ``join``s the split record
into ``temp_kv`` (StreamRW.cc:462-590); here the same contract is a
*carry buffer*: each chunk is columnar-cracked up to its last complete
record and the partial tail is prepended to the next chunk. A segment
whose first chunk is not its last skips the per-chunk work: its chunks
are kept as they come and the partition is cracked once, whole, by
whoever first materializes it (``Segment.record_batch``: a stage worker
of the overlapped merger).

``InputClient`` is the transport abstraction of reference
src/Merger/InputClient.h:30-56 (``start_fetch_req``/``comp_fetch_req``):
implementations are LocalFetchClient (single host, over the DataEngine)
and the mesh exchange client (uda_tpu.parallel).
"""

from __future__ import annotations

import abc
import random
import threading
import time
import zlib
from typing import Optional

import numpy as np

from uda_tpu.mofserver.data_engine import DataEngine, FetchResult, ShuffleRequest
from uda_tpu.utils.errors import (MergeError, StorageError, TenantError,
                                  TransportError, attribute_supplier)
from uda_tpu.utils.failpoints import failpoint
from uda_tpu.utils.flightrec import flightrec
from uda_tpu.utils.ifile import RecordBatch, crack, crack_partial
from uda_tpu.utils.locks import TrackedLock
from uda_tpu.utils.logging import get_logger
from uda_tpu.utils.metrics import metrics
from uda_tpu.utils.retry import RetryPolicy, SpeculationPolicy

log = get_logger()

__all__ = ["InputClient", "LocalFetchClient", "HostRoutingClient",
           "Segment"]


class InputClient(abc.ABC):
    """Transport abstraction (reference InputClient.h:30-56)."""

    @abc.abstractmethod
    def start_fetch(self, req: ShuffleRequest, on_complete) -> None:
        """Async fetch; ``on_complete(FetchResult | Exception)``."""

    def estimate_partition_bytes(self, job_id: str, map_ids,
                                 reduce_id: int):
        """Best-effort on-disk size of this reduce partition across
        ``map_ids``, or None when the transport cannot know it without
        fetching (the auto merge-approach policy then defaults to the
        bounded-memory path — see MergeManager.run)."""
        return None

    def resume_ok(self, host: str = "") -> bool:
        """May a retrying Segment keep its offset ledger and resume
        mid-partition instead of refetching from zero
        (``uda.tpu.fetch.resume``)? True by default — MOFs are
        immutable files, so a byte range re-read after a transport
        blip is the same bytes. Transports with per-stream state
        (DecompressingClient) or evidence of a cold supplier restart
        (RemoteFetchClient's generation tracking) answer False; the
        Segment then restarts the whole fetch."""
        return True

    def speculate_ok(self) -> bool:
        """May the straggler detector issue a DUPLICATE in-flight fetch
        for the same (job, map, reduce) through this transport? True by
        default — stateless transports serve concurrent duplicates
        independently. Transports with per-stream state keyed on the
        partition (DecompressingClient's sequential stream claim)
        answer False: a duplicate would steal the stream token and turn
        the healthy primary's completion into a fabricated fault."""
        return True

    def generation(self, host: str = "") -> Optional[int]:
        """The supplier's observed restart generation for ``host`` (the
        HELLO banner's counter), or None when the transport has no
        generation concept or has not connected yet. The checkpoint
        resume path (merger/checkpoint.py) compares a manifest's
        recorded generation against this: a changed generation means
        the supplier restarted since the ledger was written, so the
        offset ledger is dropped and that segment re-fetches from zero
        (its run files, being self-contained, are kept)."""
        return None

    def recover_partition(self, req: ShuffleRequest, ctx,
                          on_complete) -> bool:
        """k-of-n stripe reconstruction (uda_tpu.coding): rebuild
        ``req``'s whole partition from any k of its n stripe chunks,
        delivering a full-partition FetchResult (or an Exception) to
        ``on_complete``. Returns False when unsupported (no stripe
        context) — the Segment then fails terminally as before. The
        default implementation drives the generic recovery over THIS
        transport's ``start_fetch`` (shard pseudo-maps route per host
        like any other fetch); wrappers that transform the byte domain
        (DecompressingClient) override to re-wrap the result."""
        if ctx is None:
            return False
        from uda_tpu.coding.recovery import start_recovery

        start_recovery(self, req, ctx, on_complete)
        return True

    def stop(self) -> None:
        pass


class LocalFetchClient(InputClient):
    """Single-host client: fetches straight from a DataEngine (the
    minimum end-to-end slice of SURVEY §7.3)."""

    def __init__(self, engine: DataEngine):
        self.engine = engine

    def start_fetch(self, req: ShuffleRequest, on_complete) -> None:
        fut = self.engine.submit(req)

        def _done(f):
            err = f.exception()
            on_complete(err if err is not None else f.result())

        fut.add_done_callback(_done)

    def estimate_partition_bytes(self, job_id: str, map_ids,
                                 reduce_id: int):
        """Sum of raw_length over the map outputs (the spill-index
        triples the supplier serves from; resolution is cached by the
        engine's resolver). raw_length — the UNCOMPRESSED record bytes
        — is what the merge will actually hold, so the estimate stays
        correct through a DecompressingClient wrap (for uncompressed
        jobs raw == part). Exact-or-unknown: ANY unresolvable map makes
        the whole estimate None — a partial sum is a lower bound, and a
        lower bound could steer the auto policy onto the host-resident
        path for a partition that is actually huge. Fetch itself still
        fails loudly on a truly missing MOF."""
        total = 0
        for mid in map_ids:
            try:
                total += int(self.engine.resolver.resolve(
                    job_id, mid, reduce_id).raw_length)
            except Exception as e:  # noqa: BLE001 - exact-or-unknown:
                # the estimate degrades to None, but never silently —
                # a perpetually-unresolvable index would otherwise hide
                # behind "the auto policy just picked streaming again"
                metrics.add("errors.swallowed")
                log.debug(f"size estimate: {mid} unresolvable ({e}); "
                          f"partition size unknown")
                return None
        return total


class HostRoutingClient(InputClient):
    """Per-supplier-host transport table with lazy connect.

    The reference's reduce-side client opens one RDMA connection per
    supplier host ON FIRST USE and caches it (connect-per-host with DNS
    cache, reference src/DataNet/RDMAClient.cc:498-527, 602-629). Here
    ``connect(host)`` builds the host's transport (e.g. a
    LocalFetchClient over that host's DataEngine, or a remote client)
    the first time a fetch addresses it; every later fetch for the host
    reuses the cached transport. A failed connect surfaces through the
    fetch's completion callback like any transport error (the
    reference's connect-retry-then-fail path, RDMAClient.cc:215-356).

    With no ``connect`` callable the router defaults to the socket data
    plane: each host dials that supplier's ShuffleServer as
    ``host[:port]`` (port defaulting to ``uda.tpu.net.port``) through a
    :class:`~uda_tpu.net.client.RemoteFetchClient` — one multiplexed
    connection per supplier host, the deployed-service wiring.
    """

    def __init__(self, connect=None, config=None):
        self._connect = (connect if connect is not None
                         else self._socket_factory(config))
        self._clients: dict[str, InputClient] = {}
        self._stopped = False
        # elastic membership (ISSUE 18): joiners announced mid-job via
        # notify_join and leavers via notify_drain. Membership is
        # ADVISORY routing state — fetches still address whatever host
        # the entry names; the sets steer candidate ranking and let
        # MergeManager.notify_join widen in-flight segments.
        self._members: set[str] = set()
        self._draining: set[str] = set()
        # push plane (ISSUE 19): (job, reduce) -> staging, applied to
        # every transport the router builds — including transports
        # created (or re-dialed after refresh()) AFTER registration,
        # so a joiner/bounced supplier gets subscribed too
        self._push_regs: dict = {}
        self._lock = TrackedLock("host_router")

    @staticmethod
    def _socket_factory(config):
        """The default connect: dial ``host[:port]`` over TCP. Imported
        lazily (uda_tpu.net imports this module)."""
        def connect(host: str) -> InputClient:
            from uda_tpu.net.client import RemoteFetchClient
            from uda_tpu.utils.config import Config

            # accepted shapes: "name", "name:port", "[v6addr]:port",
            # and a bare IPv6 literal (2+ colons, no brackets)
            name, port = host, ""
            if host.startswith("["):
                name, bracket, rest = host[1:].partition("]")
                if not bracket or (rest and not rest.startswith(":")):
                    raise TransportError(
                        f"malformed supplier address {host!r}")
                port = rest[1:]
            elif host.count(":") == 1:
                name, _, port = host.partition(":")
            if not name:
                # an empty host would resolve to localhost and
                # misdirect the fetch to whatever listens there; fail
                # loudly instead (the entry was built without a
                # supplier host — a wiring bug, not a transport fault)
                raise TransportError(
                    "socket fetch routing needs a supplier host per "
                    "map entry; got an empty host")
            if port and not port.isdigit():
                raise TransportError(
                    f"malformed supplier port in {host!r}")
            cfg = config or Config()
            return RemoteFetchClient(
                name, int(port) if port else None, config=cfg)
        return connect

    def _client_for(self, host: str) -> InputClient:
        with self._lock:
            if self._stopped:
                raise MergeError("HostRoutingClient is stopped")
            client = self._clients.get(host)
        if client is None:
            client = self._connect(host)
            with self._lock:
                if self._stopped:
                    loser = client  # connected after stop(): tear down
                else:
                    # a concurrent connect for the same host may have
                    # won; the loser must be torn down, not leaked
                    winner = self._clients.setdefault(host, client)
                    loser = None if winner is client else client
                    client = winner
            if loser is not None:
                loser.stop()
            with self._lock:
                if self._stopped:
                    raise MergeError("HostRoutingClient is stopped")
                regs = list(self._push_regs.items())
            self._apply_push_regs(client, regs)
        return client

    @staticmethod
    def _apply_push_regs(client: InputClient, regs) -> None:
        """Subscribe an armed push registration on one transport.
        Duck-typed: transports without a push plane (LocalFetchClient,
        custom connects) simply stay pull-only."""
        reg = getattr(client, "push_register", None)
        if not callable(reg):
            return
        for (job_id, reduce_id), staging in regs:
            reg(job_id, reduce_id, staging)

    # -- push plane (ISSUE 19) -----------------------------------------------

    def push_register(self, job_id: str, reduce_id: int, staging,
                      hosts=None) -> None:
        """Register reduce-side staging across the supplier fleet:
        every cached transport subscribes now, every FUTURE transport
        (lazy first-fetch dial, join, post-refresh re-dial) subscribes
        at build time. ``hosts`` eagerly dials the named suppliers so
        pushes can arrive before the first fetch exists; dial failures
        are best-effort (those hosts stay pull-only until fetched)."""
        with self._lock:
            if self._stopped:
                return
            self._push_regs[(job_id, int(reduce_id))] = staging
            cached = list(self._clients.values())
        regs = [((job_id, int(reduce_id)), staging)]
        for client in cached:
            self._apply_push_regs(client, regs)
        for host in set(hosts or ()) | set(self.members()):
            try:
                self._client_for(host)  # _apply_push_regs rides the build
            except Exception:  # noqa: BLE001 - eager dial is advisory
                metrics.add("push.dial.failures", supplier=host)

    def push_unregister(self, job_id: str, reduce_id: int) -> None:
        with self._lock:
            self._push_regs.pop((job_id, int(reduce_id)), None)
            cached = list(self._clients.values())
        for client in cached:
            unreg = getattr(client, "push_unregister", None)
            if callable(unreg):
                unreg(job_id, reduce_id)

    def start_fetch(self, req: ShuffleRequest, on_complete) -> None:
        try:
            client = self._client_for(req.host)
        except Exception as e:  # noqa: BLE001 - connect failure ->
            on_complete(e)      # completion error, like the reference
            return
        client.start_fetch(req, on_complete)

    def resume_ok(self, host: str = "") -> bool:
        """Delegate to the host's transport (a RemoteFetchClient may
        have observed a cold supplier restart); an unconnected host is
        resumable by default — the reconnect itself revalidates."""
        with self._lock:
            client = self._clients.get(host)
        return True if client is None else client.resume_ok(host)

    def generation(self, host: str = "") -> Optional[int]:
        """Delegate to the host's transport; an unconnected host has no
        observed generation yet (None — the checkpoint resume path then
        accepts optimistically and lets the first resumed chunk's
        identity check revalidate)."""
        with self._lock:
            client = self._clients.get(host)
        return None if client is None else client.generation(host)

    # -- elastic membership (ISSUE 18) ---------------------------------------

    def notify_join(self, host: str) -> None:
        """A supplier registered mid-job (its banner carries
        CAP_ELASTIC): fold it into the membership ring and refresh any
        stale cached transport so the next fetch re-dials and observes
        the joiner's current generation."""
        with self._lock:
            already = host in self._members
            self._members.add(host)
            self._draining.discard(host)
        if not already:
            metrics.add("elastic.joins", supplier=host)
        self.refresh(host)

    def notify_drain(self, host: str) -> None:
        """A supplier announced departure (CAP_DRAINING): keep its
        transport — in-flight fetches complete against it — but mark it
        so candidate ranking demotes it and no new placement lands
        there."""
        with self._lock:
            self._members.discard(host)
            self._draining.add(host)

    def refresh(self, host: str) -> None:
        """Drop the host's cached transport (stopping it) so the next
        fetch re-dials; a no-op for unconnected hosts. Used after a
        join/restart to pick up the fresh HELLO banner."""
        with self._lock:
            client = self._clients.pop(host, None)
        if client is not None:
            client.stop()

    def members(self) -> list[str]:
        """The advisory elastic membership (joiners announced via
        notify_join, minus announced leavers), sorted for deterministic
        placement."""
        with self._lock:
            return sorted(self._members)

    def is_draining(self, host: str) -> bool:
        """Has this host announced drain — either via notify_drain or
        through a CAP_DRAINING banner its live transport observed?"""
        with self._lock:
            if host in self._draining:
                return True
            client = self._clients.get(host)
        probe = getattr(client, "peer_draining", None)
        return bool(probe(host)) if callable(probe) else False

    def estimate_partition_bytes(self, job_id: str, map_ids,
                                 reduce_id: int):
        """Per-host fan-out of the size estimate: entries group by
        supplier host and each host's transport answers for its own
        maps (RemoteFetchClient probes over the wire, LocalFetchClient
        sums its spill index). Exact-or-unknown like LocalFetchClient:
        ANY host that cannot answer (unknown size, failed connect)
        makes the whole estimate None — a partial sum is a lower bound
        and would steer the auto merge-approach policy wrong (see
        LocalFetchClient.estimate_partition_bytes). Replicated entries
        (a host LIST per map) are estimated against their first
        (primary) host."""
        by_host: dict[str, list[str]] = {}
        for entry in map_ids:
            host, mid = entry if isinstance(entry, tuple) else ("", entry)
            if isinstance(host, (list, tuple)):
                host = host[0] if host else ""
            by_host.setdefault(host, []).append(mid)

        def probe(host: str, mids: list[str]):
            try:
                return self._client_for(host).estimate_partition_bytes(
                    job_id, mids, reduce_id)
            except Exception as e:  # noqa: BLE001 - estimate is best-
                # effort (fetch itself will fail loudly later), but the
                # degradation is counted and logged, never silent
                metrics.add("errors.swallowed")
                log.debug(f"size estimate: probe of host {host!r} "
                          f"failed ({e}); partition size unknown")
                return None

        if len(by_host) == 1:  # the common case, no thread overhead
            host, mids = next(iter(by_host.items()))
            return probe(host, mids)
        # many hosts: probe concurrently — serially, one slow or dead
        # supplier's connect+probe timeout would stack per host and
        # stall the auto merge-approach decision for minutes
        from concurrent.futures import ThreadPoolExecutor

        with ThreadPoolExecutor(
                max_workers=min(16, len(by_host)),
                thread_name_prefix="uda-size-probe") as pool:
            estimates = list(pool.map(lambda kv: probe(*kv),
                                      by_host.items()))
        if any(est is None for est in estimates):
            return None
        return sum(estimates)

    def stop(self) -> None:
        with self._lock:
            self._stopped = True
            clients = list(self._clients.values())
            self._clients.clear()
        for c in clients:
            c.stop()


_K_CRACK = metrics.timer_series("fetch_crack")
_CHUNK_KEYS: dict = {}


def _chunk_keys(supplier: str, tenant: str) -> tuple:
    """-> the (fetch.bytes, fetch.chunks) counter keys of one
    (supplier, tenant) pair, built once: a chunk's counters go in with
    ONE locked update (Metrics.add_keyed), not a key build and an
    acquisition each."""
    keys = _CHUNK_KEYS.get((supplier, tenant))
    if keys is None:
        labels = {"supplier": supplier}
        if tenant:
            labels["tenant"] = tenant
        keys = _CHUNK_KEYS[(supplier, tenant)] = (
            metrics.series("fetch.bytes", **labels),
            metrics.series("fetch.chunks", **labels))
    return keys


class Segment:
    """One partition's record stream, fetched chunk-wise with a carry
    buffer for records split across chunk boundaries.

    Drives ``chunk_size``-byte fetches at increasing offsets until
    ``raw_length`` bytes have arrived (the reference's send_request /
    switch_mem loop, StreamRW.cc:462-590). A segment that arrives whole
    in its first chunk is cracked where it lands, on the thread that
    delivered it. One that does not is *deferred*: the completion thread
    (the process's one upcall thread, shared by every live task) only
    keeps each chunk, and ``record_batch()`` joins and cracks them once
    — one copy and one native call where carry + per-chunk crack +
    concat were two copies and a call a chunk. The first chunk decides,
    by what it is: a one-chunk segment has nothing to save.

    Survivable-shuffle ladder (ISSUE 8; every rung shares the task's
    :class:`~uda_tpu.merger.recovery.RecoveryLedger`):

    - **speculation** (``uda.tpu.fetch.speculate.pn``): an in-flight
      chunk that outlives max(floor, pN of the observed
      ``fetch.latency_ms`` histogram) gets a DUPLICATE fetch issued to
      the best-ranked alternate source (PenaltyBox rank over
      ``hosts``; the same source when no alternate exists).
      First-completion-wins rides the attempt-epoch machinery — the
      loser's completion is discarded as stale — and a speculation win
      switches the segment to the faster source for its remaining
      chunks;
    - **resume** (``uda.tpu.fetch.resume``): a transport-level retry
      against a resumable source (InputClient.resume_ok — warm
      supplier restart, immutable MOFs) keeps the offset ledger
      (batches + carry, or the kept chunks, + next offset) and
      continues mid-partition
      instead of refetching from zero; the first resumed chunk's
      ``raw_length`` must match the pre-fault identity or the segment
      falls back to a full restart;
    - **reconstruction** (``uda.tpu.coding.scheme``): once retries are
      exhausted, the partition is rebuilt from any k of its n erasure
      stripe chunks on the surviving suppliers
      (InputClient.recover_partition) — the rung that turns a dead
      supplier from a FallbackSignal into a completed task.
    """

    def __init__(self, client: InputClient, job_id: str, map_id: str,
                 reduce_id: int, chunk_size: int, host: str = "",
                 retries: int = 3, policy: Optional[RetryPolicy] = None,
                 *, hosts=None, ledger=None,
                 speculation: Optional[SpeculationPolicy] = None,
                 resume: bool = False, stripe=None, tenant: str = ""):
        self.client = client
        # the task's tenant identity (uda.tpu.tenant.id, from the
        # task's own MergeManager): labels the hot-path fetch counters.
        # Task-local on purpose — several reduce tasks of different
        # tenants may be live in one process (a node's reduce slots)
        self.tenant = tenant
        self.job_id = job_id
        self.map_id = map_id
        self.reduce_id = reduce_id
        self.chunk_size = chunk_size
        # candidate sources: ``hosts`` are suppliers known to hold this
        # map output (replicas); the primary is (re)picked by ledger
        # rank, speculation duplicates to the best alternate
        self.hosts: list[str] = [h for h in (hosts or ([host] if host
                                                       else [""]))]
        self.host = host or self.hosts[0]
        self.ledger = ledger
        self.speculation = speculation
        self.resume_enabled = bool(resume)
        self.stripe = stripe  # StripeContext when k-of-n coding is on
        self.batches: list[RecordBatch] = []
        # records cracked so far: monotone as chunks land on the eager
        # path; 0 on a deferred segment until record_batch() (or
        # fetched_records()) has cracked it
        self.num_records = 0
        self.raw_length: Optional[int] = None
        self.on_done = None  # callback fired once when fetch finishes
        self.on_fault = None  # callback fired on EVERY transport fault
        # (retried or terminal) — the penalty-box feedback channel
        self.policy = policy or RetryPolicy(retries=max(0, retries))
        self.trace_span = None
        self._issue_t0 = 0.0
        self._released = False
        self._carry = b""
        # deferred crack: the fetched chunks as they came, cracked once
        # by record_batch(); None while the segment cracks eagerly
        self._raw: Optional[list] = None
        self._next_offset = 0
        self._retries_left = max(0, self.policy.retries)
        self._deadline: Optional[float] = None
        self._crc_refetched: set[int] = set()  # offsets re-fetched once
        self._rng = random.Random((self.policy.seed or 0)
                                  ^ zlib.crc32(map_id.encode()))
        self._issuing = False
        self._inline = self._PENDING
        self._next_epoch = 0     # attempt-id allocator (monotone)
        self._epoch = 0          # id of the outstanding PRIMARY attempt
        self._spec: Optional[tuple] = None  # (epoch, host) of the live
        # speculative duplicate, if any — the `speculative` epoch flag
        self._epoch_settled = True  # the attempt group has completed
        self._open_attempts = 0  # live attempts (on-air accounting)
        self._attempt_hosts: dict[int, str] = {}
        self._resume_check = False   # next chunk must revalidate identity
        self._recover_tried = False  # the reconstruction rung is one-shot
        self._timeout_timer: Optional[threading.Timer] = None
        self._spec_timer: Optional[threading.Timer] = None
        self._done = threading.Event()
        self._error: Optional[Exception] = None
        # lockdep-tracked: the segment state machine is driven from
        # transport completion threads, retry timers AND the merge
        # thread — the widest thread fan-in in the tree
        self._lock = TrackedLock("segment.state")

    @property
    def supplier(self) -> str:
        """The metric/penalty label of the CURRENT source (host when
        routed per host, else the map id); tracks speculation wins."""
        return self.host or self.map_id

    def add_host(self, host: str) -> bool:
        """Mid-job joiner pickup (ISSUE 18): widen the candidate list
        of an IN-FLIGHT segment so the existing ledger-ranked paths —
        retry re-pick, speculation alternate, reconstruction anchors —
        can elect the joiner. No attempt is re-routed eagerly; the
        joiner only matters at the next decision point. Returns True
        when the host was actually added (unknown and not done)."""
        if not host:
            return False
        with self._lock:
            if self._done.is_set() or host in self.hosts:
                return False
            self.hosts.append(host)
        return True

    def _notify_done(self) -> None:
        span = self.trace_span
        if span is not None:
            err = self._error
            span.end(**({"error": type(err).__name__} if err else {}))
        cb = self.on_done
        if cb is not None:
            cb(self)

    def _finish(self, error: Optional[Exception]) -> bool:
        """The ONLY terminal transition: first caller wins, every other
        (a concurrent fail() racing the drive loop's own terminal path)
        is a no-op — on_done must fire exactly once."""
        with self._lock:
            if self._done.is_set():
                return False
            self._error = error
            self._done.set()
        # black-box state transition (per segment, off the chunk path)
        flightrec.record("segment.done", map=self.map_id,
                         supplier=self.supplier,
                         error=type(error).__name__ if error else None)
        self._notify_done()
        return True

    # -- fetch driving ------------------------------------------------------

    _PENDING = object()  # sentinel: no inline completion delivered

    def start(self) -> None:
        if self.policy.deadline_ms > 0:
            self._deadline = time.monotonic() + self.policy.deadline_ms / 1e3
        # consult box rank BEFORE the primary pick, not only on fault:
        # a replicated segment opens against the healthiest source
        if len(self.hosts) > 1 and self.ledger is not None:
            self.host = self.ledger.rank(self.hosts)[0]
        # child of the caller's current span (the fetch phase of the
        # reduce-task trace); ended by _notify_done on ANY terminal path
        self.trace_span = metrics.start_span(
            "fetch.segment", map=self.map_id, supplier=self.supplier,
            reduce=self.reduce_id)
        flightrec.record("segment.start", map=self.map_id,
                         supplier=self.supplier)
        with self._lock:
            resume_at = self._next_offset
        if resume_at > 0:
            # checkpoint-preloaded offset ledger (ckpt_preload): the
            # fetch continues mid-partition; the bytes below the offset
            # are never refetched, and the first chunk revalidates the
            # partition identity through the _resume_check ladder
            metrics.add("fetch.resumed", supplier=self.supplier)
            metrics.add("fetch.resumed.bytes", resume_at)
            flightrec.record("segment.ckpt_resume", map=self.map_id,
                             supplier=self.supplier, offset=resume_at)
            log.info(f"fetch of {self.map_id} resuming at offset "
                     f"{resume_at} from a checkpointed ledger")
        self._drive(self._try_issue(resume_at))

    def _try_issue(self, offset: int):
        """Issue one fetch. Returns None when the transport took it
        asynchronously (the completion callback will fire later), or
        the RESULT (FetchResult or Exception) when the transport raised
        synchronously / invoked the callback inline — the caller's
        _drive loop then processes it WITHOUT recursing, so a transport
        that fails inline (e.g. a router's connect error) cannot
        overflow the stack however large the retry budget is.

        Each issue opens a new attempt epoch; completions (real,
        injected, or timeout-generated) carry their epoch and only the
        FIRST one for the current epoch is accepted — a late completion
        racing its own attempt timeout is dropped as stale instead of
        double-driving the state machine."""
        with self._lock:
            if self._done.is_set():
                # administratively failed (fail()) while a retry backoff
                # timer was pending: the segment is finished — issuing
                # would open a fresh epoch on a dead segment and fire
                # on_done twice when it completed
                return None
            self._inline = self._PENDING
            self._issuing = True
            self._next_epoch += 1
            self._epoch = self._next_epoch
            self._epoch_settled = False
            self._open_attempts += 1
            self._issue_t0 = time.perf_counter()
            epoch = self._epoch
            host = self.host
            self._attempt_hosts[epoch] = host
        req = ShuffleRequest(self.job_id, self.map_id, self.reduce_id,
                             offset, self.chunk_size, host=host)
        # on-air accounting (reference AIOHandler on-air counters):
        # +1 per attempt epoch, -1 when that epoch settles (accepted
        # completion, timeout-generated completion, sync raise, or
        # abandonment of a speculation loser)
        # the +1 hands off to the attempt epoch: _on_complete (accepted
        # or timeout-generated completion) owns the -1; only the sync
        # raise below settles it here
        metrics.gauge_add("fetch.on_air", 1)  # udalint: disable=UDA101
        try:
            # the failpoint is inside the try: an injected raise takes
            # the same sync-failure path as a stopped transport. The
            # key carries map AND source so chaos schedules can target
            # one supplier of a replicated segment (match:@host)
            failpoint("segment.fetch", key=f"{self.map_id}@{host}")
            # the segment's span is the transport's parent for this
            # issue: spans a transport opens (e.g. net.fetch) join the
            # fetch span tree even when the issue happens on a
            # completion thread with no ambient context
            with metrics.use_span(self.trace_span):
                self.client.start_fetch(
                    req, lambda res, e=epoch: self._on_complete(res, e))
        except Exception as e:  # noqa: BLE001 - a sync raise must fail
            # the segment, never escape into the transport's thread
            with self._lock:
                self._issuing = False
                # settle only a LIVE attempt: fail() (watchdog rescue /
                # stop drain) may have settled this epoch's on-air
                # charge while we were wedged inside the issue — a
                # second decrement here would push the gauge negative
                # forever (found by the ResourceLedger teardown gate)
                live = epoch in self._attempt_hosts
                if live:
                    self._epoch_settled = True
                    self._open_attempts -= 1
                    self._attempt_hosts.pop(epoch, None)
            if live:
                metrics.gauge_add("fetch.on_air", -1)
            return e
        with self._lock:
            self._issuing = False
            r = self._inline
            self._inline = self._PENDING
            if r is self._PENDING and not self._epoch_settled:
                self._arm_timeout(epoch)  # only for an async in-flight fetch
                self._arm_speculation(epoch, offset)
        return None if r is self._PENDING else r

    def _arm_timeout(self, epoch: int) -> None:
        """Arm the per-attempt timeout (caller holds self._lock)."""
        timeout = self.policy.attempt_timeout_ms
        if timeout <= 0:
            return
        t = threading.Timer(timeout / 1e3, self._on_timeout, args=(epoch,))
        t.daemon = True
        self._timeout_timer = t
        t.start()

    def _cancel_timeout(self) -> None:
        with self._lock:
            t, self._timeout_timer = self._timeout_timer, None
            s, self._spec_timer = self._spec_timer, None
        if t is not None:
            t.cancel()
        if s is not None:
            s.cancel()

    def _on_timeout(self, epoch: int) -> None:
        with self._lock:
            spec_epoch = self._spec[0] if self._spec else None
            if epoch not in (self._epoch, spec_epoch) \
                    or self._epoch_settled:
                return  # the attempt completed first
        tenant = self.tenant
        if tenant:
            metrics.add("fetch.timeouts", supplier=self.supplier,
                        tenant=tenant)
        else:
            metrics.add("fetch.timeouts", supplier=self.supplier)
        self._on_complete(TransportError(
            f"fetch of {self.map_id} attempt timed out after "
            f"{self.policy.attempt_timeout_ms:g} ms"), epoch)

    # -- speculation (the straggler detector) -------------------------------

    def _arm_speculation(self, epoch: int, offset: int) -> None:
        """Arm the straggler timer for one in-flight attempt (caller
        holds self._lock): fires at max(floor, pN of the observed
        fetch.latency_ms histogram)."""
        sp = self.speculation
        if sp is None or not sp.enabled or self._spec is not None \
                or not self.client.speculate_ok():
            return
        t = threading.Timer(sp.threshold_ms() / 1e3,
                            self._maybe_speculate, args=(epoch, offset))
        t.daemon = True
        self._spec_timer = t
        t.start()

    def _pick_alt(self) -> str:
        """The speculation target: best PenaltyBox-ranked candidate
        that is not the current source; the current source itself when
        the segment has no alternates (a duplicate fetch still races a
        per-request stall)."""
        ranked = (self.ledger.rank(self.hosts) if self.ledger is not None
                  else list(self.hosts))
        for h in ranked:
            if h != self.host:
                return h
        return self.host

    def _maybe_speculate(self, epoch: int, offset: int) -> None:
        """Straggler-timer body: issue the speculative duplicate. Runs
        on the timer thread; a speculative attempt that fails (sync or
        async) is simply dropped — it must never fail the segment while
        the primary race is still open."""
        with self._lock:
            if self._done.is_set() or self._epoch_settled \
                    or epoch != self._epoch or self._spec is not None:
                return
            alt = self._pick_alt()
            self._next_epoch += 1
            spec_epoch = self._next_epoch
            self._spec = (spec_epoch, alt)
            self._attempt_hosts[spec_epoch] = alt
            self._open_attempts += 1
        metrics.add("fetch.speculated", supplier=alt or self.map_id)
        flightrec.record("segment.speculate", map=self.map_id,
                         primary=self.host, alternate=alt)
        # hands off to the speculative epoch: _on_complete settles the
        # winner, _drop_attempt the loser (and the sync-raise path)
        metrics.gauge_add("fetch.on_air", 1)  # udalint: disable=UDA101
        log.warn(f"fetch of {self.map_id} chunk at {offset} is a "
                 f"straggler; speculating against "
                 f"{alt or 'the same source'}")
        req = ShuffleRequest(self.job_id, self.map_id, self.reduce_id,
                             offset, self.chunk_size, host=alt)
        try:
            failpoint("segment.fetch", key=f"{self.map_id}@{alt}#spec")
            with metrics.use_span(self.trace_span):
                self.client.start_fetch(
                    req, lambda res, e=spec_epoch: self._on_complete(res, e))
        except Exception as e:  # noqa: BLE001 - a failed spec issue is
            # a dropped duplicate, not a segment failure
            self._drop_attempt(spec_epoch, e)

    def _drop_attempt(self, epoch: int, exc: Optional[Exception]) -> None:
        """Close ONE of two live attempts (a speculation loser that
        errored): the race continues on the surviving attempt.

        Racing failures: when BOTH attempts fail concurrently, the
        first drop leaves one live attempt (possibly by promotion) and
        the second drop finds ``_spec`` already None — that second
        failure now belongs to the SOLE live attempt, so it settles
        the group and drives the ordinary retry ladder instead of
        being discarded (discarding it would strand the segment with
        zero attempts in flight and nothing left to wake it)."""
        promoted = False
        sole_failure = False
        with self._lock:
            if self._epoch_settled:
                return
            spec = self._spec
            host = self._attempt_hosts.pop(epoch, self.host)
            if spec is not None and epoch == spec[0]:
                self._spec = None
            elif spec is not None and epoch == self._epoch:
                # the PRIMARY died while a speculative duplicate is in
                # flight: promote the duplicate — it is now the fetch
                self._epoch = spec[0]
                self._spec = None
                self.host = spec[1]
                promoted = True
                old_t, self._timeout_timer = self._timeout_timer, None
            elif spec is None and epoch == self._epoch:
                # the other attempt was dropped/promoted first: this
                # failure is the last live attempt's — settle and retry
                sole_failure = True
                self._epoch_settled = True
                settled_n = self._open_attempts
                self._open_attempts = 0
            else:
                return  # neither live attempt: stale
            if not sole_failure:
                self._open_attempts -= 1
            if promoted:
                self._arm_timeout(self._epoch)
        if sole_failure:
            metrics.gauge_add("fetch.on_air", -settled_n)
            self._cancel_timeout()
            if exc is None:
                exc = TransportError(
                    f"fetch of {self.map_id}: both racing attempts "
                    f"failed")
            attribute_supplier(exc, host or self.map_id)
            self._drive(exc)
            return
        metrics.gauge_add("fetch.on_air", -1)
        if promoted and old_t is not None:
            old_t.cancel()
        if exc is not None:
            attribute_supplier(exc, host or self.map_id)
            self._notify_fault(exc)

    def _on_complete(self, result, epoch: int) -> None:
        with self._lock:
            spec = self._spec
            spec_epoch = spec[0] if spec else None
            if self._epoch_settled or \
                    epoch not in (self._epoch, spec_epoch):
                metrics.add("fetch.stale_completions")
                return  # superseded attempt (timed out or re-issued)
            two_live = spec_epoch is not None
            drop_loser = isinstance(result, Exception) and two_live
            if not drop_loser:
                # accepted: this completion settles the attempt GROUP;
                # the loser of a speculation race is abandoned now (its
                # own completion, if it ever lands, is stale)
                self._epoch_settled = True
                won_spec = two_live and epoch == spec_epoch
                if won_spec:
                    self.host = spec[1]  # sticky: the faster source
                    # serves this segment's remaining chunks too
                self._spec = None
                self._attempt_hosts.clear()
                settled = self._open_attempts
                self._open_attempts = 0
                inline = self._issuing
                if inline:  # inline completion: hand back to _drive
                    self._inline = result
        if drop_loser:
            # one of TWO racing attempts failed: close it and keep
            # racing on the survivor (a failed primary promotes the
            # speculative duplicate)
            self._drop_attempt(epoch, result)
            return
        metrics.gauge_add("fetch.on_air", -settled)
        if two_live:
            if won_spec:
                metrics.add("fetch.speculation.won",
                            supplier=self.supplier)
            else:
                metrics.add("fetch.speculation.lost")
        if inline:
            return
        self._cancel_timeout()
        self._drive(result)

    def _notify_fault(self, exc: Exception) -> None:
        """Fire the on_fault hook (penalty-box feedback). The hook must
        never decide the segment's fate: its own errors are logged and
        swallowed."""
        hook = self.on_fault
        if hook is not None:
            try:
                hook(self, exc)
            except Exception as e:  # noqa: BLE001
                log.warn(f"on_fault hook failed for {self.map_id}: {e}")

    def _drive(self, result) -> None:
        """Iterative fetch state machine (one outstanding fetch at a
        time; runs on whichever thread delivered the completion)."""
        while result is not None:
            if isinstance(result, TenantError):
                # the service plane's refusal is TERMINAL: a fenced
                # epoch / retired job / failed registration cannot be
                # retried into legality — burning the retry+backoff
                # budget against the registry would only delay the
                # fallback (and churn the penalty box against a
                # healthy supplier)
                self._notify_fault(result)
                self._finish(result)
                return
            if isinstance(result, Exception):
                # transport-level retry (the reference retries its
                # connect dance 5x and RNR-retries sends,
                # RDMAClient.cc:41, 235-344; RDMAComm.h:29). Default:
                # restart the WHOLE segment from offset 0 —
                # re-fetch-the-MOF granularity, which also resets any
                # decompressing wrapper's stream state cleanly. With
                # uda.tpu.fetch.resume on and a resumable source
                # (warm-restarted supplier, immutable MOF), keep the
                # offset ledger and continue mid-partition instead —
                # already-served bytes are never refetched.
                deadline_hit = False
                # transport capability probed OUTSIDE self._lock (the
                # client has locks of its own; no order edge wanted).
                # Resumable failures: a disconnect (TransportError), or
                # a REMOTE StorageError (structured remote_kind stamp,
                # net/wire.py) — the supplier answered with a typed ERR
                # frame on a healthy stream, so every chunk ingested
                # before it is valid and a transient pread failure must
                # not cost a full refetch (a per-call fault probability
                # compounds over a partition's chunk count, so refetch-
                # from-zero retries lose ground they never recover —
                # the chaos error-schedule livelock shape). A LOCAL
                # StorageError (no remote_kind) still restarts from
                # zero: that class includes the resume-identity
                # invalidation below, which exists to force exactly
                # that restart.
                remote_storage = (isinstance(result, StorageError)
                                  and getattr(result, "remote_kind",
                                              None) is not None)
                resumable = (self.resume_enabled
                             and (isinstance(result, TransportError)
                                  or remote_storage)
                             and self.client.resume_ok(self.host))
                with self._lock:
                    if self._done.is_set():
                        # administratively failed (fail()) while this
                        # attempt was in flight: the segment's fate is
                        # sealed — retrying into a dead job would only
                        # burn backoff timers and churn the penalty box
                        return
                    retry = self._retries_left > 0
                    if retry and self._deadline is not None \
                            and time.monotonic() >= self._deadline:
                        retry, deadline_hit = False, True
                    resume = retry and resumable and self._next_offset > 0
                    if retry and not resume:
                        self._retries_left -= 1
                        self.batches = []
                        self.num_records = 0
                        self._carry = b""
                        self._raw = None
                        self._next_offset = 0
                        self._crc_refetched.clear()
                        self._resume_check = False
                    elif resume:
                        self._retries_left -= 1
                        self._resume_check = True  # revalidate identity
                    offset = self._next_offset if resume else 0
                    attempt = self.policy.retries - self._retries_left
                    cands = list(self.hosts)
                self._notify_fault(result)
                if retry and not resume and len(cands) > 1 \
                        and self.ledger is not None:
                    # restart-from-zero retries re-rank the candidate
                    # list (which mid-job joiners may have WIDENED via
                    # add_host): a punished primary falls behind a
                    # healthy replica or joiner. Resumed retries must
                    # stay put — the offset ledger is only valid
                    # against the host that served it.
                    self.host = self.ledger.rank(cands)[0]
                if not retry:
                    if deadline_hit:
                        metrics.add("fetch.deadline_exceeded")
                        log.warn(f"fetch of {self.map_id} gave up: "
                                 f"deadline passed with retries left")
                    if self._try_recover(result):
                        return  # the reconstruction rung owns the
                        # segment now (completes it via _on_recovered)
                    self._finish(result)
                    return
                if resume:
                    metrics.add("fetch.resumed", supplier=self.supplier)
                    metrics.add("fetch.resumed.bytes", offset)
                    log.warn(f"fetch of {self.map_id} failed ({result}); "
                             f"resuming at offset {offset} "
                             f"({self._retries_left} retries left)")
                else:
                    log.warn(f"fetch of {self.map_id} failed ({result}); "
                             f"retrying ({self._retries_left} left)")
                tenant = self.tenant
                if tenant:
                    metrics.add("fetch.retries", supplier=self.supplier,
                                tenant=tenant)
                else:
                    metrics.add("fetch.retries", supplier=self.supplier)
                flightrec.record("segment.retry", map=self.map_id,
                                 supplier=self.supplier,
                                 error=type(result).__name__,
                                 resume=resume, left=self._retries_left)
                delay = self.policy.backoff(attempt, self._rng)
                if self._deadline is not None:
                    delay = min(delay,
                                max(0.0, self._deadline - time.monotonic()))
                if delay > 0:
                    # back off without blocking the completion thread
                    # (it may be a transport worker the retry needs)
                    metrics.add("fetch.backoff_seconds", delay)
                    t = threading.Timer(
                        delay,
                        lambda o=offset: self._drive(self._try_issue(o)))
                    t.daemon = True
                    t.start()
                    return
                result = self._try_issue(offset)
                continue
            if self._resume_check:
                # first chunk after a resumed retry: the partition's
                # identity must match what the ledger was built from —
                # a supplier restarted onto a DIFFERENT map attempt
                # must not splice two attempts' bytes together. The
                # StorageError (not a TransportError) forces the next
                # retry to restart from zero.
                with self._lock:
                    prev = self.raw_length
                    self._resume_check = False
                if prev is not None and result.raw_length != prev:
                    metrics.add("fetch.resume.invalidated")
                    result = StorageError(
                        f"partition {self.map_id} changed identity "
                        f"across the supplier restart (raw_length "
                        f"{result.raw_length} != {prev}); restarting "
                        f"the fetch from zero")
                    continue
            crc = getattr(result, "crc", None)
            if crc is not None and \
                    zlib.crc32(result.data) & 0xFFFFFFFF != crc:
                # integrity layer (uda.tpu.fetch.crc): one re-fetch per
                # offset; a second mismatch at the same offset becomes a
                # transport-level error and consumes the retry budget
                metrics.add("fetch.crc_mismatch")
                off = result.offset
                if off not in self._crc_refetched:
                    self._crc_refetched.add(off)
                    metrics.add("fetch.crc_refetch")
                    log.warn(f"chunk CRC mismatch at {self.map_id}:{off}; "
                             f"re-fetching once")
                    result = self._try_issue(off)
                    continue
                result = StorageError(
                    f"chunk CRC mismatch at {self.map_id}:{off} persists "
                    f"after re-fetch")
                continue
            try:
                last = self._ingest(result)
            except Exception as e:  # crack errors -> surfaced to waiter
                self._finish(e)
                return
            # notify exactly once, outside _ingest's try scope: an
            # exception thrown by the on_done callback itself must NOT
            # re-enter the error path above and fire on_done a second
            # time (double credit release / double progress count)
            if last:
                self._finish(None)
                return
            result = self._try_issue(self._next_offset)

    def _ingest(self, res: FetchResult) -> bool:
        """Absorb one chunk; returns True when the segment is complete.
        Never calls callbacks and never touches them under self._lock —
        the completion callback may call record_batch(), which takes the
        same (non-reentrant) lock on this same thread."""
        # the chunk's whole cracking — the carry concatenation (a copy
        # of the chunk), crack_partial, the tail slice — is the
        # fetch_crack timer. With spans on it is metrics.timer under the
        # segment's span (the upcall thread has no ambient context, and
        # a span outside the task's trace is one critpath never sees);
        # with spans off two stamps, and its counter rides the chunk's
        # one locked update below
        with self._lock:
            if self._raw is not None or not (res.is_last
                                             or self._next_offset):
                # the first chunk is not the last, or the segment is
                # deferred already: keep the bytes, crack nothing here
                crack_s = None
                last = self._keep(res)
            elif metrics.record_spans:
                crack_s = None
                with metrics.use_span(self.trace_span), \
                        metrics.timer("fetch_crack"):
                    last = self._absorb(res)
            else:
                t0 = time.perf_counter()
                last = self._absorb(res)
                crack_s = time.perf_counter() - t0
            issue_t0 = self._issue_t0
        # tenanted reduce tasks label the hot-path fetch metrics;
        # untenanted jobs keep the exact two-series shape of PRs 2-13
        nbytes = len(res.data)
        k_bytes, k_chunks = _chunk_keys(self.supplier, self.tenant)
        if crack_s is None:
            metrics.add_keyed((k_bytes, nbytes), (k_chunks, 1.0))
        else:
            metrics.add_keyed((k_bytes, nbytes), (k_chunks, 1.0),
                              (_K_CRACK, crack_s))
        if metrics.stats_enabled:
            tenant = {"tenant": self.tenant} if self.tenant else {}
            metrics.observe("fetch.latency_ms",
                            (time.perf_counter() - issue_t0) * 1e3,
                            supplier=self.supplier, **tenant)
            metrics.observe("fetch.chunk.bytes", nbytes, **tenant)
        return last

    def _keep(self, res: FetchResult) -> bool:
        """self._lock held: keep a deferred segment's chunk as it came
        (no copy, no crack); record_batch() cracks the lot."""
        if self._raw is None:
            self._raw = []
        self.raw_length = res.raw_length
        if len(res.data):
            self._raw.append(res.data)
        self._next_offset = res.offset + len(res.data)
        return res.is_last

    def _absorb(self, res: FetchResult) -> bool:
        """self._lock held: crack the chunk onto the carried tail."""
        self.raw_length = res.raw_length
        data = self._carry + res.data
        last = res.is_last
        if last and not data:
            # legitimately empty partition (raw_length == 0: a byte
            # range with no records and no EOF marker, as foreign
            # writers may produce for empty reducers)
            self._carry = b""
        else:
            # crack up to the last complete record; keep the tail
            batch, consumed, _ = crack_partial(data, expect_eof=last)
            if batch.num_records:
                self.batches.append(batch)
                self.num_records += batch.num_records
            self._carry = data[consumed:] if not last else b""
            self._next_offset = res.offset + len(res.data)
        return last

    def _try_recover(self, cause: Exception) -> bool:
        """The post-retry reconstruction rung: rebuild the partition
        from any k of its n stripe chunks (uda_tpu.coding). One-shot;
        returns False when coding is off or the transport cannot
        recover — the caller then finishes the segment with ``cause``
        exactly as before."""
        if self.stripe is None or self._recover_tried:
            return False
        self._recover_tried = True
        with self._lock:
            # the recovery replaces the whole partition: drop whatever
            # partial state the failed attempts left behind
            self.batches = []
            self.num_records = 0
            self._carry = b""
            self._raw = None
            self._next_offset = 0
            self._resume_check = False
            self._issue_t0 = time.perf_counter()
        # anchor placement at the WRITER's primary (hosts[0] — the map
        # entry's first host), never the current source: rank-picks and
        # speculation wins move self.host, but the stripe was placed by
        # rotation from where the map was written
        req = ShuffleRequest(self.job_id, self.map_id, self.reduce_id,
                             0, self.chunk_size, host=self.hosts[0])
        metrics.add("coding.recover.attempts", supplier=self.supplier)
        log.warn(f"fetch of {self.map_id} exhausted retries ({cause}); "
                 f"attempting k-of-n stripe reconstruction")
        try:
            with metrics.use_span(self.trace_span):
                return bool(self.client.recover_partition(
                    req, self.stripe, self._on_recovered))
        except Exception as e:  # noqa: BLE001 - a recovery that cannot
            # even start must fall through to the terminal path, not
            # escape into the completion thread
            metrics.add("coding.recover.failures")
            log.warn(f"stripe reconstruction of {self.map_id} could "
                     f"not start: {e}")
            return False

    def _on_recovered(self, result) -> None:
        """Reconstruction completion: a full-partition FetchResult (the
        decoded on-disk bytes, decompressed by any wrapper on the way
        up) or the reconstruction's terminal error."""
        if isinstance(result, Exception):
            metrics.add("coding.recover.failures")
            self._finish(result)
            return
        try:
            last = self._ingest(result)
        except Exception as e:  # noqa: BLE001 - crack errors surface to
            # the waiter like any fetched chunk's would
            self._finish(e)
            return
        self._finish(None if last else MergeError(
            f"stripe reconstruction of {self.map_id} delivered a "
            f"non-final chunk"))

    def fail(self, exc: Exception) -> bool:
        """Administratively terminate the fetch (watchdog rescue / stop-
        path drain): the segment completes NOW with ``exc`` and every
        waiter wakes. The outstanding attempts' epochs are invalidated,
        so a transport completion that eventually arrives (e.g. a wedged
        worker finishing minutes later) is dropped as stale instead of
        double-driving the state machine. Returns False when the segment
        had already finished (success or error) — fail() never rewrites
        history. Safe from any thread; fires on_done (credit release)
        exactly once like every other terminal path.

        The failing supplier rides the STRUCTURED cause: ``exc`` gains
        a ``supplier`` attribute (first unset wins — a shared stop-path
        error keeps its first attribution) and the recovery ledger gets
        an exact per-segment record, so downstream consumers never
        parse reason strings (UDA005)."""
        with self._lock:
            if self._done.is_set():
                return False
            open_attempts = self._open_attempts
            self._open_attempts = 0
            self._next_epoch += 1     # outstanding completions -> stale
            self._epoch = self._next_epoch
            self._spec = None
            self._attempt_hosts.clear()
            self._epoch_settled = True
        if open_attempts:
            # settle the abandoned attempts' on-air accounting (their
            # own completions, if they ever land, see a stale epoch and
            # must not decrement a second time)
            metrics.gauge_add("fetch.on_air", -open_attempts)
        self._cancel_timeout()
        attribute_supplier(exc, self.supplier)
        if self.ledger is not None:
            self.ledger.record("admin_fail", supplier=self.supplier,
                               map_id=self.map_id, error=exc)
        if not self._finish(exc):
            return False  # a real terminal path won the race
        metrics.add("fetch.failed_admin")
        flightrec.record("segment.admin_fail", map=self.map_id,
                         supplier=self.supplier,
                         error=type(exc).__name__)
        return True

    # -- consumption --------------------------------------------------------

    def wait(self, timeout: Optional[float] = None) -> None:
        if not self._done.wait(timeout=timeout):
            raise MergeError(f"segment {self.map_id} fetch timed out")
        if self._error is not None:
            raise self._error

    @property
    def ready(self) -> bool:
        return self._done.is_set() and self._error is None

    def record_batch(self) -> RecordBatch:
        """All records of the partition as one batch (fetch must be
        done). A deferred segment is cracked here, by the first caller
        (a stage worker of the overlapped merger; the merge thread on
        the serial routes), and corrupt framing raises its StorageError
        here. The batch is cached: callers on different threads (the
        overlap staging thread, then the finish pass) pay for it once."""
        self.wait()
        with self._lock:
            if self._released:
                raise MergeError(
                    f"segment {self.map_id} bytes were released "
                    f"(spooled to a sorted run, or the stream is out)")
            if self._raw is not None:
                self._crack_kept()
            if len(self.batches) == 1:
                return self.batches[0]
            cat = RecordBatch.concat(self.batches)
            self.batches = [cat]
            return cat

    def _crack_kept(self) -> None:
        """self._lock held: the deferred crack — join the kept chunks
        (the one copy) and crack the partition in one call. The
        fetch_crack timer, under the segment's own span (ended already,
        still the parent: a stage worker's ambient span must not adopt
        it) as the eager crack is. The chunks go only once the batch
        stands, so a corrupt stream raises for every caller."""
        with metrics.use_span(self.trace_span), \
                metrics.timer("fetch_crack"):
            views = [np.frombuffer(c, np.uint8) for c in self._raw]
            # no bytes at all: the legitimately empty partition of
            # _absorb, no records and no EOF marker
            batch = crack(np.concatenate(views)) if views else None
        if batch is not None and batch.num_records:
            self.batches = [batch]
            self.num_records = batch.num_records
        self._raw = None
        metrics.add("fetch.crack.deferred_segments")

    def fetched_records(self) -> int:
        """The fetch side's count of a finished segment's records, for
        a caller that holds what was staged to what was fetched. A
        deferred segment that nobody materialized is cracked for it, so
        that fetched and never staged reads as records missing, not as
        none fetched."""
        with self._lock:
            if self._raw is not None:
                self._crack_kept()
            return self.num_records

    def release(self) -> None:
        """Drop the fetched bytes (streaming online mode: the sorted run
        file is now the source of truth; the in-memory route: the
        stream is out; ``num_records`` survives for accounting).
        record_batch() raises after this."""
        with self._lock:
            self.batches = []
            self._raw = None
            self._released = True

    # -- checkpoint (merger/checkpoint.py) ----------------------------------

    def ckpt_export(self) -> Optional[dict]:
        """Snapshot this segment's fetch offset ledger for a checkpoint
        manifest: the cracked batches re-framed (IFile framing, no EOF)
        plus the carry tail, with the offsets that make the state
        resumable. None when there is nothing worth persisting — the
        segment is done/released (its run file carries the records) or
        has fetched nothing yet (a fresh fetch costs the same).

        Crash-consistent by construction: state is copied under the
        segment lock (batches and kept chunks are immutable once
        appended and ``_next_offset`` advances in the same critical
        section as the append, so the copy is internally consistent);
        the re-framing runs outside the lock. A deferred segment cracks
        what it holds here, to find where its last whole record ends:
        its bytes are the framed records and the carry tail already."""
        with self._lock:
            if self._done.is_set() or self._released \
                    or self._next_offset <= 0:
                return None
            batches = list(self.batches)
            carry = self._carry
            raw = None if self._raw is None else list(self._raw)
            state = {"next_offset": self._next_offset,
                     "raw_length": self.raw_length,
                     "num_records": self.num_records,
                     "carry_len": len(carry)}
        if raw is not None:
            data = b"".join(raw)
            batch, consumed, _ = crack_partial(data, expect_eof=False)
            state.update(num_records=batch.num_records,
                         carry_len=len(data) - consumed, data=data)
            return state
        from uda_tpu import native

        framed = b"".join(native.frame_batch(b, write_eof=False)
                          for b in batches)
        state["data"] = framed + bytes(carry)
        return state

    def ckpt_preload(self, *, data: bytes, carry_len: int,
                     next_offset: int, raw_length, num_records: int) -> None:
        """Restore a checkpointed offset ledger BEFORE start(): re-crack
        the persisted framed bytes, verify they account for exactly the
        recorded records, and arm the resume (start() then issues at
        ``next_offset`` and the first chunk revalidates identity).
        Raises :class:`StorageError` on any mismatch — the caller drops
        the ledger and the segment fetches from zero."""
        framed_len = len(data) - int(carry_len)
        if framed_len < 0:
            raise StorageError(
                f"checkpoint ledger of {self.map_id}: carry "
                f"{carry_len} B exceeds payload {len(data)} B")
        batch, consumed, _ = crack_partial(bytes(data[:framed_len]),
                                           expect_eof=False)
        if consumed != framed_len or batch.num_records != int(num_records):
            raise StorageError(
                f"checkpoint ledger of {self.map_id} re-cracked to "
                f"{batch.num_records} records/{consumed} B, manifest "
                f"says {num_records}/{framed_len}")
        with self._lock:
            if self._next_epoch:
                raise StorageError(
                    f"ckpt_preload of {self.map_id} after start()")
            self.batches = [batch] if batch.num_records else []
            self.num_records = int(num_records)
            self._carry = bytes(data[framed_len:])
            self._next_offset = int(next_offset)
            self.raw_length = (int(raw_length) if raw_length is not None
                               else None)
            self._resume_check = True  # first chunk revalidates identity


