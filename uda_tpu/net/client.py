"""RemoteFetchClient: the reduce-side endpoint on the shared event loop.

The reduce side of the data plane rebuilt on the selector core
(:mod:`uda_tpu.net.evloop`): every supplier connection of every client
in the process is multiplexed onto ONE shared loop thread (the
reference ran one completion-channel epoll thread for all QPs,
RDMAClient.cc:498-527 + RDMAComm.cc), replacing PR 4's blocking reader
thread per host. The contract is the threaded client's, exactly:

- ONE multiplexed connection per supplier host, request-id correlation
  table, completions dispatched out of order;
- a dead connection (EOF, torn frame, decode error, send failure)
  fails EVERY in-flight request with ``TransportError`` — each flows
  into its Segment's retry/penalty/fallback machinery independently —
  and the next ``start_fetch`` dials fresh (connection identity is the
  epoch: frames from a dead connection can never complete new
  requests, and request ids are never reused);
- typed ERR frames re-raise the server-side error class;
- ``estimate_partition_bytes`` rides the same connection (SIZE
  frames), best effort, exact-or-unknown.

Receive path: the frame header lands in a REUSABLE per-connection
buffer via ``recv_into``; the payload is then received straight into a
single per-frame bytearray (``recv_into`` a sliced memoryview — no
accumulate-and-join), and :func:`uda_tpu.net.wire.decode_result`
parses meta fields in place so the one ``bytes()`` of the chunk region
is the ONLY reduce-side heap copy per chunk (the threaded core made
three).

Completion upcalls (``on_complete`` — Segment code that may block on
arena admission) run on the loop's dispatcher thread, never the loop
thread itself, so one slow consumer cannot stall the whole process's
fetch plane (UDA008 discipline; the reference's completion-channel
upcall thread).

Failpoints: ``net.connect`` per dial (evaluated on the CALLER thread —
a delay models a slow handshake without stalling the shared loop);
``net.frame`` per outbound request frame, also on the caller thread
(truncation queues the torn bytes and then tears the connection down
deterministically after they flush).
"""

from __future__ import annotations

import selectors
import socket
import threading
import time
from collections import deque
from typing import Callable, Optional, Sequence

from uda_tpu.merger.segment import InputClient
from uda_tpu.mofserver.data_engine import ShuffleRequest
from uda_tpu.net import wire
from uda_tpu.net.evloop import EventLoop, loop_callback, shared_client_loop
from uda_tpu.utils.config import Config
from uda_tpu.utils.errors import ProtocolError, TransportError, UdaError
from uda_tpu.utils.failpoints import failpoint
from uda_tpu.utils.locks import TrackedLock
from uda_tpu.utils.logging import get_logger
from uda_tpu.utils.metrics import metrics

__all__ = ["RemoteFetchClient", "EvLoopFetchClient"]

log = get_logger()

_READ = selectors.EVENT_READ
_WRITE = selectors.EVENT_WRITE

_SIZE_PROBE_TIMEOUT_S = 30.0

# a chunk's stage counters, keys built once (one locked update a chunk)
_K_TIMED = metrics.series("fetch.chunk.timed")
_K_PARK = metrics.series("fetch.chunk.park_seconds")
_K_SERVE = metrics.series("fetch.chunk.serve_seconds")
_K_WIRE = metrics.series("fetch.chunk.wire_seconds")


class _Waiter:
    """One in-flight request's completion slot."""

    __slots__ = ("on_complete", "span", "t0", "t_decoded", "wait_span")

    def __init__(self, on_complete: Callable, span, t0: float):
        self.on_complete = on_complete
        self.span = span
        self.t0 = t0            # posted
        # a DATA frame's decode time and the open net.dispatch.wait
        # span (_complete -> _deliver: the dispatch-queue wait)
        self.t_decoded = 0.0
        self.wait_span = None


class _ClientConn:
    """One connection's loop-side state machine (loop thread owns every
    field except ``dead``, which other threads may READ)."""

    def __init__(self, client: "EvLoopFetchClient", loop: EventLoop,
                 sock: socket.socket):
        self.client = client
        self.loop = loop
        self.sock = sock
        self.dead = False
        # write side: any thread may send inline under _wlock (the
        # opportunistic-write fast path — a fetch's REQ frame normally
        # leaves on the ISSUING thread, no loop hop, no wakeup)
        self._wlock = TrackedLock("net.client.write")
        self._outq: "deque" = deque()  # [memoryview, close_after] pairs
        self._poison = False
        self._mask = 0
        # reassembly: reusable header buffer; payload received straight
        # into its own per-frame buffer (no intermediate copies)
        self._hdr = bytearray(wire.HEADER.size)
        self._hdr_got = 0
        self._payload: Optional[bytearray] = None
        self._pay_got = 0
        self._cur = (0, 0)
        # (job, reduce) push subscriptions already SUB'd on THIS
        # connection — per connection by construction, so a reconnect
        # re-subscribes from scratch (the server's tables died with
        # the old socket)
        self.push_subbed: set = set()

    # -- registration --------------------------------------------------------

    @loop_callback
    def register(self) -> None:
        if self.dead:
            return
        self.loop.register(self.sock, _READ, self._on_event)
        self._mask = _READ

    def _update_interest(self) -> None:
        if self.dead:
            return
        mask = _READ | (_WRITE if self._outq else 0)
        if mask != self._mask:
            self.loop.set_events(self.sock, mask)
            self._mask = mask

    @loop_callback
    def _kick(self) -> None:
        self._update_interest()

    # -- outbound (any thread; _wlock serializes writers) --------------------

    def send_frame(self, data: bytes, close_after: bool = False) -> None:
        """Queue one frame and opportunistically write it NOW on the
        calling thread; the loop takes over only a would-block
        residual. Callable from any thread."""
        backlog = False
        with self._wlock:
            if self.dead or self._poison:
                return  # teardown fails this frame's waiter
            self._outq.append([memoryview(data), close_after])
            err = self._drain_locked()
            backlog = bool(self._outq) and not self._poison
        if err is not None:
            self.loop.call_soon(self.die, err)
        elif backlog:
            self.loop.call_soon(self._kick)

    def _drain_locked(self) -> Optional[Exception]:
        """_wlock held: send from the queue head until it would block.
        Returns a fatal error (send failure or a completed torn frame)
        or None."""
        while self._outq and not self._poison:
            ent = self._outq[0]
            try:
                n = self.sock.send(ent[0])
            except (BlockingIOError, InterruptedError):
                return None
            except OSError as e:
                self._poison = True
                return e
            metrics.add("net.bytes.out", n, role="client")
            if n < len(ent[0]):
                ent[0] = ent[0][n:]
                continue
            self._outq.popleft()
            if ent[1]:
                # we knowingly desynced the server's stream (torn
                # net.frame): finish the damage deterministically
                self._poison = True
                return TransportError("request frame torn by failpoint")
        return None

    @loop_callback
    def _flush(self) -> None:
        with self._wlock:
            err = self._drain_locked()
        if err is not None:
            self._die(err)
            return
        self._update_interest()

    # -- inbound -------------------------------------------------------------

    @loop_callback
    def _on_event(self, mask: int) -> None:
        if self.dead:
            return
        if mask & _WRITE:
            self._flush()
        if self.dead:
            return
        if mask & _READ:
            # the transitive recv_into is on THIS loop's non-blocking
            # socket: it returns EWOULDBLOCK instead of parking
            self._do_read()  # udalint: disable=UDA102

    def _do_read(self) -> None:
        # Fill-based recv batching, straight into the final destination
        # (header buffer or the frame's own payload buffer): keep
        # reading only while each recv FILLS what it asked for (more is
        # certainly buffered — a full header is followed by its payload
        # without a select round trip), stop on the first partial
        # return instead of spinning to EAGAIN. On emulated-syscall
        # kernels an empty-handed EAGAIN probe costs as much as a full
        # recv, and stopping early lets bytes batch up in the
        # (sockbuf-sized) kernel buffer between calls — level-triggered
        # epoll re-fires while anything remains.
        while not self.dead:
            if self._payload is None:
                dest = memoryview(self._hdr)[self._hdr_got:]
            else:
                dest = memoryview(self._payload)[self._pay_got:]
            want = len(dest)
            try:
                n = self.sock.recv_into(dest)
            except (BlockingIOError, InterruptedError):
                return
            except OSError as e:
                self._die(e)
                return
            finally:
                # drop the export BEFORE decoding: the buffer-donating
                # decode resizes the payload bytearray in place, which
                # a live memoryview would veto (BufferError)
                dest.release()
            if n == 0:
                self._die(TransportError("supplier closed the connection"))
                return
            metrics.add("net.bytes.in", n, role="client")
            try:
                self._advance(n)
            except TransportError as e:
                self._die(e)
                return
            if n < want:
                return  # kernel buffer drained (or nearly) — back to
                # select; let the next burst accumulate

    def _advance(self, n: int) -> None:
        if self._payload is None:
            self._hdr_got += n
            if self._hdr_got == wire.HEADER.size:
                msg_type, req_id, length = wire.decode_header(
                    bytes(self._hdr))
                self._cur = (msg_type, req_id)
                self._payload = bytearray(length)
                self._pay_got = 0
                if length == 0:
                    self._frame_done()
        else:
            self._pay_got += n
            if self._pay_got == len(self._payload):
                self._frame_done()

    def _frame_done(self) -> None:
        msg_type, req_id = self._cur
        payload = self._payload
        self._payload = None
        self._hdr_got = 0
        if msg_type == wire.MSG_DATA:
            # buffer-donating decode: the per-frame receive buffer
            # BECOMES FetchResult.data (one short memmove for the meta
            # prefix, no chunk-sized allocation or copy)
            result = wire.decode_result_take(payload)
        elif msg_type == wire.MSG_ERR:
            result = wire.decode_error(memoryview(payload))
        elif msg_type == wire.MSG_SIZE:
            result = wire.decode_size(memoryview(payload))
        elif msg_type == wire.MSG_JOB_OK:
            result = wire.decode_job_ok(payload)
        elif msg_type == wire.MSG_STATS_REPLY:
            result = wire.decode_stats_reply(memoryview(payload))
        elif msg_type == wire.MSG_HELLO:
            # the accept banner correlates with no request: record the
            # server generation (warm-restart continuity) and its
            # capability bits (trace-context frames, MSG_STATS), then
            # move on
            generation, warm, caps = wire.decode_hello_ex(bytes(payload))
            self.client._on_hello(generation, warm, caps)
            return
        elif msg_type == wire.MSG_PUSH:
            # supplier-initiated chunk (ISSUE 19): only arrives on
            # connections that PUSH_SUB'd. Admission (budget route,
            # possible spill write) blocks — dispatcher thread, never
            # the loop
            self.client._on_push(self, req_id, payload)
            return
        else:
            raise TransportError(
                f"unexpected frame type {msg_type} on the client side")
        self.client._complete(self, req_id, result, msg_type)

    # -- teardown ------------------------------------------------------------

    def _die(self, cause: Exception) -> None:
        """Loop thread: close this connection and fail everything in
        flight on it (via the client, which owns the table)."""
        if self.dead:
            return
        self.dead = True
        with self._wlock:
            self._poison = True
            self._outq.clear()
        self.loop.unregister(self.sock)
        wire.close_hard(self.sock)
        self.client._on_conn_dead(self, cause)

    @loop_callback
    def die(self, cause: Exception) -> None:
        self._die(cause)

    @loop_callback
    def close_quiet(self) -> None:
        """Stop-path close: the client already settled its own table,
        gauges and waiters — just release the loop/socket resources."""
        if self.dead:
            return
        self.dead = True
        with self._wlock:
            self._poison = True
            self._outq.clear()
        self.loop.unregister(self.sock)
        wire.close_hard(self.sock)


class EvLoopFetchClient(InputClient):
    """Multiplexed fetch client for one supplier host, on the shared
    process-wide event loop."""

    def __init__(self, host: str, port: Optional[int] = None,
                 config: Optional[Config] = None):
        cfg = config or Config()
        self.host = host
        self.port = int(port if port is not None
                        else cfg.get("uda.tpu.net.port"))
        self.connect_timeout_s = float(
            cfg.get("uda.tpu.net.connect.timeout.s"))
        self.sockbuf_kb = int(cfg.get("uda.tpu.net.sockbuf.kb"))
        # lockdep-tracked: PR 4's deadlock class lived exactly here
        self._lock = TrackedLock("net.client")  # table + conn identity
        self._conn: Optional[_ClientConn] = None
        self._pending: dict = {}       # req_id -> _Waiter
        self._next_id = 0              # never reused across connections
        self._stopped = False
        # warm-restart continuity (the HELLO accept banner): the last
        # observed server generation, and whether a resumed offset
        # ledger is still continuous with this supplier's bytes
        self._generation: Optional[int] = None
        self._resumable = True
        # peer capability bits from the HELLO banner (wire.CAP_TRACE:
        # the peer decodes trace-context REQ tails + serves MSG_STATS;
        # wire.CAP_TENANT: the peer runs the tenant registry).
        # 0 until the banner lands — frames sent before it stay
        # un-extended, which is always legal.
        self._peer_caps = 0
        self._hello_seen = threading.Event()
        # multi-tenant binding (uda_tpu/tenant/): when a tenant id is
        # configured, the FIRST fetch of each job on each connection is
        # preceded by an authenticated MSG_JOB frame binding
        # (tenant, job, epoch) in the supplier's registry — TCP
        # ordering makes register-before-fetch a wire guarantee. Empty
        # tenant = the pre-tenancy client, frame for frame.
        self._tenant = str(cfg.get("uda.tpu.tenant.id"))
        self._tenant_epoch = max(1, int(cfg.get("uda.tpu.tenant.epoch")))
        self._tenant_weight = max(1,
                                  int(cfg.get("uda.tpu.tenant.weight")))
        self._tenant_secret = str(cfg.get("uda.tpu.tenant.secret"))
        # jobs MSG_JOB'd on THIS conn: job -> Event set once the bind
        # frame is ON THE WIRE. Register-before-fetch must hold across
        # concurrent first fetches of one job: the loser of the bind
        # race waits for the winner's frame to be posted before its
        # REQ may leave, or the REQ could overtake the MSG_JOB and
        # land unregistered (typed refusal under strict, a silent
        # default-tenant pass otherwise).
        self._bound_jobs: dict = {}
        # push plane (ISSUE 19): (job, reduce) -> PushStaging. The
        # registration OUTLIVES connections — every fresh banner that
        # advertises CAP_PUSH gets the subscriptions re-sent (the
        # per-conn sent-set lives on the connection object).
        self._push_staging: dict = {}
        self._push_window = max(1, int(cfg.get("uda.tpu.push.window")))
        self._push_chunk = int(cfg.get("mapred.rdma.buf.size")) * 1024

    def _on_hello(self, generation: int, warm: bool,
                  caps: int = 0) -> None:
        """Loop thread (first frame of every connection). A CHANGED
        generation is a supplier restart: warm (handoff-continued)
        keeps resume legal, cold revokes it — a cold supplier may hold
        a different attempt's bytes, so retrying segments must restart
        from zero (their raw_length identity check is the backstop
        either way)."""
        with self._lock:
            prev = self._generation
            self._generation = generation
            self._peer_caps = caps
            if prev is not None and generation != prev and not warm:
                # STICKY: a later warm bounce must not re-legalize
                # resume — a segment's offset ledger may predate the
                # cold generation, and the warm flag only certifies
                # continuity with the generation it succeeded. Segments
                # created after this client object are conservative by
                # one refetch; correctness wins.
                self._resumable = False
        self._hello_seen.set()
        if prev is not None and generation != prev:
            metrics.add("net.generation.changes", host=self.host,
                        warm=str(bool(warm)).lower())
            log.warn(f"net: supplier {self.host}:{self.port} restarted "
                     f"(generation {prev} -> {generation}, "
                     f"{'warm' if warm else 'COLD'})")

    def resume_ok(self, host: str = "") -> bool:
        """May a retrying segment keep its offset ledger against this
        supplier? True until a COLD restart is observed (see
        _on_hello); optimistic across an unresolved reconnect — the
        resumed fetch's identity check revalidates on the first
        chunk."""
        with self._lock:
            return self._resumable

    def generation(self, host: str = "") -> Optional[int]:
        """Last HELLO generation observed from this supplier (None until
        the first handshake). Checkpoint manifests record it so a resume
        can tell a same-generation supplier (ledger still valid) from a
        restarted one (drop the ledger, keep the run files)."""
        with self._lock:
            return self._generation

    def peer_caps(self, host: str = "") -> int:
        """Last HELLO capability bits (0 until the first handshake —
        also the correct conservative answer: no advertised cap means
        no optional behavior)."""
        with self._lock:
            return self._peer_caps

    def peer_draining(self, host: str = "") -> bool:
        """Did the last banner carry CAP_DRAINING? A draining supplier
        still serves (in-flight work completes) but the candidate
        ranking demotes it so speculation/replica reads prefer staying
        members (segment.py HostRoutingClient / merge_manager)."""
        with self._lock:
            return bool(self._peer_caps & wire.CAP_DRAINING)

    # -- connection management ----------------------------------------------

    def _ensure_connected(self) -> _ClientConn:
        """The live connection, dialing fresh when there is none. The
        dial itself is blocking WITH a timeout and runs on the caller's
        thread (never the loop); a failed dial raises TransportError and
        the Segment's RetryPolicy paces the reconnects."""
        with self._lock:
            if self._stopped:
                raise TransportError(
                    f"RemoteFetchClient({self.host}) is stopped")
            if self._conn is not None:
                return self._conn
        failpoint("net.connect", key=f"{self.host}:{self.port}")
        try:
            sock = socket.create_connection(
                (self.host, self.port), timeout=self.connect_timeout_s)
        except OSError as e:
            metrics.add("net.connect.failures", host=self.host)
            raise TransportError(
                f"connect to supplier {self.host}:{self.port} failed: "
                f"{e}") from e
        sock.setblocking(False)
        wire.tune_socket(sock, self.sockbuf_kb)
        loop = shared_client_loop()
        conn = _ClientConn(self, loop, sock)
        with self._lock:
            if self._stopped or self._conn is not None:
                # lost the dial race (or stopped underneath): keep the
                # winner's connection
                wire.close_hard(sock)
                if self._stopped:
                    raise TransportError(
                        f"RemoteFetchClient({self.host}) is stopped")
                return self._conn
            self._conn = conn
        metrics.add("net.connects", host=self.host)
        metrics.gauge_add("net.client.connections", 1)
        loop.call_soon(conn.register)
        # bounded first-banner wait: the HELLO (first frame on every
        # accept) carries the peer's capability bits — without this, a
        # fetch racing the banner would always go un-extended and the
        # FIRST chunk of a trace would predictably lose its supplier
        # spans. Best-effort: timing out just means un-extended frames
        # (always legal), never an error. Reconnects wait too —
        # _on_conn_dead cleared the event and the caps, because the
        # peer behind host:port may have been REPLACED since the last
        # banner (stale CAP_TRACE against an old decoder tears frames).
        self._hello_seen.wait(timeout=min(2.0, self.connect_timeout_s))
        # re-subscribe the push plane on every fresh banner: the
        # server-side tables died with the previous socket (a timed-out
        # banner leaves caps=0 — no SUB, pull-only, always legal)
        self._send_push_subs(conn)
        return conn

    def _trace_of(self, span) -> Optional[tuple]:
        """The wire trace-context tail for one outbound frame: this
        request's OWN span ids (the supplier's serve span becomes its
        child), sent only when the peer's HELLO advertised
        wire.CAP_TRACE — an old decoder would tear on trailing
        bytes."""
        if span is None or span.span_id is None:
            return None  # spans disabled (noop span)
        with self._lock:
            if not self._peer_caps & wire.CAP_TRACE:
                return None
        return span.trace_id, span.span_id

    def _on_conn_dead(self, conn: _ClientConn, cause: Exception) -> None:
        """Loop thread (via _die): fail every request in flight on this
        connection. Requests registered after a reconnect belong to the
        new connection object by construction — the table swaps under
        the same lock as the connection identity."""
        with self._lock:
            if self._conn is not conn:
                return  # the stop path (or an earlier _die) settled it
            self._conn = None
            orphans = list(self._pending.items())
            self._pending.clear()
            # capability state dies with the connection: the NEXT dial
            # may reach a replaced peer (e.g. a pre-CAP_TRACE binary),
            # and a stale trace bit would make every post-reconnect REQ
            # carry the 16-byte tail its strict decoder tears on.
            # Clearing _hello_seen restores the bounded first-banner
            # wait, same as a fresh dial. Generation/resume state is
            # deliberately KEPT — resume legality is judged against the
            # new banner's generation when it lands (_on_hello).
            self._peer_caps = 0
            self._hello_seen.clear()
            # tenant bindings are per connection (the server's registry
            # entry survives; the CONNECTION's binding does not) — the
            # next fetch re-sends MSG_JOB before its REQ
            self._bound_jobs.clear()
        metrics.gauge_add("net.client.connections", -1)
        metrics.add("net.disconnects", role="client")
        err = TransportError(
            f"connection to supplier {self.host}:{self.port} lost "
            f"({type(cause).__name__}: {cause}); "
            f"{len(orphans)} fetches in flight")
        for req_id, waiter in orphans:
            waiter.span.end(error="disconnect")
            # completion upcalls may block (and may re-issue fetches):
            # dispatcher thread, same FIFO as normal completions
            conn.loop.dispatch(self._deliver, req_id, waiter, err)

    def _complete(self, conn: _ClientConn, req_id: int, result,
                  msg_type: int) -> None:
        """Loop thread: correlate one decoded frame to its waiter and
        hand the upcall to the dispatcher (the completing connection's
        own loop — no global-lock rediscovery on the per-frame path)."""
        with self._lock:
            waiter = self._pending.pop(req_id, None)
        if waiter is None:
            # dead-connection leftovers / cancelled probe: count, move on
            metrics.add("net.frames.orphaned")
            return
        now = time.perf_counter()
        if msg_type != wire.MSG_SIZE:
            metrics.observe("net.frame.latency_ms",
                            (now - waiter.t0) * 1e3, role="client")
        if isinstance(result, Exception):
            waiter.span.end(error=type(result).__name__)
        elif msg_type == wire.MSG_DATA:
            self._account_chunk(waiter, result.timing, now)
        else:
            waiter.span.end()
        conn.loop.dispatch(self._deliver, req_id, waiter, result)

    @staticmethod
    def _account_chunk(waiter: _Waiter, timing, now: float) -> None:
        """Loop thread, one DATA frame decoded: split posted -> decoded
        (chunk-seconds: a window of fetches is in flight, so the stages
        sum past the wall). ``timing`` is the supplier's own (park_us,
        serve_us) report, present only when this side's spans put the
        trace tail on the REQ; wire is the remainder — both loops, both
        socket queues, the supplier's send — and, with no report, the
        whole remote time. Then stamp the hand-off to the one upcall
        thread: ``_deliver`` closes it as the dispatch-queue wait."""
        remote = now - waiter.t0
        waiter.t_decoded = now
        if timing is None:
            waiter.span.end()
            metrics.add_keyed((_K_WIRE, remote))
        else:
            waiter.span.end(park_us=timing[0], serve_us=timing[1])
            park, serve = timing[0] * 1e-6, timing[1] * 1e-6
            metrics.add_keyed((_K_TIMED, 1.0), (_K_PARK, park),
                              (_K_SERVE, serve),
                              (_K_WIRE, max(remote - park - serve, 0.0)))
        if metrics.record_spans:
            waiter.wait_span = metrics.start_span("net.dispatch.wait",
                                                  parent=waiter.span)

    def _deliver(self, req_id: int, waiter: _Waiter, result) -> None:
        """Dispatcher thread: the actual upcall."""
        if waiter.t_decoded:
            metrics.add("fetch.chunk.dispatch_wait_seconds",
                        time.perf_counter() - waiter.t_decoded)
            if waiter.wait_span is not None:
                waiter.wait_span.end()
        try:
            waiter.on_complete(result)
        except Exception as e:  # noqa: BLE001 - one waiter's bug must
            # not starve every later completion of delivery
            log.warn(f"net: completion callback for req {req_id} "
                     f"raised: {e}")

    # -- the tenant handshake -----------------------------------------------

    def bind_tenant(self, tenant_id: str, epoch: int = 1,
                    weight: int = 1, secret: str = "") -> None:
        """Install (or change) this client's tenant identity — the
        programmatic twin of the ``uda.tpu.tenant.*`` knobs. A changed
        epoch re-binds each job on its next fetch."""
        with self._lock:
            self._tenant = str(tenant_id)
            self._tenant_epoch = max(1, int(epoch))
            self._tenant_weight = max(1, int(weight))
            if secret:
                self._tenant_secret = secret
            self._bound_jobs.clear()

    def _job_frame(self, req_id: int, job_id: str,
                   retire: bool = False) -> bytes:
        from uda_tpu.tenant import sign_job

        return wire.encode_job(
            req_id, self._tenant, job_id, self._tenant_epoch,
            weight=self._tenant_weight,
            token=sign_job(self._tenant_secret, self._tenant, job_id,
                           self._tenant_epoch),
            retire=retire)

    def _maybe_bind(self, conn: _ClientConn, job_id: str) -> None:
        """Send MSG_JOB for ``job_id`` ahead of its first REQ on this
        connection (fire-and-forget: a refusal comes back as a typed
        ERR on the MSG_JOB's req id — logged and counted; the
        subsequent REQs draw their own typed TenantErrors from the
        server's fence, which is what fails the fetch machinery).
        No-op without a configured tenant or a CAP_TENANT peer.
        Concurrent first fetches of one job serialize here: the bind
        race's winner posts the MSG_JOB frame and sets the job's
        event; losers WAIT on it (bounded) so no REQ can overtake the
        registration onto the wire."""
        with self._lock:
            if not self._tenant or self._conn is not conn \
                    or not self._peer_caps & wire.CAP_TENANT:
                return
            posted = self._bound_jobs.get(job_id)
            if posted is None:
                posted = threading.Event()
                self._bound_jobs[job_id] = posted
                self._next_id += 1
                req_id = self._next_id

                def on_bound(result) -> None:
                    if isinstance(result, Exception):
                        metrics.add("tenant.bind.errors")
                        log.warn(f"tenant bind of {self._tenant}/"
                                 f"{job_id} on {self.host} refused: "
                                 f"{result}")

                self._pending[req_id] = _Waiter(
                    on_bound, metrics.start_span("net.job_bind",
                                                 host=self.host),
                    time.perf_counter())
            else:
                req_id = None
        if req_id is None:
            # best-effort bound wait: a timeout degrades to the
            # server-side fence semantics, never an error here
            posted.wait(timeout=min(5.0, self.connect_timeout_s))
            return
        try:
            self._post(conn, self._job_frame(req_id, job_id))
        finally:
            posted.set()

    def _job_roundtrip(self, job_id: str, retire: bool,
                       timeout: float) -> int:
        """Blocking MSG_JOB round trip: returns the granted epoch or
        re-raises the typed registry refusal (tests, embedders that
        want registration confirmed before issuing work)."""
        conn = self._ensure_connected()
        box: list = [None]
        got = threading.Event()

        def on_reply(result) -> None:
            box[0] = result
            got.set()

        posted = threading.Event()
        with self._lock:
            if self._conn is not conn:
                raise TransportError(
                    f"connection to {self.host} lost before the "
                    f"MSG_JOB round trip")
            if not retire:
                self._bound_jobs[job_id] = posted
            self._next_id += 1
            req_id = self._next_id
            self._pending[req_id] = _Waiter(
                on_reply, metrics.start_span("net.job_bind",
                                             host=self.host,
                                             retire=retire),
                time.perf_counter())
        try:
            self._post(conn,
                       self._job_frame(req_id, job_id, retire=retire))
        finally:
            posted.set()
        if not got.wait(timeout=timeout):
            with self._lock:
                self._pending.pop(req_id, None)
            raise TransportError(
                f"MSG_JOB to {self.host} timed out after {timeout:g}s")
        result = box[0]
        if isinstance(result, Exception):
            if not retire:
                with self._lock:
                    self._bound_jobs.pop(job_id, None)
            raise result
        return int(result)

    # -- push plane (ISSUE 19) ----------------------------------------------

    def push_register(self, job_id: str, reduce_id: int, staging,
                      hosts=None) -> None:
        """Arm reduce-side staging for (job, reduce) and subscribe the
        supplier: committed partitions start arriving as MSG_PUSH
        chunks. Dial is eager (pushes need a live connection before
        the first fetch exists) but best-effort — a failed dial just
        leaves the plane pull-only until the next fetch redials, and
        a push-less peer (no CAP_PUSH in its banner) is never sent a
        SUB at all."""
        with self._lock:
            if self._stopped:
                return
            self._push_staging[(job_id, int(reduce_id))] = staging
        try:
            conn = self._ensure_connected()
        except TransportError:
            return
        self._send_push_subs(conn)

    def push_unregister(self, job_id: str, reduce_id: int) -> None:
        """Drop the staging registration. No un-SUB frame exists (nor
        needs to): a late push finds no staging, draws
        PUSH_NACK(UNKNOWN), and the supplier marks the partition
        pull-only — self-healing by the refusal path."""
        with self._lock:
            self._push_staging.pop((job_id, int(reduce_id)), None)

    def _send_push_subs(self, conn: _ClientConn) -> None:
        """Send MSG_PUSH_SUB for every registration not yet SUB'd on
        this connection (idempotent per conn; any thread). Fire and
        forget, the MSG_JOB discipline: a refusal would come back as a
        typed ERR with no waiter — counted as an orphan, and the plane
        simply stays pull-only."""
        frames = []
        with self._lock:
            if self._conn is not conn or not self._push_staging \
                    or not self._peer_caps & wire.CAP_PUSH:
                return
            for key in self._push_staging:
                if key in conn.push_subbed:
                    continue
                conn.push_subbed.add(key)
                self._next_id += 1
                frames.append(wire.encode_push_sub(
                    self._next_id, job_id=key[0], reduce_id=key[1],
                    window=self._push_window,
                    chunk_size=self._push_chunk))
        for frame in frames:
            self._post(conn, frame)

    def _on_push(self, conn: _ClientConn, push_id: int,
                 payload: bytearray) -> None:
        """Loop thread: hand the pushed chunk to the dispatcher —
        admission may write a spill file, and the verdict frame goes
        back inline from there."""
        conn.loop.dispatch(self._handle_push, conn, push_id, payload)

    def _handle_push(self, conn: _ClientConn, push_id: int,
                     payload: bytearray) -> None:
        """Dispatcher thread: decode, run the staging admission
        ladder, answer PUSH_ACK or PUSH_NACK."""
        from uda_tpu.net.push import NACK_UNKNOWN
        try:
            (job_id, map_id, reduce_id, offset, raw_length, last,
             data) = wire.decode_push_take(payload)
        except UdaError as e:
            conn.loop.call_soon(conn.die, e)
            return
        with self._lock:
            staging = self._push_staging.get((job_id, int(reduce_id)))
        if staging is None:
            metrics.add("push.refused", reason="unknown")
            verdict = NACK_UNKNOWN
        else:
            verdict = staging.offer(map_id, offset, raw_length, last,
                                    data)
        frame = (wire.encode_push_ack(push_id) if verdict == 0
                 else wire.encode_push_nack(push_id, verdict))
        self._post(conn, frame)

    def bind_job(self, job_id: str, timeout: float = 10.0) -> int:
        """Register (tenant, job, epoch) with the supplier and wait for
        the grant; raises the typed TenantError on refusal."""
        return self._job_roundtrip(job_id, retire=False, timeout=timeout)

    def retire_job(self, job_id: str, timeout: float = 10.0) -> int:
        """Retire the job in the supplier's registry (the lifecycle's
        final transition; the daemon drains the tenant's obligation
        books and later REQs draw typed errors)."""
        return self._job_roundtrip(job_id, retire=True, timeout=timeout)

    # -- InputClient --------------------------------------------------------

    def start_fetch(self, req: ShuffleRequest, on_complete) -> None:
        """Issue one fetch on the multiplexed connection. Completion
        (FetchResult, typed remote error, or disconnect TransportError)
        arrives on the shared dispatcher thread — the completion-
        channel upcall shape."""
        span = metrics.start_span(
            "net.fetch", host=self.host, map=req.map_id,
            reduce=req.reduce_id, offset=req.offset)
        try:
            conn = self._ensure_connected()
        except TransportError as e:
            span.end(error=type(e).__name__)
            on_complete(e)
            return
        # tenant plane: the job's MSG_JOB precedes its first REQ on
        # this connection (TCP order = registration order)
        self._maybe_bind(conn, req.job_id)
        with self._lock:
            died = self._conn is not conn
            if not died:
                self._next_id += 1
                req_id = self._next_id
                self._pending[req_id] = _Waiter(on_complete, span,
                                                time.perf_counter())
        if died:
            # connection died between dial and registration; complete
            # OUTSIDE the lock — the callback may re-issue immediately
            span.end(error="disconnect")
            on_complete(TransportError(
                f"connection to {self.host}:{self.port} lost before "
                f"the fetch was issued"))
            return
        self._post(conn, wire.encode_request(req_id, req,
                                             trace=self._trace_of(span)))

    def _post(self, conn: _ClientConn, frame: bytes) -> None:
        """Write one frame — inline on this thread when the socket has
        room (the fast path), via the loop for any residual. The
        net.frame failpoint fires HERE, on the caller thread: an
        injected error tears the connection down (failing this request
        with every other in-flight one); a truncation sends the torn
        bytes with a deterministic teardown behind them."""
        try:
            out = failpoint("net.frame", data=frame,
                            key=f"client:{self.host}")
        except Exception as e:  # noqa: BLE001
            conn.loop.call_soon(conn.die, e)
            return
        conn.send_frame(out, len(out) != len(frame))

    def estimate_partition_bytes(self, job_id: str, map_ids: Sequence[str],
                                 reduce_id: int) -> Optional[int]:
        """Partition size probe over the wire (SIZE frames). Best
        effort: any transport trouble or timeout returns None — the
        auto merge-approach policy then takes its bounded-memory
        default, it must never fail a task over a size probe."""
        try:
            conn = self._ensure_connected()
        except TransportError:
            return None
        self._maybe_bind(conn, job_id)
        box: list = [None]
        got = threading.Event()

        def on_size(result) -> None:
            box[0] = result
            got.set()

        span = metrics.start_span("net.size_probe", host=self.host,
                                  reduce=reduce_id, maps=len(map_ids))
        with self._lock:
            if self._conn is not conn:
                span.end(error="disconnect")
                return None
            self._next_id += 1
            req_id = self._next_id
            self._pending[req_id] = _Waiter(on_size, span,
                                            time.perf_counter())
        self._post(conn, wire.encode_size_request(
            req_id, job_id, list(map_ids), reduce_id,
            trace=self._trace_of(span)))
        if not got.wait(timeout=_SIZE_PROBE_TIMEOUT_S):
            with self._lock:
                self._pending.pop(req_id, None)  # late reply -> orphaned
            span.end(error="timeout")
            return None
        result = box[0]
        return None if isinstance(result, Exception) else result

    def fetch_stats(self, timeout: float = _SIZE_PROBE_TIMEOUT_S,
                    window_s: Optional[int] = None) -> Optional[dict]:
        """Snapshot the supplier's live introspection record over the
        multiplexed connection (MSG_STATS — uncredited on the server,
        so it answers even when data holds every credit). Best effort:
        transport trouble, a typed ERR (old peer), or a timeout
        returns None.

        ``window_s`` additionally requests the observability sections
        (rollup window, per-tenant SLIs, active anomalies) — sent only
        when the peer's HELLO advertised :data:`wire.CAP_OBS` (an old
        decoder would tear on the tail); against an older peer the
        plain snapshot is returned instead."""
        try:
            conn = self._ensure_connected()
        except TransportError:
            return None
        box: list = [None]
        got = threading.Event()

        def on_stats(result) -> None:
            box[0] = result
            got.set()

        span = metrics.start_span("net.stats", host=self.host)
        with self._lock:
            if self._conn is not conn:
                span.end(error="disconnect")
                return None
            self._next_id += 1
            req_id = self._next_id
            self._pending[req_id] = _Waiter(on_stats, span,
                                            time.perf_counter())
        if window_s is not None and self._peer_caps & wire.CAP_OBS:
            frame = wire.encode_stats_request(req_id, window_s=window_s)
        else:
            frame = wire.encode_stats_request(req_id)
        self._post(conn, frame)
        if not got.wait(timeout=timeout):
            with self._lock:
                self._pending.pop(req_id, None)
            span.end(error="timeout")
            return None
        result = box[0]
        return result if isinstance(result, dict) else None

    def stop(self) -> None:
        with self._lock:
            self._stopped = True
            conn, self._conn = self._conn, None
            orphans = list(self._pending.values())
            self._pending.clear()
        if conn is not None:
            conn.loop.call_soon(conn.close_quiet)
            metrics.gauge_add("net.client.connections", -1)
        err = TransportError(
            f"RemoteFetchClient({self.host}) stopped with "
            f"{len(orphans)} fetches in flight")
        for waiter in orphans:
            waiter.span.end(error="stopped")
            try:
                waiter.on_complete(err)
            except Exception as e:  # noqa: BLE001
                log.warn(f"net: completion callback raised during "
                         f"stop: {e}")


# The shared event loop is THE client core: the legacy thread-per-host
# reader (PR 4) was deleted once BENCH_NET_r07.json recorded the second
# evloop-only point (last A/B: BENCH_NET_r06.json).
RemoteFetchClient = EvLoopFetchClient


def fetch_remote_stats(host: str, port: Optional[int] = None,
                       timeout: float = 5.0,
                       config: Optional[Config] = None,
                       window_s: Optional[int] = None) -> dict:
    """One-shot MSG_STATS poll over a plain blocking socket — the
    scripts/udatop.py / udafleet.py scrape path: no shared loop, no
    client object, one dial per poll (an introspection console must
    work against a process whose client plane it is not part of).
    Consumes the HELLO banner, sends MSG_STATS, returns the decoded
    snapshot dict. Raises TransportError on dial failure/timeout and
    re-raises the typed remote error when the peer answers ERR (an old
    peer's ProtocolError refusal included).

    ``window_s`` requests the CAP_OBS observability sections
    (time-series rollups for the trailing window, per-tenant SLIs,
    active anomalies). The tail is sent only after the peer's HELLO
    advertised :data:`wire.CAP_OBS`; an older peer degrades to the
    plain snapshot — never a torn frame."""
    cfg = config or Config()
    if port is None:
        port = int(cfg.get("uda.tpu.net.port"))
    try:
        sock = socket.create_connection((host, port), timeout=timeout)
    except OSError as e:
        raise TransportError(
            f"stats poll: connect to {host}:{port} failed: {e}") from e
    try:
        sock.settimeout(timeout)
        wire.tune_socket(sock)
        sent = window_s is None  # plain polls need no caps knowledge
        if sent:
            try:
                sock.sendall(wire.encode_stats_request(1))
            except OSError as e:  # peer died between accept and send
                raise TransportError(
                    f"stats poll: send to {host}:{port} failed: "
                    f"{e}") from e
        while True:
            try:
                frame = wire.recv_frame(sock)
            except socket.timeout as e:  # noqa: PERF203 - bounded poll
                raise TransportError(
                    f"stats poll: {host}:{port} did not answer within "
                    f"{timeout:g} s") from e
            except OSError as e:
                # a mid-poll RST/EPIPE must keep the typed contract
                # (udatop's loop catches UdaError only): a raw OSError
                # escaping here crashes the console over one sick peer
                raise TransportError(
                    f"stats poll: {host}:{port} connection lost: "
                    f"{e}") from e
            if frame is None:
                # the peer spoke the wire fine and hung up on the
                # MSG_STATS frame itself: that is an old decoder
                # refusing an unknown type, not a dead endpoint —
                # ProtocolError so consoles render "unsupported", not
                # "down" (udatop branches on the TYPE, UDA005)
                raise ProtocolError(
                    f"stats poll: {host}:{port} closed the connection "
                    f"on MSG_STATS (pre-observability peer)")
            msg_type, _req_id, payload = frame
            if msg_type == wire.MSG_HELLO:
                if not sent:
                    # windowed polls hold the request until the banner
                    # tells us the peer's capabilities: the _STATS_OPT
                    # tail would tear an old decoder's framing, so a
                    # pre-CAP_OBS peer gets the plain request instead
                    # (degrade to the PR 11 snapshot, never a torn
                    # frame)
                    _gen, _warm, caps = wire.decode_hello_ex(payload)
                    if caps & wire.CAP_OBS:
                        req = wire.encode_stats_request(
                            1, window_s=window_s)
                    else:
                        req = wire.encode_stats_request(1)
                    try:
                        sock.sendall(req)
                    except OSError as e:
                        raise TransportError(
                            f"stats poll: send to {host}:{port} "
                            f"failed: {e}") from e
                    sent = True
                continue  # the banner precedes every reply
            if msg_type == wire.MSG_STATS_REPLY:
                return wire.decode_stats_reply(payload)
            if msg_type == wire.MSG_ERR:
                raise wire.decode_error(payload)
            raise TransportError(
                f"stats poll: unexpected frame type {msg_type} from "
                f"{host}:{port}")
    finally:
        wire.close_hard(sock)
