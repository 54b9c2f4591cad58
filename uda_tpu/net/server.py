"""ShuffleServer: the event-loop supplier endpoint with a zero-copy
serve path.

The supplier side of the data plane rebuilt on the selector core
(:mod:`uda_tpu.net.evloop`): ONE loop thread multiplexes every
connection — non-blocking sockets, per-connection state machines for
frame reassembly and outbound queues — replacing PR 4's reader+writer
thread pair per connection (the shape that was "fine at 64 suppliers,
dead at 10k", ROADMAP item 3). Semantics are the threaded core's,
exactly:

- **credit cap** (``mapred.rdma.wqe.per.conn``): where the threaded
  reader *blocked* at the credit gate, this core *parks the decoded
  request and pauses read interest* — the kernel receive buffer fills,
  TCP flow control pushes back on the client, credit flow without a
  credit message. A settled response re-arms read interest.
- **out-of-order completion** from DataEngine futures;
- **typed ERR frames** for engine errors (missing MOF, admission
  rejection, injected faults) — never connection teardown;
- **drain-on-stop** (``uda.tpu.net.drain.s``) vs ``stop(drain=False)``
  = killed supplier.

The zero-copy serve path (``uda.tpu.net.zerocopy``, default on): DATA
chunks are served from the DataEngine's fd cache as
:class:`~uda_tpu.mofserver.data_engine.FdSlice` plans and streamed with
``os.sendfile`` — the chunk bytes go disk-cache -> socket without ever
existing as a Python object (the RDMA-WRITE-from-registered-memory
analogue, RDMAServer.cc:537-631). The fallback ladder when a chunk is
not fd-backed (CRC stamping on, ``data_engine.pread`` failpoint armed,
or a sendfile-refusing fd): ``socket.sendmsg`` scatter-gather of
``[head, chunk]`` memoryviews — one heap copy (the engine's read), zero
encode-side copies. ``net.serve.fd`` / ``net.serve.copy`` count the
split; ``net.sendfile.bytes`` counts the zero-copy bytes.

**Opportunistic inline writes** (the RDMAbox lesson — batched
submission and completion ordering beat thread ping-pong,
arXiv:2104.12197): an engine completion WRITES the response inline on
the completing thread under the connection's write lock when the
socket has room, instead of waking the loop — the loop only takes over
the residual when a send would block (EAGAIN -> writable interest).
Frame ordering is preserved by the lock (writers always drain from the
queue head); credit settlement is marshalled back to the loop OFF the
data path. On this box that removes two thread handoffs per chunk from
the serve critical path.

**Batched byte-path serves** (``uda.tpu.read.batch``, the other half
of the RDMAbox lesson): requests that will take the engine's byte path
(zerocopy off, CRC stamping on, pread failpoint armed) accumulate per
connection during one recv's frame burst / one credit-unpark sweep and
flush as ONE ``DataEngine.submit_batch`` — per-fd grouping, range
coalescing and vectored reads turn a burst against a hot MOF into
O(files) syscalls with one pool handoff, while slice-eligible requests
keep the zero-copy plane untouched. ``off`` reproduces the
one-handoff-one-pread-per-chunk path exactly (the io_bench identity
oracle).

**Multi-tenant daemon mode** (``uda.tpu.tenant.enable``, the
Exoshuffle shuffle-as-a-service shape — uda_tpu/tenant/): HELLO
advertises ``CAP_TENANT``; MSG_JOB frames register (tenant, job,
epoch) in the :class:`~uda_tpu.tenant.TenantRegistry` and bind them to
the connection; every bound REQ is validated per request (unknown/
retired/stale-epoch -> typed TenantError). Admission then flows
through the daemon-wide :class:`~uda_tpu.tenant.CreditScheduler` —
weighted deficit round-robin over per-tenant parked queues — BEFORE
the per-conn credit gate (gate-order invariant: a conn-parked entry
always holds a tenant credit, a scheduler-parked entry never does),
the engine's read budget partitions per tenant, and serve-path
counters/watermarks/ledger books carry the tenant. Off (the default)
this file is the single-job data plane of PRs 4-13, bit for bit.

Failpoints (same sites, same frequencies as the threaded core):
``net.accept`` per accepted connection, ``net.frame`` per outbound
response frame — applied to the frame head; a truncated head is a torn
frame and the connection is closed deterministically after sending it.
"""

from __future__ import annotations

import dataclasses
import errno
import json
import os
import selectors
import socket
import threading
import time
from collections import deque
from typing import Optional

from uda_tpu.mofserver.data_engine import DataEngine, FdSlice
from uda_tpu.net import wire
from uda_tpu.net.evloop import EventLoop, loop_callback
from uda_tpu.utils.config import Config
from uda_tpu.utils.errors import (ProtocolError, StorageError, TenantError,
                                  TransportError, UdaError)
from uda_tpu.utils.failpoints import failpoint
from uda_tpu.utils.flightrec import flightrec
from uda_tpu.utils.locks import TrackedLock
from uda_tpu.utils.logging import get_logger
from uda_tpu.utils.metrics import metrics

__all__ = ["ShuffleServer", "EvLoopShuffleServer"]

log = get_logger()

_READ = selectors.EVENT_READ
_WRITE = selectors.EVENT_WRITE

_RECV_CHUNK = 256 * 1024   # reusable inbound buffer per connection
_SENDFILE_MAX = 4 << 20    # bytes per sendfile syscall (fairness bound)

# errnos on which os.sendfile is permanently useless for this pairing
# (fs/socket refuses the splice) -> fall back to the pread+sendmsg path
_SENDFILE_FALLBACK_ERRNOS = (errno.EINVAL, errno.ENOSYS, errno.EOPNOTSUPP)


def _pick_zerocopy_mode() -> str:
    """One-time per-process probe for ``zerocopy.mode=auto``: time
    ``os.sendfile`` against ``send``-from-mmap over a loopback
    socketpair and serve with the faster mechanism. Both are zero-copy
    in the sense that matters (chunk bytes never become a Python-heap
    object); which one the KERNEL moves faster varies — sandboxed/
    emulated kernels (gVisor-style) implement sendfile as an internal
    copy loop at a fraction of plain send throughput, while bare-metal
    Linux favors sendfile. Preference goes to sendfile unless mmap
    beats it by >30% (the probe's noise floor); any probe failure
    falls back to sendfile."""
    global _PROBED_MODE
    with _PROBE_LOCK:
        if _PROBED_MODE is not None:
            return _PROBED_MODE
        mode = "sendfile"
        try:
            import mmap as mmap_mod
            import tempfile

            nbytes = 4 << 20
            with tempfile.NamedTemporaryFile() as tf:
                tf.write(b"\0" * nbytes)
                tf.flush()
                fd = tf.fileno()
                mm = mmap_mod.mmap(fd, 0, prot=mmap_mod.PROT_READ)

                def tcp_pair():
                    # a real TCP loopback pair — the transport the data
                    # plane rides; AF_UNIX pairs take a different (and
                    # differently-optimized) kernel path for mapped
                    # memory and would mis-rank the mechanisms
                    srv = socket.socket(socket.AF_INET,
                                        socket.SOCK_STREAM)
                    srv.bind(("127.0.0.1", 0))
                    srv.listen(1)
                    c = socket.create_connection(srv.getsockname()[:2])
                    s, _ = srv.accept()
                    srv.close()  # udalint: disable=UDA004 - probe-local
                    # listener, nothing blocked on it
                    for x in (c, s):
                        x.setsockopt(socket.IPPROTO_TCP,
                                     socket.TCP_NODELAY, 1)
                    return c, s

                def timed(send_once) -> float:
                    a, b = tcp_pair()
                    stop = threading.Event()
                    sink = bytearray(1 << 20)

                    def drain() -> None:
                        while not stop.is_set():
                            try:
                                if not b.recv_into(sink):
                                    return
                            except OSError:
                                return

                    t = threading.Thread(target=drain, daemon=True)
                    t.start()
                    # untimed warmup pass: the serve path's mappings
                    # and fds are PERSISTENT (fd-cache retention), so
                    # steady-state behavior — page faults already
                    # taken — is what must be measured, not the cold
                    # first touch
                    send_once(a)
                    t0 = time.perf_counter()
                    for _ in range(3):
                        send_once(a)
                    dt = time.perf_counter() - t0
                    stop.set()
                    wire.close_hard(a)
                    wire.close_hard(b)
                    t.join(timeout=1.0)
                    return dt

                def via_sendfile(sock) -> None:
                    off = 0
                    while off < nbytes:
                        off += os.sendfile(sock.fileno(), fd, off,
                                           nbytes - off)

                view = memoryview(mm)

                def via_mmap(sock) -> None:
                    sock.sendall(view)

                t_sf = timed(via_sendfile)
                t_mm = timed(via_mmap)
                view.release()
                mm.close()
                if t_mm * 1.3 < t_sf:
                    mode = "mmap"
                log.info(f"net: zerocopy auto-probe: sendfile "
                         f"{t_sf * 1e3:.1f} ms vs mmap+send "
                         f"{t_mm * 1e3:.1f} ms for {3 * nbytes >> 20} MB "
                         f"-> {mode}")
        except Exception as e:  # noqa: BLE001 - a probe failure must
            # never break serving; sendfile is the safe default
            log.warn(f"net: zerocopy auto-probe failed ({e}); "
                     f"using sendfile")
        _PROBED_MODE = mode
        return mode


_PROBED_MODE: Optional[str] = None
_PROBE_LOCK = threading.Lock()

# a served DATA frame's stage counters, keys built once
_K_PARK = metrics.series("net.serve.park_seconds")
_K_SERVE = metrics.series("net.serve.serve_seconds")
_K_SEND = metrics.series("net.serve.send_seconds")


class _BufItem:
    """An outbound frame already materialized as buffers: ERR, SIZE,
    the byte-path DATA frames (``[head, chunk]`` scatter-gather — the
    chunk memoryview donates the engine's buffer, no concat), and
    mmap-mode zero-copy DATA frames (the chunk memoryview points into
    the MOF's page-cache mapping; ``slice`` pins it until written)."""

    __slots__ = ("bufs", "credited", "t0", "stamps", "close_after",
                 "slice", "zc_bytes", "tenant")

    def __init__(self, bufs, credited: bool, t0: float,
                 close_after: bool = False, sl=None, zc_bytes: int = 0,
                 tenant: str = "", stamps: Optional[tuple] = None):
        self.bufs = [memoryview(b) for b in bufs]
        self.credited = credited
        self.t0 = t0
        # a DATA frame's (park_s, serve_s, t_enc) from _serve_stamp,
        # None for any other frame: counted, with the send wait (t_enc
        # -> last byte written), as the frame leaves (_drain_locked)
        self.stamps = stamps
        self.close_after = close_after
        self.slice = sl
        self.zc_bytes = zc_bytes
        self.tenant = tenant  # the credit's tenant (scheduler release)


def _release_item(item) -> None:
    """Release an item's fd-cache pin (idempotent), dropping any
    mmap-backed memoryviews first so the cache can unmap cleanly."""
    if item.slice is None:
        return
    if isinstance(item, _BufItem):
        item.bufs.clear()
    item.slice.release()


class _FileItem:
    """An outbound DATA frame whose chunk is an fd-backed FdSlice:
    head bytes then ``os.sendfile`` straight from the MOF fd."""

    __slots__ = ("head", "slice", "file_off", "remaining", "credited",
                 "t0", "stamps", "close_after", "tenant")

    def __init__(self, head: bytes, sl: FdSlice, t0: float,
                 tenant: str = "", stamps: Optional[tuple] = None):
        self.head: Optional[memoryview] = memoryview(head)
        self.slice = sl
        self.file_off = sl.file_offset
        self.remaining = sl.length
        self.credited = True
        self.t0 = t0
        self.stamps = stamps
        self.close_after = False
        self.tenant = tenant


class _EvConn:
    """One accepted connection's state machine.

    Ownership split: the READ side (reassembly, credits, parked
    requests, selector interest) belongs to the loop thread; the WRITE
    side (outbound queue + socket sends) is guarded by ``_wlock`` so
    completion threads can write inline. The stop path only reads the
    monotone ``closed``/``inflight`` flags and marshals mutations
    through ``call_soon``."""

    def __init__(self, server: "EvLoopShuffleServer", sock: socket.socket,
                 peer: str):
        self.server = server
        self.loop = server._loop
        self.sock = sock
        self.peer = peer
        # inbound reassembly: reusable recv buffer + header/payload asm
        self._rbuf = memoryview(bytearray(_RECV_CHUNK))
        self._hdr = bytearray(wire.HEADER.size)
        self._hdr_got = 0
        self._payload: Optional[bytearray] = None
        self._pay_got = 0
        self._cur = (0, 0)  # (msg_type, req_id) of the frame being read
        # outbound (under _wlock) + credit state (loop thread)
        self._wlock = TrackedLock("net.conn.write")
        self._outq: "deque" = deque()
        self._poison = False        # no more writes (torn/failed/closed)
        self._parked: "deque" = deque()  # decoded reqs waiting for CONN
        # credit (each HOLDS a tenant credit while parked when the
        # tenant plane is on — see _admit's gate order)
        self._credits = server.credit
        self._unparking = False
        # multi-tenant service plane (uda_tpu/tenant/): the MSG_JOB
        # bindings of this connection (job -> (tenant, epoch); REQs of
        # bound jobs are validated against the registry per request)
        # and the count of requests parked in the server's per-tenant
        # scheduler queues (creditless until granted)
        self.tenant = server.default_tenant
        self.bindings: dict = {}
        self._tparked = 0
        # batched byte-path serves (loop thread): requests that would
        # take the engine's byte path accumulate here during one recv's
        # frame burst / one unpark sweep and flush as ONE
        # engine.submit_batch — one pool handoff for the burst
        self._batch: list = []
        self._batch_flushing = False
        self.inflight = 0
        self._read_paused = False
        self._mask = 0
        self.draining = False
        self.closed = False

    # -- registration / interest (loop thread) -------------------------------

    def register(self) -> None:
        self.loop.register(self.sock, _READ, self._on_event)
        self._mask = _READ

    def _set_mask(self, mask: int) -> None:
        if mask == self._mask or self.closed:
            return
        if mask == 0:
            self.loop.set_events(self.sock, 0)
        elif self._mask == 0:
            self.loop.resume(self.sock, mask)
        else:
            self.loop.set_events(self.sock, mask)
        self._mask = mask

    def _update_interest(self) -> None:
        if self.closed:
            return
        mask = 0
        if not self._read_paused and not self.draining:
            mask |= _READ
        if self._outq:  # racy read is fine: _kick converges it
            mask |= _WRITE
        self._set_mask(mask)

    @loop_callback
    def _kick(self) -> None:
        """A foreign-thread writer left residual bytes: arm writable
        interest so the loop takes the backlog over."""
        self._update_interest()

    # -- inbound (loop thread) -----------------------------------------------

    @loop_callback
    def _on_event(self, mask: int) -> None:
        if self.closed:
            return
        if mask & _WRITE:
            self._flush()
        if self.closed:
            return
        if mask & _READ and not self._read_paused and not self.draining:
            # the transitive recv_into is on THIS loop's non-blocking
            # socket: it returns EWOULDBLOCK instead of parking
            self._do_read()  # udalint: disable=UDA102

    def _do_read(self) -> None:
        try:
            n = self.sock.recv_into(self._rbuf)
        except (BlockingIOError, InterruptedError):
            return
        except OSError:
            self._drop(TransportError("recv failed (peer reset?)"))
            return
        if n == 0:
            self._eof()
            return
        metrics.add("net.bytes.in", n, role="server")
        try:
            self._feed(self._rbuf[:n])
        except TransportError as e:
            self._drop(e)
        # one recv's decoded burst -> one batch submission (requests
        # parked for credit flush later, from the unpark sweep)
        self._flush_batch()

    def _feed(self, mv) -> None:
        """Incremental frame reassembly over one recv's bytes; may park
        requests (credit) or pause reading — state survives across
        recvs, this is the per-connection state machine."""
        off, n = 0, len(mv)
        while off < n and not self.closed:
            if self._payload is None:
                take = min(wire.HEADER.size - self._hdr_got, n - off)
                self._hdr[self._hdr_got:self._hdr_got + take] = \
                    mv[off:off + take]
                self._hdr_got += take
                off += take
                if self._hdr_got < wire.HEADER.size:
                    return
                msg_type, req_id, length = wire.decode_header(
                    bytes(self._hdr))
                self._cur = (msg_type, req_id)
                self._payload = bytearray(length)
                self._pay_got = 0
                if length == 0:
                    self._frame_done()
            else:
                take = min(len(self._payload) - self._pay_got, n - off)
                self._payload[self._pay_got:self._pay_got + take] = \
                    mv[off:off + take]
                self._pay_got += take
                off += take
                if self._pay_got == len(self._payload):
                    self._frame_done()

    def _frame_done(self) -> None:
        msg_type, req_id = self._cur
        payload = memoryview(self._payload)
        self._payload = None
        self._hdr_got = 0
        if msg_type == wire.MSG_REQ:
            req, trace = wire.decode_request_ex(payload)
            # the decode stamp rides the entry through the credit
            # gates: park = decoded -> _start (net.serve.park_seconds)
            self._admit(("req", req_id,
                         (req, trace, time.perf_counter())))
        elif msg_type == wire.MSG_SIZE_REQ:
            self._admit(("size", req_id,
                         wire.decode_size_request_ex(payload)))
        elif msg_type == wire.MSG_STATS:
            # uncredited, the HELLO precedent: an introspection poll
            # must answer even when the data pipeline holds every
            # credit (that contended state is exactly what the poller
            # wants to see). The optional CAP_OBS tail (requested
            # rollup window + sections) is length-versioned exactly
            # like the trace context — a wrong-length tail is a torn
            # frame, an absent one is the PR 11 snapshot shape.
            self._start_stats(req_id, wire.decode_stats_request(payload))
        elif msg_type == wire.MSG_JOB:
            # the tenant handshake, uncredited like HELLO. Handled
            # INLINE on the loop thread deliberately: TCP ordering is
            # the registration contract (a client sends MSG_JOB then
            # its first REQ back-to-back; dispatching the registration
            # to another thread would let the REQ overtake it). The
            # registry is a dict under a leaf lock — the only blocking
            # risk is the chaos-only tenant.register failpoint, the
            # same deliberate stall shape as net.accept's.
            self._on_job(req_id, payload)
        elif msg_type == wire.MSG_PUSH_SUB \
                and self.server.push is not None:
            # push subscription, uncredited like MSG_JOB (and inline
            # for the same TCP-ordering reason: a SUB must be recorded
            # before any REQ behind it is admitted, or the catch-up
            # pushes could race the first fetch's claim). A push-less
            # server falls through to the typed-ERR refusal below —
            # the forward-compat contract doubles as the capability
            # refusal, and the client just stays pull-only.
            try:
                job_id, reduce_id, window, chunk = \
                    wire.decode_push_sub(payload)
            except UdaError as e:
                self._drop(e)
                return
            self.server.push.subscribe(self, job_id, reduce_id,
                                       window, chunk)
        elif msg_type == wire.MSG_PUSH_ACK \
                and self.server.push is not None:
            if len(payload):
                self._drop(TransportError("malformed PUSH_ACK frame"))
                return
            self.server.push.on_ack(self, req_id)
        elif msg_type == wire.MSG_PUSH_NACK \
                and self.server.push is not None:
            try:
                reason = wire.decode_push_nack(payload)
            except UdaError as e:
                self._drop(e)
                return
            self.server.push.on_nack(self, req_id, reason)
        else:
            # in-range but unknown/unexpected type: a NEWER peer
            # probing an optional message. Refuse it with a typed ERR
            # on the same req id and keep serving — tearing the
            # connection down would fail every in-flight fetch over a
            # harmless capability probe.
            log.warn(f"net: unsupported frame type {msg_type} from "
                     f"{self.peer}; answering typed ERR")
            metrics.add("net.errors")
            err = ProtocolError(
                f"unsupported frame type {msg_type} (this peer speaks "
                f"wire v{wire.WIRE_VERSION})")
            frame = wire.encode_error(req_id, err)
            self._enqueue(_BufItem([frame], credited=False,
                                   t0=time.perf_counter()), frame)

    def _eof(self) -> None:
        if self._hdr_got or self._payload is not None:
            self._drop(TransportError("connection closed mid-frame"))
            return
        # clean peer hangup at a frame boundary: half-close — in-flight
        # responses still flush, then the connection closes itself
        self.draining = True
        self._drop_parked()  # never started; the threaded reader
        # dropped un-admitted requests on drain the same way (tenant
        # credits held by conn-parked entries flow back to neighbors)
        self.server._sweep()
        self._update_interest()
        if self.inflight == 0 and not self._outq:
            self.close()

    def _drop(self, cause: Exception) -> None:
        if not self.closed:
            log.warn(f"net: dropping connection {self.peer}: {cause}")
            metrics.add("net.disconnects", role="server")
        self.close()

    # -- the tenant handshake (loop thread) ----------------------------------

    def _on_job(self, req_id: int, payload) -> None:
        """MSG_JOB: register/heartbeat/retire one (tenant, job, epoch)
        in the daemon's registry and bind it to this connection. The
        reply is MSG_JOB_OK (granted epoch) or a typed ERR carrying
        the exact registry refusal (TenantError: auth, stale epoch,
        retired) — uncredited either way. A malformed payload raises
        TransportError out of the frame machine (stream desync — the
        caller drops the connection, every decoder's contract)."""
        tenant, job, epoch, weight, token, retire = \
            wire.decode_job(payload)
        reg = self.server.registry
        if reg is None:
            metrics.add("net.errors")
            err = ProtocolError(
                "this supplier runs no tenant plane "
                "(uda.tpu.tenant.enable is off); MSG_JOB refused")
            reply = wire.encode_error(req_id, err)
        else:
            try:
                if retire:
                    reg.retire(tenant, job, epoch, token=token)
                    # the binding is KEPT: later REQs for the job must
                    # keep flowing through validate (-> typed
                    # "retired" errors), not fall back to the unbound
                    # default-tenant pass
                    reply = wire.encode_job_ok(req_id, epoch)
                else:
                    rec = reg.register(tenant, job, epoch,
                                       weight=weight, token=token)
                    self.tenant = rec.tenant_id
                    self.bindings[job] = (rec.tenant_id, rec.epoch)
                    reply = wire.encode_job_ok(req_id, rec.epoch)
            except UdaError as e:  # typed refusal (TenantError), never
                # a teardown: the client re-raises the registry's exact
                # error and the job fails terminally, not the stream.
                # The FENCE: a refused registration poisons the job's
                # binding (epoch 0) so its REQs draw TenantError too —
                # a stale-epoch predecessor must not slide back onto
                # the unbound default-tenant pass and read its
                # successor's chunks.
                if not retire:
                    self.bindings[job] = (tenant, 0)
                metrics.add("net.errors")
                reply = wire.encode_error(req_id, e)
        self._enqueue(_BufItem([reply], credited=False,
                               t0=time.perf_counter()), reply)

    def _entry_tenant(self, entry) -> str:
        """The scheduling tenant of one decoded request: its job's
        MSG_JOB binding, else this connection's tenant (the default
        tenant for never-bound old clients)."""
        kind, _rid, body = entry
        job = body[0].job_id if kind == "req" else body[0][0]
        bound = self.bindings.get(job)
        return (bound[0] or self.tenant) if bound else self.tenant

    def _entry_cost(self, entry) -> int:
        """The WDRR deficit charge of one request: its REQUESTED bytes
        under byte quanta (uda.tpu.tenant.quantum.kb > 0), 1 in
        request-count mode. chunk_size == 0 means 'the server default'
        on the wire — charge what the engine will actually serve
        (data_engine resolves it the same way), or a zero-size request
        would draw default-sized chunks at cost 1 and defeat the byte
        fairness. SIZE probes are metadata — nominal cost 1 either
        way."""
        if not self.server.quantum_bytes:
            return 1
        kind, _rid, body = entry
        if kind != "req":
            return 1
        return max(1, int(body[0].chunk_size)
                   or self.server.chunk_bytes_default)

    # -- credit + request admission (loop thread) ----------------------------

    def _admit(self, entry) -> None:
        if self.draining:
            return  # same as the threaded credit gate under drain
        if self.server.tenancy:
            # the tenant gate FIRST (gate order invariant: an entry in
            # self._parked always HOLDS a tenant credit, an entry in
            # the scheduler's queues never does): no credit -> park in
            # the tenant's WDRR queue. Reading pauses only past the
            # per-conn HIGH-water mark (the wqe.per.conn cap — parked
            # entries are decoded request structs, not data, so the
            # memory bound is loose by design): pausing on the FIRST
            # park made each connection's queue a sawtooth that hit
            # zero before refilling, and weights cannot bite unless
            # several tenants hold backlog simultaneously
            if not self.server._sched.admit(self._entry_tenant(entry),
                                            (self, entry),
                                            cost=self._entry_cost(entry)):
                self._tparked += 1
                if not self._read_paused \
                        and self._tparked >= self.server.credit:
                    self._read_paused = True
                    self._update_interest()
                return
        self._conn_gate(entry)

    def _maybe_resume_read(self) -> None:
        """Resume reading once nothing is conn-parked and the tenant
        backlog is under the LOW-water mark (hysteresis: half the
        per-conn cap — refills land before the queue runs dry)."""
        if self._read_paused and not self._parked \
                and self._tparked <= self.server.credit // 2:
            self._read_paused = False
            self._update_interest()

    def _conn_gate(self, entry) -> None:
        """The per-connection credit bound (entry holds a tenant credit
        already when the tenant plane is on)."""
        if self._credits <= 0:
            self._parked.append(entry)
            if not self._read_paused:
                # the wqe.per.conn bound: stop READING until a response
                # settles; TCP backpressure is the credit return
                self._read_paused = True
                self._update_interest()
            return
        self._start(entry)

    def _granted(self, entry) -> None:
        """A WDRR grant arrived from the server sweep (loop thread):
        the entry now holds a tenant credit; run it through the conn
        gate and resume reading once nothing of ours is parked."""
        self._tparked -= 1
        if self.closed or self.draining:
            self.server._sched.release(self._entry_tenant(entry))
            return
        self._conn_gate(entry)
        self._maybe_resume_read()
        self._flush_batch()

    def _drop_parked(self) -> None:
        """Drop every parked entry (EOF/drain/close): conn-parked ones
        hold tenant credits — release them; scheduler-parked ones are
        creditless — just remove them from the queues."""
        if self.server.tenancy:
            for entry in self._parked:
                self.server._sched.release(self._entry_tenant(entry))
            if self._tparked:
                self.server._sched.drop_conn(self)
                self._tparked = 0
        self._parked.clear()

    def _start(self, entry) -> None:
        kind, req_id, body = entry
        self._credits -= 1
        self.inflight += 1
        metrics.gauge_add("net.server.inflight", 1)
        if kind == "req":
            self._start_req(req_id, body)
        else:
            self._start_size(req_id, body)

    def _settle(self, credited: bool, tenant: str = "") -> None:
        """The single credit-settle point (loop thread): every response
        — written, torn or abandoned — feeds through here exactly once.
        ``tenant`` is the credit's scheduler account (rides the
        outbound item so out-of-order completion settles the right
        tenant); empty falls back to the connection's tenant.

        The unpark loop is ITERATIVE, not recursive: starting a parked
        entry can serve it fully inline (try_plan -> enqueue -> send
        completes -> settle), which re-enters here — the ``_unparking``
        guard turns that nested settle into a plain credit increment
        and the OUTER while loop picks it up. Without the guard a
        backlog of a few hundred parked requests blew the recursion
        limit and tore the connection down under plain burst load.
        (The server-wide WDRR sweep has the same guard on the server,
        ``_sweeping`` — a grant that serves inline re-enters here.)"""
        if not credited:
            return
        self._credits += 1
        self.inflight -= 1
        metrics.gauge_add("net.server.inflight", -1)
        if self.server.tenancy:
            self.server._sched.release(tenant or self.tenant)
        if self.closed or self.draining or self._unparking:
            if not self.closed:
                self.server._sweep()  # the freed tenant credit must
                # still flow to parked neighbors even when this conn
                # cannot unpark (nested settles hit the sweep guard)
            return
        self._unparking = True
        try:
            while self._credits > 0 and self._parked \
                    and not self.closed and not self.draining:
                # conn-parked entries already hold their tenant credit
                # (the _admit gate order) — no second tenant gate here
                self._start(self._parked.popleft())
            self._maybe_resume_read()
        finally:
            self._unparking = False
        # the unpark sweep's byte-path starts batch exactly like a
        # recv burst's (nested settles returned at the guard above and
        # never reach here — the OUTER settle flushes once)
        self._flush_batch()
        # weighted-fair grant sweep: the freed tenant credit may belong
        # to ANOTHER connection's parked backlog
        self.server._sweep()

    def _settle_offloop(self, res, span, tenant: str = "") -> None:
        """Settle a completion that arrived for a dead connection (or
        after the loop stopped): runs on whatever thread noticed. The
        loop no longer touches this connection's state, so the gauge
        decrement cannot race a loop-side settle. The tenant credit is
        marshalled back to the loop (the scheduler is loop-confined);
        a dead loop means a dead scheduler — nothing to return to."""
        if isinstance(res, FdSlice):
            res.release()
        metrics.gauge_add("net.server.inflight", -1)
        span.end(error="closed")
        if self.server.tenancy and self.loop.alive():
            self.loop.call_soon(self.server._release_and_sweep,
                                tenant or self.tenant)

    # -- serving -------------------------------------------------------------

    def _start_req(self, req_id: int, body) -> None:
        req, trace, t_dec = body
        metrics.add("net.requests")
        t0 = time.perf_counter()
        # park: decoded -> here, behind the tenant and connection
        # credit gates. Counted for every DATA frame as it leaves;
        # reported in its head only to a REQ that carried the trace
        # tail (the reduce side's spans are on), so an untraced REQ
        # gets the pre-timing frame
        parked = (t0 - t_dec, trace is not None)
        # wire-level trace adoption: a REQ that carried (trace_id,
        # parent_span_id) makes this serve span a CHILD of the remote
        # reduce task's fetch span — the supplier-side work it caused
        # lands in the same trace tree, stitched across processes by
        # scripts/trace_merge.py
        parent = (metrics.remote_parent(*trace) if trace is not None
                  else None)
        span = metrics.start_span("net.serve", parent=parent,
                                  map=req.map_id,
                                  reduce=req.reduce_id, offset=req.offset,
                                  peer=self.peer)
        try:
            if self.server.tenancy:
                # THE per-REQ registry gate: a bound job is validated
                # every request (unknown/retired -> typed TenantError;
                # a stale epoch fences a restarted job's predecessor
                # off its successor's chunks). The tenant is stamped
                # from the connection's AUTHENTICATED binding — never
                # anything the request payload could spoof — and
                # BEFORE validation, so a refused request's ERR item
                # settles its credit under the SAME tenant the _admit
                # gate charged (the engine partitions and metric
                # labels read the same stamp).
                req = dataclasses.replace(
                    req, tenant=self._entry_tenant(
                        ("req", req_id, (req, trace, t_dec))))
                self.server._validate_req(self, req)
            # the engine adopts the serve span across its pool handoff
            # (DataEngine.submit captures the current span), so
            # engine.pread / zero-copy plan work is a child of net.serve
            with metrics.use_span(span):
                if self.server.zero_copy:
                    # the inline fast path: an index-cache hit plans the
                    # (fd, offset, len) slice right here on the loop
                    # thread and the response leaves without a single
                    # pool handoff — every chunk after a partition's
                    # first
                    plan = self.server.engine.try_plan(req)
                    if plan is not None:
                        self._complete(req_id, plan, None, t0, span, req,
                                       parked)
                        return
                if self.server.batch_reads and not (
                        self.server.zero_copy
                        and self.server.engine.slice_eligible()):
                    # the byte path will be taken (zerocopy off, CRC
                    # stamping on, or the pread failpoint armed):
                    # accumulate the burst and flush ONE submit_batch
                    # (uda.tpu.read.batch; the RDMAbox lesson) instead
                    # of one pool handoff per chunk
                    self._batch.append((req_id, req, t0, span, parked))
                    return
                if self.server.zero_copy:
                    fut = self.server.engine.submit_serve(req)
                else:
                    fut = self.server.engine.submit(req)
        except Exception as e:  # noqa: BLE001 - sync rejection (stopped
            # engine, admission push-back, bad offset) -> typed ERR
            self._complete(req_id, None, e, t0, span, req, parked)
            return
        fut.add_done_callback(
            lambda f: self._engine_done(req_id, f, t0, span, req, parked))

    def _flush_batch(self) -> None:
        """Submit the accumulated byte-path burst (loop thread). The
        loop is ITERATIVE like the unpark sweep: a synchronously-
        failed batch (stopped engine) completes inline -> settle ->
        unpark -> more entries may land in self._batch — the outer
        while picks them up instead of recursing."""
        if self._batch_flushing or self.closed or not self._batch:
            return
        self._batch_flushing = True
        try:
            while self._batch:
                entries, self._batch = self._batch, []
                bmax = self.server.batch_max
                for i in range(0, len(entries), bmax):
                    part = entries[i:i + bmax]
                    futs = self.server.engine.submit_batch(
                        [ent[1] for ent in part],
                        parent_spans=[ent[3] for ent in part])
                    for (req_id, req, t0, span, parked), fut in zip(part,
                                                                     futs):
                        fut.add_done_callback(
                            lambda f, req_id=req_id, t0=t0, span=span,
                            req=req, parked=parked:
                            self._engine_done(req_id, f, t0, span, req,
                                              parked))
        finally:
            self._batch_flushing = False

    def _engine_done(self, req_id: int, f, t0: float, span, req,
                     parked: tuple = (0.0, False)) -> None:
        """Engine worker thread (or the loop, when the future was
        already resolved at callback registration)."""
        err = f.exception()
        res = None if err is not None else f.result(timeout=0)
        if self.closed or not self.loop.alive():
            self._settle_offloop(res, span,
                                 getattr(req, "tenant", ""))
            return
        self._complete(req_id, res, err, t0, span, req, parked)

    @staticmethod
    def _serve_stamp(t0: float, parked: tuple) -> tuple:
        """-> (stamps, timing) as a DATA head is about to be encoded:
        serve = _start_req -> now (index lookup, slice plan or pread,
        pool hand-off). ``stamps`` = (park_s, serve_s, t_enc) ride the
        outbound item to the counters; ``timing`` is the head's
        ``(parked, serve_us)`` block, None for an untraced REQ."""
        t_enc = time.perf_counter()
        park, timed = parked
        serve = t_enc - t0
        return ((park, serve, t_enc),
                (int(park * 1e6), int(serve * 1e6)) if timed else None)

    def _complete(self, req_id: int, res, err, t0: float, span,
                  req=None, parked: tuple = (0.0, False)) -> None:
        """Engine completion -> outbound item, on the COMPLETING thread
        (inline-write fast path). Responses complete out of order
        across requests, exactly like the threaded core's
        future->queue pipeline. ``parked`` is the REQ's (park seconds,
        carried the trace tail) pair from _start_req."""
        tenant = getattr(req, "tenant", "") if req is not None else ""
        try:
            if err is not None:
                head = wire.encode_error(req_id, err)
                item = _BufItem([head], credited=True, t0=t0,
                                tenant=tenant)
                metrics.add("net.errors")
                span.end(error=type(err).__name__)
                if self.server.tenancy and tenant and \
                        isinstance(err, (StorageError, TenantError)):
                    # tenant-scoped penalty feedback: repeated
                    # admission push-back / injected faults box THIS
                    # tenant in the WDRR (deprioritized, not starved);
                    # marshalled — the scheduler is loop-confined
                    self.loop.call_soon(self.server._note_fault, tenant)
            elif isinstance(res, FdSlice):
                view = (res.view()
                        if self.server.zc_mode == "mmap" else None)
                if view is None and self.server._sendfile_refused:
                    # last rung: neither sendfile (refused) nor mmap
                    # (unmappable file) works — serve the bytes once
                    # and stop planning slices; future requests take
                    # the engine's worker-thread byte path
                    data = os.pread(res.fd, res.length, res.file_offset)
                    if len(data) != res.length:
                        # truncated MOF under its cached index entry:
                        # fail loudly (the _send_file fallback's exact
                        # contract), never serve a silently-short frame
                        raise TransportError(
                            f"short read {len(data)}/{res.length} at "
                            f"{res.path}:{res.file_offset}")
                    res.release()
                    self.server.zero_copy = False
                    log.warn("net: zero-copy serve disabled (sendfile "
                             "refused and MOF not mappable); serving "
                             "via engine byte reads")
                    stamps, timing = self._serve_stamp(t0, parked)
                    head = wire.encode_result_head(
                        req_id, raw_length=res.raw_length,
                        part_length=res.part_length, offset=res.offset,
                        last=res.last, path=res.path, crc=None,
                        data_len=len(data), timing=timing)
                    item = _BufItem([head, data], credited=True, t0=t0,
                                    tenant=tenant, stamps=stamps)
                    self._count_serve("net.serve.copy", tenant)
                    span.end(bytes=len(data))
                else:
                    stamps, timing = self._serve_stamp(t0, parked)
                    head = wire.encode_result_head(
                        req_id, raw_length=res.raw_length,
                        part_length=res.part_length, offset=res.offset,
                        last=res.last, path=res.path, crc=None,
                        data_len=res.length, timing=timing)
                    if view is not None:
                        # mmap mode: the chunk memoryview points into
                        # the MOF's page-cache mapping — sendmsg moves
                        # it kernel-side, no Python-heap object either
                        item = _BufItem([head, view], credited=True,
                                        t0=t0, sl=res,
                                        zc_bytes=res.length,
                                        tenant=tenant, stamps=stamps)
                    else:
                        item = _FileItem(head, res, t0, tenant=tenant,
                                         stamps=stamps)
                    self._count_serve("net.serve.fd", tenant)
                    span.end(bytes=res.length, zero_copy=True)
            else:
                stamps, timing = self._serve_stamp(t0, parked)
                head = wire.encode_result_head(
                    req_id, raw_length=res.raw_length,
                    part_length=res.part_length, offset=res.offset,
                    last=res.last, path=res.path, crc=res.crc,
                    data_len=len(res.data), timing=timing)
                item = _BufItem([head, res.data], credited=True, t0=t0,
                                tenant=tenant, stamps=stamps)
                self._count_serve("net.serve.copy", tenant)
                span.end(bytes=len(res.data))
        except Exception as e:  # noqa: BLE001 - an unencodable response
            # would strand the request's credit; settle and drop, the
            # client re-fetches on the disconnect (threaded parity)
            log.error(f"net: response encoding for {self.peer} failed: "
                      f"{e}; dropping the connection")
            if isinstance(res, FdSlice):
                res.release()
            span.end(error="encode_failed")
            self.loop.call_soon(self._abandon_item,
                                _BufItem([], credited=True, t0=t0,
                                         tenant=tenant), e)
            return
        if err is None and req is not None:
            # warm-restart watermark: the highest partition offset this
            # server has answered (advisory — the resuming client's own
            # offset ledger is authoritative; see the handoff docstring)
            served = res.length if isinstance(res, FdSlice) \
                else len(res.data)
            self.server._mark_served(self.peer, req, req.offset + served,
                                     tenant=tenant)
        self._enqueue(item, head)

    @staticmethod
    def _count_serve(name: str, tenant: str) -> None:
        """Serve-path counters with a tenant label when the request is
        tenant-stamped (both the total and the series advance);
        literal names only — the metrics linter audits call sites."""
        if name == "net.serve.fd":
            if tenant:
                metrics.add("net.serve.fd", tenant=tenant)
            else:
                metrics.add("net.serve.fd")
        else:
            if tenant:
                metrics.add("net.serve.copy", tenant=tenant)
            else:
                metrics.add("net.serve.copy")

    def _start_size(self, req_id: int, body) -> None:
        """SIZE probes are credited like DATA (no frame escapes the
        wqe.per.conn bound) but the resolver sums may ride an embedder
        upcall — run them on the dispatcher thread, never the loop."""
        (job_id, mids, reduce_id), trace = body
        t0 = time.perf_counter()
        self.loop.dispatch(self._do_size, req_id, job_id, mids,
                           reduce_id, t0, trace,
                           self._entry_tenant(("size", req_id, body))
                           if self.server.tenancy else "")

    def _do_size(self, req_id: int, job_id: str, mids, reduce_id: int,
                 t0: float, trace=None, tenant: str = "") -> None:
        """Dispatcher thread: delegate to LocalFetchClient so wire and
        in-process estimates cannot diverge (exact-or-unknown). A
        wire-carried trace context parents the serve span under the
        remote net.size_probe, same adoption as _start_req."""
        from uda_tpu.merger.segment import LocalFetchClient

        parent = (metrics.remote_parent(*trace) if trace is not None
                  else None)
        span = metrics.start_span("net.serve", parent=parent, kind="size",
                                  reduce=reduce_id, peer=self.peer)
        with metrics.use_span(span):
            total = LocalFetchClient(self.server.engine) \
                .estimate_partition_bytes(job_id, mids, reduce_id)
        span.end(known=total is not None)
        frame = wire.encode_size(req_id, total)
        if self.closed or not self.loop.alive():
            metrics.gauge_add("net.server.inflight", -1)
            if self.server.tenancy and self.loop.alive():
                self.loop.call_soon(self.server._release_and_sweep,
                                    tenant or self.tenant)
            return
        self._enqueue(_BufItem([frame], credited=True, t0=t0,
                               tenant=tenant), frame)

    def _start_stats(self, req_id: int,
                     opt: Optional[tuple] = None) -> None:
        """MSG_STATS (loop thread): snapshot building walks metrics and
        provider locks — cheap, but off the loop on principle (a
        provider is component code). Uncredited: the reply rides the
        outbound queue like the HELLO banner. ``opt`` is the decoded
        CAP_OBS tail (window seconds, section bits) or None for the
        plain PR 11 poll."""
        self.loop.dispatch(self._do_stats, req_id, opt)

    def _do_stats(self, req_id: int, opt: Optional[tuple] = None) -> None:
        """Dispatcher thread: build + encode the introspection
        snapshot, folding in the observability sections a CAP_OBS
        poller asked for (time-series window, per-tenant SLI book,
        active anomalies). Old pollers pay nothing: the sections are
        built only on request."""
        from uda_tpu.utils.stats import introspection_snapshot

        metrics.add("net.stats.requests")
        try:
            snap = introspection_snapshot()
            if opt is not None:
                window_s, sections = opt
                if sections & wire.STATS_SEC_TS:
                    from uda_tpu.utils.timeseries import timeseries
                    snap["timeseries"] = timeseries.wire_block(
                        seconds=window_s or None)
                if sections & wire.STATS_SEC_SLI:
                    from uda_tpu.tenant.sli import sli_book
                    snap["sli"] = sli_book.snapshot()
                if sections & wire.STATS_SEC_ANOMALY:
                    from uda_tpu.utils.anomaly import anomaly_engine
                    snap["anomalies"] = anomaly_engine.snapshot()
            frame = wire.encode_stats_reply(req_id, snap)
        except Exception as e:  # noqa: BLE001 - an unencodable snapshot
            # must degrade to a typed ERR, never strand the poller
            log.warn(f"net: stats snapshot failed: {e}")
            frame = wire.encode_error(req_id, e)
        if self.closed or not self.loop.alive():
            return  # uncredited: nothing to settle
        self._enqueue(_BufItem([frame], credited=False,
                               t0=time.perf_counter()), frame)

    # -- outbound (any thread; _wlock serializes writers) --------------------

    def push_frame(self, frame: bytes, close_after: bool = False) -> None:
        """Queue one supplier-initiated frame (MSG_PUSH), any thread.
        Uncredited — the push plane runs its OWN window (PUSH_ACK
        settles it), so pushes never consume the fetch pipeline's
        credits; ordering and inline writes ride the normal outbound
        path."""
        self._enqueue(_BufItem([frame], credited=False,
                               t0=time.perf_counter(),
                               close_after=close_after), frame)

    def _enqueue(self, item, head: bytes) -> None:
        """Queue one response and opportunistically write it NOW on the
        calling thread. The net.frame failpoint fires here, once per
        response frame, against the frame HEAD — a truncated head is a
        torn frame (the peer's stream desyncs mid-header/meta)
        regardless of how the chunk itself would have travelled."""
        try:
            out = failpoint("net.frame", data=head, key=self.peer)
        except Exception as e:  # noqa: BLE001 - injected send failure:
            # the connection is over (threaded write-loop parity)
            _release_item(item)
            self.loop.call_soon(self._abandon_item, item, e)
            return
        if len(out) != len(head):
            # torn frame: send the damaged head bytes, then finish the
            # damage deterministically (mid-stream disconnect)
            _release_item(item)
            item = _BufItem([out], credited=item.credited, t0=item.t0,
                            close_after=True,
                            tenant=getattr(item, "tenant", ""))
        abandoned = False
        with self._wlock:
            if self.closed or self._poison:
                abandoned = True
            else:
                self._outq.append(item)
                completed, err = self._drain_locked()
                backlog = bool(self._outq) and not self._poison
        if abandoned:
            _release_item(item)
            self.loop.call_soon(self._abandon_item, item, None)
            return
        on_loop = self.loop.on_loop_thread()
        for it in completed:
            if on_loop:
                self._settle_item(it)
            else:
                self.loop.call_soon(self._settle_item, it)
        if err is not None:
            self.loop.call_soon(self._writer_failed, err)
        elif backlog:
            if on_loop:
                self._update_interest()
            else:
                self.loop.call_soon(self._kick)

    def _drain_locked(self):
        """_wlock held. Send from the queue head until it would block.
        Returns (completed items, fatal send error or None)."""
        completed = []
        while self._outq and not self._poison:
            item = self._outq[0]
            try:
                done = (self._send_file(item)
                        if isinstance(item, _FileItem)
                        else self._send_bufs(item))
            except (BlockingIOError, InterruptedError):
                break
            except Exception as e:  # noqa: BLE001 - send failure: peer
                # gone or injected; the client's reader sees the
                # disconnect and fails its in-flight fetches into the
                # Segment retry machinery
                self._poison = True
                return completed, e
            if not done:
                break
            self._outq.popleft()
            completed.append(item)
            if item.stamps is not None:
                # a DATA frame's last byte is written: its three stages
                # in one locked update — head encoded -> here is the one
                # its own header cannot carry
                park, serve, t_enc = item.stamps
                metrics.add_keyed((_K_PARK, park), (_K_SERVE, serve),
                                  (_K_SEND, time.perf_counter() - t_enc))
            if item.close_after:
                self._poison = True
                break
        return completed, None

    @loop_callback
    def _flush(self) -> None:
        """Loop-side writable handler: take the backlog over."""
        with self._wlock:
            completed, err = self._drain_locked()
        for it in completed:
            self._settle_item(it)
        if err is not None:
            self._writer_failed(err)
            return
        self._update_interest()
        if self.draining and self.inflight == 0 and not self._outq:
            self.close()

    @loop_callback
    def _settle_item(self, item) -> None:
        if item.credited:
            metrics.observe("net.frame.latency_ms",
                            (time.perf_counter() - item.t0) * 1e3,
                            role="server")
        self._settle(item.credited, getattr(item, "tenant", ""))
        if item.close_after and not self.closed:
            log.warn(f"net: frame to {self.peer} torn by failpoint; "
                     f"closing")
            metrics.add("net.disconnects", role="server")
            self.close()
        elif self.draining and self.inflight == 0 and not self._outq:
            self.close()

    @loop_callback
    def _abandon_item(self, item, cause) -> None:
        """Settle a response that will never be written (enqueued
        against a closed/poisoned connection, injected send failure, or
        unencodable)."""
        self._settle(item.credited, getattr(item, "tenant", ""))
        if cause is not None:
            if not self.closed:
                log.warn(f"net: send to {self.peer} failed: {cause}")
                metrics.add("net.disconnects", role="server")
            self.close()

    @loop_callback
    def _writer_failed(self, cause: Exception) -> None:
        if not self.closed:
            log.warn(f"net: send to {self.peer} failed: {cause}")
            metrics.add("net.disconnects", role="server")
        self.close()

    def _send_bufs(self, item: _BufItem) -> bool:
        while item.bufs:
            sent = self.sock.sendmsg(item.bufs)
            metrics.add("net.bytes.out", sent, role="server")
            while sent:
                if sent >= len(item.bufs[0]):
                    sent -= len(item.bufs[0])
                    item.bufs.pop(0)
                else:
                    item.bufs[0] = item.bufs[0][sent:]
                    sent = 0
        if item.zc_bytes:
            metrics.add("net.mmap.bytes", item.zc_bytes)
        if item.slice is not None:
            item.slice.release()
        return True

    def _send_file(self, item: _FileItem) -> bool:
        while item.head is not None:
            n = self.sock.send(item.head)
            metrics.add("net.bytes.out", n, role="server")
            item.head = item.head[n:] if n < len(item.head) else None
        while item.remaining:
            try:
                n = os.sendfile(self.sock.fileno(), item.slice.fd,
                                item.file_off,
                                min(item.remaining, _SENDFILE_MAX))
            except OSError as e:
                if isinstance(e, (BlockingIOError, InterruptedError)):
                    raise
                if e.errno in _SENDFILE_FALLBACK_ERRNOS:
                    # fs/socket pairing refuses the splice: degrade to
                    # the one-copy pread + sendmsg ladder rung, and
                    # memoize the refusal so this stays a ONE-shot
                    # event, not a per-chunk loop-stalling disk read
                    self.server._sendfile_refused_once()
                    metrics.add("net.serve.copy")
                    data = os.pread(item.slice.fd, item.remaining,
                                    item.file_off)
                    if len(data) != item.remaining:
                        raise TransportError(
                            f"short read {len(data)}/{item.remaining} "
                            f"at {item.slice.path}:{item.file_off}")
                    item.slice.release()
                    self._outq[0] = _BufItem(
                        [data], credited=item.credited, t0=item.t0,
                        tenant=item.tenant, stamps=item.stamps)
                    return self._send_bufs(self._outq[0])
                raise
            if n == 0:
                raise TransportError(
                    f"sendfile hit EOF mid-chunk at {item.slice.path}:"
                    f"{item.file_off} (truncated MOF?)")
            item.file_off += n
            item.remaining -= n
            metrics.add("net.bytes.out", n, role="server")
            metrics.add("net.sendfile.bytes", n)
        item.slice.release()
        return True

    # -- teardown (loop thread) ----------------------------------------------

    @loop_callback
    def begin_drain(self) -> None:
        """Stop reading; let in-flight responses flush (the stop(drain=
        True) path)."""
        if self.closed or self.draining:
            return
        self.draining = True
        self._drop_parked()
        self.server._sweep()
        self._update_interest()
        if self.inflight == 0 and not self._outq:
            self.close()

    def drained(self) -> bool:
        return self.inflight == 0 and not self._outq

    @loop_callback
    def close(self) -> None:
        if self.closed:
            return
        self.closed = True
        self.loop.unregister(self.sock)
        wire.close_hard(self.sock)  # shutdown-then-close: forces the
        # FIN out and wakes the peer's blocked reader (see close_hard)
        with self._wlock:
            items = list(self._outq)
            self._outq.clear()
            self._poison = True
        for item in items:
            _release_item(item)
            self._settle(item.credited, getattr(item, "tenant", ""))
        # batched-but-unflushed requests die with the connection: they
        # were credited at _start, so settle them like torn responses
        # (closed flag is set — _settle only rebalances the gauge and
        # returns the tenant credit)
        batch, self._batch = self._batch, []
        for (_req_id, req, _t0, span) in batch:
            span.end(error="closed")
            self._settle(True, getattr(req, "tenant", ""))
        self._drop_parked()
        if self.server.push is not None:
            # settle the push window (resledger: a dead peer must not
            # strand push.on_air) and forget its subscriptions
            self.server.push.drop_conn(self)
        self.server._forget(self)
        metrics.gauge_add("net.server.connections", -1)
        self.server._sweep()  # freed tenant credits flow to neighbors


class EvLoopShuffleServer:
    """Serves many concurrent reduce clients over TCP from one
    DataEngine, all on one event loop. ``port=0`` binds an ephemeral
    port (tests); read the bound address back from :attr:`address` /
    :attr:`port`."""

    def __init__(self, engine: DataEngine, config: Optional[Config] = None,
                 host: Optional[str] = None, port: Optional[int] = None,
                 registry=None):
        cfg = config or Config()
        self.engine = engine
        self.bind_host = host if host is not None \
            else str(cfg.get("uda.tpu.net.bind"))
        self.bind_port = int(port if port is not None
                             else cfg.get("uda.tpu.net.port"))
        self.credit = max(1, int(cfg.get("mapred.rdma.wqe.per.conn")))
        # multi-tenant service plane (uda_tpu/tenant/): on when a
        # registry is injected or uda.tpu.tenant.enable is set. Off =
        # the single-job data plane of PRs 4-13, bit for bit (no
        # registry lookups, no scheduler, empty tenant stamps).
        self.tenancy = registry is not None \
            or bool(cfg.get("uda.tpu.tenant.enable"))
        self.registry = registry
        self._sched = None
        self.quantum_bytes = 0
        self.default_tenant = ""
        self.strict_tenancy = False
        self._sweeping = False
        if self.tenancy:
            from uda_tpu.tenant import (DEFAULT_TENANT, CreditScheduler,
                                        TenantRegistry)
            if self.registry is None:
                self.registry = TenantRegistry.from_config(cfg)
            self.default_tenant = DEFAULT_TENANT
            self.strict_tenancy = bool(cfg.get("uda.tpu.tenant.strict"))
            # the shared credit pool: uda.tpu.tenant.wqe.total, default
            # = the per-conn cap (the bound the single knob provided,
            # now weighted-fair ACROSS connections and jobs)
            total = int(cfg.get("uda.tpu.tenant.wqe.total")) \
                or self.credit
            # byte-cost quanta: deficits earned/charged in requested
            # bytes so mixed chunk sizes stay byte-fair (0 = the
            # request-count quanta of the original scheduler); a
            # chunk_size=0 REQ is charged the engine's default serve
            # size (the same resolution data_engine applies)
            self.quantum_bytes = max(
                0, int(cfg.get("uda.tpu.tenant.quantum.kb"))) * 1024
            # the ENGINE's own default-serve size — one resolution,
            # read not re-derived (stub engines in tests fall back to
            # the same flag the engine derives it from)
            self.chunk_bytes_default = max(1, int(getattr(
                engine, "chunk_size_default",
                int(cfg.get("mapred.rdma.buf.size")) * 1024)))
            self._sched = CreditScheduler(
                total, weight_of=self.registry.weight_of,
                quantum=float(self.quantum_bytes or 1),
                penalty_threshold=int(
                    cfg.get("uda.tpu.tenant.penalty.threshold")),
                penalty_ms=int(cfg.get("uda.tpu.tenant.penalty.ms")))
            # per-tenant read-budget partitions + retire-time ledger
            # drains (getattr: stub engines in tests have no registry
            # seam and simply skip the partition layer)
            wire_registry = getattr(engine, "set_tenant_registry", None)
            if wire_registry is not None:
                wire_registry(self.registry)
        self.drain_s = float(cfg.get("uda.tpu.net.drain.s"))
        self.sockbuf_kb = int(cfg.get("uda.tpu.net.sockbuf.kb"))
        self.zero_copy = bool(cfg.get("uda.tpu.net.zerocopy"))
        mode = str(cfg.get("uda.tpu.net.zerocopy.mode")).strip().lower()
        if not self.zero_copy:
            self.zc_mode = "off"
        elif mode in ("sendfile", "mmap"):
            self.zc_mode = mode
        else:  # auto: probe once per process
            self.zc_mode = _pick_zerocopy_mode()
        self._sendfile_refused = False
        # batched byte-path serves (uda.tpu.read.batch; the engine owns
        # the knob/tuning-cache resolution — getattr keeps stub engines
        # in tests working)
        self.batch_reads = bool(getattr(engine, "batch_enabled", False))
        self.batch_max = int(getattr(engine, "batch_max", 256))
        self._cfg = cfg  # start() arms the live-telemetry plane from it
        self._listener: Optional[socket.socket] = None
        self._loop: Optional[EventLoop] = None
        self._conns: set = set()
        self._lock = TrackedLock("net.server")
        self._stopping = threading.Event()
        # warm-restart handoff (uda.tpu.net.handoff.path): generation
        # identity + served-offset watermarks; minted per start()
        self.handoff_path = str(cfg.get("uda.tpu.net.handoff.path"))
        self.generation = 0
        self.warm_restart = False
        # elastic drain (ISSUE 18): once announce_drain() flips this,
        # every subsequent HELLO banner carries CAP_DRAINING so reduce
        # sides stop placing NEW work here while in-flight serves
        # complete; the store layer migrates retained MOFs in parallel
        # udarace: lockfree=_draining - one-way bool latch flipped by
        # the control thread; the loop reading it one accept late just
        # sends one more non-draining banner (harmless, self-corrects)
        self._draining = False
        self._marks: dict = {}  # "peer|job|map|reduce" -> served end
        self._marks_lock = threading.Lock()
        # push plane (ISSUE 19, uda.tpu.push.enable): supplier-
        # initiated MSG_PUSH of committed partitions to subscribed
        # reduce connections. Off = the pull-only plane, bit for bit
        # (no CAP_PUSH in the banner, MSG_PUSH_SUB answered with the
        # typed-ERR refusal every unknown frame gets).
        self.push = None
        if bool(cfg.get("uda.tpu.push.enable")):
            from uda_tpu.net.push import PushScheduler
            self.push = PushScheduler(self, engine, cfg)

    # -- warm-restart handoff -----------------------------------------------

    def _load_generation(self) -> tuple[int, bool]:
        """The advertised server generation: a persisted handoff record
        continues as generation+1 with the warm flag (clients may keep
        resumed offsets); without one — first boot, kill -9, unreadable
        record — a fresh random generation is minted so a COLD restart
        can never masquerade as the same server instance."""
        path = self.handoff_path
        if path:
            try:
                failpoint("net.handoff", key="load")
                with open(path) as f:
                    rec = json.load(f)
                # CONSUME the record: it proves exactly ONE graceful
                # stop. Left in place, a later kill -9 would replay it
                # and the cold restart would advertise the same warm
                # generation as the killed instance — clients would
                # see no generation change and keep resuming against
                # possibly-different bytes.
                os.unlink(path)
                gen = (int(rec["generation"]) + 1) & 0x7FFFFFFF
                metrics.add("net.handoff.loaded")
                return max(1, gen), True
            except FileNotFoundError:
                pass  # first boot: cold by definition
            except Exception as e:  # noqa: BLE001 - a bad record is a
                # cold start, never a refused start
                metrics.add("errors.swallowed")
                log.warn(f"net: handoff record {path} unreadable ({e}); "
                         f"cold start")
        gen = int.from_bytes(os.urandom(4), "big") & 0x7FFFFFFF
        return max(1, gen), False

    # -- the weighted-fair credit plane (loop thread) ------------------------

    def _sweep(self) -> None:
        """The WDRR grant sweep: move freed credits to parked requests
        across ALL connections by weighted deficit round-robin.
        ITERATIVE like the per-conn unpark loop (the PR 6 recursion
        lesson): a grant served fully inline re-enters via _settle —
        the ``_sweeping`` guard turns that into a no-op and the outer
        loop re-runs grant_parked until nothing moves."""
        if not self.tenancy or self._sweeping:
            return
        self._sweeping = True
        try:
            while True:
                granted = self._sched.grant_parked()
                if not granted:
                    return
                for conn, entry in granted:
                    conn._granted(entry)
        finally:
            self._sweeping = False

    def _release_and_sweep(self, tenant: str) -> None:
        """Loop-marshalled credit return for off-loop settles (dead
        connection, stopped-loop races)."""
        if self.tenancy:
            self._sched.release(tenant)
            self._sweep()

    def _note_fault(self, tenant: str) -> None:
        """Loop-marshalled tenant-penalty feedback (see _complete)."""
        if self.tenancy:
            self._sched.note_fault(tenant)

    def _validate_req(self, conn: _EvConn, req) -> None:
        """The per-REQ registry gate. Bound jobs validate every
        request (typed TenantError on unknown/retired/stale-epoch).
        Never-bound jobs keep the pre-tenancy contract — they ride the
        default tenant — unless ``uda.tpu.tenant.strict`` demands
        registration. (The tenant itself is resolved by
        ``_entry_tenant`` and stamped before this gate runs, so a
        refusal settles the same account the admit charged.)"""
        bound = conn.bindings.get(req.job_id)
        if bound is None:
            if self.strict_tenancy:
                raise TenantError(
                    f"job {req.job_id!r} is not registered on this "
                    f"connection and the daemon requires MSG_JOB "
                    f"registration (uda.tpu.tenant.strict)")
            return
        tenant, epoch = bound
        if epoch <= 0:
            raise TenantError(
                f"job {req.job_id!r}: registration was refused on "
                f"this connection (stale epoch or failed auth); its "
                f"fetches stay fenced")
        self.registry.validate(tenant, req.job_id, epoch)

    _MARKS_CAP = 4096  # bound the table: oldest partition evicted

    def _mark_served(self, peer: str, req, end: int,
                     tenant: str = "") -> None:
        """Track the served-offset watermark per PARTITION (not per
        conn — peers carry ephemeral ports, and keying by them would
        grow the table one entry per reconnect for the server's
        lifetime). Advisory: it may lead the wire by in-flight frames
        — resume correctness never depends on it (the CLIENT's offset
        ledger is authoritative); the record is the drain proof +
        diagnostics a restarted supplier starts from. Bounded: beyond
        the cap the oldest partition's mark is evicted (insertion
        order — long-finished partitions go first).

        Keyed by (tenant, job, map, reduce) — partition identity alone
        was the PR 8 single-tenant assumption: two tenants may carry
        the SAME job/map/reduce ids (each embedder mints its own), and
        a warm bounce must never hand one job's served offsets to
        another's fetch ledger."""
        if not self.handoff_path:
            return
        key = f"{tenant}|{req.job_id}|{req.map_id}|{req.reduce_id}"
        with self._marks_lock:
            if end > self._marks.get(key, -1):
                self._marks.pop(key, None)  # refresh insertion order
                self._marks[key] = end
                if len(self._marks) > self._MARKS_CAP:
                    self._marks.pop(next(iter(self._marks)))

    def _write_handoff(self) -> None:
        if not self.handoff_path:
            return
        with self._marks_lock:
            marks = dict(self._marks)
        try:
            failpoint("net.handoff", key="save")
            tmp = self.handoff_path + ".tmp"
            with open(tmp, "w") as f:
                json.dump({"generation": self.generation,
                           "watermarks": marks}, f)
            os.replace(tmp, self.handoff_path)
            metrics.add("net.handoff.persisted")
        except Exception as e:  # noqa: BLE001 - losing the handoff
            # downgrades the NEXT start to cold; it must not turn a
            # graceful stop into a crash
            metrics.add("errors.swallowed")
            log.warn(f"net: handoff record {self.handoff_path} not "
                     f"persisted ({e}); next start will be cold")

    # -- lifecycle ----------------------------------------------------------

    def start(self) -> "EvLoopShuffleServer":
        if self._listener is not None:
            raise UdaError("ShuffleServer already started")
        ls = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        ls.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        ls.bind((self.bind_host, self.bind_port))
        ls.listen(128)
        ls.setblocking(False)
        # the handoff record is CONSUMED by _load_generation, so it
        # must survive a failed start: load only after bind/listen
        # succeeded — a transient EADDRINUSE (old socket in TIME_WAIT)
        # must not silently downgrade the supervisor's retry to cold
        self.generation, self.warm_restart = self._load_generation()
        metrics.gauge("net.server.generation", self.generation)
        self._listener = ls
        self._stopping.clear()
        self._loop = EventLoop("uda-net-loop").start()
        self._loop.call_soon(self._loop.register, ls, _READ,
                             self._on_accept)
        # the MSG_STATS scrape surface: this server's conn table +
        # generation, folded into every introspection snapshot — plus
        # the time-accounting block (serve-bucket-dominant on a pure
        # supplier), so udatop's where-time-goes column answers for
        # both roles
        from uda_tpu.utils.critpath import install_stats_provider
        from uda_tpu.utils.stats import register_stats_provider
        register_stats_provider("net.server", self._stats_snapshot)
        install_stats_provider()
        # the live-telemetry plane (ISSUE 17): rollup ring + anomaly
        # detectors + SLI book + optional OpenMetrics exposition —
        # armed once per process, gated on the stats plane like the
        # StatsReporter (arm_observability_plane is idempotent)
        from uda_tpu.utils.timeseries import arm_observability_plane
        arm_observability_plane(self._cfg)
        if self.tenancy and self._sched is not None:
            # the fairness audit needs the scheduler's granted-byte
            # view regardless of whether the ring is armed yet — the
            # book holds state only once rollups flow
            from uda_tpu.tenant.sli import sli_book
            sli_book.attach(scheduler=self._sched,
                            registry=self.registry)
        log.info(f"shuffle server listening on {self.address[0]}:"
                 f"{self.address[1]} (credit/conn={self.credit}, "
                 f"core=evloop, zerocopy={self.zero_copy}, "
                 f"generation={self.generation}"
                 f"{' warm' if self.warm_restart else ''})")
        return self

    @property
    def address(self) -> tuple:
        if self._listener is None:
            raise UdaError("ShuffleServer not started")
        return self._listener.getsockname()[:2]

    @property
    def port(self) -> int:
        return self.address[1]

    @loop_callback
    def _on_accept(self, mask: int) -> None:
        ls = self._listener  # stop() nulls the attribute concurrently
        if ls is None:
            return
        while True:
            try:
                sock, addr = ls.accept()
            except (BlockingIOError, InterruptedError):
                return
            except OSError:
                return  # listener closed (stop path)
            peer = f"{addr[0]}:{addr[1]}"
            try:
                # slow-accept / dropped-at-birth injection point (a
                # delay here stalls the loop like a slow accept stalls
                # the reference's cm_event_handler — chaos-only)
                failpoint("net.accept", key=peer)
            except UdaError as e:
                log.warn(f"net: accept of {peer} rejected: {e}")
                wire.close_hard(sock)
                continue
            sock.setblocking(False)
            wire.tune_socket(sock, self.sockbuf_kb)
            conn = _EvConn(self, sock, peer)
            with self._lock:
                # stopping-check and _conns.add are ATOMIC under the
                # lock (threaded-core parity): a connection accepted
                # during stop() must either be closed here or appear
                # in stop()'s snapshot — never slip between them and
                # leak an ESTABLISHED socket with no reader
                if self._stopping.is_set():
                    wire.close_hard(sock)
                    return
                self._conns.add(conn)
            metrics.add("net.accepts")
            metrics.gauge_add("net.server.connections", 1)
            conn.register()
            # the accept banner: generation + warm flag + capability
            # bits (CAP_TENANT advertises the tenant plane), the FIRST
            # frame on the connection (uncredited — it answers no
            # request); rides _enqueue so the net.frame failpoint can
            # tear it like any other frame
            caps = wire.CAP_TRACE | wire.CAP_OBS | wire.CAP_ELASTIC \
                | (wire.CAP_TENANT if self.tenancy else 0) \
                | (wire.CAP_DRAINING if self._draining else 0) \
                | (wire.CAP_PUSH if self.push is not None
                   and not self._draining else 0)
            hello = wire.encode_hello(self.generation, self.warm_restart,
                                      caps=caps)
            conn._enqueue(_BufItem([hello], credited=False,
                                   t0=time.perf_counter()), hello)

    def _forget(self, conn: _EvConn) -> None:
        with self._lock:
            self._conns.discard(conn)

    def notify_commit(self, job_id: str, map_id: str) -> None:
        """The MOFWriter commit seam: a map output just became
        fetchable — push it to every subscribed reduce connection
        (wire a writer with ``on_commit=server.notify_commit``). A
        no-op on a pull-only or draining server, so embedders can
        call it unconditionally."""
        if self.push is not None and not self._draining:
            self.push.notify_commit(job_id, map_id)

    def _stats_snapshot(self) -> dict:
        """The introspection provider: generation, bound port, loop
        health and the per-connection table (peer, in-flight depth,
        parked backlog, drain state). Lock-light reads of monotone
        fields — a racy glance is the contract of a live console."""
        with self._lock:
            conns = list(self._conns)
        loop = self._loop
        with self._marks_lock:
            nmarks = len(self._marks)
        snap = {
            "generation": self.generation,
            "warm_restart": self.warm_restart,
            "port": (self._listener.getsockname()[1]
                     if self._listener is not None else None),
            "credit_per_conn": self.credit,
            "zerocopy_mode": self.zc_mode,
            "loop": (loop.stats() if loop is not None
                     else {"alive": False}),
            "watermarks": nmarks,
            "connections": [
                {"peer": c.peer, "inflight": c.inflight,
                 "parked": len(c._parked), "credits": c._credits,
                 "tenant": c.tenant,
                 "draining": c.draining, "closed": c.closed}
                for c in conns],
        }
        if self.tenancy:
            # racy glance of loop-owned scheduler state (the live-
            # console contract); a mid-mutation dict walk degrades to
            # an error marker, never a broken MSG_STATS reply
            try:
                snap["tenancy"] = {"registry": self.registry.snapshot(),
                                   "scheduler": self._sched.stats()}
            except RuntimeError:  # udalint: disable=UDA006 - a racing
                snap["tenancy"] = {"racing": True}  # sweep moved the
                # dicts under the walk; the next poll answers
        return snap

    def _sendfile_refused_once(self) -> None:
        """First sendfile refusal (EINVAL-class: the fs/socket pairing
        will never splice): memoize it so the serve path stops planning
        sendfile — the one-shot pread fallback must not become a
        per-chunk loop-stalling disk read. Subsequent fd slices ride
        the mmap mechanism; files that cannot be mapped either drop
        zero-copy planning entirely (see _complete's last rung)."""
        if self._sendfile_refused:
            return
        self._sendfile_refused = True
        if self.zc_mode == "sendfile":
            self.zc_mode = "mmap"
            log.warn("net: sendfile refused by the fs/socket pairing; "
                     "switching the zero-copy serve mechanism to mmap")

    def announce_drain(self, store=None, job_id: Optional[str] = None):
        """Begin elastic departure (the symmetric half of mid-job join):
        flip the banner to CAP_DRAINING — every connection accepted
        from here on learns this supplier is leaving and demotes it in
        candidate ranking (already-connected peers keep their credits;
        in-flight serves complete normally) — and, when a StoreManager
        is attached, migrate the retained MOF partitions to the blob
        tier so the job can still fetch them AFTER this process exits
        (migrated, not reconstructed). Idempotent; returns the list of
        migration records (empty without a store). The caller follows
        with ``stop(drain=True)`` once its producers are quiesced."""
        first = not self._draining
        self._draining = True
        if first:
            metrics.add("elastic.drains")
            flightrec.record("elastic.drain", generation=self.generation)
            log.info(f"net: drain announced (generation "
                     f"{self.generation}); new banners carry "
                     f"CAP_DRAINING")
        moved = []
        if store is not None:
            moved = store.drain(job_id)
        return moved

    def stop(self, drain: bool = True) -> None:
        """Stop serving. ``drain=True`` (the default) completes what the
        engine already accepted: stop reading new requests everywhere,
        flush in-flight responses for up to ``uda.tpu.net.drain.s``,
        then close. ``drain=False`` tears connections down mid-stream
        (clients see TransportError — the killed-supplier shape the
        retry/penalty machinery must absorb)."""
        if self._loop is None:
            return
        self._stopping.set()
        if self.push is not None:
            self.push.stop()
        from uda_tpu.utils.stats import unregister_stats_provider
        unregister_stats_provider("net.server", self._stats_snapshot)
        if self.tenancy and self._sched is not None:
            from uda_tpu.tenant.sli import sli_book
            sli_book.detach(self._sched)  # only if still ours
        loop = self._loop
        ls, self._listener = self._listener, None
        if ls is not None:
            loop.call_soon(loop.unregister, ls)
            wire.close_hard(ls)
        with self._lock:
            conns = list(self._conns)
        if drain:
            for c in conns:
                loop.call_soon(c.begin_drain)
            deadline = time.monotonic() + self.drain_s
            while time.monotonic() < deadline:
                if all(c.drained() or c.closed for c in conns):
                    break
                time.sleep(0.01)
            # the graceful-stop handoff: everything the engine accepted
            # has flushed (or the drain window closed) — persist the
            # generation + watermarks so the NEXT start advertises a
            # warm generation+1 and clients keep their resumed offsets
            self._write_handoff()
        for c in conns:
            loop.call_soon(c.close)
        deadline = time.monotonic() + 2.0
        while time.monotonic() < deadline:
            if all(c.closed for c in conns):
                break
            time.sleep(0.005)
        loop.stop()
        self._loop = None
        # Deliberately NOT a ResourceLedger drain point: the engine
        # outlives the server (a warm bounce reuses it, and its pool
        # may still be running a delayed pread for a force-closed conn
        # — that pread's fd pin is live, not leaked). fd-pin quiescence
        # is asserted where it is a contract: DataEngine.stop (pool
        # drained, cache closed) and the bridge-EXIT full drain.

    def __enter__(self) -> "EvLoopShuffleServer":
        return self

    def __exit__(self, *exc) -> None:
        self.stop()


# The event loop is THE server core: the legacy thread-per-connection
# baseline (PR 4) was deleted once BENCH_NET_r07.json recorded the
# second evloop-only point (last A/B: BENCH_NET_r06.json, 2.92x).
ShuffleServer = EvLoopShuffleServer
