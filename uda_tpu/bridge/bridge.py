"""The UdaBridge control surface.

Re-creation of the reference's JNI bridge contract (reference
src/UdaBridge.cc) as an embeddable Python API with the same shape:

- down-calls: ``start(is_net_merger, argv, callable)`` (startNative,
  UdaBridge.cc:187-263), ``do_command(cmd)`` (doCommandNative :266-295),
  ``reduce_exit()`` (reduceExitMsgNative :299-314), ``set_log_level``
  (:318-333);
- up-calls on the registered ``UdaCallable``: ``fetch_over_message``,
  ``data_from_uda``, ``get_path_uda``, ``get_conf_data``, ``log_to``
  and ``failure_in_uda`` — the 6 cached callback methods of
  UdaBridge.cc:138-170, 516-522;
- role dispatch: NetMerger (reduce side, MergeManager_main +
  reduce_downcall_handler, reference src/Merger/NetMergerMain.cc:44-88)
  vs MOFSupplier (server side, MOFSupplier_main + mof_downcall_handler,
  reference src/MOFServer/MOFSupplierMain.cc:37-143), selected by the
  ``is_net_merger`` flag exactly like UdaBridge.cc:217-238;
- the fallback contract: any engine failure is reported through
  ``failure_in_uda`` and the bridge goes inert, unless
  ``mapred.rdma.developer.mode`` is set, in which case it re-raises
  (reference UdaBridge.cc:506-530, UdaShuffleConsumerPluginShared.java:
  205-242).

A JNI-loadable C shim over this class (libuda replacement for running
under an actual Hadoop JVM) is planned for a later round; the command
protocol and up-call semantics here are the compatibility layer it will
bind to.
"""

from __future__ import annotations

import contextlib
import json
import threading
from typing import Optional, Protocol, Sequence

from uda_tpu.bridge.protocol import Cmd, parse_cmd
from uda_tpu.merger import LocalFetchClient, MergeManager
from uda_tpu.merger.segment import InputClient
from uda_tpu.mofserver import DataEngine, IndexRecord, IndexResolver
from uda_tpu.utils import compile_cache
from uda_tpu.utils.budget import MemoryBudget, hbm_ledger
from uda_tpu.utils.config import Config
from uda_tpu.utils.errors import FallbackSignal, ProtocolError, UdaError
from uda_tpu.utils.failpoints import failpoint
from uda_tpu.utils.logging import LogLevel, get_logger
from uda_tpu.utils.metrics import metrics, stats_enabled_from_env
from uda_tpu.utils.resledger import resledger
from uda_tpu.utils.stats import (StatsReporter, reporter_output_from_env,
                                 telemetry_block)

__all__ = ["UdaCallable", "UdaBridge"]

log = get_logger()

# The down-calls that open a reduce task. With start() they run on the
# caller's thread before the merge thread opens the reduce_task root
# span, so the span tree cannot see them: the bridge_open timer does.
# EXIT is not one of them (it follows the end of the stream).
_OPEN_CMDS = (Cmd.INIT, Cmd.FETCH, Cmd.FINAL)


class UdaCallable(Protocol):
    """The up-call interface the embedder registers (the reference's
    UdaCallable/UdaPluginRT/UdaPluginSH surface, UdaBridge.java:85-145).
    All methods are optional; missing ones are no-ops (except
    get_path_uda, required on the supplier side when no local root is
    configured)."""

    def fetch_over_message(self) -> None: ...

    def data_from_uda(self, data: memoryview, length: int) -> None: ...

    def get_path_uda(self, job_id: str, map_id: str,
                     reduce_id: int) -> IndexRecord: ...

    def get_conf_data(self, name: str, default: str) -> str: ...

    def log_to(self, level: int, message: str) -> None: ...

    def failure_in_uda(self, error: Exception) -> None: ...


class _UpcallIndexResolver(IndexResolver):
    """Supplier index resolution through the get_path_uda up-call — the
    reference's first-fetch Java IndexCache round trip (IndexInfo.cc:
    237-251, UdaPluginSH.java:107-144), cached per (job, map, reduce)."""

    def __init__(self, callable_obj):
        self._callable = callable_obj
        self._cache: dict[tuple, IndexRecord] = {}
        self._lock = threading.Lock()

    def resolve(self, job_id: str, map_id: str, reduce_id: int) -> IndexRecord:
        key = (job_id, map_id, reduce_id)
        with self._lock:
            rec = self._cache.get(key)
        if rec is None:
            rec = self._callable.get_path_uda(job_id, map_id, reduce_id)
            with self._lock:
                self._cache[key] = rec
        return rec

    def resolve_cached(self, job_id: str, map_id: str,
                       reduce_id: int) -> Optional[IndexRecord]:
        """Cache-hit-only resolve (no upcall): the event-loop serve
        path's inline fast path; a miss returns None and the caller
        falls back to the engine pool, whose resolve() pays the upcall
        off the loop thread."""
        with self._lock:
            return self._cache.get((job_id, map_id, reduce_id))

    def invalidate(self, job_id: str) -> None:
        with self._lock:
            for key in [k for k in self._cache if k[0] == job_id]:
                del self._cache[key]


class UdaBridge:
    """One bridge instance per role process (the reference allows one
    reduce task per NetMerger process, reducer.h:137)."""

    def __init__(self) -> None:
        self.callable: Optional[UdaCallable] = None
        self.is_net_merger = False
        self.cfg = Config()
        self.started = False
        self._failed = False
        self._dev_error: Optional[Exception] = None
        # reduce side
        self._mm: Optional[MergeManager] = None
        self._client: Optional[InputClient] = None
        self._job_id: Optional[str] = None
        self._reduce_id: Optional[int] = None
        self._key_class = "uda.tpu.RawBytes"
        self._pending_maps: list[tuple[str, str]] = []  # (host, attempt)
        self._attempt_by_task: dict[str, str] = {}
        self._merge_started = False
        self._merge_thread: Optional[threading.Thread] = None
        # supplier side
        self._engine: Optional[DataEngine] = None
        self._resolver: Optional[IndexResolver] = None
        self._owned_engine: Optional[DataEngine] = None
        # network data plane (uda.tpu.net.listen): the ShuffleServer
        # serving this role's engine to remote reduce clients
        self._net_server = None
        # multi-tenant registry (uda.tpu.tenant.enable): one per
        # bridge lifetime, shared across re-INITs
        self._tenant_registry = None
        # observability
        self._stats: Optional[StatsReporter] = None

    # -- down-calls ---------------------------------------------------------

    def start(self, is_net_merger: bool, argv: Sequence[str],
              callable_obj: Optional[UdaCallable] = None) -> None:
        """startNative: parse argv (the reference's getopt channel), wire
        the conf pull channel, pick the role (UdaBridge.cc:187-263)."""
        with (metrics.timer("bridge_open") if is_net_merger
              else contextlib.nullcontext()):
            self._start(is_net_merger, argv, callable_obj)

    def _start(self, is_net_merger: bool, argv: Sequence[str],
               callable_obj: Optional[UdaCallable]) -> None:
        compile_cache.enable()
        self.callable = callable_obj
        self.is_net_merger = is_net_merger
        self._argv = list(argv)
        self.cfg = self._fresh_cfg()
        if callable_obj is not None and hasattr(callable_obj, "log_to"):
            get_logger().set_sink(callable_obj.log_to)
        get_logger().set_level(self.cfg.get("uda.log.level"))
        # the flight recorder rides both roles from process start
        # (uda.tpu.flightrec.*; the env kill switch still wins)
        from uda_tpu.utils.flightrec import (flightrec,
                                             flightrec_enabled_from_env)
        flightrec.configure(
            enabled=(bool(self.cfg.get("uda.tpu.flightrec.enable"))
                     and flightrec_enabled_from_env()),
            capacity=int(self.cfg.get("uda.tpu.flightrec.events")),
            dump_dir=str(self.cfg.get("uda.tpu.flightrec.dir")))
        if not is_net_merger:
            # MOFSupplier_main: the data engine serves fetches; paths
            # resolve through the up-call (the IndexCache round trip).
            # Reader threads scale with the configured disk count
            # (reference AsyncReaderManager.cc:16-50).
            self._resolver = _UpcallIndexResolver(self.callable)
            dirs = [d for d in str(
                self.cfg.get("mapred.local.dir", default="")).split(",")
                if d.strip()]
            self._engine = DataEngine(self._resolver, self.cfg,
                                      num_disks=max(1, len(dirs)))
        self._start_stats()
        self.started = True
        log.info(f"uda_tpu bridge started as "
                 f"{'NetMerger' if is_net_merger else 'MOFSupplier'}")

    def _start_stats(self) -> None:
        """Observability wiring (UDA_TPU_STATS=1 / uda.tpu.stats.enable):
        switch the optional metrics layers on and run a StatsReporter
        for the life of the bridge role. Off by default — zero threads,
        no histogram/span recording."""
        if self._stats is not None:  # re-start(): recycle the reporter
            self._stats.stop(final=False)
            self._stats = None
        if not (stats_enabled_from_env()
                or self.cfg.get("uda.tpu.stats.enable")):
            return
        metrics.enable_stats()
        self._stats = StatsReporter(
            interval_s=self.cfg.get("uda.tpu.stats.interval.ms") / 1e3,
            out=reporter_output_from_env(
                str(self.cfg.get("uda.tpu.stats.jsonl", default="")))).start()
        # the live telemetry plane rides the same opt-in: rollup ring,
        # anomaly detectors, SLO book, optional OpenMetrics endpoint
        from uda_tpu.utils.timeseries import arm_observability_plane
        arm_observability_plane(self.cfg)

    def _fresh_cfg(self) -> Config:
        """Config rebuilt from the start-time argv + conf up-call. Each
        INIT gets a FRESH one: INIT-derived settings (codec class,
        shrunken buffer size, lpq size) are per-job and must not leak
        into the next re-INIT on the same bridge — a stale
        compress=True would wrap an uncompressed job's fetches in a
        DecompressingClient and hang the merge."""
        cfg = Config.from_argv(list(self._argv))
        if self.callable is not None and hasattr(self.callable,
                                                 "get_conf_data"):
            cfg.conf_source = self.callable.get_conf_data
        return cfg

    def data_engine(self) -> DataEngine:
        """The supplier's engine (for in-process reduce-side clients —
        the single-host wiring where both roles share a process)."""
        if self._engine is None:
            raise UdaError("bridge not started as MOFSupplier")
        return self._engine

    def do_command(self, cmd: str) -> Optional[str]:
        """doCommandNative: dispatch by role (UdaBridge.cc:266-295).
        Most commands return None; GET_STATS returns the current stats
        record as a JSON string."""
        if not self.started:
            raise UdaError("bridge not started")
        if self._dev_error is not None:
            raise self._dev_error  # developer mode: surface the stored
            # background failure loudly on the next synchronous call
        if self._failed:
            return None  # inert after failure (Java fell back to vanilla)
        try:
            header, params = parse_cmd(cmd)
            if header == Cmd.GET_STATS:  # role-independent, like
                return json.dumps(self.get_stats())  # set_log_level
            if self.is_net_merger:
                with (metrics.timer("bridge_open") if header in _OPEN_CMDS
                      else contextlib.nullcontext()):
                    self._reduce_downcall(header, params)
            else:
                self._mof_downcall(header, params)
        except Exception as e:  # noqa: BLE001 - ANY engine failure must
            # flow through the fallback contract (e.g. a ValueError from
            # a malformed INIT param), not escape into the embedder
            self._fail(e)
        return None

    def get_stats(self) -> dict:
        """The on-demand stats pull (the GET_STATS command body): the
        reporter's latest record when one is running, else a one-shot
        telemetry block computed directly from the metrics hub."""
        if self._stats is not None:
            return self._stats.latest()
        return telemetry_block()

    def _maybe_start_net_server(self, engine: Optional[DataEngine]) -> None:
        """Start the shuffle data plane next to the role's engine when
        ``uda.tpu.net.listen`` is set (the RDMAServer-next-to-DataEngine
        shape, reference MOFSupplierMain.cc:84-143). Idempotent per
        bridge lifetime; torn down with the engine."""
        if self._net_server is not None or engine is None:
            return
        if not self.cfg.get("uda.tpu.net.listen"):
            return
        from uda_tpu.net import ShuffleServer
        registry = None
        if self.cfg.get("uda.tpu.tenant.enable"):
            # the multi-tenant daemon shape (uda_tpu/tenant/): one
            # registry per bridge lifetime — re-INITs on the same
            # bridge keep serving the same tenant books
            from uda_tpu.tenant import TenantRegistry
            if self._tenant_registry is None:
                self._tenant_registry = TenantRegistry.from_config(
                    self.cfg)
            registry = self._tenant_registry
        self._net_server = ShuffleServer(engine, self.cfg,
                                         registry=registry).start()

    def _stop_net_server(self) -> None:
        srv, self._net_server = self._net_server, None
        if srv is not None:
            srv.stop()

    def net_server(self):
        """The running ShuffleServer (None unless uda.tpu.net.listen):
        embedders read its bound port for service registration."""
        return self._net_server

    def reduce_exit(self) -> None:
        """reduceExitMsgNative: synchronous teardown of the reduce task
        (UdaBridge.cc:299-314, finalize_reduce_task reducer.cc:354-410)."""
        t = self._merge_thread
        if t is not None:
            t.join()
        if self._mm is not None:
            self._mm.stop()
            self._mm = None
        self._stop_net_server()  # before its engine goes away
        if self._owned_engine is not None:
            self._owned_engine.stop()
            self._owned_engine = None
        self._merge_thread = None
        if self._stats is not None:
            # the per-reduce-task aggregate record (the reference's
            # teardown-time counter trio, StreamRW.cc:555-569): one
            # final-flagged JSONL record; the reporter keeps running for
            # a possible re-INIT on the same bridge
            self._stats.report_once(final=True)
        if self._dev_error is not None:
            # developer mode: a failure that happened on the merge thread
            # must not vanish with the thread — teardown re-raises it
            err, self._dev_error = self._dev_error, None
            raise err

    def set_log_level(self, level: int) -> None:
        """setLogLevelNative (UdaBridge.cc:318-333)."""
        get_logger().set_level(level)

    # -- reduce side (reduce_downcall_handler, reducer.cc:144-217) ----------

    PAGE = 4096  # buffer page alignment (reference getpagesize())

    def _reduce_downcall(self, header: Cmd, params: list[str]) -> None:
        if header == Cmd.INIT:
            if self._mm is not None or self._owned_engine is not None:
                # re-INIT (a second reduce attempt on the same bridge):
                # tear down the previous task first — the prior engine's
                # thread pool / fd cache must not leak until process exit
                self.reduce_exit()
            self._pending_maps = []
            self._attempt_by_task = {}
            self._merge_started = False
            self.cfg = self._fresh_cfg()  # per-job settings must not leak
            if (len(params) >= 10 and params[0].isdigit()
                    and params[3].isdigit()):
                # reference layout: [0]=num_maps and [3]=lpq_size are
                # numeric; in the short form [0] is the job id and [3]
                # the key CLASS name — never all-digits — so a short
                # form with many local dirs cannot be misrouted here
                local_dirs = self._init_reference_layout(params)
            elif len(params) >= 4:
                # short form (embedder convenience): job_id, reduce_id,
                # num_maps, key_class, then optional local dirs
                self._job_id, rid, _num_maps, self._key_class = params[:4]
                self._reduce_id = int(rid)
                local_dirs = params[4:]
            else:
                raise ProtocolError(
                    f"INIT needs >= 4 params, got {len(params)}")
            # the reduce task's tenant identity (uda.tpu.tenant.id) is
            # read from this task's cfg by those who stamp it: the
            # RemoteFetchClients for their binding, the MergeManager for
            # its hot-path metric labels (fetch.bytes{tenant=})
            # INIT-time admission: the fetch-window + staging working
            # set must fit the host budget (the reducer.cc:56-133
            # buffer validation, generalized; with a tenant budget
            # share configured, the budgets are this job's PARTITION
            # of the machine, not the whole machine). Over budget
            # either shrinks the window in cfg with a warning
            # (enforce=reroute) or raises -> the fallback contract
            # (enforce=reject); an unfittable chunk always raises.
            # Runs BEFORE the MergeManager reads the window.
            MemoryBudget.from_config(self.cfg).validate_init(self.cfg)
            client = self._make_client(local_dirs)
            # data plane (uda.tpu.net.listen): serve THIS host's map
            # outputs to remote reduce clients next to the owned engine
            self._maybe_start_net_server(self._owned_engine)
            # fetch progress -> fetchOverMessage, the reference cadence:
            # one up-call per PROGRESS_INTERVAL fetched segments plus one
            # at fetch completion (MergeManager.cc:124-130); the embedder
            # counts them against numMaps (UdaPlugin.java:351-364). The
            # END of the merge STREAM is signaled in-band by the IFile
            # EOF marker, exactly as the reference's J2CQueue consumed it
            # — so a bounded staging ring (KVBuf) can apply backpressure
            # to the emitter without deadlocking fetchOutputs.
            def _fetch_progress(done: int, total: int) -> None:
                cb = getattr(self.callable, "fetch_over_message", None)
                if cb is not None:
                    cb()

            self._mm = MergeManager(client, self._key_class, self.cfg,
                                    progress=_fetch_progress)
            ckpt_dir = str(self.cfg.get("uda.tpu.ckpt.dir"))
            if ckpt_dir:
                # crash-consistent checkpointing armed
                # (merger/checkpoint.py): a restarted attempt of this
                # reduce resumes from the newest valid manifest there.
                # EXIT deliberately leaves the checkpoint alone — EXIT
                # also follows failed attempts, and the manifest IS the
                # retry's resume state; the manager discards it itself
                # on successful completion
                log.info(f"bridge INIT: crash-consistent checkpointing "
                         f"armed under {ckpt_dir} (interval "
                         f"{self.cfg.get('uda.tpu.ckpt.interval.s')} s)")
        elif header == Cmd.FETCH:
            # reference FETCH: host:jobid:attemptid:partition
            # (UdaPlugin.java:322-334); host rides with the attempt so
            # a HostRoutingClient can route per supplier
            if len(params) < 4:
                raise ProtocolError("FETCH needs 4 params")
            host, job_id, map_attempt, _partition = params[:4]
            self._fetch_attempt(host, map_attempt)
        elif header == Cmd.FINAL:
            if self._mm is None:
                raise UdaError("FINAL before INIT")
            self._merge_started = True
            maps = list(self._pending_maps)
            self._merge_thread = threading.Thread(
                target=self._merge_main, args=(maps,), daemon=True,
                name="uda-merge-thread")
            self._merge_thread.start()
        elif header == Cmd.EXIT:
            self.reduce_exit()  # emits the final-flagged stats record
            if self._stats is not None:
                self._stats.stop(final=False)
                self._stats = None
            # the reduce task is over: EVERY obligation — leases, fd
            # pins, paired-gauge increments, scoped failpoints — must
            # be settled (the process-end full drain, no pair filter).
            # The books are the PROCESS's: while another reduce task of
            # this process is live (a node's reduce slots) its open
            # obligations are legitimate, and the last task's EXIT
            # drains for all
            if hbm_ledger.holders == 0:
                resledger.drain("bridge.exit")
        else:
            raise ProtocolError(f"unexpected command {header.name} for "
                                "NetMerger role")

    def _init_reference_layout(self, params: list[str]) -> list[str]:
        """Parse the reference's 10-param INIT and validate the buffer
        budget (handle_init_msg, reducer.cc:56-133):

          0 num_maps, 1 job_id, 2 reduce_task_id, 3 lpq_size,
          4 rdma_buf_size(B), 5 min_buf(B), 6 key class, 7 codec class,
          8 comp block size(B), 9 shuffle memory size(B),
          [10 num_dirs, 11.. dirs]

        Buffer sizing mirrors the reference exactly: shrink the buffer
        when the double-buffered pool would exceed shuffleMemorySize,
        page-align, and fail (-> fallback) when the result drops under
        the configured minimum."""
        num_maps = int(params[0])
        self._job_id = params[1]
        self._reduce_id = int(params[2])
        lpq_size = int(params[3])
        max_buf = int(params[4])
        min_buf = int(params[5])
        self._key_class = params[6]
        comp_alg = params[7]
        comp_block = int(params[8])
        shuffle_mem = int(params[9])

        # buffer pairs the pool will hold: 2 per in-flight segment + the
        # extra staging buffers (RDMA_BUFFERS_PER_SEGMENT=2 /
        # EXTRA_RDMA_BUFFERS=10, reducer.cc:49-50 -> pairs = maps + 5)
        kv_bufs = max(1, num_maps + 5)
        if shuffle_mem < kv_bufs * max_buf * 2:  # 2: double buffering
            max_buf = shuffle_mem // (kv_bufs * 2)
            if max_buf < min_buf:
                raise UdaError(
                    f"Not enough memory for rdma buffers: "
                    f"shuffleMemorySize={shuffle_mem}B with {kv_bufs} "
                    f"double-buffered pairs needs >= "
                    f"{kv_bufs * min_buf * 2}B")
            log.warn(f"shrinking buffer to {max_buf}B to fit "
                     f"shuffleMemorySize={shuffle_mem}B")
        buffer_size = max_buf - max_buf % self.PAGE  # page alignment
        if buffer_size <= 0 or buffer_size < min_buf:
            raise UdaError(
                f"RDMA Buffer is too small: {max_buf}B aligns to "
                f"{buffer_size}B < min {min_buf}B")
        self.cfg.set("mapred.rdma.buf.size", max(1, buffer_size // 1024))
        if lpq_size:
            self.cfg.set("mapred.netmerger.hybrid.lpq.size", lpq_size)
        if comp_alg and comp_alg not in ("0", "null", "None"):
            self.cfg.set("mapred.compress.map.output", True)
            self.cfg.set("mapred.map.output.compression.codec", comp_alg)
            # under the codec's own key (DefaultCodec has none: its
            # blocks are io.file.buffer.size, which nothing here reads)
            family = next((f for f in ("snappy", "lzo")
                           if f in comp_alg.lower()), None)
            if comp_block and family:
                self.cfg.set(f"io.compression.codec.{family}.buffersize",
                             comp_block)
        num_dirs = int(params[10]) if len(params) > 10 else 0
        return params[11:11 + num_dirs]

    @staticmethod
    def _attempt_task(attempt: str) -> str:
        """Map-task identity of an attempt id: attempt_X_m_NNNNNN_A ->
        task X_m_NNNNNN (the dedupe key of the reference's
        GetMapEventsThread, UdaShuffleConsumerPluginShared.java:434-602).
        Ids not shaped like attempts dedupe by full string."""
        parts = attempt.rsplit("_", 1)
        if (len(parts) == 2 and attempt.startswith("attempt_")
                and parts[1].isdigit()):
            return parts[0]
        return attempt

    def _fetch_attempt(self, host: str, map_attempt: str) -> None:
        """Fetch-attempt hygiene (reference UdaShuffleConsumerPluginShared
        .java:568-589): an exact duplicate attempt is dropped; a NEW
        attempt for a map task whose earlier attempt is already merged
        (or merging) cannot be un-merged -> failure_in_uda (the
        obsolete-after-success fallback); before the merge starts the
        newer attempt simply replaces the stale one. ``host`` rides with
        the attempt so the transport can route per supplier
        (HostRoutingClient; reference RDMAClient.cc:498-527)."""
        task = self._attempt_task(map_attempt)
        existing = self._attempt_by_task.get(task)
        if existing == map_attempt:
            log.debug(f"duplicate fetch for {map_attempt}, ignored")
            return
        if self._merge_started:
            raise UdaError(
                f"map attempt {map_attempt} arrived after the merge "
                f"started"
                + (f" (obsoletes already-merged {existing})"
                   if existing else ""))
        if existing is not None:
            log.warn(f"map attempt {existing} obsoleted by {map_attempt}")
            idx = next(i for i, (_, a) in enumerate(self._pending_maps)
                       if a == existing)
            self._pending_maps[idx] = (host, map_attempt)
        else:
            self._pending_maps.append((host, map_attempt))
        self._attempt_by_task[task] = map_attempt

    def _make_client(self, local_dirs: list[str]) -> InputClient:
        """createInputClient: plain or decompressing transport by codec
        class (reference reducer.cc:412-450); with ``uda.tpu.net.fetch``
        set, a host-routing client over the socket data plane instead of
        an in-process engine client."""
        if self._client is not None:
            return self._client
        if self.cfg.get("uda.tpu.net.fetch"):
            from uda_tpu.merger import HostRoutingClient
            # fetches dial each FETCH-carried supplier host's
            # ShuffleServer; a local engine is still built (from the
            # local dirs) when this host also LISTENS — it serves this
            # host's own map outputs to the other reduce hosts
            if local_dirs and self.cfg.get("uda.tpu.net.listen"):
                from uda_tpu.mofserver import DirIndexResolver
                self._owned_engine = DataEngine(
                    DirIndexResolver(local_dirs), self.cfg,
                    num_disks=len(local_dirs))
            client: InputClient = HostRoutingClient(config=self.cfg)
            return self._wrap_codec(client)
        if local_dirs:
            from uda_tpu.mofserver import DirIndexResolver
            # reader threads scale with the disk count, the reference's
            # per-disk AIO pools (AsyncReaderManager.cc:16-50 sized by
            # threads.per.disk x local dirs)
            engine = DataEngine(DirIndexResolver(local_dirs), self.cfg,
                                num_disks=len(local_dirs))
        else:
            engine = DataEngine(_UpcallIndexResolver(self.callable), self.cfg)
        self._owned_engine = engine
        return self._wrap_codec(LocalFetchClient(engine))

    def _wrap_codec(self, client: InputClient) -> InputClient:
        """Decompressing wrap by codec class (reducer.cc:412-450)."""
        if self.cfg.get("mapred.compress.map.output"):
            from uda_tpu.compress import (BLOCK_HEADER, DecompressingClient,
                                          get_codec)
            codec = get_codec(
                self.cfg.get("mapred.map.output.compression.codec") or "zlib")
            # calculateMemPool's buffer split (reducer.cc:453-496): the
            # compressed (wire) sub-buffer gets `ratio` of each pair,
            # the decompressed side the rest — so compressed fetches are
            # sized ratio * buffer while the merge consumes full chunks
            ratio = float(
                self.cfg.get("mapred.rdma.compression.buffer.ratio"))
            buf_bytes = self.cfg.get("mapred.rdma.buf.size") * 1024
            comp_chunk = max(BLOCK_HEADER.size + 1, int(buf_bytes * ratio))
            client = DecompressingClient(client, codec,
                                         comp_chunk_size=comp_chunk)
        return client

    def set_input_client(self, client: InputClient) -> None:
        """Inject a transport (e.g. the mesh exchange client) — the
        createInputClient factory seam (reducer.cc:412-450)."""
        self._client = client

    def _merge_main(self, maps: list[str]) -> None:
        """The merge thread: fetch (progress -> fetchOverMessage) ->
        merge -> stream dataFromUda blocks, the last one carrying the
        IFile EOF marker as the in-band end-of-stream signal
        (merge_thread_main, MergeManager.cc:291-314)."""
        try:
            def consumer(block: memoryview) -> None:
                failpoint("bridge.upcall", key="data_from_uda")
                cb = getattr(self.callable, "data_from_uda", None)
                if cb is not None:
                    cb(block, len(block))

            self._mm.run(self._job_id, maps, self._reduce_id, consumer)
        except Exception as e:  # noqa: BLE001 - the fallback boundary
            self._fail(e, in_thread=True)

    # -- supplier side (mof_downcall_handler, MOFSupplierMain.cc:37-81) -----

    def _mof_downcall(self, header: Cmd, params: list[str]) -> None:
        if header == Cmd.NEW_MAP:
            pass  # map registration is implicit (resolution is pull-based)
        elif header == Cmd.JOB_OVER:
            if params and self._resolver is not None:
                self._resolver.invalidate(params[0])
        elif header == Cmd.INIT:
            # data plane (uda.tpu.net.listen): start serving this
            # supplier's engine to remote reduce clients (the
            # RDMAServer bound next to the DataEngine)
            self._maybe_start_net_server(self._engine)
        elif header == Cmd.EXIT:
            self._stop_net_server()  # drain before the engine stops
            if self._engine is not None:
                self._engine.stop()
                self._engine = None
            if self._stats is not None:
                self._stats.stop(final=True)
                self._stats = None
            # supplier side of the process-end full drain: with the
            # server stopped and the engine shut down, the books must
            # be empty (anything open leaked past both scoped drains)
            resledger.drain("bridge.exit")
        else:
            raise ProtocolError(f"unexpected command {header.name} for "
                                "MOFSupplier role")

    # -- failure contract ---------------------------------------------------

    def _fail(self, error: Exception, in_thread: bool = False) -> None:
        """exceptionInNativeThread -> failureInUda -> inert bridge
        (UdaBridge.cc:506-530); developer mode fails loudly instead of
        falling back (UdaShuffleConsumerPluginShared.java:210-217).

        Developer mode on a BACKGROUND thread cannot usefully re-raise
        (the exception would die in Thread.run and the embedder — which
        gets no failure_in_uda in developer mode — would block on
        fetch_over forever): the error is stored and re-raised by the
        next synchronous call (do_command / reduce_exit), and
        failure_in_uda still fires so waiters wake; the embedder must
        not treat it as a fallback request in developer mode (the
        reference aborts the process outright there, :210-217 — an
        embedded library cannot).

        The embedder is reported the ROOT CAUSE: a FallbackSignal from
        the engine is unwrapped to its ``cause``, whose captured
        backtrace (UdaError.backtrace) and ``__traceback__`` ride along
        on the exception object — the original failure point is never
        lost at the fallback boundary."""
        root = error.cause if isinstance(error, FallbackSignal) else error
        if self.cfg.get("mapred.rdma.developer.mode"):
            if not in_thread:
                raise error
            self._failed = True
            self._dev_error = error
            log.error(f"merge-thread failure (developer mode, will "
                      f"re-raise on next call): {error}")
        else:
            self._failed = True
            log.error(f"engine failure, requesting fallback: {root}")
            bt = getattr(root, "backtrace", "")
            if bt:
                log.debug(f"failure origin backtrace:\n{bt}")
        cb = getattr(self.callable, "failure_in_uda", None)
        if cb is not None:
            cb(root)

    @property
    def failed(self) -> bool:
        return self._failed
