"""Memory admission control + pressure-adaptive degradation (ISSUE 3):
the MemoryBudget routing matrix, INIT validation, the arena's
total-deadline acquire + soft-pressure callback, the supplier read-pool
admission, the stall watchdog, and the stop-path drain."""

import io
import threading
import time

import pytest

from tests.helpers import make_mof_tree, map_ids
from uda_tpu.merger import LocalFetchClient, MergeManager
from uda_tpu.merger.arena import BufferArena
from uda_tpu.merger.segment import InputClient
from uda_tpu.mofserver import DataEngine, DirIndexResolver
from uda_tpu.mofserver.data_engine import ShuffleRequest
from uda_tpu.utils import comparators
from uda_tpu.utils.budget import (MemoryBudget, WORKING_SET_FACTOR,
                                  device_bytes_estimate)
from uda_tpu.utils.config import Config
from uda_tpu.utils.errors import (FallbackSignal, MergeError, StorageError,
                                  UdaError)
from uda_tpu.utils.failpoints import failpoints
from uda_tpu.utils.ifile import IFileReader
from uda_tpu.utils.metrics import metrics
from uda_tpu.utils.watchdog import StallError, StallWatchdog

MB = 1 << 20
KT = comparators.get_key_type("uda.tpu.RawBytes")


# -- the device-bytes model --------------------------------------------------

def test_device_bytes_model_shape():
    # the admission model: a 10 GB TeraSort partition's device working
    # set exceeds a v5e's 16 GB HBM (the OOM scenario this PR closes)
    dev = device_bytes_estimate(10 << 30, key_width=16)
    assert dev > 16 << 30
    # ... and is ~1.08x shuffle bytes x working-set factor at that shape
    assert dev == int((10 << 30) * 1.08 * WORKING_SET_FACTOR)
    # tiny records charge the run forest (row bytes dominate when
    # records are smaller than a row): 7 columns are stored as 8, a run
    # pads to at most twice its rows, the merger holds at most three
    # times the staged rows
    assert device_bytes_estimate(1000, key_width=16, record_bytes=10) \
        == 100 * 32 * 2 * 3
    # 13 columns (a 40 B key) are stored as 16
    assert device_bytes_estimate(1000, key_width=40, record_bytes=10) \
        == 100 * 64 * 2 * 3
    assert device_bytes_estimate(0, 16) == 0


def test_detect_hbm_on_an_accelerator_reports_or_raises(monkeypatch):
    # off the CPU the budget is what the device reports, else its
    # device_kind's table entry — never a guess
    import jax

    from uda_tpu.utils import budget

    class Dev:
        def __init__(self, kind, stats):
            self.device_kind, self._stats = kind, stats

        def memory_stats(self):
            return self._stats

    dev = []
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "devices", lambda: dev)
    dev[:] = [Dev("TPU v5 lite", {"bytes_limit": 1234 * MB})]
    assert budget._detect_hbm_mb() == 1234
    dev[:] = [Dev("TPU v5 lite", None)]
    assert budget._detect_hbm_mb() == 16 * 1024
    dev[:] = [Dev("TPU v9 mystery", {})]
    with pytest.raises(UdaError, match="TPU v9 mystery"):
        budget._detect_hbm_mb()


def test_budget_defaults_resolve_lazily_and_from_config():
    b = MemoryBudget(hbm_budget_mb=123, host_budget_mb=456)
    assert b.hbm_budget_bytes == 123 * MB
    assert b.host_budget_bytes == 456 * MB
    # auto budgets resolve to something positive on any platform (CPU
    # backend: host memory stands in for HBM)
    auto = MemoryBudget()
    assert auto.host_budget_bytes > 0
    assert auto.hbm_budget_bytes > 0
    with pytest.raises(UdaError):
        MemoryBudget(enforce="panic")


# -- the routing matrix (estimate x budgets -> decision) ---------------------

@pytest.mark.parametrize(
    "est_mb,hbm_mb,hard_mb,want,counter",
    [
        # in budget, under the hybrid crossover -> hybrid
        (10, 4096, 0, "hybrid", "budget.admitted"),
        # in budget (1.3 GB of rows + 4.3 GB of merge temporaries), over
        # the crossover -> streaming (still admitted)
        (600, 8192, 0, "streaming", "budget.admitted"),
        # the rows alone fit, not beside the merge's temporaries ->
        # streaming reroute (merged on the device in groups)
        (600, 4096, 0, "streaming", "budget.rerouted"),
        # device working set over the HBM budget -> streaming reroute
        (1024, 512, 0, "streaming", "budget.rerouted"),
        # over the hard ceiling -> reject (FallbackSignal at the caller)
        (4096, 512, 2048, "reject", "budget.rejected"),
        # unknown estimate -> streaming
        (None, 4096, 0, "streaming", "budget.admitted"),
    ])
def test_routing_matrix(est_mb, hbm_mb, hard_mb, want, counter):
    before = metrics.get(counter)
    b = MemoryBudget(hbm_budget_mb=hbm_mb, host_budget_mb=64 * 1024,
                     hard_ceiling_mb=hard_mb)
    est = None if est_mb is None else est_mb * MB
    adm = b.route(est, threshold_bytes=512 * MB)
    assert adm.decision == want
    assert metrics.get(counter) == before + 1
    if want == "reject":
        assert adm.rejected
    if counter == "budget.rerouted":
        assert adm.rerouted


def test_route_host_budget_gates_hybrid():
    # fits HBM but not host RSS (hybrid holds fetched bytes host-
    # resident through the LPQ spill) -> streaming reroute
    b = MemoryBudget(hbm_budget_mb=64 * 1024, host_budget_mb=256)
    adm = b.route(1024 * MB, threshold_bytes=4096 * MB)
    assert adm.decision == "streaming" and adm.rerouted
    assert adm.cause == "host"


# -- MergeManager auto-approach consumes the routing -------------------------

class _FixedEstimateClient(LocalFetchClient):
    def __init__(self, engine, estimate):
        super().__init__(engine)
        self._estimate = estimate
        self.fetches = 0

    def estimate_partition_bytes(self, job_id, mids, reduce_id):
        return self._estimate

    def start_fetch(self, req, on_complete):
        self.fetches += 1
        super().start_fetch(req, on_complete)


def test_auto_approach_over_hbm_budget_reroutes_to_streaming(tmp_path):
    expected = make_mof_tree(str(tmp_path), "jobB1", 4, 1, 50, seed=7)
    engine = DataEngine(DirIndexResolver(str(tmp_path)))
    # pretend the partition is 1 GB against a 64 MB HBM budget: the
    # fast path would OOM, so routing must land on streaming and the
    # merger must hold no more than a group on the device at a time
    client = _FixedEstimateClient(engine, 1 << 30)
    cfg = Config({"mapred.netmerger.merge.approach": 0,
                  "uda.tpu.hbm.budget.mb": 64,
                  "uda.tpu.host.budget.mb": 64 * 1024})
    mm = MergeManager(client, KT, cfg)
    blocks = []
    try:
        mm.run("jobB1", map_ids("jobB1", 4), 0,
               lambda b: blocks.append(bytes(b)))
    finally:
        engine.stop()
    adm = mm.last_admission
    assert adm is not None and adm.decision == "streaming" and adm.rerouted
    om = mm._active_overlap
    assert om is not None
    assert adm.cause == "hbm" and adm.group_rows == 1 << 17
    assert om.stats["device_groups"] == 1   # four small runs: one group
    assert metrics.get("budget.rerouted") == 1  # route's, not twice
    got = list(IFileReader(io.BytesIO(b"".join(blocks))))
    assert got == sorted(expected[0])


def test_auto_approach_hard_ceiling_rejects_before_any_fetch(tmp_path):
    make_mof_tree(str(tmp_path), "jobB2", 3, 1, 30, seed=8)
    engine = DataEngine(DirIndexResolver(str(tmp_path)))
    client = _FixedEstimateClient(engine, 100 << 30)  # 100 GB estimate
    cfg = Config({"mapred.netmerger.merge.approach": 0,
                  "uda.tpu.budget.hard.mb": 1024})
    mm = MergeManager(client, KT, cfg)
    try:
        with pytest.raises(FallbackSignal) as ei:
            mm.run("jobB2", map_ids("jobB2", 3), 0, lambda b: None)
    finally:
        engine.stop()
    # the admission gate fired BEFORE any allocation or fetch
    assert client.fetches == 0
    assert "admission" in str(ei.value.cause)
    assert mm.last_admission.rejected


def test_auto_approach_in_budget_keeps_measured_crossover(tmp_path):
    # generous budgets: the decision reduces to the measured hybrid/
    # streaming crossover (the pre-budget behavior, now via route())
    expected = make_mof_tree(str(tmp_path), "jobB3", 4, 1, 40, seed=9)
    engine = DataEngine(DirIndexResolver(str(tmp_path)))
    try:
        for threshold_mb, want in ((1 << 10, "hybrid"), (0, "streaming")):
            cfg = Config({"mapred.netmerger.merge.approach": 0,
                          "uda.tpu.hbm.budget.mb": 64 * 1024,
                          "uda.tpu.host.budget.mb": 64 * 1024,
                          "uda.tpu.auto.approach.threshold.mb":
                          threshold_mb})
            mm = MergeManager(LocalFetchClient(engine), KT, cfg)
            blocks = []
            mm.run("jobB3", map_ids("jobB3", 4), 0,
                   lambda b: blocks.append(bytes(b)))
            assert mm.last_admission.decision == want, threshold_mb
            got = list(IFileReader(io.BytesIO(b"".join(blocks))))
            assert got == sorted(expected[0])
    finally:
        engine.stop()


# -- INIT validation (the reducer.cc:56-133 mirror) --------------------------

def test_validate_init_shrinks_window_to_fit_host_budget():
    cfg = Config({"uda.tpu.host.budget.mb": 64,
                  "mapred.rdma.buf.size": 1024,       # 1 MB chunks
                  "mapred.rdma.wqe.per.conn": 256})   # wants 256 MB
    before = metrics.get("budget.rerouted")
    adm = MemoryBudget.from_config(cfg).validate_init(cfg)
    new_window = cfg.get("mapred.rdma.wqe.per.conn")
    assert 1 <= new_window < 256
    # the shrunken working set actually fits
    slots = cfg.get("uda.tpu.arena.slots")
    assert (new_window + slots + 2) * MB <= 64 * MB
    assert adm.rerouted
    assert metrics.get("budget.rerouted") == before + 1


def test_validate_init_reject_mode_raises():
    cfg = Config({"uda.tpu.host.budget.mb": 64,
                  "mapred.rdma.buf.size": 1024,
                  "mapred.rdma.wqe.per.conn": 256,
                  "uda.tpu.budget.enforce": "reject"})
    with pytest.raises(UdaError):
        MemoryBudget.from_config(cfg).validate_init(cfg)
    assert cfg.get("mapred.rdma.wqe.per.conn") == 256  # untouched


def test_validate_init_unfittable_chunk_always_raises():
    cfg = Config({"uda.tpu.host.budget.mb": 8,
                  "mapred.rdma.buf.size": 1024})  # 18 MB fixed > 8 MB
    with pytest.raises(UdaError):
        MemoryBudget.from_config(cfg).validate_init(cfg)
    assert metrics.get("budget.rejected") >= 1


def test_bridge_init_over_budget_falls_back():
    """The bridge wires validate_init into INIT: enforce=reject + a
    tiny host budget -> failure_in_uda, inert bridge (the reference's
    'Not enough memory for rdma buffers' fallback)."""
    from uda_tpu.bridge import UdaBridge

    failures = []

    class CB:
        def failure_in_uda(self, e):
            failures.append(e)

        def get_conf_data(self, name, default):
            return {"uda.tpu.host.budget.mb": "8"}.get(name, default)

    from uda_tpu.bridge.protocol import Cmd, form_cmd

    br = UdaBridge()
    br.start(True, ["-s", "1024"], CB())
    br.do_command(form_cmd(Cmd.INIT, ["jobX", "0", "2",
                                      "uda.tpu.RawBytes"]))
    assert br.failed
    assert failures and isinstance(failures[0], UdaError)


def test_bridge_init_in_budget_proceeds(tmp_path):
    """A comfortable budget leaves INIT untouched (admitted, counted)."""
    from uda_tpu.bridge import UdaBridge

    before = metrics.get("budget.admitted")
    from uda_tpu.bridge.protocol import Cmd, form_cmd

    br = UdaBridge()
    br.start(True, [], None)
    br.do_command(form_cmd(Cmd.INIT, ["jobY", "0", "1",
                                      "uda.tpu.RawBytes", str(tmp_path)]))
    assert not br.failed
    assert metrics.get("budget.admitted") == before + 1
    br.do_command(form_cmd(Cmd.EXIT, []))


# -- arena: total deadline + soft pressure -----------------------------------

def test_arena_acquire_timeout_is_total_deadline():
    """Spurious/notify wakeups must not restart the clock: under a
    notify storm the acquire still fails at ~the requested deadline
    (pre-fix each wakeup re-armed the full timeout)."""
    arena = BufferArena(1, 64)
    held = arena.acquire()
    stop = threading.Event()

    def storm():
        while not stop.is_set():
            with arena._cv:
                arena._cv.notify()
            time.sleep(0.01)

    t = threading.Thread(target=storm, daemon=True)
    t.start()
    t0 = time.monotonic()
    try:
        with pytest.raises(MergeError):
            arena.acquire(timeout=0.3)
        waited = time.monotonic() - t0
        assert waited < 2.0, f"deadline restarted: waited {waited:.1f}s"
        assert waited >= 0.25
    finally:
        stop.set()
        t.join()
        arena.release(held)


def test_arena_pressure_callback_fires_once_per_starved_acquire():
    events = []
    arena = BufferArena(1, 64, on_pressure=events.append,
                        pressure_after_s=0.05)
    slot = arena.acquire()
    before = metrics.get("arena.pressure_events")
    threading.Timer(0.4, lambda: arena.release(slot)).start()
    got = arena.acquire(timeout=5.0)  # succeeds after the release
    assert len(events) == 1 and events[0] >= 0.05
    assert metrics.get("arena.pressure_events") == before + 1
    arena.release(got)


def test_arena_fast_acquire_no_pressure():
    events = []
    arena = BufferArena(2, 64, on_pressure=events.append,
                        pressure_after_s=0.05)
    arena.release(arena.acquire())
    assert events == []


# -- supplier read-pool admission --------------------------------------------

def test_supplier_admission_rejects_over_budget_nonblocking(tmp_path):
    make_mof_tree(str(tmp_path), "jobS", 1, 1, 50, seed=11)
    cfg = Config({"uda.tpu.supplier.read.budget.mb": 1,
                  "mapred.rdma.buf.size": 512})  # 512 KB chunks
    engine = DataEngine(DirIndexResolver(str(tmp_path)), cfg)
    mid = map_ids("jobS", 1)[0]
    try:
        # wedge the workers so admitted bytes stay claimed
        with failpoints.scoped("data_engine.pread=delay:300"):
            before = metrics.get("supplier.admission.rejections")
            futs = [engine.submit(ShuffleRequest("jobS", mid, 0, 0,
                                                 512 * 1024))
                    for _ in range(2)]  # 2 x 512 KB = the full budget
            t0 = time.monotonic()
            with pytest.raises(StorageError) as ei:
                engine.submit(ShuffleRequest("jobS", mid, 0, 0, 512 * 1024))
            # the rejection is immediate (non-blocking), never a wait
            assert time.monotonic() - t0 < 0.2
            assert "read pool exhausted" in str(ei.value)
            assert metrics.get("supplier.admission.rejections") \
                == before + 1
        for f in futs:
            f.result(timeout=10)
        # budget fully released -> admission works again
        assert engine.fetch(ShuffleRequest("jobS", mid, 0, 0,
                                           512 * 1024)).data
    finally:
        engine.stop()


def test_supplier_admission_oversized_single_request_admitted(tmp_path):
    # a request larger than the whole budget is served when the pool is
    # idle: push-back must never become a permanent dead end
    make_mof_tree(str(tmp_path), "jobS2", 1, 1, 10, seed=12)
    cfg = Config({"uda.tpu.supplier.read.budget.mb": 1})
    engine = DataEngine(DirIndexResolver(str(tmp_path)), cfg)
    try:
        res = engine.fetch(ShuffleRequest("jobS2", map_ids("jobS2", 1)[0],
                                          0, 0, 8 * MB))
        assert res.data
    finally:
        engine.stop()


# -- stop-path drain (the fetch_all leak fix) --------------------------------

def test_fetch_all_stop_drains_inflight_segments():
    """stop() mid-window: fetch_all must fail+drain the started
    segments (credits released, on_done delivered) before raising —
    not abandon them mid-flight."""

    class WedgeClient(InputClient):
        def __init__(self):
            self.started = []

        def start_fetch(self, req, on_complete):
            self.started.append(req.map_id)  # never completes

    client = WedgeClient()
    cfg = Config({"mapred.rdma.wqe.per.conn": 2})
    mm = MergeManager(client, KT, cfg)
    fed = []
    err = []

    def run():
        try:
            mm.fetch_all("jobD", [f"m{i}" for i in range(4)], 0,
                         on_segment=lambda i, s: fed.append(i))
        except Exception as e:  # noqa: BLE001
            err.append(e)

    t = threading.Thread(target=run)
    t.start()
    deadline = time.monotonic() + 5
    while len(client.started) < 2 and time.monotonic() < deadline:
        time.sleep(0.01)
    assert len(client.started) == 2  # window filled, loop blocked
    mm.stop()
    t.join(timeout=10)
    assert not t.is_alive(), "fetch_all did not return after stop()"
    assert err and isinstance(err[0], MergeError)
    # every started segment was administratively completed (drained)
    drained = [s for s in mm._live_segments if s._done.is_set()]
    assert len(drained) >= 2
    assert metrics.get("fetch.failed_admin") >= 2
    assert fed == []  # no half-delivered on_segment


def test_fetch_all_stop_breaks_all_notified_wait():
    """A completion thread wedged inside the on_segment consumer (the
    overlapped merger's bounded feed in real runs) blocks the
    all-callbacks-delivered wait — stop() must break that wait too,
    not only the credit wait."""
    from uda_tpu.mofserver.data_engine import FetchResult

    class AsyncEmpty(InputClient):
        def start_fetch(self, req, on_complete):
            threading.Thread(
                target=lambda: on_complete(
                    FetchResult(b"", 0, 0, 0, "p", last=True)),
                daemon=True).start()

    release = threading.Event()
    cfg = Config({"mapred.rdma.wqe.per.conn": 8})
    mm = MergeManager(AsyncEmpty(), KT, cfg)
    err = []

    def run():
        try:
            mm.fetch_all("jobN", [f"m{i}" for i in range(3)], 0,
                         on_segment=lambda i, s: release.wait())
        except Exception as e:  # noqa: BLE001
            err.append(e)

    t = threading.Thread(target=run)
    t.start()
    # all segments complete their fetch; callbacks wedge in on_segment
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline and \
            sum(1 for s in mm._live_segments if s._done.is_set()) < 3:
        time.sleep(0.01)
    mm.stop()
    threading.Timer(0.3, release.set).start()  # the om.abort analogue
    t.join(timeout=10)
    release.set()
    assert not t.is_alive(), "fetch_all hung in all_notified despite stop"
    assert err and isinstance(err[0], MergeError)


# -- the stall watchdog ------------------------------------------------------

def test_watchdog_unit_fires_and_dumps():
    fired = []
    wd = StallWatchdog(0.15, lambda: 7, on_stall=fired.append,
                       name="wd-test").start()
    try:
        deadline = time.monotonic() + 5
        while not wd.fired and time.monotonic() < deadline:
            time.sleep(0.02)
        assert wd.fired and isinstance(fired[0], StallError)
        assert "thread stacks" in wd.last_dump
        assert "wd-test" in wd.last_dump  # its own stack is in there
    finally:
        wd.stop()


def test_watchdog_does_not_fire_while_progressing():
    token = [0]

    def progress():
        token[0] += 1
        return token[0]

    wd = StallWatchdog(0.2, progress).start()
    time.sleep(0.7)
    try:
        assert not wd.fired
    finally:
        wd.stop()


@pytest.mark.faults
def test_watchdog_rescues_wedged_fetch(tmp_path):
    """The acceptance scenario: a fetch wedged via the segment.fetch
    failpoint terminates through the watchdog within ~the stall
    deadline — stall dump + FallbackSignal(StallError) — instead of
    hanging forever."""
    # preload the overlap/pallas modules: the watchdog measures ENGINE
    # stalls, not cold-import latency
    import uda_tpu.merger.overlap  # noqa: F401

    make_mof_tree(str(tmp_path), "jobWd", 2, 1, 60, seed=13)
    cfg = Config({"mapred.rdma.buf.size": 1,  # 1 KB chunks: many issues
                  "uda.tpu.watchdog.stall.s": 0.5,
                  "mapred.rdma.fetch.attempt.timeout.ms": 0})
    engine = DataEngine(DirIndexResolver(str(tmp_path)), cfg)
    mm = MergeManager(LocalFetchClient(engine), KT, cfg)
    before = metrics.get("watchdog.stalls")
    t0 = time.monotonic()
    try:
        # every 4th issue wedges for 3 s >> the 0.5 s stall deadline
        # (pread pinned harmless: a chaos-armed error schedule there
        # would exhaust retries and mask the stall with a transport
        # failure — this test is about the WEDGE, not recoverable noise)
        with failpoints.scoped("data_engine.pread=delay:0,"
                               "segment.fetch=delay:3000:every:4"):
            with pytest.raises(FallbackSignal) as ei:
                mm.run("jobWd", map_ids("jobWd", 2), 0, lambda b: None)
        took = time.monotonic() - t0
        assert isinstance(ei.value.cause, StallError)
        assert took < 3.0, f"terminated by the delay, not the watchdog " \
                           f"({took:.1f}s)"
        assert metrics.get("watchdog.stalls") == before + 1
        assert mm._watchdog is None  # stopped by run()'s finally
    finally:
        engine.stop()  # blocks until the wedged worker's sleep ends


@pytest.mark.faults
def test_memory_pressure_schedule_reroutes_not_crashes(tmp_path):
    """The chaos memory-pressure rung (scripts/run_chaos.sh): a tiny
    HBM budget + armed failpoints must degrade to the bounded streaming
    path and still produce the exact sorted output — graceful reroute,
    never a crash."""
    expected = make_mof_tree(str(tmp_path), "jobMP", 6, 1, 50, seed=17)
    engine = DataEngine(DirIndexResolver(str(tmp_path)))
    client = _FixedEstimateClient(engine, 2 << 30)  # 2 GB claim
    cfg = Config({"mapred.netmerger.merge.approach": 0,
                  "uda.tpu.hbm.budget.mb": 32,      # tiny arena/HBM
                  "uda.tpu.host.budget.mb": 64 * 1024,
                  "uda.tpu.fetch.retries": 25,
                  "mapred.rdma.fetch.retry.backoff.ms": 1,
                  "mapred.rdma.fetch.retry.backoff.max.ms": 20})
    mm = MergeManager(client, KT, cfg)
    blocks = []
    try:
        mm.run("jobMP", map_ids("jobMP", 6), 0,
               lambda b: blocks.append(bytes(b)))
    finally:
        engine.stop()
    assert mm.last_admission.rerouted
    assert mm.last_admission.group_rows == 1 << 16
    assert mm._active_overlap.stats["device_groups"] == 1
    got = list(IFileReader(io.BytesIO(b"".join(blocks))))
    import functools
    want = sorted(expected[0], key=functools.cmp_to_key(
        lambda a, b: KT.compare(a[0], b[0])))
    assert got == want
