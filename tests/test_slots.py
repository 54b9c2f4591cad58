"""A node's reduce slots (ISSUE 26): several NetMerger bridges live at
once in ONE process — the one that holds the chip — each a different
reduce task of one job fetching from one supplier; and the chip-wide
HBM ledger they are admitted through (utils/budget.py HbmLedger)."""

import io
import os
import threading
import time

import pytest

from tests.helpers import make_mof_tree, map_ids
from uda_tpu.bridge import UdaBridge
from uda_tpu.bridge.protocol import Cmd, form_cmd
from uda_tpu.merger import LocalFetchClient, MergeManager
from uda_tpu.mofserver import DataEngine, DirIndexResolver, read_index_file
from uda_tpu.utils import comparators, critpath
from uda_tpu.utils.budget import (HbmLedger, MemoryBudget,
                                  device_bytes_estimate, hbm_ledger,
                                  merge_temp_bytes_estimate)
from uda_tpu.utils.config import Config
from uda_tpu.utils.errors import MergeError, UdaError
from uda_tpu.utils.failpoints import failpoints
from uda_tpu.utils.ifile import IFileReader, IFileWriter
from uda_tpu.utils.metrics import metrics

MB = 1 << 20
JOB = "jobslots"
SLOTS, MAPS = 4, 5
KT = comparators.get_key_type("uda.tpu.RawBytes")


def _range_partitioner(key: bytes, num_reducers: int) -> int:
    """A TeraSort reduce task owns a key range."""
    return key[0] * num_reducers // 256


def _reference(records: list) -> bytes:
    """The plain host sort of one partition: stable, IFile-framed, EOF
    marker included."""
    out = io.BytesIO()
    w = IFileWriter(out)
    for k, v in sorted(records, key=lambda kv: kv[0]):
        w.append(k, v)
    w.close()
    return out.getvalue()


@pytest.fixture
def node(tmp_path):
    """One job's map outputs (four partitions a map) behind one
    MOFSupplier bridge serving over loopback; yields ``(port,
    references)``."""
    expected = make_mof_tree(str(tmp_path), JOB, MAPS, SLOTS, 400, seed=26,
                             partitioner=_range_partitioner)

    class SupplierCallable:
        def get_path_uda(self, job_id, map_id, reduce_id):
            d = os.path.join(str(tmp_path), job_id, map_id)
            return read_index_file(os.path.join(d, "file.out.index"),
                                   os.path.join(d, "file.out"))[reduce_id]

    supplier = UdaBridge()
    supplier.start(False, [], SupplierCallable())
    supplier.cfg.set("uda.tpu.net.listen", True)
    supplier.cfg.set("uda.tpu.net.port", 0)
    supplier.do_command(form_cmd(Cmd.INIT, []))
    assert not supplier.failed
    try:
        yield (supplier.net_server().port,
               [_reference(expected[r]) for r in range(SLOTS)])
    finally:
        supplier.do_command(form_cmd(Cmd.EXIT, []))


class _Reducer:
    """One slot's embedder: collects its stream; ``meet`` (a barrier)
    holds every task at its first block so all are live at once."""

    def __init__(self, port: int, meet=None):
        self.port = port
        self.meet = meet
        self.blocks: list = []
        self.failure = None

    def get_conf_data(self, name, default):
        return {"uda.tpu.net.fetch": "true",
                "uda.tpu.net.port": str(self.port)}.get(name, "")

    def data_from_uda(self, data, length):
        if self.meet is not None and not self.blocks:
            self.meet.wait(timeout=60)
        self.blocks.append(bytes(data[:length]))

    def failure_in_uda(self, error):
        self.failure = error


def _run_slots(port: int, hosts: list, meet=None) -> list:
    """Reduce ids 0..SLOTS-1 at once, each a fresh NetMerger bridge with
    reference-layout INIT/FETCH/FINAL on a thread of its own; returns
    the callables."""
    cbs = [_Reducer(port, meet) for _ in range(SLOTS)]
    go = threading.Barrier(SLOTS)

    def slot(r: int) -> None:
        bridge = UdaBridge()
        go.wait(timeout=60)
        bridge.start(True, [], cbs[r])
        try:
            bridge.do_command(form_cmd(Cmd.INIT, [
                str(MAPS), JOB, str(r), "0", str(MB), "16384",
                "uda.tpu.RawBytes", "0", "0", str(1 << 30)]))
            for mid in map_ids(JOB, MAPS):
                bridge.do_command(form_cmd(
                    Cmd.FETCH, [hosts[r], JOB, mid, str(r)]))
            bridge.do_command(form_cmd(Cmd.FINAL, []))
        finally:
            bridge.reduce_exit()
        bridge.do_command(form_cmd(Cmd.EXIT, []))

    threads = [threading.Thread(target=slot, args=(r,), name=f"slot-{r}")
               for r in range(SLOTS)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not any(t.is_alive() for t in threads), "a slot hung"
    return cbs


def _books_are_empty() -> None:
    assert hbm_ledger.holders == 0 and hbm_ledger.reserved_bytes == 0
    assert metrics.get_gauge("reduce.tasks.live") == 0
    assert metrics.get_gauge("budget.hbm.reserved") == 0
    assert metrics.get_gauge("stage.inflight.bytes") == 0
    assert metrics.get_gauge("arena.slots_in_use") == 0


def test_four_live_netmergers_keep_their_streams_and_their_span_trees(node):
    port, refs = node
    metrics.enable_spans()
    metrics.restart_gauge_peaks()
    cbs = _run_slots(port, ["127.0.0.1"] * SLOTS,
                     meet=threading.Barrier(SLOTS))
    spans = [s for s in metrics.spans if s.get("dur") is not None]
    metrics.disable_spans()
    for r, cb in enumerate(cbs):
        assert cb.failure is None
        # byte-identical to the plain host sort of ITS OWN partition
        assert b"".join(cb.blocks) == refs[r], f"reduce id {r}"
    # all four were live on the chip's books at once, each holding its
    # device estimate; nothing waited; everything came back
    peaks = metrics.gauge_peaks_snapshot()
    assert peaks["reduce.tasks.live"] == SLOTS
    assert peaks["budget.hbm.reserved"] > 0
    assert metrics.get("budget.waited") == 0
    _books_are_empty()

    roots = [s for s in spans if s["name"] == "reduce_task"]
    assert sorted(s["attrs"]["reduce"] for s in roots) == list(range(SLOTS))
    assert len({s["trace"] for s in roots}) == SLOTS
    reduce_of = {s["trace"]: s["attrs"]["reduce"] for s in roots}
    by_id = {s["id"]: s for s in spans}
    staged = ("overlap_pack", "overlap_stage", "merge", "emit_readback",
              "emit_gather", "emit_frame", "emit_deliver", "emit",
              "fetch.segment", "hbm_admit")
    for s in spans:
        if s["name"] == "bridge_open":
            continue                  # the caller's thread, before a root
        # every span is in one of the four trees, under a parent of ITS
        # tree, and a span that names a reduce id names its tree's
        assert s["trace"] in reduce_of, s
        parent = by_id.get(s["parent"])
        assert parent is None or parent["trace"] == s["trace"], s
        if "reduce" in s.get("attrs", {}):
            assert s["attrs"]["reduce"] == reduce_of[s["trace"]], s
    for root in roots:
        tree = [s for s in spans if s["trace"] == root["trace"]]
        names = {s["name"] for s in tree}
        assert set(staged) <= names, (root["attrs"], set(staged) - names)
        # the task's wall is partitioned over ITS spans alone
        block = critpath.analyze(tree)
        charged = sum(b["critical_s"] for b in block["buckets"].values())
        assert block["wall_s"] == pytest.approx(root["dur"], abs=1e-5)
        assert charged + block["idle_s"] == pytest.approx(block["wall_s"],
                                                          abs=1e-4)


@pytest.mark.faults
@pytest.mark.parametrize("spec", [
    # every fetch of the one task that dials the supplier as "localhost"
    "segment.fetch=error:transport:match:@localhost",
    # the first data_from_uda up-call of whichever task emits first
    "bridge.upcall=error:once",
], ids=["fetch", "emit"])
def test_one_failed_slot_leaves_the_other_three_identical(node, spec):
    port, refs = node
    hosts = ["127.0.0.1", "127.0.0.1", "localhost", "127.0.0.1"]
    with failpoints.scoped(spec):
        cbs = _run_slots(port, hosts)
    failed = [r for r, cb in enumerate(cbs) if cb.failure is not None]
    assert len(failed) == 1, failed
    if "localhost" in spec:
        assert failed == [2]
    assert isinstance(cbs[failed[0]].failure, UdaError)
    for r, cb in enumerate(cbs):
        if r not in failed:
            assert b"".join(cb.blocks) == refs[r], f"reduce id {r}"
    assert metrics.get("fallback.signals") == 1
    _books_are_empty()


# -- the chip-wide HBM ledger -------------------------------------------------

def _budget(hbm_mb: int) -> MemoryBudget:
    return MemoryBudget(hbm_budget_mb=hbm_mb, host_budget_mb=1024)


def test_ledger_admits_a_lone_task_without_waiting():
    budget = _budget(32)
    est = 2 * MB          # 4.32 MB of rows + 16 MB of merge temporaries
    hold, reroute = budget.admit_device(est)
    assert reroute is None
    assert (hold.nbytes, hold.temp_bytes) == budget.device_need(est)
    assert hold.nbytes == budget.device_bytes(est) and hold.temp_bytes > 0
    assert hbm_ledger.holders == 1
    assert hbm_ledger.reserved_bytes == hold.nbytes + hold.temp_bytes
    assert metrics.get_gauge("budget.hbm.reserved") \
        == hold.nbytes + hold.temp_bytes
    hold.release()
    hold.release()                    # idempotent
    assert metrics.get("budget.waited") == 0
    _books_are_empty()


def test_ledger_waits_when_the_sum_exceeds_the_budget_and_wakes_on_release():
    budget = _budget(26)
    est = 3 * MB      # 6.48 MB of rows each beside 16 MB of temporaries,
    #                   booked once: one needs 22.5 MB, two 29 of the 26
    first, _ = budget.admit_device(est)
    admitted = threading.Event()
    second: list = []

    def late() -> None:
        second.append(budget.admit_device(est)[0])
        admitted.set()

    t = threading.Thread(target=late)
    t.start()
    assert not admitted.wait(0.4)     # parked behind the live task
    assert metrics.get("budget.waited") == 1
    assert hbm_ledger.holders == 1
    first.release()
    assert admitted.wait(10)
    t.join()
    assert hbm_ledger.reserved_bytes \
        == second[0].nbytes + second[0].temp_bytes
    assert metrics.get("hbm_admit_time") >= 0.3
    assert metrics.gauge_peaks_snapshot()["reduce.tasks.live"] == 1
    second[0].release()
    _books_are_empty()


def test_ledger_releases_on_exception_and_a_stopped_waiter_leaves_the_queue():
    budget = _budget(26)
    est = 3 * MB
    with pytest.raises(RuntimeError):
        with budget.admit_device(est)[0]:
            assert hbm_ledger.holders == 1
            raise RuntimeError("the task died")
    _books_are_empty()

    first, _ = budget.admit_device(est)
    stop = threading.Event()
    errors: list = []

    def doomed() -> None:
        try:
            budget.admit_device(est, stopped=stop.is_set)
        except MergeError as e:
            errors.append(e)

    t = threading.Thread(target=doomed)
    t.start()
    time.sleep(0.2)
    stop.set()                        # the waiting task is torn down
    t.join(10)
    assert len(errors) == 1 and "waiting" in str(errors[0])
    # its ticket is gone: the next task is not queued behind a ghost
    first.release()
    with budget.admit_device(est)[0]:
        assert hbm_ledger.holders == 1
    _books_are_empty()


def test_ledger_is_first_come_first_served():
    ledger = HbmLedger()
    budget = 10
    big_first = ledger.reserve(6, budget)
    order: list = []

    def want(n: int, tag: str) -> None:
        with ledger.reserve(n, budget):
            order.append(tag)

    big = threading.Thread(target=want, args=(8, "big"))
    big.start()
    time.sleep(0.2)                   # big is queued; 4 would fit now
    small = threading.Thread(target=want, args=(4, "small"))
    small.start()
    time.sleep(0.3)
    assert order == []                # small does not overtake big
    big_first.release()
    big.join(10)
    small.join(10)
    assert order == ["big", "small"]
    assert ledger.holders == 0 and ledger.reserved_bytes == 0
    with pytest.raises(UdaError):
        ledger.reserve(11, budget)    # can never fit: the caller's bug


def test_a_task_too_large_alone_is_sized_into_groups_and_waits_its_turn():
    """A task the chip cannot hold whole reserves what the largest
    group the budget holds needs, first come first served like any
    task: beside a live one that leaves no room it waits, and the live
    one is never failed."""
    from uda_tpu.utils.budget import (FOREST_FACTOR, MERGE_TEMP_ROW_BYTES,
                                      group_capacity_rows)

    budget = _budget(26)
    held, _ = budget.admit_device(3 * MB)     # 22.5 of the 26 MB booked
    admitted = threading.Event()
    got: list = []

    def big() -> None:
        got.append(budget.admit_device(64 * MB))
        admitted.set()

    t = threading.Thread(target=big)
    t.start()
    assert not admitted.wait(0.4)             # parked behind the live task
    assert metrics.get("budget.waited") == 1
    assert hbm_ledger.holders == 1
    held.release()                            # the live task finishes
    assert admitted.wait(10)
    t.join()
    hold, reroute = got[0]
    assert reroute is not None and reroute.cause == "hbm"
    assert reroute.decision == "streaming" and reroute.rerouted
    group = group_capacity_rows(26 * MB, budget.key_width)
    assert reroute.group_rows == group == 1 << 16
    assert hold.nbytes == FOREST_FACTOR * 32 * group
    assert hold.temp_bytes == MERGE_TEMP_ROW_BYTES * group
    assert hold.nbytes + hold.temp_bytes <= 26 * MB
    assert hbm_ledger.reserved_bytes == hold.nbytes + hold.temp_bytes
    assert metrics.get("budget.rerouted") == 1
    hold.release()
    _books_are_empty()


@pytest.mark.parametrize("record_bytes, grows",
                         ((20.0, True), (100.0, False), (102.0, False)))
def test_the_rebook_grows_a_hold_for_20_byte_records_never_for_100(
        record_bytes, grows):
    """Admission reckons 100 bytes a record; staging tells the ledger
    what they are. Smaller records are more rows: the hold grows to the
    model's figure for them. Records of 100 bytes or more (TeraSort's
    are 102 on disk) leave the books alone."""
    budget = _budget(256)
    est, segments = 2 * MB, 8
    hold, _ = budget.admit_device(est, segments=segments)
    rows, temps = hold.nbytes, hold.temp_bytes
    assert budget.rebook_device(hold, est, segments, record_bytes) is grows
    if grows:
        assert hold.nbytes == device_bytes_estimate(est, 16, record_bytes)
        assert hold.nbytes == 192 * (est // 20) > 4 * rows  # 5 x the forest
        assert hold.temp_bytes == merge_temp_bytes_estimate(
            est, segments, record_bytes) > temps
        # grow-only: the same figure again, or a larger record, moves nothing
        assert not budget.rebook_device(hold, est, segments, record_bytes)
        assert not budget.rebook_device(hold, est, segments, 50.0)
        assert budget.rebook_device(hold, est, segments, 10.0)
        assert hold.nbytes == 192 * (est // 10)
    else:
        assert (hold.nbytes, hold.temp_bytes) == (rows, temps)
    assert metrics.get("budget.hbm.rebooked") == (2 if grows else 0)
    assert hbm_ledger.reserved_bytes == hold.nbytes + hold.temp_bytes \
        == metrics.get_gauge("budget.hbm.reserved")
    hold.release()
    assert not budget.rebook_device(hold, est, segments, 5.0)   # released
    _books_are_empty()


def test_the_rebook_never_waits_even_past_the_budget():
    """Two live tasks both grow beyond what the chip's budget holds:
    neither waits (a wait could deadlock them; their rows are on the
    way whatever the books say) — the task that asks next does."""
    budget = _budget(44)
    est = 2 * MB      # 4.03 MB of rows + 16 MB of temporaries each
    a, _ = budget.admit_device(est)
    b, _ = budget.admit_device(est)
    t0 = time.monotonic()
    assert budget.rebook_device(a, est, None, 20.0)
    assert budget.rebook_device(b, est, None, 20.0)
    assert time.monotonic() - t0 < 0.5
    assert metrics.get("budget.waited") == 0
    assert metrics.get("hbm_admit_time") < 0.1
    # 2 x 20.1 MB of rows + 64 MB of temporaries, booked once
    assert hbm_ledger.reserved_bytes == 2 * a.nbytes + a.temp_bytes \
        > budget.hbm_budget_bytes
    assert metrics.gauge_peaks_snapshot()["budget.hbm.reserved"] \
        == hbm_ledger.reserved_bytes
    admitted = threading.Event()
    third: list = []

    def late() -> None:
        third.append(budget.admit_device(64 * 1024)[0])
        admitted.set()

    t = threading.Thread(target=late)
    t.start()
    assert not admitted.wait(0.3)     # the books are over the budget
    a.release()
    b.release()
    assert admitted.wait(10)
    t.join()
    third[0].release()
    _books_are_empty()


@pytest.mark.parametrize("val_bytes, rebooked", ((8, 1), (90, 0)))
def test_a_task_of_small_records_rebooks_once_through_the_manager(
        tmp_path, val_bytes, rebooked):
    """Through ``MergeManager.run``: staging's first segment tells the
    ledger the record size (10-byte keys: 20 or 102 bytes a frame); the
    hold of the 20-byte task is five times the rows by the time it
    emits, the TeraSort-shaped task's is what admission booked."""
    expected = make_mof_tree(str(tmp_path), "jobR", 4, 1, 200, seed=5,
                             val_bytes=val_bytes)
    engine = DataEngine(DirIndexResolver(str(tmp_path)))
    try:
        cfg = Config({"uda.tpu.hbm.budget.mb": 64,
                      "uda.tpu.host.budget.mb": 1024})
        mm = MergeManager(LocalFetchClient(engine), KT, cfg)
        est = mm.client.estimate_partition_bytes("jobR",
                                                 map_ids("jobR", 4), 0)
        assert est == 4 * (200 * (12 + val_bytes) + 2)
        seen: list = []
        blocks: list = []

        def consumer(block) -> None:
            seen.append(hbm_ledger.reserved_bytes)
            blocks.append(bytes(block))

        mm.run("jobR", map_ids("jobR", 4), 0, consumer)
        assert list(IFileReader(io.BytesIO(b"".join(blocks)))) \
            == sorted(expected[0])
        assert metrics.get("budget.hbm.rebooked") == rebooked
        assert metrics.get("budget.waited") == 0
        booked = sum(mm.budget().device_need(est, 4))
        if rebooked:
            assert seen[-1] == device_bytes_estimate(est, 16, 20.0) \
                + merge_temp_bytes_estimate(est, 4, 20.0) > booked
        else:
            assert seen[-1] == booked
        _books_are_empty()
    finally:
        engine.stop()


def test_default_approach_reserves_and_reroutes_over_a_small_budget(tmp_path):
    """At the DEFAULT merge approach (1) a task reserves its device
    estimate from the ledger; one the chip cannot hold alone takes the
    streaming route and is merged on the device in groups — same bytes
    out, nothing left on the books."""
    expected = make_mof_tree(str(tmp_path), "jobL", 4, 1, 60, seed=3)
    want = sorted(expected[0])
    engine = DataEngine(DirIndexResolver(str(tmp_path)))
    try:
        seen: list = []

        def run(hbm_mb: int):
            cfg = Config({"uda.tpu.hbm.budget.mb": hbm_mb,
                          "uda.tpu.host.budget.mb": 1024})
            assert cfg.get("mapred.netmerger.merge.approach") == 1
            mm = MergeManager(LocalFetchClient(engine), KT, cfg)
            blocks: list = []

            def consumer(block) -> None:
                seen.append((hbm_ledger.holders,
                             hbm_ledger.reserved_bytes))
                blocks.append(bytes(block))

            mm.run("jobL", map_ids("jobL", 4), 0, consumer)
            assert list(IFileReader(io.BytesIO(b"".join(blocks)))) == want
            return mm

        mm = run(64)
        assert mm.last_admission is None
        assert seen[-1][0] == 1 and seen[-1][1] > 0   # held through emit
        _books_are_empty()

        # 240 records x 40 B model to ~21 KB of device bytes: a budget
        # the floor of tenant_share rounds to 1 MB still holds them, so
        # shrink the chip with an estimate instead
        class Huge(LocalFetchClient):
            def estimate_partition_bytes(self, job_id, mids, reduce_id):
                return 1 << 30

        cfg = Config({"uda.tpu.hbm.budget.mb": 64,
                      "uda.tpu.host.budget.mb": 1024})
        mm = MergeManager(Huge(engine), KT, cfg)
        blocks: list = []
        mm.run("jobL", map_ids("jobL", 4), 0,
               lambda b: blocks.append(bytes(b)))
        assert list(IFileReader(io.BytesIO(b"".join(blocks)))) == want
        adm = mm.last_admission
        assert adm is not None and adm.cause == "hbm" and adm.rerouted
        om = mm._active_overlap
        assert adm.group_rows == 1 << 17
        # four runs of 60 rows fill a fraction of one group
        assert om.stats["device_groups"] == 1 \
            == metrics.get("merge.device_groups")
        assert metrics.get("budget.rerouted") == 1
        _books_are_empty()
    finally:
        engine.stop()


def test_a_stopped_manager_leaves_the_ledger_queue(tmp_path):
    """``MergeManager.stop()`` reaches a task parked in admission: it
    ends in the fallback contract, not in a hang, and holds nothing."""
    from uda_tpu.utils.errors import FallbackSignal

    make_mof_tree(str(tmp_path), "jobS", 2, 1, 20, seed=5)
    engine = DataEngine(DirIndexResolver(str(tmp_path)))
    cfg = Config({"uda.tpu.hbm.budget.mb": 200,
                  "uda.tpu.host.budget.mb": 1024})

    class Big(LocalFetchClient):
        def estimate_partition_bytes(self, job_id, mids, reduce_id):
            # 43 MB of rows each and 128 MB of merge temporaries between
            # them: one fits the 200 MB, two do not
            return 20 * MB

    held, _ = MemoryBudget.from_config(cfg).admit_device(20 * MB)
    mm = MergeManager(Big(engine), KT, cfg)
    errors: list = []

    def task() -> None:
        try:
            mm.run("jobS", map_ids("jobS", 2), 0, lambda b: None)
        except FallbackSignal as e:
            errors.append(e)

    t = threading.Thread(target=task)
    try:
        t.start()
        time.sleep(0.3)
        assert t.is_alive() and hbm_ledger.holders == 1
        mm.stop()
        t.join(10)
        assert not t.is_alive()
        assert len(errors) == 1 and isinstance(errors[0].cause, MergeError)
    finally:
        held.release()
        engine.stop()
    _books_are_empty()


def test_merge_dispatch_runs_ahead_of_the_device_by_twice_the_staged_bytes(
        monkeypatch):
    """What keeps a task's device rows within what the ledger reserved
    for it (FOREST_FACTOR x its staged rows): outputs of merges that may
    not have executed never exceed FOREST_FACTOR - 1 times the bytes of
    the runs staged so far; the wait is for the oldest, and an executed
    merge costs no wait."""
    from uda_tpu.merger import overlap
    from uda_tpu.utils.budget import FOREST_FACTOR

    class Out:
        def __init__(self, ready: bool):
            self.ready = ready

        def is_ready(self) -> bool:
            return self.ready

    assert FOREST_FACTOR == 3.0
    waited: list = []
    monkeypatch.setattr(overlap.jax, "block_until_ready", waited.append)
    om = overlap.OverlappedMerger(KT, width=16, engine="host")
    try:
        om._device_staged_bytes = 50           # room: 100
        done, a, b = Out(True), Out(False), Out(False)
        om._device_pending.extend([(done, 10), (a, 40), (b, 40)])
        om._device_pending_bytes = 90
        om._await_device_room(30)
        # the executed one leaves the books unwaited; 80 + 30 > 100 waits
        # for the oldest pending merge and for no more than it takes
        assert waited == [a]
        assert [o for o, _ in om._device_pending] == [b]
        assert om._device_pending_bytes == 40
        om._await_device_room(60)              # 40 + 60 fits: no wait
        assert waited == [a] and om._device_pending_bytes == 40
        om._await_device_room(200)     # larger than all that was staged:
        assert waited == [a, b]        # drains, then goes ahead
        assert not om._device_pending and om._device_pending_bytes == 0
    finally:
        om.abort()
