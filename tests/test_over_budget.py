"""A partition the chip cannot hold whole is merged on the device in
GROUPS (merger/overlap.py ``group_rows``, sized by utils/budget.py from
what admission reserved): the forest is folded into one run and read
back whenever the next run would not fit, the group runs are joined on
the host, and the streaming emit gathers natively through a table of
run cursors. Whatever the number of groups, the engine or the key type,
the stream is the stable host sort's bytes — equal keys keep map order
across groups; the counters read what the shape implies and 0 on the
in-memory route; the reservation goes home on every exit; the model
books a merge program's temporaries once a chip.

The forced pallas engine runs interpreted on the CPU, one compile a
merge shape: those cases pull ``DEVICE_MIN_BUCKET`` down so that the
shapes stay small."""

import io
import os
import struct
import sys
import threading
import time

import numpy as np
import pytest

from uda_tpu import native
from uda_tpu.merger import LocalFetchClient, overlap, streaming
from uda_tpu.merger.emitter import FramedEmitter
from uda_tpu.merger.merge_manager import MergeManager
from uda_tpu.merger.overlap import OverlappedMerger
from uda_tpu.merger.streaming import RunStore
from uda_tpu.mofserver import DataEngine, DirIndexResolver
from uda_tpu.ops import merge as merge_ops
from uda_tpu.utils import comparators, ifile, vint
from uda_tpu.utils.budget import (FOREST_FACTOR, MERGE_TEMP_ROW_BYTES,
                                  MemoryBudget, group_capacity_rows,
                                  hbm_ledger, merge_temp_bytes_estimate)
from uda_tpu.utils.config import Config
from uda_tpu.utils.errors import MergeError, UdaError
from uda_tpu.utils.ifile import crack, write_records
from uda_tpu.utils.metrics import metrics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.reference import host_sort, host_sort_blocks  # noqa: E402
from benchmark.reference.host_sort_parts import digest  # noqa: E402

MB = 1 << 20
RAW = comparators.get_key_type("uda.tpu.RawBytes")
KEY_TYPES = {
    "raw": RAW,
    "text": comparators.get_key_type("org.apache.hadoop.io.Text"),
    "bytes": comparators.get_key_type("org.apache.hadoop.io.BytesWritable"),
    "long_numeric": comparators.get_key_type("uda.tpu.LongNumeric"),
}
WIDTH = 16
JOB = "over"


@pytest.fixture(autouse=True)
def _native_on():
    assert native.build(), "the native library must build for these tests"
    yield
    ifile.set_native_enabled(True)


def _books_are_empty():
    assert hbm_ledger.holders == 0 and hbm_ledger.reserved_bytes == 0
    assert metrics.get_gauge("budget.hbm.reserved") == 0
    assert metrics.get_gauge("reduce.tasks.live") == 0


# -- TeraSort-framed map outputs with many equal keys -------------------------

def _write_maps(root: str, sizes, seed: int = 0, key_space: int = 40):
    """Map outputs in the benchmark's layout (102-byte frames, sorted a
    map) whose keys come from ``key_space`` values: nearly every key is
    held by several maps and several rows, so stability decides."""
    rng = np.random.default_rng(seed)
    ids = []
    for m, n in enumerate(sizes):
        frames = np.empty((n, 102), np.uint8)
        frames[:, 0], frames[:, 1] = 10, 90
        keys = np.sort(rng.integers(0, key_space, n)).astype(">u8")
        frames[:, 2:10] = keys.view(np.uint8).reshape(n, 8)
        frames[:, 10:12] = 7
        frames[:, 12:] = rng.integers(0, 256, (n, 90), dtype=np.uint8)
        map_id = f"attempt_{JOB}_m_{m:06d}_0"
        d = os.path.join(root, JOB, map_id)
        os.makedirs(d)
        with open(os.path.join(d, "file.out"), "wb") as f:
            f.write(frames.tobytes() + b"\xff\xff")
        with open(os.path.join(d, "file.out.index"), "wb") as f:
            f.write(struct.pack(">qqq", 0, frames.size + 2, frames.size + 2))
        ids.append(map_id)
    return ids


@pytest.mark.parametrize("block_records", (7, 100, 1 << 20))
def test_blocks_reference_equals_host_sort(tmp_path, block_records):
    """The benchmark's blocked reference is the plain one: same size,
    same digest, whatever the block."""
    ids = _write_maps(str(tmp_path), (120, 0, 33, 250, 1), seed=1)
    want = host_sort.sorted_stream(str(tmp_path), JOB, ids)
    got = host_sort_blocks.sorted_digest(str(tmp_path), JOB, ids,
                                         block_records)
    assert got == (want.size, digest(want))
    stream = np.concatenate([want, np.frombuffer(b"\xff\xff", np.uint8)])
    assert host_sort_blocks.compare_digest(stream, *got) is None
    stream[5] ^= 1
    assert "digest" in host_sort_blocks.compare_digest(stream, *got)


# -- a whole task through MergeManager (host engine on the CPU) ---------------

class _Huge(LocalFetchClient):
    """A transport whose partition estimate no budget of these tests
    holds: admission sizes the task into groups whatever it fetches."""

    def estimate_partition_bytes(self, job_id, mids, reduce_id):
        return 1 << 30


def _run_task(root: str, ids, hbm_mb: int, client=_Huge, consumer=None):
    engine = DataEngine(DirIndexResolver(root))
    blocks: list = []
    try:
        cfg = Config({"uda.tpu.hbm.budget.mb": hbm_mb,
                      "uda.tpu.host.budget.mb": 1024})
        mm = MergeManager(client(engine), RAW, cfg)
        mm.run(JOB, ids, 0, consumer or (lambda b: blocks.append(bytes(b))))
    finally:
        engine.stop()
    return mm, b"".join(blocks)


# a 1 MB budget holds groups of 2,048 rows of run capacity: two maps of
# 600 records (capacity 1,024 each) a group
@pytest.mark.parametrize("maps,groups", ((2, 1), (3, 2), (5, 3), (9, 5)))
def test_grouped_task_is_the_stable_host_sort(tmp_path, maps, groups):
    assert group_capacity_rows(1 * MB, WIDTH) == 2048
    ids = _write_maps(str(tmp_path), [600] * maps, seed=maps)
    before = metrics.snapshot()
    mm, got = _run_task(str(tmp_path), ids, hbm_mb=1)
    want = host_sort.sorted_stream(str(tmp_path), JOB, ids)
    assert got == want.tobytes() + b"\xff\xff"
    known = host_sort_blocks.sorted_digest(str(tmp_path), JOB, ids, 500)
    assert host_sort_blocks.compare_digest(
        np.frombuffer(got, np.uint8), *known) is None
    adm = mm.last_admission
    assert adm.cause == "hbm" and adm.rerouted and adm.group_rows == 2048
    grew = {k: v - before.get(k, 0.0) for k, v in metrics.snapshot().items()}
    assert grew["merge.device_groups"] == groups \
        == mm._active_overlap.stats["device_groups"]
    assert grew["budget.rerouted"] == 1
    assert grew["emit.gather.native_slabs"] == 1    # 600 x maps: one slab
    assert grew["merge_group_flush_time"] > 0
    assert grew["run_spool_time"] > 0 and grew["merge_group_join_time"] >= 0
    assert grew.get("fallback.signals", 0) == 0 \
        and grew.get("budget.rejected", 0) == 0
    _books_are_empty()


def test_a_partition_that_fits_takes_the_in_memory_route_and_reads_zero(
        tmp_path):
    ids = _write_maps(str(tmp_path), [600] * 3, seed=4)
    metrics.reset()
    mm, got = _run_task(str(tmp_path), ids, hbm_mb=1024,
                        client=LocalFetchClient)
    want = host_sort.sorted_stream(str(tmp_path), JOB, ids)
    assert got == want.tobytes() + b"\xff\xff"
    assert mm.last_admission is None
    snap = metrics.snapshot()
    # 0, not nothing: a reader of counter deltas tells a task with no
    # group from a program with no groups
    for name in ("merge.device_groups", "merge_group_flush_time",
                 "merge_group_join_time", "run_spool_time"):
        assert snap[name] == 0.0, name
    assert metrics.get("budget.rerouted") == 0
    assert metrics.get("emit.gather.native_slabs") == 1   # slab_batch's
    _books_are_empty()


def test_reservation_goes_home_when_the_consumer_raises(tmp_path):
    ids = _write_maps(str(tmp_path), [600] * 5, seed=5)

    def consumer(block):
        raise RuntimeError("the reducer died")

    with pytest.raises(Exception) as ei:
        _run_task(str(tmp_path), ids, hbm_mb=1, consumer=consumer)
    assert "the reducer died" in repr(ei.value) \
        or "the reducer died" in repr(getattr(ei.value, "cause", ""))
    _books_are_empty()


def test_reservation_goes_home_when_the_fetch_fails_and_the_merger_aborts(
        tmp_path):
    ids = _write_maps(str(tmp_path), [600] * 5, seed=6)

    class Breaks(_Huge):
        def start_fetch(self, req, on_complete):
            if req.map_id == ids[3]:
                raise UdaError("the supplier is gone")
            super().start_fetch(req, on_complete)

    with pytest.raises(Exception):
        _run_task(str(tmp_path), ids, hbm_mb=1, client=Breaks)
    _books_are_empty()


def test_beside_a_live_task_the_grouped_task_waits_and_the_live_one_finishes(
        tmp_path):
    ids = _write_maps(str(tmp_path), [600] * 5, seed=7)
    budget = MemoryBudget(hbm_budget_mb=26, host_budget_mb=1024)
    live, _ = budget.admit_device(3 * MB)     # 22.5 of the 26 MB booked
    done: list = []

    def task():
        done.append(_run_task(str(tmp_path), ids, hbm_mb=26))

    t = threading.Thread(target=task)
    t.start()
    time.sleep(0.5)
    # parked in admission: nothing fetched, the live task untouched
    assert t.is_alive() and hbm_ledger.holders == 1
    assert metrics.get("budget.waited") == 1
    live.release()                            # the live task finishes
    t.join(30)
    assert not t.is_alive()
    mm, got = done[0]
    want = host_sort.sorted_stream(str(tmp_path), JOB, ids)
    assert got == want.tobytes() + b"\xff\xff"
    assert mm.last_admission.group_rows == 1 << 16
    assert metrics.get("hbm_admit_time") >= 0.3
    _books_are_empty()


# -- the model: temporaries once a chip ---------------------------------------

V5E_BUDGET_MB = int(16128 * 0.9)     # memory_stats' 15.75 GiB x the reserve


def test_model_books_merge_temporaries_once_a_chip():
    """Four 1.05 GB tasks in 64 maps each are admitted together at the
    v5e budget (``reduce_slots4``): their rows add up, the 4.3 GB of
    the largest merge's temporaries are booked once. A 4 GB partition's
    rows fit, its last merge's temporaries do not: sized into groups;
    so is the 10.5 GB one, into the same groups."""
    budget = MemoryBudget(hbm_budget_mb=V5E_BUDGET_MB, host_budget_mb=1024)
    est = 10_500_000 * 102 + 2
    temps = merge_temp_bytes_estimate(est, 64)
    assert temps == MERGE_TEMP_ROW_BYTES << 24 == 4_294_967_296
    holds = [budget.admit_device(est, segments=64) for _ in range(4)]
    assert all(reroute is None for _, reroute in holds)
    assert metrics.get("budget.waited") == 0 and hbm_ledger.holders == 4
    rows = budget.device_bytes(est)
    assert hbm_ledger.reserved_bytes == 4 * rows + temps \
        == metrics.get_gauge("budget.hbm.reserved")
    assert 4 * (rows + temps) > budget.hbm_budget_bytes   # not as a sum
    holds[0][0].release()
    assert hbm_ledger.reserved_bytes == 3 * rows + temps   # still the max
    for hold, _ in holds:
        hold.release()
    _books_are_empty()

    group = 1 << 25                       # 128 runs of 2^18 rows
    for est, maps in ((4 * 10_500_000 * 102, 256), (104_999_680 * 102, 640)):
        assert budget.device_bytes(est) < budget.hbm_budget_bytes \
            or maps == 640
        hold, reroute = budget.admit_device(est, segments=maps)
        assert reroute is not None and reroute.group_rows == group
        assert hold.nbytes == FOREST_FACTOR * 32 * group
        assert hold.temp_bytes == MERGE_TEMP_ROW_BYTES * group
        assert hold.nbytes + hold.temp_bytes <= budget.hbm_budget_bytes
        # one row more of capacity and the fold would write 2^26 rows
        assert (FOREST_FACTOR * 32 + MERGE_TEMP_ROW_BYTES) * 2 * group \
            > budget.hbm_budget_bytes
        hold.release()
    assert metrics.get("budget.rerouted") == 2
    _books_are_empty()


# -- the merger, driven directly: engines, key types, shapes ------------------

def _serialize(kind: str, content: bytes) -> bytes:
    if kind == "text":
        return vint.encode_vlong(len(content)) + content
    if kind == "bytes":
        return struct.pack(">i", len(content)) + content
    return content


def _batch(kind: str, n: int, seed: int, dup: bool = True):
    """A presorted segment of ``n`` records; with ``dup`` its keys come
    from a few values, so equal keys span segments and groups."""
    rng = np.random.default_rng(seed)
    kt = KEY_TYPES[kind]

    def content() -> bytes:
        if kind == "long_numeric":
            return struct.pack(">q", int(rng.integers(-4, 5)) if dup
                               else int(rng.integers(-1 << 40, 1 << 40)))
        pool = (b"", b"a", b"ab", b"abc\x00", b"b")
        return pool[int(rng.integers(0, len(pool)))] if dup \
            else rng.bytes(int(rng.integers(0, 9)))

    recs = [(_serialize(kind, content()), rng.bytes(5)) for _ in range(n)]
    recs.sort(key=lambda kv: kt.content(kv[0]) if kind != "long_numeric"
              else struct.unpack(">q", kv[0]))
    return crack(write_records(recs))


def _oracle(batches, kt) -> bytes:
    out = io.BytesIO()
    FramedEmitter(1 << 14).emit_batch(
        merge_ops.merge_batches_host(batches, kt),
        lambda blk: out.write(bytes(blk)))
    return out.getvalue()


def _grouped_stream(tmp_path, batches, kt, engine: str, group_rows: int,
                    width: int = WIDTH):
    om = OverlappedMerger(kt, width, engine=engine,
                          run_store=RunStore(str(tmp_path)), stagers=3,
                          inflight_bytes=8 << 20, group_rows=group_rows)
    for i, b in enumerate(batches):
        om.feed(i, b)
    out = io.BytesIO()
    om.finish_streaming(FramedEmitter(1 << 14),
                        lambda blk: out.write(bytes(blk)),
                        expected_records=sum(b.num_records for b in batches))
    return om, out.getvalue()


@pytest.mark.parametrize("sizes,groups", (((300, 300, 300), 2),
                                          ((300,) * 5, 3)),
                         ids=("two_groups", "three_groups"))
def test_interpreted_pallas_groups(tmp_path, monkeypatch, sizes, groups):
    """The device engine: runs of capacity 512, two to a group of 1,024
    rows, the last group partial; one merge shape compiles."""
    monkeypatch.setattr(overlap, "DEVICE_MIN_BUCKET", 512)
    batches = [_batch("raw", n, 11 * i) for i, n in enumerate(sizes)]
    om, got = _grouped_stream(tmp_path, batches, RAW, "pallas", 1024)
    assert got == _oracle(batches, RAW)
    assert om.stats["device_groups"] == groups \
        == metrics.get("merge.device_groups")
    assert metrics.get("merge.device_runs") == len(sizes)
    assert metrics.get("emit.gather.native_slabs") == 1


def test_interpreted_pallas_groups_with_host_classes(tmp_path, monkeypatch):
    """Runs of a host class carry on the host inside a group and reach
    the device when their class does; a group's capacity counts them
    at the capacity they will have there."""
    monkeypatch.setattr(overlap, "DEVICE_MIN_BUCKET", 1024)
    batches = [_batch("raw", 300, 13 * i) for i in range(6)]
    om, got = _grouped_stream(tmp_path, batches, RAW, "pallas", 2048)
    assert got == _oracle(batches, RAW)
    assert om.stats["device_groups"] == 2       # four runs of 512, then two
    assert metrics.get("merge.host_merges") == 3


@pytest.mark.parametrize("kind", sorted(KEY_TYPES))
def test_key_types_and_equal_keys_across_groups(tmp_path, kind):
    kt = KEY_TYPES[kind]
    batches = [_batch(kind, n, 17 * i)
               for i, n in enumerate((200, 0, 350, 90, 0, 400, 10))]
    om, got = _grouped_stream(tmp_path, batches, kt, "host", 1024)
    assert got == _oracle(batches, kt)
    assert om.stats["device_groups"] >= 3


def test_one_key_in_every_group_keeps_map_order(tmp_path):
    """Every record has the same key: the stream is the arrival order
    (map, then row), whichever group a map went through."""
    rng = np.random.default_rng(3)
    recs = [[(b"samekey", bytes([m]) + rng.bytes(3)) for _ in range(300)]
            for m in range(7)]
    batches = [crack(write_records(r)) for r in recs]
    om, got = _grouped_stream(tmp_path, batches, RAW, "host", 1024)
    assert om.stats["device_groups"] == 4
    assert got == write_records([kv for r in recs for kv in r])


@pytest.mark.parametrize("engine", ("host", "pallas"))
def test_a_segment_larger_than_a_group_is_a_group_run_of_its_own(
        tmp_path, monkeypatch, engine):
    monkeypatch.setattr(overlap, "DEVICE_MIN_BUCKET", 512)
    puts = []
    real = overlap.jax.device_put
    monkeypatch.setattr(overlap.jax, "device_put",
                        lambda x: puts.append(x.shape[0]) or real(x))
    batches = [_batch("raw", n, 19 * i) for i, n in enumerate((300, 3000, 300))]
    om, got = _grouped_stream(tmp_path, batches, RAW, engine, 1024)
    assert got == _oracle(batches, RAW)
    assert om.stats["device_groups"] == 1       # the two small runs
    assert 4096 not in puts                     # never on the device


def test_an_overflow_key_still_takes_the_kway_files_path(tmp_path):
    batches = [_batch("raw", 300, 23 * i, dup=False) for i in range(5)]
    long = crack(write_records(sorted(
        [(b"k" * 40, b"v"), (b"a", b"w"), (b"zz", b"x")])))
    batches.insert(2, long)
    om, got = _grouped_stream(tmp_path, batches, RAW, "host", 1024)
    assert om.stats["overflow"]
    assert got == _oracle(batches, RAW)
    assert metrics.get("emit.gather.native_slabs") == 0


def test_abort_drops_the_groups(tmp_path):
    om = OverlappedMerger(RAW, WIDTH, engine="host",
                          run_store=RunStore(str(tmp_path)),
                          group_rows=1024)
    for i in range(5):
        om.feed(i, _batch("raw", 300, i))
    deadline = time.monotonic() + 10
    while om.stats["staged_runs"] < 5 and time.monotonic() < deadline:
        time.sleep(0.01)
    om.abort()
    assert om._group_runs == [] and not om._forest
    assert metrics.get_gauge("stage.inflight.bytes") == 0
    with pytest.raises(MergeError):
        OverlappedMerger(RAW, WIDTH, engine="host", group_rows=1024)


# -- the native streaming gather against the numpy one ------------------------

def _runs_and_rows(tmp_path, segments: int, per: int, seed: int):
    """``segments`` spooled runs and the merged composite rows over
    them (key word, length, segment, row)."""
    rng = np.random.default_rng(seed)
    store = RunStore(str(tmp_path))
    rows = []
    for s in range(segments):
        n = int(rng.integers(1, per + 1))
        keys = np.sort(rng.integers(0, 50, n))
        recs = [(struct.pack(">I", int(k)),
                 rng.bytes(int(rng.integers(0, 200)))) for k in keys]
        store.write_run(s, crack(write_records(recs)), np.arange(n))
        rows += [(int(k), 4, s, r) for r, k in enumerate(keys)]
    rows.sort()
    return store, np.asarray(rows, np.uint32)


def _interleaved(store, rows, slab: int) -> bytes:
    slabs = (rows[a:a + slab] for a in range(0, len(rows), slab))
    return b"".join(bytes(p) for p in
                    streaming.interleave_runs(slabs, store, 1))


@pytest.mark.parametrize("segments,per,cursors", ((1, 900, 256), (7, 300, 2),
                                                  (640, 12, 5)))
def test_native_run_gather_equals_the_numpy_cursors(
        tmp_path, monkeypatch, segments, per, cursors):
    """Byte for byte, over 1 / 7 / 640 runs, with the numpy path's open
    cursors capped so low that it suspends and reopens them."""
    monkeypatch.setattr(streaming, "MAX_OPEN_CURSORS", cursors)
    store, rows = _runs_and_rows(tmp_path, segments, per, seed=segments)
    slab = 257
    got = _interleaved(store, rows, slab)
    slabs = -(-len(rows) // slab)
    assert metrics.get("emit.gather.native_slabs") == slabs
    ifile.set_native_enabled(False)
    want = _interleaved(store, rows, slab)
    assert metrics.get("emit.gather.native_slabs") == slabs   # none more
    assert got == want and got.endswith(b"\xff\xff")
    assert len(got) == sum(store.bytes.values()) + 2


@pytest.mark.parametrize("buffer_size,keep_open", ((64, False), (300, True),
                                                   (4096, False)))
def test_run_cursors_refill_and_grow(tmp_path, buffer_size, keep_open):
    """Read buffers smaller than a record, than a slab's share of a
    run, than a run: every fill, compaction and growth of the cursors'
    buffers and of the table's output buffer, descriptors held or
    reopened — the same bytes as the numpy cursors."""
    store, rows = _runs_and_rows(tmp_path, 9, 120, seed=21)
    table = native.RunTable(
        {s: (store.run_path(s), n, store.bytes[s])
         for s, n in store.counts.items()},
        keep_open=keep_open, buffer_size=buffer_size)
    try:
        got = b"".join(bytes(native.gather_runs_native(table, rows[a:a + 97, 2]))
                       for a in range(0, len(rows), 97))
        assert [table.consumed(s) for s in sorted(store.counts)] \
            == [store.counts[s] for s in sorted(store.counts)]
    finally:
        table.close()
    ifile.set_native_enabled(False)
    assert got + b"\xff\xff" == _interleaved(store, rows, 97)


@pytest.mark.parametrize("fault", ("lost_record", "unstaged_segment",
                                   "one_too_many", "truncated_file"))
def test_native_run_gather_refuses_what_the_numpy_cursors_refuse(
        tmp_path, fault):
    store, rows = _runs_and_rows(tmp_path, 4, 50, seed=9)
    if fault == "lost_record":
        rows = rows[:-1]
    elif fault == "unstaged_segment":
        rows[3, 2] = 9
    elif fault == "one_too_many":
        rows = np.concatenate([rows, rows[-1:]])
    else:
        with open(store.run_path(2), "ab") as f:
            f.write(b"x")
    with pytest.raises(MergeError):
        _interleaved(store, rows, 64)
