"""The pallas engine's run forest carries its small size classes on the
host (merger/overlap.py, ``DEVICE_MIN_BUCKET``): a run reaches the device
only when its class does, or at finish. Whatever engine merged a class,
the stream is the host sort oracle's bytes; the counters
``merge.device_runs`` / ``merge.host_merges`` equal what the binary-counter
arithmetic predicts; leases and the in-flight charge go home on every
exit.

The forced pallas engine runs interpreted on the CPU, one compile a merge
shape: the cases that need device classes pull the threshold down to
``SMALL_DEVICE_CLASS`` rows so that those shapes stay small."""

import io
import time

import numpy as np
import pytest

from tests.helpers import emit_stream_bytes
from uda_tpu.merger import overlap
from uda_tpu.merger.emitter import FramedEmitter
from uda_tpu.merger.overlap import OverlappedMerger
from uda_tpu.merger.streaming import RunStore
from uda_tpu.ops import merge as merge_ops
from uda_tpu.utils import comparators, critpath
from uda_tpu.utils.budget import FOREST_FACTOR
from uda_tpu.utils.errors import MergeError
from uda_tpu.utils.ifile import crack, write_records
from uda_tpu.utils.metrics import metrics
from uda_tpu.utils.resledger import resledger

KT = comparators.get_key_type("uda.tpu.RawBytes")
WIDTH = 16
ROW_BYTES = 4 * (WIDTH // 4 + merge_ops.ROW_EXTRA_COLS)
SMALL_DEVICE_CLASS = 1024
# the stage pool's width: one worker hands its runs to the consumer in
# feed order, three in whatever order they finish — the stream and the
# counts below must not depend on it
POOL = pytest.mark.parametrize("workers", (1, 3), ids=("pool1", "pool3"))


def _batch(seed: int, n: int, presorted: bool = False):
    rng = np.random.default_rng(seed)
    recs = [(rng.bytes(6) if i % 5 else b"dupkey", rng.bytes(9))
            for i in range(n)]
    if presorted:
        recs.sort(key=lambda kv: kv[0])
    return crack(write_records(recs))


def _batches(sizes, seed: int = 0):
    return [_batch(seed + 31 * i, n) for i, n in enumerate(sizes)]


def _oracle_bytes(batches) -> bytes:
    out = io.BytesIO()
    FramedEmitter(1 << 14).emit_batch(
        merge_ops.merge_batches_host(batches, KT),
        lambda blk: out.write(bytes(blk)))
    return out.getvalue()


def _merger(workers: int = 3, store=None) -> OverlappedMerger:
    return OverlappedMerger(KT, WIDTH, engine="pallas", run_store=store,
                            stagers=workers, inflight_bytes=8 << 20)


def _stream_bytes(batches, workers: int = 3, store=None, order=None) -> bytes:
    om = _merger(workers, store)
    for i in (order if order is not None else range(len(batches))):
        om.feed(i, batches[i])
    if store is None:
        got = emit_stream_bytes(om, batches)
    else:
        out = io.BytesIO()
        om.finish_streaming(FramedEmitter(1 << 14),
                            lambda blk: out.write(bytes(blk)),
                            expected_records=sum(b.num_records
                                                 for b in batches))
        got = out.getvalue()
    assert metrics.get_gauge("stage.inflight.bytes") == 0
    return got


def _forest_counts(sizes, threshold: int, native: bool = True):
    """(device runs, host merges) of the binary-counter forest fed runs
    of these row counts in this order."""
    forest: dict = {}           # size class -> on the device?
    runs = host = 0
    for n in sizes:
        if n == 0:
            continue
        bucket = merge_ops.next_run_capacity(n)
        on_device = not native or bucket >= threshold
        runs += on_device
        while bucket in forest:
            forest.pop(bucket)
            bucket *= 2
            if not on_device:
                host += 1
                if bucket >= threshold:
                    on_device, runs = True, runs + 1
        forest[bucket] = on_device
    left_host = sum(not d for d in forest.values())
    if left_host:               # folded on the host, then put once
        return runs + 1, host + left_host - 1
    return runs, host


def _counts():
    return (metrics.get("merge.device_runs"), metrics.get("merge.host_merges"))


# -- every run small: the whole forest is the host's --------------------------

@POOL
@pytest.mark.parametrize("fanin", (1, 3, 65, 200))
def test_small_fanins_merge_on_the_host_and_transfer_once(fanin, workers):
    sizes = [4 + (7 * i) % 23 for i in range(fanin)]
    batches = _batches(sizes, seed=fanin)
    order = list(np.random.default_rng(fanin).permutation(fanin))
    assert _stream_bytes(batches, workers, order=order) \
        == _oracle_bytes(batches)
    # one run ever reaches the device, at finish, and nothing merges there
    assert _counts() == (1, fanin - 1)
    assert _counts() == _forest_counts(sizes, overlap.DEVICE_MIN_BUCKET)
    assert 0 <= metrics.get("merge_host_batch_time") \
        <= metrics.get("overlap_device_merge_time")


def test_all_segments_empty_put_nothing_on_the_device():
    batches = _batches([0, 0, 0])
    assert _stream_bytes(batches) == _oracle_bytes(batches)
    assert _counts() == (0, 0)


# -- host and device classes in one forest ------------------------------------

MIXED = (300, 700, 0, 280, 650, 310, 290, 1500, 5, 0, 260, 270, 520)


@POOL
@pytest.mark.parametrize("streaming", (False, True),
                         ids=("in_memory", "streaming"))
def test_mixed_size_classes_keep_the_stream_and_the_counts(
        monkeypatch, tmp_path, streaming, workers):
    monkeypatch.setattr(overlap, "DEVICE_MIN_BUCKET", SMALL_DEVICE_CLASS)
    batches = _batches(MIXED, seed=9)
    store = RunStore([str(tmp_path)], tag="hostclass") if streaming else None
    assert _stream_bytes(batches, workers, store) == _oracle_bytes(batches)
    want = _forest_counts(MIXED, SMALL_DEVICE_CLASS)
    # one worker feeds the forest in segment order; three workers'
    # completion order varies, and with it which carries happen — the
    # totals it must respect do not
    runs, host = _counts()
    staged = sum(n > 0 for n in MIXED)
    if workers == 1:
        assert (runs, host) == want == (8, 3)
    assert 4 <= runs < staged and 0 < host < staged
    assert host + runs >= staged    # a host merge saves at most one transfer


@POOL
def test_device_class_segments_never_merge_on_the_host(monkeypatch, workers):
    monkeypatch.setattr(overlap, "DEVICE_MIN_BUCKET", SMALL_DEVICE_CLASS)
    sizes = (600, 900, 1024, 513, 1100)
    batches = _batches(sizes, seed=4)
    assert _stream_bytes(batches, workers) == _oracle_bytes(batches)
    assert _counts() == (len(sizes), 0)
    # present and zero: what the benchmark's counter reader tells from a
    # program that has no host classes
    snap = metrics.snapshot()
    assert snap["merge_host_batch_time"] == 0.0
    assert snap["merge.host_merges"] == 0.0


def test_without_the_native_merge_every_run_goes_to_the_device(monkeypatch):
    monkeypatch.setattr(merge_ops, "resolve_native_rows_merge", lambda: None)
    sizes = (40, 0, 25, 33)
    batches = _batches(sizes, seed=2)
    assert _stream_bytes(batches) == _oracle_bytes(batches)
    assert _counts() == (3, 0)
    assert _counts() == _forest_counts(sizes, overlap.DEVICE_MIN_BUCKET,
                                       native=False)


def test_adopted_runs_join_the_host_classes():
    """The checkpoint-resume route: runs a previous attempt spooled are
    adopted, the rest fed; same forest, same stream."""
    batches = [_batch(50 + i, 20 + i, presorted=True) for i in range(5)]
    om = _merger()
    for i in (0, 1, 2):
        om.adopt_run(i, batches[i])
    for i in (3, 4):
        om.feed(i, batches[i])
    assert emit_stream_bytes(om, batches) == _oracle_bytes(batches)
    assert _counts() == (1, 4)


def test_host_carries_are_timed_inside_the_merge_timer():
    metrics.enable_spans()
    batches = _batches([30] * 8, seed=6)
    assert _stream_bytes(batches) == _oracle_bytes(batches)
    spans = {s["id"]: s for s in metrics.spans}
    inner = [s for s in spans.values() if s["name"] == "merge_host_batch"]
    assert len(inner) == 7 == metrics.get("merge.host_merges")
    assert {spans[s["parent"]]["name"] for s in inner} \
        == {"overlap_device_merge"}
    assert critpath.SPAN_BUCKETS["merge_host_batch"] == "merge"
    puts = [s for s in spans.values() if s["name"] == "merge.device_put"]
    assert len(puts) == 1 == metrics.get("merge.device_runs")


# -- what the task holds on the device ----------------------------------------

def test_device_rows_stay_within_the_reservation(monkeypatch):
    """A task holds fewer, equally padded device rows than the all-device
    forest, never more, and at no insert more than FOREST_FACTOR x the
    bytes it has staged to the device."""
    monkeypatch.setattr(overlap, "DEVICE_MIN_BUCKET", SMALL_DEVICE_CLASS)
    sizes = [300] * 9 + [700, 1500]
    batches = _batches(sizes, seed=12)
    om = _merger(workers=1)       # the runs reach the forest in feed order
    held_over = []
    insert = om._insert

    def watched(run):
        insert(run)
        arrays = {id(r.rows): r.rows for r in om._forest.values()
                  if not r.on_host}
        arrays.update((id(o), o) for o, _ in om._device_pending)
        held = sum(int(a.nbytes) for a in arrays.values())
        if held > FOREST_FACTOR * om._device_staged_bytes:
            held_over.append((held, om._device_staged_bytes))

    om._insert = watched
    for i, b in enumerate(batches):
        om.feed(i, b)
    assert emit_stream_bytes(om, batches) == _oracle_bytes(batches)
    assert not held_over
    # 9 runs of class 512 -> four promoted at class 1,024 and one at
    # finish (padded to 512); the two large ones as staged
    staged_rows = 4 * 1024 + 512 + 1024 + 2048
    assert om._device_staged_bytes == staged_rows * ROW_BYTES
    all_device = sum(merge_ops.next_run_capacity(n) for n in sizes)
    assert staged_rows <= all_device


# -- every exit returns what it took ------------------------------------------

def _pooled_merger(monkeypatch) -> OverlappedMerger:
    """A pallas merger with the buffer pool the compiled engine has (the
    interpreted one owns its arrays: device_put may alias them), its
    leases on the books."""
    monkeypatch.setattr(resledger, "enabled", True)
    monkeypatch.setattr(resledger, "leak_reports", [])
    om = _merger()
    om._buf_pool = merge_ops.RowBufferPool()
    return om


def _books_whole(om: OverlappedMerger) -> None:
    for t in om._threads:
        t.join(timeout=10)
        assert not t.is_alive()
    assert resledger.outstanding(("pool.lease",), owner=id(om._buf_pool)) == []
    # a drain point pops what it finds open and reports it as leaked
    assert resledger.leak_reports == []
    assert om._inflight == 0
    assert metrics.get_gauge("stage.inflight.bytes") == 0


def test_abort_mid_forest_returns_every_lease(monkeypatch):
    om = _pooled_merger(monkeypatch)
    batches = _batches([20 + i for i in range(11)], seed=3)
    for i, b in enumerate(batches):
        om.feed(i, b)
    # wait until the forest holds leased host runs (11 = 0b1011: three)
    deadline = time.monotonic() + 5
    while not (om.stats["pending"] == 0 and len(om._forest) == 3):
        assert time.monotonic() < deadline
        time.sleep(0.01)
    assert resledger.outstanding(("pool.lease",), owner=id(om._buf_pool))
    om.abort()
    _books_whole(om)
    assert metrics.get("merge.device_runs") == 0


def test_stage_error_mid_batch_returns_every_lease(monkeypatch):
    om = _pooled_merger(monkeypatch)
    batches = _batches([20 + i for i in range(9)], seed=8)

    class Broken:
        raw_length = 64

        def record_batch(self):
            raise MergeError("segment 4 is unreadable")

    for i, b in enumerate(batches):
        om.feed(i, Broken() if i == 4 else b)
    with pytest.raises(MergeError, match="unreadable"):
        emit_stream_bytes(om, batches)
    _books_whole(om)


@pytest.mark.parametrize("fanin, failing_call", ((4, 3), (3, 2)),
                         ids=("mid_carry_chain", "finish_fold"))
def test_failed_host_merge_returns_both_inputs_leases(monkeypatch, fanin,
                                                      failing_call):
    """A native merge that raises holds two runs that are in no forest:
    the second link of the fourth run's carry chain (12 + 34), or the
    leftovers' fold (12 + 3). Their leases go home with the output's."""
    om = _pooled_merger(monkeypatch)
    real, calls = merge_ops.merge_rows_split_into, []

    def breaks(a, b, out, parts):
        calls.append(len(a) + len(b))
        if len(calls) == failing_call:
            raise MergeError("the native merge broke")
        return real(a, b, out, parts)

    monkeypatch.setattr(merge_ops, "merge_rows_split_into", breaks)
    batches = _batches([20] * fanin, seed=13)
    for i, b in enumerate(batches):
        om.feed(i, b)
    with pytest.raises(MergeError, match="native merge broke"):
        emit_stream_bytes(om, batches)
    assert len(calls) == failing_call
    _books_whole(om)


def test_pooled_host_classes_finish_with_the_books_whole(monkeypatch):
    """Carries, the promotion's padded copy and the transfer all lease and
    release; a finished task owes the pool nothing."""
    monkeypatch.setattr(overlap, "DEVICE_MIN_BUCKET", SMALL_DEVICE_CLASS)
    om = _pooled_merger(monkeypatch)
    sizes = [300] * 5 + [700]
    batches = _batches(sizes, seed=5)
    for i, b in enumerate(batches):
        om.feed(i, b)
    # the rows themselves are not read: on the CPU a transferred pool
    # buffer may be aliased, not copied, and recycling it then rewrites
    # the "device" run (why the interpreted engine has no pool)
    om._drain()
    acc = om._merge_leftovers()
    om._finish_cleanup(acc)
    assert acc.valid == sum(sizes) and not acc.on_host
    _books_whole(om)
    assert metrics.get("stage.buffer.reuses") > 0
