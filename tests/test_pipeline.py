"""Staged fetch->decompress->pack->stage pipeline (ISSUE 9): the
bounded stage pool + merge consumer must be byte-identical to a sort
of the whole partition on every engine/compression/spool combination,
drain cleanly (no leaked in-flight budget bytes) when a fault lands
mid-pipeline, and bound in-flight bytes under a slow consumer."""

import io
import threading
import time

import numpy as np
import pytest

from tests.helpers import (emit_stream_bytes, framed_bytes, host_sort_bytes,
                           make_mof_tree, map_ids)
from uda_tpu.compress import DecompressingClient, get_codec
from uda_tpu.merger import LocalFetchClient, MergeManager
from uda_tpu.merger.emitter import FramedEmitter
from uda_tpu.merger.overlap import OverlappedMerger
from uda_tpu.merger.streaming import RunStore
from uda_tpu.mofserver import DataEngine, DirIndexResolver
from uda_tpu.mofserver.writer import MOFWriter
from uda_tpu.ops import merge as merge_ops
from uda_tpu.utils import comparators
from uda_tpu.utils.budget import STAGE_INFLIGHT_FLOOR_MB, stage_inflight_cap
from uda_tpu.utils.config import Config
from uda_tpu.utils.errors import FallbackSignal
from uda_tpu.utils.failpoints import failpoints
from uda_tpu.utils.ifile import crack, write_records
from uda_tpu.utils.metrics import metrics

KT = "uda.tpu.RawBytes"


def _batch(recs):
    return crack(write_records(recs))


def _rand_recs(seed, n, dup_every=5, key_bytes=6):
    rng = np.random.default_rng(seed)
    recs = []
    for i in range(n):
        k = rng.bytes(key_bytes) if i % dup_every else b"dupkey"
        recs.append((k, rng.bytes(20)))
    return recs


def _pool_bytes(batches, engine="host", spool=False, tmp=None):
    """The stage pool's output for ``batches``: through a run store and
    ``finish_streaming`` (``spool``), or in memory."""
    store = RunStore([str(tmp)], tag="pipetest") if spool else None
    kt = comparators.get_key_type(KT)
    om = OverlappedMerger(kt, 16, engine=engine, run_store=store,
                          stagers=2, inflight_bytes=8 << 20)
    for i, b in enumerate(batches):
        om.feed(i, b)
    if not spool:
        return emit_stream_bytes(om, batches)
    out = io.BytesIO()
    om.finish_streaming(
        FramedEmitter(1 << 14), lambda blk: out.write(bytes(blk)),
        expected_records=sum(b.num_records for b in batches))
    return out.getvalue()


def _sort_bytes(batches):
    return host_sort_bytes(batches, comparators.get_key_type(KT))


# -- byte-identity: the stage pool vs a sort of the whole partition ----------

def test_pipeline_identity_host_engine():
    batches = [_batch(_rand_recs(s, 60 + 11 * s)) for s in range(7)]
    got = _pool_bytes(batches)
    assert got == _sort_bytes(batches) and len(got) > 0


def test_pipeline_identity_out_of_order_feed():
    # completion order never decides anything: fed in a scrambled
    # order, the result stays the stable sort's
    batches = [_batch(_rand_recs(40 + s, 50)) for s in range(6)]
    kt = comparators.get_key_type(KT)
    want = merge_ops.merge_batches(batches, kt, 16)
    om = OverlappedMerger(kt, 16, engine="host", stagers=3)
    for i in (4, 0, 5, 2, 1, 3):
        om.feed(i, batches[i])
    assert emit_stream_bytes(om, batches) == framed_bytes(want)


def test_pipeline_identity_spool(tmp_path):
    batches = [_batch(_rand_recs(s, 80)) for s in range(5)]
    got = _pool_bytes(batches, spool=True, tmp=tmp_path)
    assert got == _sort_bytes(batches) and len(got) > 0


@pytest.mark.slow
def test_pipeline_identity_pallas_engine(monkeypatch):
    # every size class on the device: the kernel is what this compares
    from uda_tpu.merger import overlap
    monkeypatch.setattr(overlap, "DEVICE_MIN_BUCKET",
                        overlap.MIN_RUN_CAPACITY)
    batches = [_batch(_rand_recs(70 + s, 30)) for s in range(4)]
    got = _pool_bytes(batches, engine="pallas")
    assert got == _sort_bytes(batches) and len(got) > 0


def test_pipeline_identity_overflow_keys():
    # keys longer than the carried width, equal in all of it: the
    # comparator's order whichever worker staged which segment
    pre = b"Q" * 17
    batches = [_batch([(pre + b"z", b"v0"), (b"a", b"v1")]),
               _batch([(pre + b"b", b"v2"), (b"c", b"v3")])]
    got = _pool_bytes(batches)
    assert got == _sort_bytes(batches) and len(got) > 0


def test_pipeline_identity_compressed_e2e(tmp_path):
    # a whole task over zlib map outputs in 8 KB chunks, against a host
    # sort of the records the maps wrote
    codec = get_codec("zlib")
    rng = np.random.default_rng(11)
    job = "jobPC"
    writer = MOFWriter(str(tmp_path), job, codec=codec)
    written = []
    for m in range(4):
        recs = sorted((rng.bytes(8), rng.bytes(24)) for _ in range(120))
        writer.write(f"attempt_{job}_m_{m:06d}_0", [recs])
        written.extend(recs)
    cfg = Config({"mapred.rdma.buf.size": 8, "uda.tpu.stage.pool": 2})
    engine = DataEngine(DirIndexResolver(str(tmp_path)), cfg)
    try:
        mm = MergeManager(DecompressingClient(LocalFetchClient(engine),
                                              codec), KT, cfg)
        blocks = []
        mm.run(job, writer.map_ids, 0, lambda b: blocks.append(bytes(b)))
    finally:
        engine.stop()
    # stable by key: equal keys keep (map, row) order
    want = sorted(written, key=lambda kv: kv[0])
    assert b"".join(blocks) == framed_bytes(_batch(want))
    assert len(mm._active_overlap._workers) == 2


# -- merge-path split + buffer pool (the pipeline's merge half) --------------

def _sorted_rows(rng, n, k=5):
    r = rng.integers(0, 4, (n, k)).astype(np.uint32)  # heavy ties
    order = np.lexsort(tuple(r[:, c] for c in range(k - 1, -1, -1)))
    return np.ascontiguousarray(r[order])


def test_merge_split_point_is_the_stable_partition():
    rng = np.random.default_rng(5)
    a, b = _sorted_rows(rng, 37), _sorted_rows(rng, 53)
    ref = None
    nat = merge_ops.resolve_native_rows_merge()
    if nat is not None:
        ref = nat(a, b)
    for m in (0, 1, 17, 45, 89, 90):
        ia = merge_ops.merge_split_point(a, b, m)
        ib = m - ia
        assert 0 <= ia <= a.shape[0] and 0 <= ib <= b.shape[0]
        # partition invariants of the ties-to-a merge path
        if ia > 0 and ib < b.shape[0]:
            assert tuple(a[ia - 1]) <= tuple(b[ib])
        if ib > 0 and ia < a.shape[0]:
            assert tuple(b[ib - 1]) < tuple(a[ia])
    if ref is not None:
        out = np.empty_like(ref)
        assert merge_ops.merge_rows_split_into(a, b, out, parts=3)
        assert np.array_equal(out, ref)


def test_merge_rows_split_identical_across_part_counts():
    nat = merge_ops.resolve_native_rows_merge()
    if nat is None:
        pytest.skip("native library not built")
    rng = np.random.default_rng(9)
    for na, nb in ((0, 40), (40, 0), (1, 1), (1000, 3), (517, 801)):
        a, b = _sorted_rows(rng, na), _sorted_rows(rng, nb)
        ref = nat(a, b)
        for parts in (1, 2, 4):
            out = np.empty_like(ref)
            assert merge_ops.merge_rows_split_into(a, b, out, parts)
            assert np.array_equal(out, ref), (na, nb, parts)


def test_row_buffer_pool_reuses_and_bounds():
    pool = merge_ops.RowBufferPool("stage.bufpool")
    before = metrics.get("stage.buffer.reuses")
    a = pool.lease(100, 7)
    assert a.shape == (100, 7) and a.dtype == np.uint32
    pool.release(a)
    b = pool.lease(50, 7)  # smaller fits in the released buffer
    assert b.shape == (50, 7)
    assert metrics.get("stage.buffer.reuses") == before + 1
    pool.release(b)
    pool.release(None)  # tolerated: fallback paths pass leaseless runs
    for _ in range(pool.MAX_FREE + 4):
        pool.release(np.empty((8, 7), np.uint32))
    assert len(pool._free) == pool.MAX_FREE


# -- one staging architecture -------------------------------------------------

def test_a_bare_merger_runs_the_pool_and_abort_joins_it():
    """No argument selects the staging: a merger built with the key
    type and the width alone starts the auto-width stage pool and the
    one merge consumer, and ``abort()`` joins every one of them."""
    from uda_tpu.merger import overlap

    om = OverlappedMerger(comparators.get_key_type(KT), 16)
    try:
        names = sorted(t.name for t in om._threads)
        width = overlap._auto_width()
        assert names == sorted([f"uda-stage-w{i}" for i in range(width)]
                               + ["uda-overlap-merge"])
        assert all(t.is_alive() for t in om._threads)
        om.feed(0, _batch(_rand_recs(1, 30)))
    finally:
        om.abort()
    assert not any(t.is_alive() for t in om._threads)
    assert om._inflight == 0 and om.stats["pending"] == 0


def test_feed_racing_abort_releases_charge():
    # the narrow window: _charge() sees the abort flag unset, abort()
    # then completes fully (threads joined, queue reaped) before the
    # item lands in the queue — nothing would ever release its charge.
    # Forced deterministically by completing abort() inside _charge.
    kt = comparators.get_key_type(KT)
    b = _batch(_rand_recs(50, 10))
    om = OverlappedMerger(kt, 16, inflight_bytes=1 << 20)
    orig_charge = om._charge

    def charge_then_abort(source):
        c = orig_charge(source)
        om.abort()  # runs to completion: workers joined, queues reaped
        return c

    om._charge = charge_then_abort
    om.feed(0, b)
    assert om._inflight == 0  # the post-put re-drain reaped the charge


def test_merge_split_reports_part_failure(monkeypatch):
    # a part whose native merge refuses (e.g. the .so momentarily
    # unloaded by a concurrent rebuild) leaves stale bytes in its out
    # slice — the split must return False so the caller falls back
    from uda_tpu import native

    calls = []

    def flaky(a, b, o):
        calls.append(o.shape[0])
        return len(calls) != 1  # exactly one part refuses

    monkeypatch.setattr(native, "merge_rows_native_into", flaky)
    monkeypatch.setattr(native, "available", lambda: True)
    a = np.zeros((64, 5), np.uint32)
    b = np.ones((64, 5), np.uint32)
    out = np.empty((128, 5), np.uint32)
    assert merge_ops.merge_rows_split_into(a, b, out, parts=2) is False
    assert len(calls) == 2  # both parts ran; one refusal fails the whole


# -- overflow comparator fast path -------------------------------------------

def test_overflow_lexsort_matches_comparator_path():
    kt = comparators.get_key_type(KT)
    assert comparators.uses_default_bytewise(kt)
    rng = np.random.default_rng(17)
    recs = []
    for i in range(120):
        # oversize keys with shared prefixes and length-tiebreak cases
        k = bytes([i % 3]) * (17 + int(rng.integers(0, 12)))
        recs.append((k, rng.bytes(8)))
    batch = _batch(recs)
    om = OverlappedMerger(kt, 16, engine="host")
    fast = om._overflow_order(batch, batch.num_records)

    class CmpOnly(type(kt)):
        def compare(self, a, b):  # force the cmp_to_key slow path
            return super().compare(a, b)

    cmp_kt = CmpOnly.__new__(CmpOnly)
    cmp_kt.__dict__.update(kt.__dict__)
    assert not comparators.uses_default_bytewise(cmp_kt)
    om_slow = OverlappedMerger(kt, 16, engine="host")
    om_slow.key_type = cmp_kt
    slow = om_slow._overflow_order(batch, batch.num_records)
    assert np.array_equal(fast, slow)


def test_stage_inflight_cap_resolution():
    # explicit MB wins
    cfg = Config({"uda.tpu.stage.inflight.mb": 64})
    assert stage_inflight_cap(cfg, 4, 1 << 20) == 64 << 20
    # auto: floor dominates small windows
    assert stage_inflight_cap(Config(), 4, 1 << 20) == \
        STAGE_INFLIGHT_FLOOR_MB << 20
    # auto: big windows scale 2x
    assert stage_inflight_cap(Config(), 512, 1 << 20) == 2 * 512 * (1 << 20)


# -- faults: a failure mid-pipeline drains clean -----------------------------

@pytest.mark.faults
def test_pipeline_pread_fault_drains_clean(tmp_path):
    """A storage fault mid-pipeline surfaces as FallbackSignal; the
    stage pool drains and the in-flight byte gauge returns to zero."""
    make_mof_tree(str(tmp_path), "jobPF", 6, 1, 40, seed=3)
    engine = DataEngine(DirIndexResolver(str(tmp_path)))
    cfg = Config({"uda.tpu.stage.pool": 2,
                  "uda.tpu.fetch.retries": 0})
    mm = MergeManager(LocalFetchClient(engine), KT, cfg)
    try:
        with failpoints.scoped("data_engine.pread=error:prob:0.7:seed:5"):
            with pytest.raises(FallbackSignal):
                mm.run("jobPF", map_ids("jobPF", 6), 0, lambda b: None)
    finally:
        engine.stop()
    om = mm._active_overlap
    assert om is not None and om._aborted
    for t in om._threads:
        t.join(timeout=10)
        assert not t.is_alive()
    assert om.stats["inflight_bytes"] == 0
    assert metrics.get_gauge("stage.inflight.bytes") == 0


@pytest.mark.faults
def test_pipeline_decompress_fault_drains_clean(tmp_path):
    """decompress.block mid-pipeline: the typed CompressionError is the
    stream's terminal error; abort drains workers, no budget leak."""
    codec = get_codec("zlib")
    rng = np.random.default_rng(23)
    job = "jobDF"
    writer = MOFWriter(str(tmp_path), job, codec=codec)
    for m in range(3):
        recs = sorted((rng.bytes(8), rng.bytes(24)) for _ in range(100))
        writer.write(f"attempt_{job}_m_{m:06d}_0", [recs])
    cfg = Config({"uda.tpu.fetch.retries": 0})
    engine = DataEngine(DirIndexResolver(str(tmp_path)), cfg)
    mm = MergeManager(DecompressingClient(LocalFetchClient(engine), codec),
                      KT, cfg)
    try:
        with failpoints.scoped("decompress.block=error:once"):
            with pytest.raises(FallbackSignal):
                mm.run(job, writer.map_ids, 0, lambda b: None)
    finally:
        engine.stop()
    om = mm._active_overlap
    assert om is not None
    for t in om._threads:
        t.join(timeout=10)
        assert not t.is_alive()
    assert metrics.get_gauge("stage.inflight.bytes") == 0


# -- backpressure: bounded in-flight bytes under a slow consumer -------------

def test_pipeline_backpressure_bounds_inflight(monkeypatch):
    kt = comparators.get_key_type(KT)
    batches = [_batch(_rand_recs(s, 150)) for s in range(8)]
    one = OverlappedMerger._source_bytes(batches[0])
    assert one > 0
    cap = int(2.5 * one)  # at most two batches in flight

    real_insert = OverlappedMerger._insert

    def slow_insert(self, run):
        time.sleep(0.05)  # a slow device consumer
        real_insert(self, run)

    monkeypatch.setattr(OverlappedMerger, "_insert", slow_insert)
    om = OverlappedMerger(kt, 16, engine="host", stagers=2,
                          inflight_bytes=cap)
    peak = {"v": 0}
    done = threading.Event()

    def watch():
        while not done.is_set():
            peak["v"] = max(peak["v"], om._inflight)
            time.sleep(0.002)

    w = threading.Thread(target=watch, daemon=True)
    w.start()
    before = metrics.get("stage.backpressure_events")
    for i, b in enumerate(batches):
        om.feed(i, b)  # blocks past the cap — that IS the test
    got = emit_stream_bytes(om, batches)
    done.set()
    w.join(timeout=5)
    assert peak["v"] <= cap
    assert metrics.get("stage.backpressure_events") > before
    assert om._inflight == 0
    want = merge_ops.merge_batches(batches, kt, 16)
    assert got == framed_bytes(want)


def test_pipeline_abort_releases_blocked_feed():
    kt = comparators.get_key_type(KT)
    batches = [_batch(_rand_recs(s, 120)) for s in range(4)]
    one = OverlappedMerger._source_bytes(batches[0])
    om = OverlappedMerger(kt, 16, engine="host", stagers=1,
                          inflight_bytes=int(1.5 * one))
    # wedge the consumer (abort-responsive) so charges stay held
    hold = threading.Event()
    orig = OverlappedMerger._consume_run

    def wedge(self, staged):
        while not hold.is_set() and not self._aborted:
            time.sleep(0.01)
        orig(self, staged)

    om._consume_run = wedge.__get__(om)
    fed = threading.Event()

    def feeder():
        for i, b in enumerate(batches):
            om.feed(i, b)  # blocks on the budget
        fed.set()

    t = threading.Thread(target=feeder, daemon=True)
    t.start()
    time.sleep(0.3)
    assert not fed.is_set()  # feeder is blocked on the in-flight budget
    om.abort()
    hold.set()
    t.join(timeout=10)
    assert not t.is_alive()
    for th in om._threads:
        th.join(timeout=10)
        assert not th.is_alive()
    assert om._inflight == 0
    assert metrics.get_gauge("stage.inflight.bytes") == 0

