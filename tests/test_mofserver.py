"""Supplier side: index files, resolver cache, data engine chunk serving
(reference src/MOFServer/)."""

import os
import threading

import pytest

from tests.helpers import make_mof_tree, map_ids
from uda_tpu.mofserver import (DataEngine, DirIndexResolver, ShuffleRequest,
                               read_index_file, write_index_file)
from uda_tpu.utils.config import Config
from uda_tpu.utils.errors import StorageError
from uda_tpu.utils.ifile import crack


def test_index_file_round_trip(tmp_path):
    path = str(tmp_path / "file.out.index")
    triples = [(0, 100, 100), (100, 250, 250), (350, 0, 2)]
    write_index_file(path, triples)
    recs = read_index_file(path, "/data/file.out")
    assert [(r.start_offset, r.raw_length, r.part_length) for r in recs] == triples
    assert all(r.path == "/data/file.out" for r in recs)


def test_index_file_corrupt(tmp_path):
    path = str(tmp_path / "bad.index")
    with open(path, "wb") as f:
        f.write(b"\x00" * 23)  # not a multiple of 24
    with pytest.raises(StorageError):
        read_index_file(path, "x")


def test_resolver_caches_lookup(tmp_path):
    make_mof_tree(str(tmp_path), "job1", num_maps=1, num_reducers=2,
                  records_per_map=10)
    calls = []
    inner = DirIndexResolver(str(tmp_path))
    orig = inner._lookup

    def counting(job, mapid):
        calls.append(mapid)
        return orig(job, mapid)

    inner._lookup = counting
    mid = map_ids("job1", 1)[0]
    a = inner.resolve("job1", mid, 0)
    b = inner.resolve("job1", mid, 1)
    assert len(calls) == 1  # first-fetch-only up-call (IndexInfo.cc:237-251)
    assert a.start_offset == 0 and b.start_offset > 0
    with pytest.raises(StorageError):
        inner.resolve("job1", mid, 5)


def test_data_engine_serves_partitions(tmp_path):
    expected = make_mof_tree(str(tmp_path), "job2", num_maps=3, num_reducers=2,
                             records_per_map=50)
    engine = DataEngine(DirIndexResolver(str(tmp_path)))
    try:
        for r in range(2):
            got = []
            for mid in map_ids("job2", 3):
                res = engine.fetch(ShuffleRequest("job2", mid, r, 0, 1 << 20))
                assert res.is_last
                got += list(crack(res.data).iter_records())
            assert sorted(got) == sorted(expected[r])
    finally:
        engine.stop()


def test_data_engine_chunked_reads(tmp_path):
    make_mof_tree(str(tmp_path), "job3", num_maps=1, num_reducers=1,
                  records_per_map=100, val_bytes=100)
    engine = DataEngine(DirIndexResolver(str(tmp_path)))
    try:
        mid = map_ids("job3", 1)[0]
        # fetch in small chunks and reassemble
        chunks = []
        offset = 0
        while True:
            res = engine.fetch(ShuffleRequest("job3", mid, 0, offset, 512))
            chunks.append(res.data)
            offset += len(res.data)
            if res.is_last:
                break
        assert offset == res.raw_length
        batch = crack(b"".join(chunks))
        assert batch.num_records == 100
    finally:
        engine.stop()


def test_data_engine_bad_offset(tmp_path):
    make_mof_tree(str(tmp_path), "job4", num_maps=1, num_reducers=1,
                  records_per_map=5)
    engine = DataEngine(DirIndexResolver(str(tmp_path)))
    try:
        mid = map_ids("job4", 1)[0]
        with pytest.raises(StorageError):
            engine.fetch(ShuffleRequest("job4", mid, 0, 10**9, 512))
    finally:
        engine.stop()


def test_data_engine_concurrent(tmp_path):
    make_mof_tree(str(tmp_path), "job5", num_maps=8, num_reducers=4,
                  records_per_map=40)
    cfg = Config({"mapred.uda.provider.blocked.threads.per.disk": 4})
    engine = DataEngine(DirIndexResolver(str(tmp_path)), cfg)
    errors = []

    def worker(r):
        try:
            for mid in map_ids("job5", 8):
                res = engine.fetch(ShuffleRequest("job5", mid, r, 0, 1 << 20))
                crack(res.data)
        except Exception as e:  # pragma: no cover
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(r,)) for r in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    engine.stop()
    assert not errors


def test_multi_root_resolution_and_per_disk_threads(tmp_path):
    """Map outputs spread across local dirs resolve (the reference's
    LocalDirAllocator search) and reader threads scale per disk
    (AsyncReaderManager.cc:16-50)."""
    from tests.helpers import make_mof_tree, map_ids
    from uda_tpu.mofserver import DataEngine, DirIndexResolver, ShuffleRequest
    from uda_tpu.utils.config import Config

    r1, r2 = tmp_path / "d0", tmp_path / "d1"
    make_mof_tree(str(r1), "jobMR", 2, 1, 10, seed=31)
    make_mof_tree(str(r2), "jobMR", 4, 1, 10, seed=31)
    # keep only maps 2..3 in r2 so each root holds a disjoint subset
    import shutil
    for mid in map_ids("jobMR", 2):
        shutil.rmtree(r2 / "jobMR" / mid)
    cfg = Config({"mapred.uda.provider.blocked.threads.per.disk": 2})
    engine = DataEngine(DirIndexResolver([str(r1), str(r2)]), cfg,
                        num_disks=2)
    try:
        assert engine._pool._max_workers == 4  # 2 threads x 2 disks
        for mid in map_ids("jobMR", 4):
            res = engine.fetch(ShuffleRequest("jobMR", mid, 0, 0, 1 << 20))
            assert res.is_last and len(res.data) > 0
    finally:
        engine.stop()


def test_chained_fetches_under_delay_failpoint_no_deadlock(tmp_path):
    """DataEngine.submit's docstring warns that blocking in completion
    callbacks can deadlock the pool. The fetch path's chained re-issue
    (a Segment's completion callback submitting its next chunk) must
    therefore stay non-blocking: with ONE pool thread, multi-chunk
    segments and a delay failpoint slowing every read, the whole fetch
    must still complete inside a bounded wall clock — a wedge here is
    the deadlock shape the warning describes."""
    from uda_tpu.merger import LocalFetchClient, MergeManager
    from uda_tpu.utils.failpoints import failpoints

    make_mof_tree(str(tmp_path), "jobDl", num_maps=4, num_reducers=1,
                  records_per_map=60, seed=41)
    cfg = Config({"mapred.uda.provider.blocked.threads.per.disk": 1,
                  "mapred.rdma.buf.size": 1,       # 1 KB -> many chunks
                  "mapred.rdma.wqe.per.conn": 4})  # window > pool threads
    engine = DataEngine(DirIndexResolver(str(tmp_path)), cfg)
    done = threading.Event()
    out = {}

    def fetch_everything():
        mm = MergeManager(LocalFetchClient(engine), "uda.tpu.RawBytes", cfg)
        out["segs"] = mm.fetch_all("jobDl", map_ids("jobDl", 4), 0)
        done.set()

    t = threading.Thread(target=fetch_everything, daemon=True)
    try:
        with failpoints.scoped("data_engine.pread=delay:5"):
            t.start()
            assert done.wait(timeout=60), \
                "chained fetches deadlocked the 1-thread pool"
    finally:
        engine.stop()
    assert all(s.ready for s in out["segs"])
    assert sum(s.record_batch().num_records for s in out["segs"]) == 240


@pytest.mark.faults
def test_sync_fetch_timeout_releases_admission_budget(tmp_path):
    """fetch() is deadline-bounded (derived from mapred.rdma.fetch.*)
    AND accounting-clean on both timeout shapes: a request cancelled
    while still QUEUED (its _serve never runs) must hand back its
    admission bytes and gauges, or repeated timeouts pin the read
    budget on an idle engine."""
    import time

    from uda_tpu.utils.failpoints import failpoints
    from uda_tpu.utils.metrics import metrics

    make_mof_tree(str(tmp_path), "job9", num_maps=1, num_reducers=1,
                  records_per_map=20)
    cfg = Config({"mapred.uda.provider.blocked.threads.per.disk": 1,
                  "mapred.rdma.fetch.attempt.timeout.ms": 200})
    engine = DataEngine(DirIndexResolver(str(tmp_path)), cfg)
    assert engine.sync_fetch_timeout_s == pytest.approx(0.2)
    mid = map_ids("job9", 1)[0]
    try:
        with failpoints.scoped("data_engine.pread=delay:800"):
            # occupy the single reader thread...
            running = engine.submit(ShuffleRequest("job9", mid, 0, 0, 512))
            time.sleep(0.05)
            # ...so this one times out QUEUED and gets truly cancelled
            with pytest.raises(StorageError, match="did not complete"):
                engine.fetch(ShuffleRequest("job9", mid, 0, 0, 512))
            running.result(timeout=5.0)
        # the running read settled in _serve, the cancelled one in
        # fetch(): all admission state must be back to idle
        deadline = time.monotonic() + 5.0
        while engine._admitted_bytes and time.monotonic() < deadline:
            time.sleep(0.01)
        assert engine._admitted_bytes == 0
        assert metrics.get_gauge("supplier.read.bytes.on_air") == 0
        assert metrics.get_gauge("supplier.reads.on_air") == 0
        # and the engine is NOT spuriously "exhausted" afterwards —
        # probed with the ambient chaos-rung pread schedule pinned out
        # (this fetch asserts admission recovery, not fault recovery;
        # an injected error here would fail the wrong invariant)
        with failpoints.scoped(""):
            failpoints.disarm("data_engine.pread")
            res = engine.fetch(ShuffleRequest("job9", mid, 0, 0, 1 << 20))
        assert res.data
    finally:
        engine.stop()


def test_try_plan_unwinds_admission_on_open_failure(tmp_path):
    """The zero-copy fast path's charge must pair with an unwind: a
    cached index entry whose MOF was deleted underneath (job-cleanup
    race) fails the fd open AFTER admission — repeated failures must
    leave the read budget untouched, not leak it until the supplier
    wedges on 'read pool exhausted'."""
    job = "jobLeak"
    make_mof_tree(str(tmp_path), job, num_maps=1, num_reducers=1,
                  records_per_map=10, seed=1)
    engine = DataEngine(DirIndexResolver(str(tmp_path)), Config())
    mid = map_ids(job, 1)[0]
    req = ShuffleRequest(job, mid, 0, 0, 1 << 20)
    try:
        # warm the index cache (try_plan only fires on cache hits)
        engine.fetch(req)
        plan = engine.try_plan(req)
        assert plan is not None  # sanity: planable while the MOF exists
        plan.release()           # a live slice HOLDS its charge
        # engine-visible state back to idle before the breakage
        engine._fds.close_all()
        os.remove(os.path.join(str(tmp_path), job, mid, "file.out"))
        assert engine._admitted_bytes == 0
        for _ in range(3):
            with pytest.raises(OSError):
                engine.try_plan(req)
        assert engine._admitted_bytes == 0  # no leak, no wedge
    finally:
        engine.stop()
