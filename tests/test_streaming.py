"""Streaming bounded-memory online emission (uda_tpu.merger.streaming).

The contract under test: with ``uda.tpu.online.streaming`` on, the online
merge produces BYTE-IDENTICAL output to the memory-resident path while
(a) spooling every segment to a sorted run + releasing its fetched bytes,
(b) never allocating a shuffle-sized host buffer, and (c) cleaning up its
scratch runs on every exit path — the reference's staging-loop memory
model (reference src/Merger/StreamRW.cc:151-225, MergeManager.cc:155-182)
around the device permutation.
"""

import io
import os

import numpy as np
import pytest

from tests.helpers import make_mof_tree, map_ids
from uda_tpu.merger import LocalFetchClient, MergeManager
from uda_tpu.merger import streaming as stream_mod
from uda_tpu.merger.overlap import OverlappedMerger
from uda_tpu.merger.streaming import RunStore, framed_lengths
from uda_tpu.mofserver import DataEngine, DirIndexResolver
from uda_tpu.utils import comparators, vint
from uda_tpu.utils.config import Config
from uda_tpu.utils.errors import MergeError
from uda_tpu.utils.ifile import IFileReader, crack, write_records


def _merge_once(tmp_path, streaming, *, num_maps=6, num_reducers=2,
                records_per_map=120, key_bytes=10, seed=5,
                key_type="uda.tpu.RawBytes", extra_cfg=None):
    root = os.path.join(str(tmp_path), "stream" if streaming else "inmem")
    make_mof_tree(root, "jobS", num_maps, num_reducers, records_per_map,
                  seed=seed, key_bytes=key_bytes)
    cfg = Config(dict({"uda.tpu.online.streaming": streaming},
                      **(extra_cfg or {})))
    engine = DataEngine(DirIndexResolver(root), cfg)
    kt = comparators.get_key_type(key_type)
    streams = []
    try:
        for r in range(num_reducers):
            mm = MergeManager(LocalFetchClient(engine), kt, cfg)
            blocks = []
            total = mm.run("jobS", map_ids("jobS", num_maps), r,
                           lambda b: blocks.append(bytes(b)))
            s = b"".join(blocks)
            assert total == len(s)
            streams.append(s)
    finally:
        engine.stop()
    return streams


def test_framed_lengths_matches_writer():
    recs = [(bytes([i]) * (i % 200), b"v" * ((i * 37) % 500))
            for i in range(1, 120)]
    data = write_records(recs)
    b = crack(data)
    fl = framed_lengths(b.key_len, b.val_len)
    assert int(fl.sum()) + 2 == len(data)  # +2 = EOF marker
    for n in (0, 1, 127, 128, 255, 256, 65535, 65536, 2**31):
        assert int(stream_mod._vlong_sizes(np.array([n]))[0]) \
            == vint.vlong_size(n)


def test_streaming_byte_parity_with_inmem(tmp_path):
    a = _merge_once(tmp_path, False)
    b = _merge_once(tmp_path, True)
    assert a == b


def test_streaming_multi_slab(tmp_path, monkeypatch):
    # tiny slabs force many interleave rounds + sequential cursor reuse,
    # and a 2-cursor fd cap forces suspend/reopen-seek cycles on every
    # slab (the large-shuffle fd-bound path)
    monkeypatch.setattr(stream_mod, "SLAB_RECORDS", 64)
    monkeypatch.setattr(stream_mod, "MAX_OPEN_CURSORS", 2)
    a = _merge_once(tmp_path, False, records_per_map=211, num_maps=7)
    b = _merge_once(tmp_path, True, records_per_map=211, num_maps=7)
    assert a == b


def test_streaming_oversize_keys_fallback(tmp_path):
    # keys longer than the carried width -> comparator-sorted runs +
    # k-way merge fallback over the run files; bytes must still match
    a = _merge_once(tmp_path, False, key_bytes=40,
                    extra_cfg={"uda.tpu.key.width": 8})
    b = _merge_once(tmp_path, True, key_bytes=40,
                    extra_cfg={"uda.tpu.key.width": 8})
    assert a == b
    # and the result is truly sorted
    kt = comparators.get_key_type("uda.tpu.RawBytes")
    recs = list(IFileReader(io.BytesIO(b[0])))
    keys = [k for k, _ in recs]
    assert keys == sorted(keys)


def test_streaming_oversize_python_heap_fallback(tmp_path):
    # a comparator outside the native k-way table exercises the Python
    # heap fallback over run-file cursors
    from uda_tpu.utils.ifile import set_native_enabled

    set_native_enabled(False)
    try:
        a = _merge_once(tmp_path, False, key_bytes=24,
                        extra_cfg={"uda.tpu.key.width": 8})
        b = _merge_once(tmp_path, True, key_bytes=24,
                        extra_cfg={"uda.tpu.key.width": 8})
    finally:
        set_native_enabled(True)
    assert a == b


def test_streaming_over_compressed_fetch(tmp_path):
    # streaming online mode composed with the decompressing transport:
    # chunks decompress, crack, stage to runs, release — output matches
    # the in-memory path byte for byte
    import functools

    from uda_tpu.compress import DecompressingClient, get_codec
    from uda_tpu.mofserver.writer import MOFWriter

    codec = get_codec("lzo")
    rng = np.random.default_rng(77)
    expected = []
    job = "jobZ"
    writer = MOFWriter(str(tmp_path), job, codec=codec)
    for m in range(4):
        recs = sorted((rng.bytes(8), rng.bytes(40)) for _ in range(120))
        expected += recs
        writer.write(f"attempt_{job}_m_{m:06d}_0", [recs])
    out = {}
    for streaming in (False, True):
        cfg = Config({"mapred.rdma.buf.size": 1,
                      "uda.tpu.online.streaming": streaming})
        engine = DataEngine(DirIndexResolver(str(tmp_path)), cfg)
        try:
            client = DecompressingClient(LocalFetchClient(engine), codec)
            mm = MergeManager(client, "uda.tpu.RawBytes", cfg)
            blocks = []
            mm.run(job, writer.map_ids, 0,
                   lambda b: blocks.append(bytes(b)))
        finally:
            engine.stop()
        out[streaming] = b"".join(blocks)
    assert out[False] == out[True]
    kt = comparators.get_key_type("uda.tpu.RawBytes")
    got = list(IFileReader(io.BytesIO(out[True])))
    want = sorted(expected, key=functools.cmp_to_key(
        lambda a, b: kt.compare(a[0], b[0])))
    assert got == want


def test_streaming_over_host_routing_client(tmp_path):
    # streaming mode over the per-host lazy transport table (the
    # reference's connect-per-host client, RDMAClient.cc:498-527)
    from uda_tpu.merger.segment import HostRoutingClient

    root = str(tmp_path)
    make_mof_tree(root, "jobH", 6, 1, 80, seed=11)
    cfg = Config({"uda.tpu.online.streaming": True})
    engines = {}

    def connect(host):
        engines[host] = DataEngine(DirIndexResolver(root), cfg)
        return LocalFetchClient(engines[host])

    kt = comparators.get_key_type("uda.tpu.RawBytes")
    try:
        mm = MergeManager(HostRoutingClient(connect), kt, cfg)
        mids = [(f"host{m % 2}", mid)
                for m, mid in enumerate(map_ids("jobH", 6))]
        blocks = []
        total = mm.run("jobH", mids, 0, lambda b: blocks.append(bytes(b)))
    finally:
        for e in engines.values():
            e.stop()
    assert len(engines) == 2  # one lazy transport per host
    recs = list(IFileReader(io.BytesIO(b"".join(blocks))))
    keys = [k for k, _ in recs]
    assert len(recs) == 480 and keys == sorted(keys) and total > 0


def test_streaming_releases_segment_bytes(tmp_path):
    root = str(tmp_path)
    make_mof_tree(root, "jobR", 4, 1, 60, seed=2)
    cfg = Config({"uda.tpu.online.streaming": True})
    engine = DataEngine(DirIndexResolver(root), cfg)
    kt = comparators.get_key_type("uda.tpu.RawBytes")
    try:
        mm = MergeManager(LocalFetchClient(engine), kt, cfg)
        held = []
        orig = mm.fetch_all

        def spy(*args, **kwargs):
            segs = orig(*args, **kwargs)
            held.extend(segs)
            return segs

        mm.fetch_all = spy
        mm.run("jobR", map_ids("jobR", 4), 0, lambda b: None)
    finally:
        engine.stop()
    assert held and all(s.batches == [] for s in held)
    with pytest.raises(MergeError):
        held[0].record_batch()


def test_streaming_cleans_scratch_dir(tmp_path):
    root = str(tmp_path)
    make_mof_tree(root, "jobC", 3, 1, 40, seed=9)
    scratch = os.path.join(root, "scratch")
    cfg = Config({"uda.tpu.online.streaming": True,
                  "uda.tpu.spill.dirs": scratch})
    engine = DataEngine(DirIndexResolver(root), cfg)
    kt = comparators.get_key_type("uda.tpu.RawBytes")
    try:
        mm = MergeManager(LocalFetchClient(engine), kt, cfg)
        mm.run("jobC", map_ids("jobC", 3), 0, lambda b: None)
    finally:
        engine.stop()
    assert os.listdir(scratch) == []  # run dirs removed after emission


def test_run_store_rejects_double_stage(tmp_path):
    store = RunStore(str(tmp_path))
    batch = crack(write_records([(b"a", b"1"), (b"b", b"2")]))
    order = np.arange(2, dtype=np.int64)
    store.write_run(0, batch, order)
    with pytest.raises(MergeError):
        store.write_run(0, batch, order)
    store.cleanup()
    assert not os.path.exists(store.dir)


def test_interleave_detects_lost_records(tmp_path):
    kt = comparators.get_key_type("uda.tpu.RawBytes")
    store = RunStore(str(tmp_path))
    om = OverlappedMerger(kt, 16, run_store=store)
    batch = crack(write_records(
        [(bytes([i]), b"x") for i in range(10)]))
    om.feed(0, batch)

    class _Emitter:
        def emit_framed(self, pieces, consumer):
            total = 0
            for p in pieces:
                consumer(memoryview(p))
                total += len(p)
            return total

    # lie about the expected count -> accounting must catch it
    with pytest.raises(MergeError):
        om.finish_streaming(_Emitter(), lambda b: None, expected_records=11)


def test_backpressure_bounded_queue(tmp_path):
    # staging far slower than fetch: bounded queue must block feeders,
    # not grow; the run still completes with correct output
    import time

    kt = comparators.get_key_type("uda.tpu.RawBytes")
    store = RunStore(str(tmp_path))
    om = OverlappedMerger(kt, 16, run_store=store, max_pending=2)
    orig_prepare = om._prepare

    def slow_prepare(i, src, fed_t):
        time.sleep(0.02)
        return orig_prepare(i, src, fed_t)

    om._prepare = slow_prepare
    batches = [crack(write_records(sorted(
        (bytes([s, i]), bytes([i])) for i in range(20))))
        for s in range(12)]
    for s, b in enumerate(batches):
        om.feed(s, b)  # blocks when > max_pending are queued
        assert om._q.qsize() <= 2

    class _Emitter:
        def emit_framed(self, pieces, consumer):
            return sum(len(p) for p in pieces)

        def emit(self, records, consumer):  # pragma: no cover
            return 0

    n = om.finish_streaming(_Emitter(), lambda b: None,
                            expected_records=240)
    assert n > 0
    assert not os.path.exists(store.dir)


def test_staging_pool_parity(tmp_path):
    # 4 stage workers must produce byte-identical output (the forest
    # carries in the one consumer; insertion order may differ but
    # the composite key is total, so the merged rows are identical)
    a = _merge_once(tmp_path, True, num_maps=9, records_per_map=150,
                    extra_cfg={"uda.tpu.stage.pool": 4})
    b = _merge_once(tmp_path, False, num_maps=9, records_per_map=150)
    assert a == b


def test_spill_dir_rotation(tmp_path):
    d1, d2 = os.path.join(str(tmp_path), "d1"), os.path.join(
        str(tmp_path), "d2")
    store = RunStore([d1, d2], tag="rot")
    batch = crack(write_records([(b"a", b"1")]))
    order = np.arange(1, dtype=np.int64)
    for seg in range(4):
        store.write_run(seg, batch, order)
    assert store.run_path(0).startswith(d1)
    assert store.run_path(1).startswith(d2)
    assert all(os.path.exists(store.run_path(s)) for s in range(4))
    store.cleanup()
    assert os.listdir(d1) == [] and os.listdir(d2) == []


@pytest.mark.slow
def test_staging_pool_stress_parity(tmp_path):
    # adversarial pool schedule: 64 segments of random sizes (empty,
    # tiny, big, oversize-key mix) staged by 4 workers with random
    # per-stage delays must produce byte-identical output to the
    # one-worker run — the forest-carry and run-store locking
    # under real interleaving
    import random as _random
    import time

    from uda_tpu.merger.emitter import FramedEmitter

    rng = np.random.default_rng(31337)
    batches = []
    for s in range(64):
        n = int(rng.integers(0, 400))
        # key lengths straddle the width (16): the oversize-key
        # overflow branch runs under real pool interleaving too
        recs = sorted((rng.bytes(int(rng.integers(1, 25))),
                       rng.bytes(int(rng.integers(0, 30))))
                      for _ in range(n))
        batches.append(crack(write_records(recs)))
    kt = comparators.get_key_type("uda.tpu.RawBytes")
    outs = {}
    for stagers in (1, 4):
        store = RunStore(str(tmp_path), tag=f"stress{stagers}")
        om = OverlappedMerger(kt, 16, run_store=store, max_pending=8,
                              stagers=stagers)
        if stagers > 1:
            orig = om._prepare
            delay = _random.Random(7)

            def jitter_prepare(i, src, fed_t, _orig=orig, _d=delay):
                time.sleep(_d.random() * 0.004)
                return _orig(i, src, fed_t)

            om._prepare = jitter_prepare
        for s, b in enumerate(batches):
            om.feed(s, b)
        blocks = []
        emitter = FramedEmitter(1 << 14)
        om.finish_streaming(
            emitter, lambda mv: blocks.append(bytes(mv)),
            expected_records=sum(b.num_records for b in batches))
        outs[stagers] = b"".join(blocks)
    assert outs[1] == outs[4]


def test_abort_with_full_queue_does_not_deadlock(tmp_path):
    kt = comparators.get_key_type("uda.tpu.RawBytes")
    store = RunStore(str(tmp_path))
    om = OverlappedMerger(kt, 16, run_store=store, max_pending=1, stagers=1)
    # wedge the one stage worker so the queue stays full
    import threading
    gate = threading.Event()

    def wedged(i, src, fed_t):
        gate.wait(5)        # and stages nothing

    om._prepare = wedged
    b = crack(write_records([(b"k", b"v")]))
    om.feed(0, b)
    om.feed(1, b)
    om.abort()  # must return promptly and clean the store
    gate.set()
    assert not os.path.exists(store.dir)


def test_streaming_byte_parity_under_truncation_failpoint(tmp_path):
    # chunks truncated mid-record by an armed failpoint: the carry
    # buffer re-joins every split record from the re-fetched remainder,
    # and the streaming run stays byte-identical to the unfaulted
    # in-memory run (the spooled runs never see the damage)
    from uda_tpu.utils.failpoints import failpoints

    a = _merge_once(tmp_path, False, records_per_map=90,
                    extra_cfg={"mapred.rdma.buf.size": 1})
    hits0 = failpoints.hits["data_engine.pread"]
    with failpoints.scoped("data_engine.pread=truncate:23:every:2"):
        b = _merge_once(tmp_path, True, records_per_map=90,
                        extra_cfg={"mapred.rdma.buf.size": 1})
        assert failpoints.hits["data_engine.pread"] > hits0
    assert a == b
