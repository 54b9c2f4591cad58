"""Overlapped fetch/merge (the network-levitated property,
uda_tpu.merger.overlap): runs stage + merge on device WHILE later
fetches are in flight, output byte-identical to the global re-sort."""

import functools
import io
import threading

import numpy as np
import pytest

from tests.helpers import make_mof_tree, map_ids
from uda_tpu.merger import LocalFetchClient, MergeManager
from uda_tpu.merger.overlap import OverlappedMerger
from uda_tpu.mofserver import DataEngine, DirIndexResolver
from uda_tpu.ops import merge as merge_ops
from uda_tpu.utils import comparators
from uda_tpu.utils.config import Config
from uda_tpu.utils.ifile import IFileReader, RecordBatch, crack, write_records


def _batch(recs):
    return crack(write_records(recs))


def _rand_recs(seed, n, dup_every=5):
    rng = np.random.default_rng(seed)
    recs = []
    for i in range(n):
        k = rng.bytes(6) if i % dup_every else b"dupkey"
        recs.append((k, rng.bytes(20)))
    return recs


def test_overlap_matches_global_resort():
    kt = comparators.get_key_type("uda.tpu.RawBytes")
    batches = [_batch(_rand_recs(s, 40 + 7 * s)) for s in range(5)]
    om = OverlappedMerger(kt, width=16)
    # feed OUT of completion order: stability must still follow original
    # (segment, row) order, not completion order
    for i in (3, 0, 4, 1, 2):
        om.feed(i, batches[i])
    got = om.finish(batches)
    want = merge_ops.merge_batches(batches, kt, 16)
    assert list(got.iter_records()) == list(want.iter_records())
    assert om.stats["device_merges"] >= 1
    assert not om.stats["overflow"]


@pytest.mark.slow
def test_overlap_pallas_engine_matches_host(monkeypatch):
    # force the device merge-path kernel (interpret mode on CPU): the
    # integration the TPU deployment runs, against the host twin. Every
    # size class on the device, or these small runs would merge on the
    # host and never reach the kernel (tests/test_forest_host_classes.py)
    from uda_tpu.merger import overlap
    monkeypatch.setattr(overlap, "DEVICE_MIN_BUCKET",
                        overlap.MIN_RUN_CAPACITY)
    kt = comparators.get_key_type("uda.tpu.RawBytes")
    batches = [_batch(_rand_recs(100 + s, 30 + s)) for s in range(3)]
    om_p = OverlappedMerger(kt, width=16, engine="pallas")
    om_h = OverlappedMerger(kt, width=16, engine="host")
    for i, b in enumerate(batches):
        om_p.feed(i, b)
        om_h.feed(i, b)
    got_p = om_p.finish(batches)
    got_h = om_h.finish(batches)
    assert list(got_p.iter_records()) == list(got_h.iter_records())
    assert om_p.stats["device_merges"] >= 1


def test_overlap_oversize_keys_fall_back():
    kt = comparators.get_key_type("uda.tpu.RawBytes")
    # keys longer than the carried width with colliding prefixes across
    # segments: exactly the case the fast path cannot order
    pre = b"P" * 16
    b0 = _batch([(pre + b"zz", b"v0"), (b"a", b"v1")])
    b1 = _batch([(pre + b"ab", b"v2"), (b"b", b"v3")])
    om = OverlappedMerger(kt, width=16)
    om.feed(0, b0)
    om.feed(1, b1)
    got = om.finish([b0, b1])
    want = merge_ops.merge_batches_host([b0, b1], kt)
    assert list(got.iter_records()) == list(want.iter_records())
    assert om.stats["overflow"]


def test_overlap_empty_and_single_segment():
    kt = comparators.get_key_type("uda.tpu.RawBytes")
    empty = RecordBatch.concat([])
    one = _batch(_rand_recs(9, 17))
    om = OverlappedMerger(kt, width=16)
    om.feed(0, empty)
    om.feed(1, one)
    got = om.finish([empty, one])
    want = merge_ops.merge_batches([empty, one], kt, 16)
    assert list(got.iter_records()) == list(want.iter_records())


def test_merge_work_happens_before_last_fetch(tmp_path):
    """The VERDICT contract: device merge work completes while the last
    fetch is still outstanding (reference MergeManager.cc:47-182)."""
    num_maps = 9
    make_mof_tree(str(tmp_path), "jobO", num_maps, 1, 40, seed=21)
    engine = DataEngine(DirIndexResolver(str(tmp_path)))
    release_last = threading.Event()
    state = {"completed": 0, "merges_at_last_start": None}
    lock = threading.Lock()

    class GatedClient(LocalFetchClient):
        """Holds back ONE map's fetch until the test observes overlap."""

        def start_fetch(self, req, on_complete):
            if req.map_id.endswith("000008_0") and req.offset == 0:
                def gated(res):
                    release_last.wait(timeout=30)
                    on_complete(res)
                super().start_fetch(req, gated)
            else:
                super().start_fetch(req, on_complete)

    cfg = Config({"mapred.rdma.wqe.per.conn": num_maps})  # all in flight
    mm = MergeManager(GatedClient(engine), "uda.tpu.RawBytes", cfg)
    result = {}

    def run():
        blocks = []
        result["total"] = mm.run("jobO", map_ids("jobO", num_maps), 0,
                                 lambda b: blocks.append(bytes(b)))
        result["stream"] = b"".join(blocks)

    t = threading.Thread(target=run)
    t.start()
    try:
        # wait until the 8 ungated segments have been staged AND merged
        # into the forest (binary counter: 8 runs => >= 4 device merges),
        # all while the gated fetch is still outstanding
        waiter = threading.Event()
        for _ in range(3000):
            if _overlap_stats(mm)["device_merges"] >= 4:
                break
            waiter.wait(0.01)
        stats = _overlap_stats(mm)
        state["merges_at_last_start"] = stats["device_merges"]
        assert stats["device_merges"] >= 4, (
            f"no overlap: only {stats} before last fetch released")
    finally:
        release_last.set()
        t.join(timeout=60)
        engine.stop()
    assert not t.is_alive()
    # and the result is still the correctly sorted stream
    kt = comparators.get_key_type("uda.tpu.RawBytes")
    got = list(IFileReader(io.BytesIO(result["stream"])))
    assert len(got) == num_maps * 40
    keys = [k for k, _ in got]
    assert keys == sorted(keys, key=functools.cmp_to_key(kt.compare))


def _overlap_stats(mm):
    om = getattr(mm, "_active_overlap", None)
    return om.stats if om is not None else {"device_merges": 0}


def test_online_merge_with_overlap_disabled_still_works(tmp_path):
    make_mof_tree(str(tmp_path), "jobN", 4, 1, 25, seed=13)
    engine = DataEngine(DirIndexResolver(str(tmp_path)))
    cfg = Config({"uda.tpu.merge.overlap": False})
    try:
        mm = MergeManager(LocalFetchClient(engine), "uda.tpu.RawBytes", cfg)
        blocks = []
        mm.run("jobN", map_ids("jobN", 4), 0,
               lambda b: blocks.append(bytes(b)))
        got = list(IFileReader(io.BytesIO(b"".join(blocks))))
        assert len(got) == 100
    finally:
        engine.stop()
