"""Overlapped fetch/merge (the network-levitated property,
uda_tpu.merger.overlap): runs stage + merge on device WHILE later
fetches are in flight, output byte-identical to the global re-sort."""

import functools
import io
import threading

import numpy as np
import pytest

from tests.helpers import (emit_stream_bytes, framed_bytes, host_sort_bytes,
                           make_mof_tree, map_ids)
from uda_tpu.merger import LocalFetchClient, MergeManager
from uda_tpu.merger.overlap import OverlappedMerger
from uda_tpu.mofserver import DataEngine, DirIndexResolver
from uda_tpu.ops import merge as merge_ops
from uda_tpu.utils import comparators
from uda_tpu.utils.config import Config
from uda_tpu.utils.ifile import IFileReader, RecordBatch, crack, write_records
from uda_tpu.utils.metrics import metrics


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
    want = merge_ops.merge_batches(batches, kt, 16)
    assert emit_stream_bytes(om, batches) == framed_bytes(want)
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
    got_p = emit_stream_bytes(om_p, batches)
    assert got_p == emit_stream_bytes(om_h, batches) and len(got_p) > 0
    assert om_p.stats["device_merges"] >= 1


def test_overlap_oversize_keys_stay_on_the_forest():
    kt = comparators.get_key_type("uda.tpu.RawBytes")
    # keys longer than the carried width with colliding prefixes across
    # segments: the forest orders them by (prefix, length), the emit
    # re-orders the block by whole content
    pre = b"P" * 16
    b0 = _batch([(pre + b"zz", b"v0"), (b"a", b"v1")])
    b1 = _batch([(pre + b"ab", b"v2"), (b"b", b"v3")])
    om = OverlappedMerger(kt, width=16)
    om.feed(0, b0)
    om.feed(1, b1)
    assert emit_stream_bytes(om, [b0, b1]) == host_sort_bytes([b0, b1], kt)
    assert om.stats["oversize"] and not om.stats["overflow"]
    assert om.stats["device_merges"] >= 1
    assert metrics.get("merge.overflow.fallbacks") == 0
    assert metrics.get("merge.overflow.keys") == 2
    assert metrics.get("merge.oversize.blocks") == 1
    assert metrics.snapshot()["overflow_resort_time"] == 0


STEM = b"abcdefghijklmnop"                      # 16 bytes: the carried width


def _values_in_order(stream: bytes) -> list:
    return [v for _, v in IFileReader(io.BytesIO(stream))]


def test_a_block_with_a_prefix_equal_keys_across_maps_and_the_stem():
    """One equal-prefix block: a proper prefix among the oversize keys,
    two equal oversize keys from different maps (map order, then row
    order) and a key that IS the 16-byte stem (within the width: first,
    and never part of the block)."""
    kt = comparators.get_key_type("uda.tpu.RawBytes")
    maps = [[STEM + b"zq", STEM + b"long", STEM + b"long"],
            [STEM, STEM + b"longer", STEM[:9]],
            [STEM + b"long", STEM + b"l", STEM + b"zq"]]
    batches = [_batch(sorted((k, b"m%dr%d" % (m, r))
                             for r, k in enumerate(keys)))
               for m, keys in enumerate(maps)]
    om = OverlappedMerger(kt, width=16)
    for i, b in enumerate(batches):
        om.feed(i, b)
    got = emit_stream_bytes(om, batches)
    assert got == host_sort_bytes(batches, kt)
    keys = [k for k, _ in IFileReader(io.BytesIO(got))]
    assert keys == [STEM[:9], STEM, STEM + b"l", STEM + b"long",
                    STEM + b"long", STEM + b"long", STEM + b"longer",
                    STEM + b"zq", STEM + b"zq"]
    values = _values_in_order(got)
    # the three equal keys: map 0's two rows in row order, then map 2's
    assert [v[:2] for v in values[3:6]] == [b"m0", b"m0", b"m2"]
    assert values[3] < values[4]
    assert [v[:2] for v in values[7:]] == [b"m0", b"m2"]
    assert metrics.get("merge.overflow.keys") == 7
    assert metrics.get("merge.oversize.blocks") == 1
    assert metrics.get("merge.overflow.fallbacks") == 0


@pytest.mark.parametrize("slab", (1, 2, 3, 5, 64))
def test_a_block_that_straddles_a_read_back_slab_is_fixed_as_one(
        monkeypatch, slab):
    """The emit reads the merged rows back ``slab`` rows at a time; a
    block open at a slab's end is held back until it closes — over
    several slabs when the slab is smaller than the block."""
    from uda_tpu.merger import streaming
    real = streaming.iter_row_slabs
    monkeypatch.setattr(
        streaming, "iter_row_slabs",
        lambda rows, valid: real(rows, valid, slab=slab))
    kt = comparators.get_key_type("uda.tpu.RawBytes")
    rng = np.random.default_rng(slab)
    tails = [b"zz", b"a", b"ab", b"b" * 30, b"a", b"", b"za", b"ab", b"y"]
    batches = []
    for m in range(3):
        keys = [STEM + b"x" + tails[(3 * m + i) % 9] for i in range(5)]
        keys += [b"OTHERSTEM0123456" + tails[m], STEM, b"b", b"zzz"]
        keys += [rng.bytes(int(rng.integers(1, 12))) for _ in range(4)]
        batches.append(_batch(sorted((k, b"m%d" % m) for k in keys)))
    om = OverlappedMerger(kt, width=16)
    for i, b in enumerate(batches):
        om.feed(i, b)
    assert emit_stream_bytes(om, batches) == host_sort_bytes(batches, kt)
    assert metrics.get("merge.overflow.keys") == 18
    # the fifteen STEM + "x" keys are one block whatever the slab; the
    # three OTHERSTEM keys another
    assert metrics.get("merge.oversize.blocks") == 2
    assert metrics.snapshot()["oversize_fixup_time"] > 0


def _shared_stem_batches(seed: int, maps: int = 4, records: int = 60):
    """Random keys of 1-48 bytes over three stems and a two-letter
    alphabet: equal prefixes, proper prefixes and equal keys abound."""
    rng = np.random.default_rng(seed)
    stems = [bytes(rng.integers(97, 100, 16, dtype=np.uint8))
             for _ in range(3)]

    def key() -> bytes:
        stem = stems[int(rng.integers(0, 3))]
        n = int(rng.integers(1, 49))
        return stem[:n] if n <= 16 else stem + bytes(
            rng.integers(97, 99, n - 16, dtype=np.uint8))

    return [_batch(sorted((key(), b"m%d" % m) for _ in range(records)))
            for m in range(maps)]


@pytest.mark.parametrize("engine", ("host", "pallas"))
def test_random_keys_with_shared_stems_on_both_engines(monkeypatch, engine):
    """The forest with its fix-up against the comparator sort of the
    concatenation, on the host engine and on the device kernel
    (interpreted here), every size class on the device."""
    from uda_tpu.merger import overlap
    monkeypatch.setattr(overlap, "DEVICE_MIN_BUCKET",
                        overlap.MIN_RUN_CAPACITY)
    kt = comparators.get_key_type("uda.tpu.RawBytes")
    batches = _shared_stem_batches(seed=42)
    want = host_sort_bytes(batches, kt)
    om = OverlappedMerger(kt, width=16, engine=engine)
    for i in (2, 0, 3, 1):
        om.feed(i, batches[i])
    assert emit_stream_bytes(om, batches) == want
    assert om.stats["oversize"] and om.stats["device_merges"] == 3
    oversize = sum(int((b.key_len > 16).sum()) for b in batches)
    assert oversize > 50
    assert metrics.get("merge.overflow.keys") == oversize
    assert metrics.get("merge.oversize.blocks") >= 3
    assert metrics.get("merge.overflow.fallbacks") == 0
    # fed in map order this time: the same bytes
    om = OverlappedMerger(kt, width=16, engine="host")
    for i, b in enumerate(batches):
        om.feed(i, b)
    assert emit_stream_bytes(om, batches) == want


def test_a_key_type_with_its_own_compare_still_takes_the_fallback():
    """Prefix order says nothing about a comparator the forest does not
    know: such a key type latches the global re-sort as it always has,
    and is counted."""

    class Reversed(comparators.KeyType):
        def compare(self, a: bytes, b: bytes) -> int:
            return comparators.memcmp(self.content(a)[::-1],
                                      self.content(b)[::-1])

    kt = Reversed("raw", bytes)
    assert not comparators.uses_default_bytewise(kt)
    batches = [_batch([(b"a", b"v0"), (STEM + b"ab", b"v1")]),
               _batch([(b"b", b"v2"), (STEM + b"zz", b"v3")])]
    om = OverlappedMerger(kt, width=16)
    for i, b in enumerate(batches):
        om.feed(i, b)
    got = emit_stream_bytes(om, batches)
    # the fallback's order is the bytewise one (ops.merge.merge_batches
    # ranks by content), as before this route existed
    want = merge_ops.merge_batches(batches, kt, 16)
    assert got == framed_bytes(want)
    assert om.stats["overflow"] and not om.stats["oversize"]
    assert metrics.get("merge.overflow.fallbacks") == 1
    assert metrics.get("merge.overflow.keys") == 4   # ranked twice: got, want
    assert metrics.get("merge.oversize.blocks") == 0
    assert metrics.snapshot()["overflow_resort_time"] > 0


def test_overlap_empty_and_single_segment():
    kt = comparators.get_key_type("uda.tpu.RawBytes")
    empty = RecordBatch.concat([])
    one = _batch(_rand_recs(9, 17))
    om = OverlappedMerger(kt, width=16)
    om.feed(0, empty)
    om.feed(1, one)
    want = merge_ops.merge_batches([empty, one], kt, 16)
    assert emit_stream_bytes(om, [empty, one]) == framed_bytes(want)
    # nothing fed at all: the emit is the bare end-of-stream marker
    om = OverlappedMerger(kt, width=16)
    om.feed(0, empty)
    assert emit_stream_bytes(om, [empty]) == framed_bytes(empty)


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
