"""The in-memory emit path's slab gather (ISSUE 25): the native routine
over a per-task segment table against ``slab_batch``'s numpy path, which
stays in the tree as the plain reference. Equal bytes in every column and
in the framed stream; bad indices raise MergeError, never scribble; the
``uda.tpu.use.native`` kill switch and the ``emit.gather.native_slabs``
engagement counter."""

import io

import numpy as np
import pytest

from uda_tpu import native
from uda_tpu.merger import streaming
from uda_tpu.merger.emitter import FramedEmitter
from uda_tpu.merger.overlap import OverlappedMerger
from uda_tpu.utils import comparators, ifile
from uda_tpu.utils.errors import MergeError
from uda_tpu.utils.ifile import RecordBatch
from uda_tpu.utils.metrics import metrics

pytestmark = pytest.mark.skipif(
    not (native.available() or native.build()),
    reason="native library not built and build failed")

COUNTER = "emit.gather.native_slabs"
COLUMNS = ("data", "key_off", "key_len", "val_off", "val_len")


def _segment(rng, records, max_key=24, max_val=120, gap=3):
    """One segment's batch with variable key and value lengths (zero
    included), records laid out with ``gap`` unaddressed bytes between
    them, framing-like."""
    k_len = rng.integers(0, max_key + 1, records).astype(np.int64)
    v_len = rng.integers(0, max_val + 1, records).astype(np.int64)
    k_off = np.cumsum(k_len + v_len + gap) - (k_len + v_len)
    data = rng.integers(0, 256, int((k_len + v_len + gap).sum()) + 2,
                        dtype=np.uint8)
    return RecordBatch(data, k_off.astype(np.int64), k_len,
                       (k_off + k_len).astype(np.int64), v_len)


def _terasort_segment(rng, records):
    ko = np.arange(records, dtype=np.int64) * 102 + 2
    return RecordBatch(rng.integers(0, 256, records * 102 + 2, dtype=np.uint8),
                       ko, np.full(records, 10, np.int64),
                       ko + 10, np.full(records, 90, np.int64))


def _slab(rng, batches, n, drawn_from=None):
    """A slab of the merged rows' shape: ``uint32[n, 5]`` whose columns 3
    and 4 are (segment, row). As in a merge of sorted runs, each
    segment's rows appear in ascending order."""
    sizes = np.asarray([b.num_records for b in batches])
    pool = np.flatnonzero(sizes) if drawn_from is None \
        else np.asarray(drawn_from)
    pairs = np.concatenate([np.stack([np.full(sizes[s], s), np.arange(sizes[s])], 1)
                            for s in pool])
    pick = np.sort(rng.choice(len(pairs), size=min(n, len(pairs)),
                              replace=False))
    pairs = pairs[pick][np.argsort(rng.random(len(pick)), kind="stable")]
    # restore ascending rows within each segment
    for s in np.unique(pairs[:, 0]):
        m = pairs[:, 0] == s
        pairs[m, 1] = np.sort(pairs[m, 1])
    rows = rng.integers(0, 1 << 32, (len(pairs), 5), dtype=np.uint32)
    rows[:, 3:] = pairs
    return rows


def _case(name):
    rng = np.random.default_rng(sum(name.encode()))
    if name == "1_segment":
        batches = [_segment(rng, 700)]
        return batches, _slab(rng, batches, 500)
    if name == "64_segments":
        batches = [_segment(rng, 40 + s) for s in range(64)]
        return batches, _slab(rng, batches, 3000)
    if name == "1024_segments":
        batches = [_segment(rng, 3 + s % 5) for s in range(1024)]
        return batches, _slab(rng, batches, 4000)
    if name == "zero_length_keys_and_values":
        batches = [_segment(rng, 300, max_key=1, max_val=1)
                   for _ in range(5)]
        batches.append(_segment(rng, 50, max_key=0, max_val=0))
        rows = _slab(rng, batches, 1200)
        sub = streaming.slab_batch(batches, rows[:, 3], rows[:, 4])
        assert (sub.key_len == 0).any() and (sub.val_len == 0).any()
        return batches, rows
    if name == "absent_and_empty_segments":
        # segments 1 and 4 hold no record at all; 2 and 6 none in the slab
        batches = [_segment(rng, 0 if s in (1, 4) else 90) for s in range(8)]
        return batches, _slab(rng, batches, 300, drawn_from=(0, 3, 5, 7))
    if name == "slab_from_one_segment_of_many":
        batches = [_segment(rng, 200) for _ in range(16)]
        return batches, _slab(rng, batches, 150, drawn_from=(9,))
    if name == "full_65536_record_slab":
        batches = [_terasort_segment(rng, 2100) for _ in range(32)]
        rows = _slab(rng, batches, streaming.SLAB_RECORDS)
        assert len(rows) == streaming.SLAB_RECORDS
        return batches, rows
    if name == "short_last_slab":
        batches = [_terasort_segment(rng, 64) for _ in range(4)]
        return batches, _slab(rng, batches, 7)
    if name == "noncontiguous_columns":
        # every second record of wider columns: the table must copy
        # them once, and read the copies
        wide = [_segment(rng, 120) for _ in range(6)]
        batches = [RecordBatch(b.data, b.key_off[::2], b.key_len[::2],
                               b.val_off[::2], b.val_len[::2]) for b in wide]
        assert not batches[0].key_off.flags["C_CONTIGUOUS"]
        return batches, _slab(rng, batches, 250)
    if name == "empty_slab":
        batches = [_segment(rng, 10) for _ in range(3)]
        return batches, np.zeros((0, 5), np.uint32)
    raise AssertionError(name)


CASES = ("1_segment", "64_segments", "1024_segments",
         "zero_length_keys_and_values", "absent_and_empty_segments",
         "slab_from_one_segment_of_many", "full_65536_record_slab",
         "short_last_slab", "noncontiguous_columns", "empty_slab")


def _columns(rows, form):
    if form == "strided_uint32":  # the slab's own columns, in place
        seg, row = rows[:, 3], rows[:, 4]
        assert seg.dtype == np.uint32 and (
            not len(rows) or seg.strides[0] == rows.shape[1] * 4)
        return seg, row
    return rows[:, 3].astype(np.int64), rows[:, 4].astype(np.int64)


def _assert_same(got, want):
    for name in COLUMNS:
        a, b = getattr(got, name), getattr(want, name)
        assert a.dtype == b.dtype, name
        assert np.array_equal(a, b), name
    assert native.frame_batch(got, write_eof=False) \
        == native.frame_batch(want, write_eof=False)


@pytest.mark.parametrize("form", ["strided_uint32", "int64"])
@pytest.mark.parametrize("case", CASES)
def test_native_slab_gather_matches_numpy_path(case, form):
    batches, rows = _case(case)
    seg, row = _columns(rows, form)
    want = streaming.slab_batch(batches, seg, row)
    assert metrics.get(COUNTER) == 0  # no table: the numpy path ran
    assert want.num_records == len(rows)
    table = streaming.segment_table(batches)
    assert table is not None and table.segments == len(batches)
    got = streaming.slab_batch(batches, seg, row, table)
    assert metrics.get(COUNTER) == 1
    _assert_same(got, want)
    # the compact layout: all keys, then all values, in slab order
    assert int(got.data.size) == int(got.key_len.sum() + got.val_len.sum())
    if len(rows):
        i = len(rows) // 2
        b = batches[int(rows[i, 3])]
        assert got.key(i) == b.key(int(rows[i, 4]))
        assert got.value(i) == b.value(int(rows[i, 4]))


def test_kill_switch_takes_numpy_path_same_bytes():
    batches, rows = _case("64_segments")
    table = streaming.segment_table(batches)
    native_sub = streaming.slab_batch(batches, rows[:, 3], rows[:, 4], table)
    assert metrics.get(COUNTER) == 1
    ifile.set_native_enabled(False)
    try:
        # live per call: a table built while native was on is not used
        sub = streaming.slab_batch(batches, rows[:, 3], rows[:, 4], table)
        assert streaming.segment_table(batches) is None
    finally:
        ifile.set_native_enabled(True)
    assert metrics.get(COUNTER) == 1
    _assert_same(sub, native_sub)


def test_library_unavailable_takes_numpy_path(monkeypatch):
    batches, rows = _case("short_last_slab")
    want = streaming.slab_batch(batches, rows[:, 3], rows[:, 4])
    table = streaming.segment_table(batches)
    with monkeypatch.context() as m:
        m.setattr(native, "_load", lambda: None)
        got = streaming.slab_batch(batches, rows[:, 3], rows[:, 4], table)
        m.setattr(streaming, "_native_built", False)
        assert streaming.segment_table(batches) is None
    assert metrics.get(COUNTER) == 0
    _assert_same(got, want)


def _corrupt(kind):
    rng = np.random.default_rng(5)
    batches = [_segment(rng, 30) for _ in range(4)]
    rows = _slab(rng, batches, 60)
    seg, row = rows[:, 3], rows[:, 4]
    hit = int(np.flatnonzero(seg == 2)[0])
    if kind == "segment_out_of_range":
        seg[41] = 4
    elif kind == "segment_far_out_of_range":
        seg[41] = 0xFFFFFFFF
    elif kind == "row_out_of_range":
        row[41] = batches[int(seg[41])].num_records
    elif kind == "row_in_empty_segment":
        batches[1] = _segment(rng, 0)
        seg[41], row[41] = 1, 0
    elif kind == "key_span_past_end_of_data":
        batches[2].key_off[int(row[hit])] = batches[2].data.size - 1
        batches[2].key_len[int(row[hit])] = 2
    elif kind == "value_span_past_end_of_data":
        batches[2].val_len[int(row[hit])] = batches[2].data.size
    elif kind == "negative_offset":
        batches[2].val_off[int(row[hit])] = -8
    elif kind == "negative_length":
        batches[2].key_len[int(row[hit])] = -1
    elif kind == "huge_offset":
        batches[2].key_off[int(row[hit])] = np.iinfo(np.int64).max
    elif kind == "negative_int64_segment":
        seg = seg.astype(np.int64)
        seg[41] = -1
    elif kind == "int64_row_beyond_uint32":
        row = row.astype(np.int64)
        row[41] = (1 << 32) + 1  # must not wrap to row 1
    else:
        raise AssertionError(kind)
    return batches, seg, row


@pytest.mark.parametrize("kind", [
    "segment_out_of_range", "segment_far_out_of_range", "row_out_of_range",
    "row_in_empty_segment", "key_span_past_end_of_data",
    "value_span_past_end_of_data", "negative_offset", "negative_length",
    "huge_offset", "negative_int64_segment", "int64_row_beyond_uint32"])
def test_bad_index_or_span_raises_merge_error(kind):
    batches, seg, row = _corrupt(kind)
    table = streaming.segment_table(batches)
    with pytest.raises(MergeError, match="slab gather"):
        streaming.slab_batch(batches, seg, row, table)
    assert metrics.get(COUNTER) == 0


def test_segment_table_rejects_ragged_columns_and_mismatched_slab():
    rng = np.random.default_rng(9)
    good = _segment(rng, 12)
    ragged = RecordBatch(good.data, good.key_off, good.key_len[:-1],
                         good.val_off, good.val_len)
    with pytest.raises(ValueError, match="ragged"):
        streaming.segment_table([good, ragged])
    table = streaming.segment_table([good])
    with pytest.raises(ValueError, match="disagree"):
        streaming.slab_batch([good], np.zeros(3, np.uint32),
                             np.zeros(2, np.uint32), table)
    with pytest.raises(ValueError, match="integer"):
        streaming.slab_batch([good], np.zeros(3, np.float32),
                             np.zeros(3, np.uint32), table)


def test_segment_table_keeps_its_arrays_alive():
    rng = np.random.default_rng(11)
    batches = [_segment(rng, 50) for _ in range(3)]
    rows = _slab(rng, batches, 100)
    want = streaming.slab_batch(batches, rows[:, 3], rows[:, 4])
    # the table's addresses must outlive the caller's references
    copies = [RecordBatch(*(getattr(b, c).copy() for c in COLUMNS))
              for b in batches]
    table = streaming.segment_table(copies)
    del copies
    junk = [np.full(1 << 16, 0xAB, np.uint8) for _ in range(8)]
    got = native.gather_slab_native(table, rows[:, 3], rows[:, 4])
    del junk
    _assert_same(got, want)


def _emit_stream_bytes(batches):
    om = OverlappedMerger(comparators.get_key_type("uda.tpu.RawBytes"), 16,
                          engine="host")
    for i, b in enumerate(batches):
        om.feed(i, b)
    out = io.BytesIO()
    om.emit_stream(batches, FramedEmitter(1 << 16),
                   lambda blk: out.write(bytes(blk)))
    return out.getvalue()


def test_emit_stream_counts_every_slab_native_and_none_with_switch_off():
    rng = np.random.default_rng(13)
    batches = []
    for s in range(5):  # sorted runs, 2 full slabs and a short one
        b = _terasort_segment(rng, 28000 + 100 * s)
        keys = b.data[b.key_off[:, None] + np.arange(10)]
        order = np.lexsort(keys.T[::-1])
        batches.append(b.take(order))
    total = sum(b.num_records for b in batches)
    slabs = -(-total // streaming.SLAB_RECORDS)
    assert slabs == 3
    metrics.enable_spans()
    try:
        got = _emit_stream_bytes(batches)
        spans = [s for s in metrics.spans if s["name"] == "emit_gather"]
    finally:
        metrics.disable_spans()
    assert len(spans) == slabs
    assert metrics.get(COUNTER) == slabs
    ifile.set_native_enabled(False)
    try:
        want = _emit_stream_bytes(batches)
    finally:
        ifile.set_native_enabled(True)
    assert metrics.get(COUNTER) == slabs  # the numpy path counted nothing
    assert got == want and len(got) > total * 100
