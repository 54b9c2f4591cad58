"""A segment is staged by ONE native pass (``native.stage_segment_native``
behind ``ops.merge.stage_run_rows``): key gather, big-endian words, the
composite-key row fill, the (words, len) order check and — for a segment
that is not presorted — the stable sort, where the numpy path takes
``pack_keys`` + ``run_row_order`` + ``fill_run_rows``. The numpy path is
the reference: same rows byte for byte — a key longer than the width
gets its row too, on both paths — same longest key, same byte sum, for
every key type, order, size and key length; it is what runs
when ``uda.tpu.use.native`` is off or the library is absent. A task
staged natively counts each segment in ``stage.native_segments`` and
emits the numpy-path task's stream; every exit sends the row lease home."""

import io
import struct
import time

import numpy as np
import pytest

from tests.helpers import emit_stream_bytes
from uda_tpu import native
from uda_tpu.merger.emitter import FramedEmitter
from uda_tpu.merger.overlap import OverlappedMerger
from uda_tpu.merger.streaming import RunStore
from uda_tpu.ops import merge as merge_ops
from uda_tpu.ops import packing
from uda_tpu.utils import comparators, vint
from uda_tpu.utils.errors import MergeError
from uda_tpu.utils.ifile import crack, set_native_enabled, write_records
from uda_tpu.utils.metrics import metrics
from uda_tpu.utils.resledger import resledger

RAW = comparators.get_key_type("uda.tpu.RawBytes")
KEY_TYPES = {
    "raw": RAW,
    "text": comparators.get_key_type("org.apache.hadoop.io.Text"),
    "bytes": comparators.get_key_type("org.apache.hadoop.io.BytesWritable"),
    "int_numeric": comparators.get_key_type("uda.tpu.IntNumeric"),
    "long_numeric": comparators.get_key_type("uda.tpu.LongNumeric"),
}
NUMERIC_BYTES = {"int_numeric": 4, "long_numeric": 8}
WIDTH = 8   # of the variable-length key types; a numeric type's follows
            # from its fixed key length and the relation under test
SEG = 37


@pytest.fixture(autouse=True)
def _native_built():
    assert native.build(), "the native library must build for these tests"
    yield
    set_native_enabled(True)


def _serialize(kind: str, content: bytes) -> bytes:
    if kind == "text":
        return vint.encode_vlong(len(content)) + content
    if kind == "bytes":
        return struct.pack(">i", len(content)) + content
    return content


def _width(kind: str, relation: str) -> int:
    fixed = NUMERIC_BYTES.get(kind)
    if fixed is None:
        return WIDTH
    return fixed + {"shorter": 4, "equal": 0, "longer": -4}[relation]


def _contents(kind: str, relation: str, order: str, n: int,
              seed: int) -> list:
    """n key contents of lengths shorter than / equal to / (some) longer
    than the width, arriving presorted, unsorted, or as a few distinct
    keys repeated (stability decides their order)."""
    rng = np.random.default_rng(seed)
    width = _width(kind, relation)

    def one() -> bytes:
        if kind in NUMERIC_BYTES:
            return rng.bytes(NUMERIC_BYTES[kind])
        if relation == "equal":
            return rng.bytes(width)
        top = width - 1 if relation == "shorter" else width + 4
        # few distinct byte values: prefixes collide, lengths decide
        return bytes(rng.integers(0, 3, int(rng.integers(0, top + 1)),
                                  dtype=np.uint8))

    if order == "equal":
        pool = [one() for _ in range(3)]
        contents = [pool[int(i)] for i in rng.integers(0, 3, n)]
    else:
        contents = [one() for _ in range(n)]
    if relation == "longer" and n and kind not in NUMERIC_BYTES:
        contents[n // 2] = b"\x01" * (width + 3)   # at least one overflows
    if order == "presorted":
        kt = KEY_TYPES[kind]
        contents.sort(key=lambda c: kt.content(_serialize(kind, c)))
    return contents


def _batch(kind: str, contents, seed: int = 0):
    rng = np.random.default_rng(seed)
    return crack(write_records(
        [(_serialize(kind, c), rng.bytes(int(rng.integers(0, 9))))
         for c in contents]))


def _numpy_rows(batch, kt, width: int, cap: int):
    """The reference: the three numpy passes, into a fresh matrix."""
    packed = packing.pack_keys(batch, kt, width, ranks=False)
    longest = int(np.max(packed.key_lens, initial=0))
    nbytes = int(batch.key_len.sum() + batch.val_len.sum())
    order = merge_ops.run_row_order(packed)
    rows = np.zeros((cap, width // 4 + merge_ops.ROW_EXTRA_COLS), np.uint32)
    merge_ops.fill_run_rows(rows, packed, order, SEG)
    return rows, (order is None, longest, nbytes)


CASES = [(kind, relation) for kind in KEY_TYPES
         for relation in ("shorter", "equal", "longer")
         if _width(kind, relation) > 0]


@pytest.mark.parametrize("n, cap", ((0, 4), (1, 1), (1280, 1280),
                                    (1000, 1024)),
                         ids=("n0", "n1", "n1280", "n1000_padded"))
@pytest.mark.parametrize("order", ("presorted", "unsorted", "equal"))
@pytest.mark.parametrize("kind, relation", CASES,
                         ids=[f"{k}-{r}" for k, r in CASES])
def test_native_rows_equal_the_numpy_rows(kind, relation, order, n, cap):
    kt, width = KEY_TYPES[kind], _width(kind, relation)
    batch = _batch(kind, _contents(kind, relation, order, n, seed=n + cap))
    want_rows, want = _numpy_rows(batch, kt, width, cap)
    rows = np.full((cap, width // 4 + merge_ops.ROW_EXTRA_COLS), 0x5A5A5A5A,
                   np.uint32)
    got = merge_ops.stage_run_rows(rows, batch, kt, width, SEG)
    assert metrics.get("stage.native_segments") == 1
    assert got == want
    assert rows.tobytes() == want_rows.tobytes()
    if relation == "longer" and n and kind not in NUMERIC_BYTES:
        # an oversize key's row: its first ``width`` bytes, its whole
        # length; the caller counts such keys, staging does not
        kw = width // 4
        assert got[1] == int(rows[:n, kw].max()) > width
        assert metrics.get("merge.overflow.keys") == 0
    elif order == "presorted" or n < 2:
        assert got[0]


@pytest.mark.parametrize("use_native", (True, False), ids=("native", "numpy"))
def test_an_oversize_segment_is_staged_in_words_length_row_order(use_native):
    """Inside a block of oversize keys with equal words the staged
    order is (length, row) — not the comparator's, which the emit
    restores — and both paths fill the same rows."""
    set_native_enabled(use_native)
    stem = b"s" * WIDTH
    contents = [b"a", stem + b"ab", stem + b"b" * 9, stem + b"za", stem,
                stem + b"ab"]                 # comparator-sorted but for...
    contents.sort()
    batch = _batch("raw", contents)
    rows = np.empty((8, WIDTH // 4 + 3), np.uint32)
    presorted, longest, _ = merge_ops.stage_run_rows(rows, batch, RAW, WIDTH,
                                                     SEG)
    assert not presorted and longest == WIDTH + 9
    kw = WIDTH // 4
    # a, the stem (within the width), then the block by (length, row)
    assert rows[:6, kw].tolist() == [1, WIDTH, WIDTH + 2, WIDTH + 2,
                                     WIDTH + 2, WIDTH + 9]
    assert rows[:6, kw + 2].tolist() == [0, 1, 2, 3, 5, 4]
    assert (rows[1:6, :kw] == rows[1, :kw]).all()
    assert (rows[6:] == merge_ops.PAD_WORD).all()
    assert metrics.get("stage.native_segments") == int(use_native)


@pytest.mark.parametrize("use_native", (True, False), ids=("native", "numpy"))
@pytest.mark.parametrize("kind, key, message", (
    ("text", b"", "empty serialized Text key"),
    ("bytes", b"\x00\x00", "shorter than its length field")))
def test_a_malformed_key_raises_on_both_paths(kind, key, message, use_native):
    batch = crack(write_records([(b"\x01a" if kind == "text" else
                                  b"\x00\x00\x00\x01a", b"v"), (key, b"v")]))
    set_native_enabled(use_native)
    rows = np.empty((2, WIDTH // 4 + 3), np.uint32)
    with pytest.raises(MergeError, match=message):
        merge_ops.stage_run_rows(rows, batch, KEY_TYPES[kind], WIDTH, SEG)


def test_a_key_span_outside_the_data_raises_and_reads_nothing():
    batch = _batch("raw", [b"abcd", b"efgh"])
    batch.key_off[1] = batch.data.size - 2      # 4 bytes from 2 before the end
    rows = np.empty((2, WIDTH // 4 + 3), np.uint32)
    with pytest.raises(MergeError, match="outside the segment's data.*"
                                         "record 1"):
        native.stage_segment_native(batch, RAW, WIDTH, SEG, rows)


@pytest.mark.parametrize("rows", (
    np.empty((4, WIDTH // 4 + 3), np.int32),
    np.empty((4, WIDTH // 4 + 2), np.uint32),
    np.empty((2, WIDTH // 4 + 3), np.uint32),
    np.empty((8, WIDTH // 4 + 3), np.uint32)[::2]),
    ids=("dtype", "columns", "too_few_rows", "strided"))
def test_a_row_matrix_of_the_wrong_layout_is_refused(rows):
    batch = _batch("raw", [b"a", b"b", b"c"])
    with pytest.raises(ValueError, match="row matrix"):
        native.stage_segment_native(batch, RAW, WIDTH, SEG, rows)


@pytest.mark.parametrize("width", (0, 6, -4))
def test_a_bad_width_raises_as_pack_keys_does(width):
    batch = _batch("raw", [b"a"])
    with pytest.raises(MergeError, match="positive multiple of 4"):
        native.stage_segment_native(batch, RAW, width, SEG,
                                    np.empty((1, 5), np.uint32))


# -- the fallback --------------------------------------------------------------

@pytest.mark.parametrize("why", ("switched_off", "library_absent"))
@pytest.mark.parametrize("order", ("presorted", "unsorted"))
def test_the_numpy_path_runs_when_native_is_off_or_absent(monkeypatch, why,
                                                          order):
    if why == "switched_off":
        set_native_enabled(False)       # uda.tpu.use.native = false
    else:
        monkeypatch.setattr(native, "_load", lambda: None)
    batch = _batch("raw", _contents("raw", "shorter", order, 300, seed=5))
    want_rows, want = _numpy_rows(batch, RAW, WIDTH, 320)
    rows = np.empty((320, WIDTH // 4 + 3), np.uint32)
    assert merge_ops.stage_run_rows(rows, batch, RAW, WIDTH, SEG) == want
    assert rows.tobytes() == want_rows.tobytes()
    assert metrics.get("stage.native_segments") == 0


# -- whole tasks ----------------------------------------------------------------

def _task_bytes(batches, kt, store=None, numpy_path=False,
                monkeypatch=None) -> bytes:
    """One host-engine pipelined task over ``batches``: its framed
    stream. ``numpy_path`` takes the native pass away and nothing else
    (the native merge, gather and framer stay)."""
    if numpy_path:
        monkeypatch.setattr(native, "stage_segment_native",
                            lambda *a, **k: None)
    om = OverlappedMerger(kt, WIDTH, engine="host", run_store=store,
                          stagers=4, inflight_bytes=8 << 20)
    for i in np.random.default_rng(1).permutation(len(batches)):
        om.feed(int(i), batches[int(i)])
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


def _oracle_bytes(batches, kt) -> bytes:
    out = io.BytesIO()
    FramedEmitter(1 << 14).emit_batch(
        merge_ops.merge_batches_host(batches, kt),
        lambda blk: out.write(bytes(blk)))
    return out.getvalue()


@pytest.mark.parametrize("streaming", (False, True),
                         ids=("in_memory", "streaming"))
@pytest.mark.parametrize("order", ("presorted", "unsorted", "equal"))
@pytest.mark.parametrize("kind", ("raw", "text"))
def test_a_task_of_many_small_segments_stages_every_one_natively(
        kind, order, streaming, tmp_path, monkeypatch):
    segments = 120
    batches = [_batch(kind, _contents(kind, "shorter", order, 5 + i % 40,
                                      seed=100 + i), seed=i)
               for i in range(segments)]
    batches[17] = _batch(kind, [])      # an empty segment is not staged

    def store(tag):
        return (RunStore([str(tmp_path)], tag=tag) if streaming else None)

    kt = KEY_TYPES[kind]
    got = _task_bytes(batches, kt, store("native"))
    assert metrics.get("stage.native_segments") == segments - 1
    assert metrics.get("merge.records") == sum(b.num_records for b in batches)
    staged_bytes = metrics.get("stage.bytes")
    metrics.reset()
    want = _task_bytes(batches, kt, store("numpy"), numpy_path=True,
                       monkeypatch=monkeypatch)
    assert metrics.get("stage.native_segments") == 0
    assert metrics.get("stage.bytes") == staged_bytes
    assert got == want == _oracle_bytes(batches, kt)


def test_a_task_that_fell_back_reads_zero_not_nothing():
    set_native_enabled(False)
    om = OverlappedMerger(RAW, WIDTH, engine="host")
    batch = _batch("raw", [b"a", b"b"])
    om.feed(0, batch)
    assert emit_stream_bytes(om, [batch]) == _oracle_bytes([batch], RAW)
    assert "stage.native_segments" in metrics.snapshot()
    assert metrics.get("stage.native_segments") == 0


@pytest.mark.parametrize("streaming", (False, True),
                         ids=("in_memory", "streaming"))
def test_a_task_with_oversize_keys_emits_what_the_numpy_path_does(
        streaming, tmp_path, monkeypatch):
    """In memory the oversize segment's rows go to the forest on both
    paths; with a run store both latch the k-way fallback."""
    batches = [_batch("raw", _contents("raw", "shorter", "presorted", 30,
                                       seed=i)) for i in range(6)]
    batches[3] = _batch("raw", _contents("raw", "longer", "unsorted", 30,
                                         seed=9))

    def store(tag):
        return (RunStore([str(tmp_path)], tag=tag) if streaming else None)

    got = _task_bytes(batches, RAW, store("native"))
    counted = [metrics.get("merge.overflow." + c)
               for c in ("keys", "fallbacks")]
    assert counted[0] > 0 and counted[1] == int(streaming)
    metrics.reset()
    want = _task_bytes(batches, RAW, store("numpy"), numpy_path=True,
                       monkeypatch=monkeypatch)
    assert got == want == _oracle_bytes(batches, RAW)
    assert counted == [metrics.get("merge.overflow." + c)
                       for c in ("keys", "fallbacks")]


# -- every exit returns the row lease --------------------------------------------

def _pooled_merger(monkeypatch, **kwargs) -> OverlappedMerger:
    monkeypatch.setattr(resledger, "enabled", True)
    monkeypatch.setattr(resledger, "leak_reports", [])
    om = OverlappedMerger(RAW, WIDTH, engine="host", stagers=3,
                          inflight_bytes=8 << 20, **kwargs)
    assert om._buf_pool is not None
    return om


def _books_whole(om: OverlappedMerger) -> None:
    for t in om._threads:
        t.join(timeout=10)
        assert not t.is_alive()
    assert resledger.outstanding(("pool.lease",), owner=id(om._buf_pool)) == []
    assert resledger.leak_reports == []
    assert om._inflight == 0
    assert metrics.get_gauge("stage.inflight.bytes") == 0


def test_a_raising_native_pass_releases_its_lease(monkeypatch):
    om = _pooled_merger(monkeypatch)
    real = native.stage_segment_native

    def breaks(batch, kt, width, seg_index, rows):
        if seg_index == 4:
            raise MergeError("the native pass broke")
        return real(batch, kt, width, seg_index, rows)

    monkeypatch.setattr(native, "stage_segment_native", breaks)
    batches = [_batch("raw", _contents("raw", "shorter", "presorted", 20 + i,
                                       seed=i)) for i in range(9)]
    for i, b in enumerate(batches):
        om.feed(i, b)
    with pytest.raises(MergeError, match="native pass broke"):
        emit_stream_bytes(om, batches)
    _books_whole(om)


@pytest.mark.parametrize("how", ("overflow", "streaming_overflow",
                                 "spool_raises"))
def test_rows_the_forest_does_not_take_go_back_to_the_pool(monkeypatch, how,
                                                           tmp_path):
    """Every lease goes home: an in-memory task whose keys overflow keeps
    its rows on the forest (the leases return as runs merge away and at
    the finish; the pool is whole after the emit); a streaming task
    latches the k-way merge over its run files at the first oversize
    key and every later segment only spools, and a failing spool hands
    its rows back from staging too."""
    store = None
    if how != "overflow":
        store = RunStore([str(tmp_path)], tag=how)
    om = _pooled_merger(monkeypatch, run_store=store)
    if how == "spool_raises":
        def full(*a, **k):
            raise MergeError("the spool disk is full")
        monkeypatch.setattr(store, "write_run", full)
    # streaming_overflow: the first segment alone has keys longer than
    # the width, and is staged before the rest are fed
    relations = ["longer" if how == "overflow"
                 or (how == "streaming_overflow" and i == 0) else "shorter"
                 for i in range(5)]
    batches = [_batch("raw", _contents("raw", relations[i], "unsorted", 25,
                                       seed=i)) for i in range(5)]
    for i, b in enumerate(batches):
        om.feed(i, b)
        if how == "streaming_overflow" and i == 0:
            deadline = time.monotonic() + 10
            while not om.stats["staged_runs"]:
                assert time.monotonic() < deadline
                time.sleep(0.005)
            assert om.stats["overflow"]
    out = io.BytesIO()
    if how == "overflow":
        assert emit_stream_bytes(om, batches) == _oracle_bytes(batches, RAW)
        assert om.stats["oversize"] and not om.stats["overflow"]
        assert om.stats["staged_runs"] == 5 and om.stats["device_merges"] == 4
        assert metrics.get("merge.overflow.fallbacks") == 0
    elif how == "streaming_overflow":
        om.finish_streaming(FramedEmitter(1 << 14),
                            lambda blk: out.write(bytes(blk)),
                            expected_records=125)
        assert out.getvalue() == _oracle_bytes(batches, RAW)
        # spooled, every one; nothing reached the forest
        assert om.stats["staged_runs"] == 5 and om.stats["device_merges"] == 0
        assert metrics.get("merge.overflow.fallbacks") == 1
    else:
        with pytest.raises(MergeError, match="disk is full"):
            om.finish_streaming(FramedEmitter(1 << 14),
                                lambda blk: out.write(bytes(blk)))
    _books_whole(om)


def test_adopt_run_stages_through_the_same_pass(monkeypatch):
    om = _pooled_merger(monkeypatch)
    batches = [_batch("raw", _contents("raw", "shorter", "presorted", 20 + i,
                                       seed=i)) for i in range(4)]
    for i in (0, 1):
        om.adopt_run(i, batches[i])
    for i in (2, 3):
        om.feed(i, batches[i])
    assert emit_stream_bytes(om, batches) == _oracle_bytes(batches, RAW)
    assert metrics.get("stage.native_segments") == 4
    _books_whole(om)
