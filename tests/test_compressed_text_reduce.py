"""A Text-keyed reduce task whose map outputs are block-compressed (the
benchmark deployment ``invindex_text_compressed``): a MOFSupplier-role
and a NetMerger-role ``UdaBridge`` over loopback TCP, reference-layout
INIT with the codec class in parameter 7 and its buffer size in 8, so
the fetches go through ``uda_tpu/compress``'s ``DecompressingClient`` —
compressed bytes on the wire, the compressed sub-buffer
``mapred.rdma.compression.buffer.ratio`` of the buffer an inner fetch,
a partial block carried from one inner fetch to the next — held byte
for byte to a plain reference that inflates on its own (Python's
``zlib``, or the benchmark reference's ``libsnappy`` binding) and sorts
stably under the Text comparator (``host_sort_text``). Plus what the
compressed fetch path books: the ``fetch_inflate`` timer and span, the
``decompress.*`` counters."""

import ctypes
import os
import struct
import sys
import zlib

import numpy as np
import pytest

from uda_tpu import compress, native
from uda_tpu.bridge import UdaBridge
from uda_tpu.bridge.protocol import Cmd, form_cmd
from uda_tpu.mofserver import read_index_file
from uda_tpu.utils import critpath
from uda_tpu.utils.errors import CompressionError
from uda_tpu.utils.metrics import metrics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.reference import host_sort_text  # noqa: E402

JOB = "invidxc"
PLAIN = "invidxc_inflated"      # the reference's own inflated twin
TEXT = "org.apache.hadoop.io.Text"
HEADER = struct.Struct(">II")   # raw length, compressed length
CLASSES = {"zlib": "org.apache.hadoop.io.compress.DefaultCodec",
           "snappy": "org.apache.hadoop.io.compress.SnappyCodec"}
BUFFER = 1 << 16                # INIT's buffer: 13,107 B an inner fetch
COMP_CHUNK = int(BUFFER * 0.20)


def _codec_names():
    try:
        compress.get_codec("snappy")
    except CompressionError:
        return ["zlib"]
    return ["zlib", "snappy"]


CODECS = _codec_names()


@pytest.fixture(autouse=True)
def _native_on():
    assert native.build(), "the native library must build for these tests"


def _stream(m: int, words: list) -> bytes:
    """Map ``m``'s IFile stream: its records sorted under the Text
    comparator, stably (the posting says map and row), and the EOF
    marker."""
    rows = sorted(range(len(words)), key=lambda i: words[i])
    return b"".join(
        bytes([len(words[i]) + 1, 8, len(words[i])]) + words[i]
        + struct.pack(">II", m, i) for i in rows) + b"\xff\xff"


def _write_map(root: str, m: int, raw: bytes, cuts: list, codec) -> dict:
    """Write ``raw`` cut at ``cuts`` as a compressed map output with its
    index; returns what the counters must add up to."""
    bounds = [0] + list(cuts) + [len(raw)]
    blocks = []
    for lo, hi in zip(bounds, bounds[1:]):
        body = codec.compress(raw[lo:hi])
        blocks.append(HEADER.pack(hi - lo, len(body)) + body)
    data = b"".join(blocks)
    map_id = f"attempt_{JOB}_m_{m:06d}_0"
    d = os.path.join(root, JOB, map_id)
    os.makedirs(d)
    with open(os.path.join(d, "file.out"), "wb") as f:
        f.write(data)
    with open(os.path.join(d, "file.out.index"), "wb") as f:
        f.write(struct.pack(">qqq", 0, len(raw), len(data)))
    return {"map_id": map_id, "raw": len(raw), "part": len(data),
            "blocks": len(blocks), "ends": np.cumsum([len(b) for b in blocks])}


def _inflate(name: str, body: bytes, raw_len: int) -> bytes:
    """The reference's own inflate: nothing of ``uda_tpu``."""
    if name == "zlib":
        return zlib.decompress(body)
    from benchmark.reference.host_sort_text_compressed import _snappy

    out = np.empty(raw_len, np.uint8)
    size = ctypes.c_size_t(raw_len)
    assert _snappy().snappy_uncompress(
        body, len(body), out.ctypes.data, ctypes.byref(size)) == 0
    return out[:size.value].tobytes()


def _reference(root: str, name: str, ids: list) -> host_sort_text.Sorted:
    """Inflate every map output block by block, checking each header,
    and sort the inflated twin as the uncompressed job's reference
    does."""
    for map_id in ids:
        with open(os.path.join(root, JOB, map_id, "file.out"), "rb") as f:
            data = f.read()
        out, pos = [], 0
        while pos < len(data):
            raw_len, comp_len = HEADER.unpack_from(data, pos)
            pos += HEADER.size
            assert pos + comp_len <= len(data)
            out.append(_inflate(name, data[pos:pos + comp_len], raw_len))
            assert len(out[-1]) == raw_len
            pos += comp_len
        d = os.path.join(root, PLAIN, map_id)
        os.makedirs(d)
        with open(os.path.join(d, "file.out"), "wb") as f:
            f.write(b"".join(out))
    return host_sort_text.sorted_stream(root, PLAIN, ids)


class _Supplier:
    def __init__(self, root):
        self.root = root

    def get_path_uda(self, job_id, map_id, reduce_id):
        d = os.path.join(self.root, job_id, map_id)
        return read_index_file(os.path.join(d, "file.out.index"),
                               os.path.join(d, "file.out"))[reduce_id]


class _Reducer:
    def __init__(self, port: int):
        self.conf = {"uda.tpu.net.fetch": "true",
                     "uda.tpu.net.port": str(port)}
        self.blocks: list = []
        self.failure = None

    def get_conf_data(self, name, default):
        return self.conf.get(name, "")

    def data_from_uda(self, data, length):
        self.blocks.append(bytes(data[:length]))

    def failure_in_uda(self, error):
        self.failure = error


def _run_task(root: str, ids: list, name: str, buffer: int = BUFFER):
    """One reduce task over loopback; ``(callable, reducer bridge)``."""
    supplier = UdaBridge()
    supplier.start(False, [], _Supplier(root))
    supplier.cfg.set("uda.tpu.net.listen", True)
    supplier.cfg.set("uda.tpu.net.port", 0)
    supplier.do_command(form_cmd(Cmd.INIT, []))
    assert not supplier.failed
    try:
        cb = _Reducer(supplier.net_server().port)
        reducer = UdaBridge()
        reducer.start(True, [], cb)
        try:
            reducer.do_command(form_cmd(Cmd.INIT, [
                str(len(ids)), JOB, "0", "0", str(buffer), "16384", TEXT,
                CLASSES[name], "262144", str(1 << 30)]))
            for mid in ids:
                reducer.do_command(form_cmd(
                    Cmd.FETCH, ["127.0.0.1", JOB, mid, "0"]))
            reducer.do_command(form_cmd(Cmd.FINAL, []))
        finally:
            reducer.reduce_exit()
        reducer.do_command(form_cmd(Cmd.EXIT, []))
    finally:
        supplier.do_command(form_cmd(Cmd.EXIT, []))
    return cb, reducer


def _words(rng, n: int, lo: int = 5, hi: int = 13) -> list:
    """``n`` lowercase words from ``n // 2`` stems: words repeat within
    a map and across maps, and the letters are random, so a codec does
    not shrink the stream to nothing."""
    stems = [bytes(rng.integers(97, 123, rng.integers(lo, hi + 1),
                                dtype=np.uint8)) for _ in range(n // 2 + 20)]
    return [stems[i] for i in rng.integers(0, len(stems), n)]


def _even_cuts(raw: bytes, block: int) -> list:
    return list(range(block, len(raw), block))


def _cut_ending_on_the_boundary(raw: bytes, codec) -> int:
    """The cut ``n`` at which the first block, header included, is
    exactly one inner fetch."""
    lo, hi = COMP_CHUNK // 2, len(raw) - 1
    while lo < hi:                  # the first n that reaches the size
        mid = (lo + hi) // 2
        if HEADER.size + len(codec.compress(raw[:mid])) < COMP_CHUNK:
            lo = mid + 1
        else:
            hi = mid
    for n in range(max(lo - 400, 1), min(lo + 400, len(raw) - 1)):
        if HEADER.size + len(codec.compress(raw[:n])) == COMP_CHUNK:
            return n
    raise AssertionError("no cut compresses to exactly one inner fetch")


def _shape_one_fetch_segments(rng, codec):
    """Five small maps, two blocks each: every segment fits one inner
    fetch."""
    raws = [_stream(m, _words(rng, 300)) for m in range(5)]
    return raws, [_even_cuts(r, 4000) for r in raws]


def _shape_a_block_straddling_a_fetch_boundary(rng, codec):
    """Three maps of ~60 KB in 8,000-byte blocks: several inner fetches
    a segment, most of them ending inside a block."""
    raws = [_stream(m, _words(rng, 3000)) for m in range(3)]
    return raws, [_even_cuts(r, 8000) for r in raws]


def _shape_a_block_ending_exactly_on_a_boundary(rng, codec):
    """One map whose first block is exactly the first inner fetch: the
    second fetch starts on a block header with nothing carried."""
    raw = _stream(0, _words(rng, 8000))
    first = _cut_ending_on_the_boundary(raw, codec)
    return [raw], [[first] + [first + c for c in
                              _even_cuts(raw[first:], 8000)]]


def _shape_oversize_keys(rng, codec):
    stem = b"abcdefghijklmnop"
    maps = [_words(rng, 1200) for _ in range(3)]
    for i, tail in enumerate([b"zz", b"a", b"ab", b"b" * 30, b"a", b"",
                              b"za", b"ab"]):
        maps[i % 3].append(stem + b"x" + tail)
    maps[0] += [stem, stem + b"q"]
    raws = [_stream(m, words) for m, words in enumerate(maps)]
    return raws, [_even_cuts(r, 8000) for r in raws]


def _shape_an_empty_partition(rng, codec):
    """No map has a record: every stream is the EOF marker alone."""
    return [_stream(m, []) for m in range(3)], [[], [], []]


SHAPES = {
    "one_fetch_segments": _shape_one_fetch_segments,
    "a_block_straddling_a_fetch_boundary":
        _shape_a_block_straddling_a_fetch_boundary,
    "a_block_ending_exactly_on_a_boundary":
        _shape_a_block_ending_exactly_on_a_boundary,
    "oversize_keys": _shape_oversize_keys,
    "an_empty_partition": _shape_an_empty_partition,
}


def _write_shape(root: str, name: str, shape: str) -> list:
    codec = compress.get_codec(name)
    raws, cuts = SHAPES[shape](np.random.default_rng(46), codec)
    return [_write_map(root, m, raw, cut, codec)
            for m, (raw, cut) in enumerate(zip(raws, cuts))]


@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("name", CODECS)
def test_compressed_text_task_equals_the_plain_reference(tmp_path, name,
                                                         shape):
    root = str(tmp_path)
    maps = _write_shape(root, name, shape)
    ids = [m["map_id"] for m in maps]
    cb, reducer = _run_task(root, ids, name)
    assert cb.failure is None and not reducer.failed, cb.failure
    assert metrics.get("fallback.signals") == 0
    stream = np.frombuffer(b"".join(cb.blocks), np.uint8)
    ref = _reference(root, name, ids)
    assert host_sort_text.compare(stream, ref) is None
    assert metrics.get("merge.overflow.fallbacks") == 0
    assert metrics.get("merge.overflow.keys") == \
        (9 if shape == "oversize_keys" else 0)
    # every byte merged came off the wire compressed, and the books say
    # so: the index's two lengths and the writer's block count
    assert metrics.get("decompress.wire_bytes") == sum(
        m["part"] for m in maps)
    assert metrics.get("decompress.bytes") == sum(m["raw"] for m in maps)
    assert metrics.get("decompress.blocks") == sum(
        m["blocks"] for m in maps)
    assert metrics.get("fetch_inflate_time") > 0.0
    # inner fetches: the compressed sub-buffer's size each
    fetches = sum(-(-m["part"] // COMP_CHUNK) for m in maps)
    assert metrics.get("decompress.fetches") == fetches
    # what a fetch boundary cut off a block is carried forward
    carried = sum(int(at - m["ends"][m["ends"] <= at].max(initial=0))
                  for m in maps
                  for at in range(COMP_CHUNK, m["part"], COMP_CHUNK))
    assert metrics.get("decompress.carry_bytes") == carried
    if shape == "one_fetch_segments":
        assert fetches == len(maps) and carried == 0
    elif shape == "a_block_straddling_a_fetch_boundary":
        assert fetches > len(maps) and carried > 0
    elif shape == "a_block_ending_exactly_on_a_boundary":
        assert maps[0]["ends"][0] == COMP_CHUNK and fetches > 1


@pytest.mark.parametrize("field", ["raw_length", "compressed_length"])
@pytest.mark.parametrize("name", CODECS)
def test_a_corrupt_block_header_fails_the_task(tmp_path, name, field):
    """A header that lies — about the bytes the block inflates to, or
    about where the block ends — is the task's failure (the embedder is
    told, the vanilla shuffle takes over); never a stream that ends
    early or carries other bytes."""
    root = str(tmp_path)
    maps = _write_shape(root, name, "a_block_straddling_a_fetch_boundary")
    path = os.path.join(root, JOB, maps[1]["map_id"], "file.out")
    with open(path, "r+b") as f:
        at = int(maps[1]["ends"][1])        # the third block's header
        f.seek(at)
        raw_len, comp_len = HEADER.unpack(f.read(HEADER.size))
        f.seek(at)
        f.write(HEADER.pack(raw_len - 1, comp_len) if field == "raw_length"
                else HEADER.pack(raw_len, comp_len - 3))
    cb, reducer = _run_task(root, [m["map_id"] for m in maps], name)
    assert cb.failure is not None and reducer.failed
    want = sum(m["raw"] for m in maps) - 2 * (len(maps) - 1)
    assert sum(len(b) for b in cb.blocks) < want    # and no EOF marker
    assert not b"".join(cb.blocks).endswith(b"\xff\xff")


def test_spans_on_the_inflate_sits_under_its_segment_and_is_fetch(tmp_path):
    metrics.enable_spans()
    root = str(tmp_path)
    maps = _write_shape(root, "zlib", "a_block_straddling_a_fetch_boundary")
    cb, reducer = _run_task(root, [m["map_id"] for m in maps], "zlib")
    assert cb.failure is None and not reducer.failed, cb.failure
    spans = list(metrics.spans)
    root_span, = (s for s in spans if s["name"] == "reduce_task")
    segments = {s["id"] for s in spans if s["name"] == "fetch.segment"}
    inflates = [s for s in spans if s["name"] == "fetch_inflate"]
    # one span an inner fetch, each under the segment that issued it and
    # so inside the task's trace, where critpath sees it
    assert len(inflates) == metrics.get("decompress.fetches") > len(maps)
    assert {s["parent"] for s in inflates} <= segments
    assert {s["trace"] for s in inflates} == {root_span["trace"]}
    # the timer runs inside its span
    assert 0.0 < metrics.get("fetch_inflate_time") \
        <= sum(s["dur"] for s in inflates)
    assert critpath.SPAN_BUCKETS["fetch_inflate"] == "fetch"
    buckets = critpath.analyze(spans)["buckets"]
    assert buckets["fetch"]["busy_s"] >= sum(s["dur"] for s in inflates)
    assert buckets["other"]["busy_s"] == 0.0


def test_critpath_charges_an_inflate_outside_the_fetch_timer_to_fetch():
    def span(name, ts, dur, sid, parent=None):
        return {"name": name, "ts": ts, "dur": dur, "tid": 1, "trace": 7,
                "id": sid, "parent": parent}

    spans = [span("reduce_task", 0.0, 4.0, 1),
             span("fetch.segment", 0.0, 0.5, 2, parent=1),
             span("fetch_inflate", 1.0, 1.0, 3, parent=2),
             span("fetch_crack", 2.0, 1.0, 4, parent=2)]
    b = critpath.analyze(spans)["buckets"]
    assert b["fetch"]["critical_s"] == pytest.approx(2.5)
    assert b["other"]["critical_s"] == pytest.approx(0.0)


def test_an_uncompressed_task_books_no_inflate(tmp_path):
    """The counters are the compressed path's: a plain job's snapshot
    does not hold them, so a reader finds nothing rather than 0."""
    from uda_tpu.mofserver import DataEngine, DirIndexResolver
    from uda_tpu.merger import LocalFetchClient
    from uda_tpu.utils.config import Config

    engine = DataEngine(DirIndexResolver([str(tmp_path)]), Config())
    try:
        LocalFetchClient(engine)
        assert "fetch_inflate_time" not in metrics.snapshot()
        compress.DecompressingClient(LocalFetchClient(engine),
                                     compress.get_codec("zlib"))
        snap = metrics.snapshot()
        for key in ("fetch_inflate_time", "decompress.bytes",
                    "decompress.blocks", "decompress.wire_bytes",
                    "decompress.fetches", "decompress.carry_bytes"):
            assert snap[key] == 0.0, key
    finally:
        engine.stop()


@pytest.mark.parametrize("codec_class,key", [
    ("org.apache.hadoop.io.compress.SnappyCodec",
     "io.compression.codec.snappy.buffersize"),
    ("com.hadoop.compression.lzo.LzoCodec",
     "io.compression.codec.lzo.buffersize"),
    ("org.apache.hadoop.io.compress.DefaultCodec", None),
])
def test_init_stores_the_block_size_under_the_codecs_own_key(codec_class,
                                                             key):
    bridge = UdaBridge()
    bridge._init_reference_layout([
        "1", JOB, "0", "0", str(1 << 20), "16384", TEXT, codec_class,
        "131072", str(1 << 30)])
    assert bridge.cfg.get("mapred.map.output.compression.codec") \
        == codec_class
    for flag in ("io.compression.codec.snappy.buffersize",
                 "io.compression.codec.lzo.buffersize"):
        assert bridge.cfg.is_set(flag) == (flag == key)
    if key:
        assert bridge.cfg.get(key) == 131072
