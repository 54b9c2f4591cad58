"""A Text-keyed reduce task (the benchmark deployment ``invindex_text``:
an inverted index's ``<word, posting>`` records, no combiner) through
bridge INIT / FETCH / FINAL with ``org.apache.hadoop.io.Text`` as the
key class and every flag at its default, held to the benchmark's plain
reference ``benchmark/reference/host_sort_text.py``: on the run forest
whether or not every word fits the carried width — the words that do
not are staged by their first 16 bytes and their equal-prefix blocks
re-ordered at emit (``merger/overlap.py``; counters
``merge.overflow.keys`` and ``merge.oversize.blocks``, timer
``oversize_fixup``) — and on the overflow fallback
(``merge.overflow.fallbacks``, timer ``overflow_resort``) only with a
run store, the streaming route."""

import os
import struct
import sys

import numpy as np
import pytest

from uda_tpu import native
from uda_tpu.bridge import UdaBridge
from uda_tpu.bridge.protocol import Cmd, form_cmd
from uda_tpu.mofserver import read_index_file
from uda_tpu.utils.metrics import metrics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.reference import host_sort_text  # noqa: E402

JOB = "invidx"
TEXT = "org.apache.hadoop.io.Text"
STEM = b"abcdefghijklmnop"                      # 16 bytes: the carried width


@pytest.fixture(autouse=True)
def _native_on():
    assert native.build(), "the native library must build for these tests"


def _write_maps(root: str, maps: list) -> list:
    """``maps``: a list of word lists, one a map. Writes each map's
    records — the posting says (map, row in the text) — sorted under the
    Text comparator, stably, in IFile framing with a spill index."""
    ids = []
    for m, words in enumerate(maps):
        rows = sorted(range(len(words)), key=lambda i: words[i])
        body = b"".join(
            bytes([len(words[i]) + 1, 8, len(words[i])]) + words[i]
            + struct.pack(">II", m, i) for i in rows) + b"\xff\xff"
        map_id = f"attempt_{JOB}_m_{m:06d}_0"
        d = os.path.join(root, JOB, map_id)
        os.makedirs(d)
        with open(os.path.join(d, "file.out"), "wb") as f:
            f.write(body)
        with open(os.path.join(d, "file.out.index"), "wb") as f:
            f.write(struct.pack(">qqq", 0, len(body), len(body)))
        ids.append(map_id)
    return ids


class _Supplier:
    def __init__(self, root):
        self.root = root

    def get_path_uda(self, job_id, map_id, reduce_id):
        d = os.path.join(self.root, job_id, map_id)
        return read_index_file(os.path.join(d, "file.out.index"),
                               os.path.join(d, "file.out"))[reduce_id]


class _Reducer:
    def __init__(self, port: int, conf: dict):
        self.conf = dict({"uda.tpu.net.fetch": "true",
                          "uda.tpu.net.port": str(port)}, **conf)
        self.blocks: list = []
        self.failure = None

    def get_conf_data(self, name, default):
        return self.conf.get(name, "")

    def data_from_uda(self, data, length):
        self.blocks.append(bytes(data[:length]))

    def failure_in_uda(self, error):
        self.failure = error


def _run_task(root: str, ids: list, conf: dict) -> np.ndarray:
    """One reduce task: a MOFSupplier bridge over loopback, a NetMerger
    bridge taking reference-layout INIT / FETCH / FINAL; the stream."""
    supplier = UdaBridge()
    supplier.start(False, [], _Supplier(root))
    supplier.cfg.set("uda.tpu.net.listen", True)
    supplier.cfg.set("uda.tpu.net.port", 0)
    supplier.do_command(form_cmd(Cmd.INIT, []))
    assert not supplier.failed
    try:
        cb = _Reducer(supplier.net_server().port, conf)
        reducer = UdaBridge()
        reducer.start(True, [], cb)
        try:
            reducer.do_command(form_cmd(Cmd.INIT, [
                str(len(ids)), JOB, "0", "0", str(1 << 20), "16384", TEXT,
                "0", "0", str(1 << 30)]))
            for mid in ids:
                reducer.do_command(form_cmd(
                    Cmd.FETCH, ["127.0.0.1", JOB, mid, "0"]))
            reducer.do_command(form_cmd(Cmd.FINAL, []))
        finally:
            reducer.reduce_exit()
        reducer.do_command(form_cmd(Cmd.EXIT, []))
    finally:
        supplier.do_command(form_cmd(Cmd.EXIT, []))
    assert cb.failure is None and not reducer.failed, cb.failure
    assert metrics.get("fallback.signals") == 0
    return np.frombuffer(b"".join(cb.blocks), np.uint8)


def _words(rng, n: int, lo: int = 5, hi: int = 13) -> list:
    """``n`` lowercase words of ``lo``-``hi`` letters from 40 stems, so
    that words repeat within a map and across maps."""
    stems = [bytes(rng.integers(97, 123, rng.integers(lo, hi + 1),
                                dtype=np.uint8)) for _ in range(40)]
    return [stems[i] for i in rng.integers(0, len(stems), n)]


def _maps_within_width(rng):
    return [_words(rng, 300) for _ in range(5)]


def _maps_one_oversize_in_the_last_map(rng):
    maps = [_words(rng, 200) for _ in range(4)]
    maps[-1].append(b"antidisestablishmentarianism")
    return maps


def _maps_oversize_sharing_their_first_16_bytes(rng):
    tails = [b"zz", b"a", b"ab", b"b" * 30, b"a", b"", b"za", b"ab"]
    maps = [_words(rng, 100) for _ in range(4)]
    for i, tail in enumerate(tails):          # several a map, repeats
        maps[i % 4].append(STEM + b"x" + tail)
    return maps


def _maps_exactly_16_and_17_bytes(rng):
    maps = [_words(rng, 50) for _ in range(3)]
    maps[0] += [STEM + b"q", STEM]
    maps[1] += [STEM, STEM[:15], STEM + b"a"]
    maps[2] += [STEM + b"q", STEM[:15] + b"q"]
    return maps


def _maps_a_beside_a_nul(rng):
    maps = [_words(rng, 50) for _ in range(3)]
    maps[0] += [b"a\x00", b"a"]
    maps[1] += [b"a", b"a\x00\x00", b"a\x00"]
    maps[2] += [b"a\x00b", b"a"]
    return maps


def _maps_a_term_that_is_a_prefix_of_another(rng):
    maps = [_words(rng, 50) for _ in range(3)]
    maps[0] += [b"stemmer", b"stem", STEM + b"long" + b"er"]
    maps[1] += [b"stem", b"stemmers", STEM + b"long"]
    maps[2] += [STEM + b"longest", b"stemm", STEM + b"long"]
    return maps


def _maps_thousands_of_equal_keys(rng):
    hot = [b"the", b"of", b"and"]
    return [[hot[i] for i in rng.integers(0, 3, 900)] for _ in range(6)]


def _maps_an_empty_map(rng):
    return [_words(rng, 120), [], _words(rng, 80), []]


# case -> (maps, oversize keys in the partition, equal-prefix blocks
# of two or more of them)
CASES = {
    "every_key_within_16_bytes": (_maps_within_width, 0, 0),
    "one_oversize_key_in_the_last_map": (
        _maps_one_oversize_in_the_last_map, 1, 0),
    "oversize_keys_sharing_their_first_16_bytes": (
        _maps_oversize_sharing_their_first_16_bytes, 8, 1),
    "content_of_exactly_16_and_of_17_bytes": (
        _maps_exactly_16_and_17_bytes, 3, 1),
    "a_beside_a_nul": (_maps_a_beside_a_nul, 0, 0),
    "a_term_that_is_a_prefix_of_another": (
        _maps_a_term_that_is_a_prefix_of_another, 4, 1),
    "thousands_of_equal_keys_across_maps": (
        _maps_thousands_of_equal_keys, 0, 0),
    "an_empty_map": (_maps_an_empty_map, 0, 0),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_text_task_through_the_bridge_equals_the_plain_reference(tmp_path,
                                                                 case):
    build, oversize, blocks = CASES[case]
    maps = build(np.random.default_rng(41))
    ids = _write_maps(str(tmp_path), maps)
    assert sum(len(w) > 16 for words in maps for w in words) == oversize
    stream = _run_task(str(tmp_path), ids, {})
    ref = host_sort_text.sorted_stream(str(tmp_path), JOB, ids)
    assert ref.starts.size == sum(len(words) for words in maps)
    assert host_sort_text.compare(stream, ref) is None
    # the route: the forest, oversize words or not — every segment
    # staged by the native pass, no fallback, the keys counted as they
    # are staged and their blocks as the emit re-orders them
    assert metrics.get("merge.overflow.fallbacks") == 0
    assert metrics.get("merge.overflow.keys") == oversize
    assert metrics.get("merge.oversize.blocks") == blocks
    assert metrics.get("merge.records") == ref.starts.size
    assert metrics.get("stage.native_segments") == sum(
        1 for words in maps if words)
    counters = metrics.snapshot()
    assert counters["overflow_resort_time"] == 0
    assert counters["overflow_rank_time"] == 0
    assert "overflow_concat_time" not in counters
    if oversize:
        assert counters["oversize_fixup_time"] > 0
    else:
        assert counters["oversize_fixup_time"] == 0


def test_equal_keys_keep_map_order_then_row_order(tmp_path):
    """What the reference's stability means, spelled out on the stream:
    among equal words the postings come out by map, then by row."""
    maps = _maps_thousands_of_equal_keys(np.random.default_rng(7))
    ids = _write_maps(str(tmp_path), maps)
    stream = _run_task(str(tmp_path), ids, {}).tobytes()
    seen, at = {}, 0
    while stream[at:at + 2] != b"\xff\xff":
        n = stream[at + 2]
        word = stream[at + 3:at + 3 + n]
        posting = struct.unpack(">II", stream[at + 3 + n:at + 11 + n])
        assert seen.get(word, (-1, -1)) < posting
        seen[word] = posting
        at += 11 + n
    assert at == len(stream) - 2 and sorted(seen) == [b"and", b"of", b"the"]


def test_the_streaming_route_with_oversize_keys(tmp_path):
    """With a run store the fallback is the k-way merge over run files
    ordered by the full comparator; it is counted as a fallback too."""
    maps = _maps_a_term_that_is_a_prefix_of_another(np.random.default_rng(3))
    maps += _maps_oversize_sharing_their_first_16_bytes(
        np.random.default_rng(4))
    ids = _write_maps(str(tmp_path), maps)
    stream = _run_task(str(tmp_path), ids,
                       {"uda.tpu.online.streaming": "true"})
    ref = host_sort_text.sorted_stream(str(tmp_path), JOB, ids)
    assert host_sort_text.compare(stream, ref) is None
    assert metrics.get("merge.overflow.fallbacks") == 1
    assert metrics.get("spool.bytes") > 0          # the route was taken
    assert metrics.snapshot()["overflow_resort_time"] == 0


def test_compare_names_the_first_differing_byte_and_its_record(tmp_path):
    ids = _write_maps(str(tmp_path), [[b"gamma", b"alpha"], [b"beta" * 5]])
    ref = host_sort_text.sorted_stream(str(tmp_path), JOB, ids)
    good = np.concatenate([ref.stream, np.frombuffer(b"\xff\xff", np.uint8)])
    assert host_sort_text.compare(good, ref) is None
    assert "bytes emitted" in host_sort_text.compare(good[:-1], ref)
    bad = good.copy()
    bad[int(ref.starts[1]) + 4] ^= 1
    assert "record 1" in host_sort_text.compare(bad, ref)
    assert "beta" in host_sort_text.compare(bad, ref)
    bad = good.copy()
    bad[-1] = 0
    assert "EOF marker" in host_sort_text.compare(bad, ref)
