"""The inverted-index generator writes Text-keyed map outputs the
program's own readers read, sorted under the Text comparator; its
vocabulary has the words the configuration promises; and the plain
reference ``host_sort_text`` is the comparator-faithful stable sort of
exactly those records."""

import os
import struct

import numpy as np
import pytest

from benchmark.gen import invindex_mofs as gen
from benchmark.reference import host_sort_text as ref

JOB, SEED = "t", 4100000021


def _records(path: str) -> list:
    from uda_tpu.utils.ifile import IFileReader

    with open(path, "rb") as f:
        return list(IFileReader(f))


def test_a_maps_bytes_do_not_depend_on_the_number_of_maps(tmp_path):
    a = gen.generate(str(tmp_path / "a"), JOB, SEED, 400, 4)
    b = gen.generate(str(tmp_path / "b"), JOB, SEED, 500, 5)
    for m in range(4):
        one, other = (open(os.path.join(tmp_path, d, JOB, p.map_ids[m],
                                        "file.out"), "rb").read()
                      for d, p in (("a", a), ("b", b)))
        assert one == other == gen.draw_map(SEED, m, 100).tobytes() + b"\xff\xff"
    assert not np.array_equal(gen.draw_map(SEED, 2, 100),
                              gen.draw_map(SEED + 1, 2, 100))
    assert (a.records, b.records) == (400, 500)
    assert a.file_bytes == a.frame_bytes + 2 * 4
    assert a.payload_bytes == a.frame_bytes - 2 * 400


def test_every_map_is_sorted_under_the_comparator_and_reads_back(tmp_path):
    from uda_tpu.mofserver import read_index_file
    from uda_tpu.utils import comparators

    kt = comparators.get_key_type("org.apache.hadoop.io.Text")
    part = gen.generate(str(tmp_path), JOB, SEED, 3000, 7)
    total = 0
    for m, mid in enumerate(part.map_ids):
        mof = os.path.join(tmp_path, JOB, mid, "file.out")
        (rec,) = read_index_file(mof + ".index", mof)
        size = os.path.getsize(mof)
        assert (rec.start_offset, rec.raw_length, rec.part_length) == \
            (0, size, size)
        got = _records(mof)
        total += sum(2 + len(k) + len(v) for k, v in got)
        words = [kt.content(k) for k, _ in got]
        assert all(k[0] == len(w) and 5 <= len(w) <= 48 and w.islower()
                   for (k, _), w in zip(got, words))
        assert words == sorted(words)             # bytes: memcmp, prefix first
        postings = [struct.unpack(">II", v) for _, v in got]
        assert all(doc >> 16 == m for doc, _ in postings)
        # equal words stay in the order of the text
        assert all(a < b for (wa, a), (wb, b) in
                   zip(zip(words, postings), zip(words[1:], postings[1:]))
                   if wa == wb)
        assert sorted((d & 0xFFFF) * gen.WORDS_PER_DOC + p
                      for d, p in postings) == list(range(len(got)))
    assert total == part.frame_bytes


def test_the_vocabulary_is_what_the_configuration_says():
    voc = gen.vocabulary()
    ids = np.arange(1, gen.K)
    share = np.log2(1 + 1 / ids) / gen.RANKS_LOG2        # P(id)
    lens = voc.lens[1:]
    assert (lens.min(), lens.max()) == (5, 48)
    assert 8 <= (share * lens).sum() <= 10               # mean content
    assert 0.0025 < share[lens > 16].sum() < 0.0035      # oversize records
    assert lens[:gen.LONG_FROM - 1].max() <= 13          # the hot end is short
    # a long block: one stem, six oversize terms, the stem, a short prefix
    first = int(np.flatnonzero(voc.lens > 16)[0]) & ~7
    terms = [bytes(voc.table[i, :voc.lens[i]]) for i in range(first, first + 8)]
    stem = terms[6]
    assert len(stem) == 16 and len(terms[3]) == 17
    assert all(t.startswith(stem) and len(t) > 16 for t in terms[:6])
    assert len({t[16:] for t in terms[:6]}) == 6          # differ after it
    assert terms[5].startswith(terms[4]) and len(terms[5]) > len(terms[4])
    assert stem.startswith(terms[7]) and 5 <= len(terms[7]) <= 12
    # the rank is the comparator's order, equal bytes equal rank
    some = np.random.default_rng(1).integers(1, gen.K, 2000)
    by_rank = sorted(some, key=lambda i: voc.rank[i])
    by_bytes = sorted(some, key=lambda i: bytes(voc.table[i, :voc.lens[i]]))
    assert [bytes(voc.table[i]) for i in by_rank] == \
        [bytes(voc.table[i]) for i in by_bytes]


def test_the_oversize_share_and_the_skew_at_the_cells_size_class():
    """64 of the cell's 1,024 maps: 1,024,000 records."""
    ids = np.concatenate([gen.draw_terms(SEED, m, 16000) for m in range(64)])
    voc = gen.vocabulary()
    over = (voc.lens[ids] > 16).mean()
    assert 0.002 < over < 0.005
    assert 0.045 < (ids == 1).mean() < 0.055             # the hottest term
    assert 8 <= voc.lens[ids].mean() <= 10
    assert ids.min() >= 1 and ids.max() < gen.K
    # every map of the cell meets the carried width
    assert all((voc.lens[gen.draw_terms(SEED, m, 16000)] > 16).any()
               for m in range(64))


@pytest.mark.parametrize("records,maps", ((300, 5), (7, 9), (640, 3)))
def test_the_reference_agrees_with_the_comparator_faithful_oracle(
        tmp_path, records, maps):
    from uda_tpu import native
    from uda_tpu.ops.merge import merge_batches_host
    from uda_tpu.utils import comparators
    from uda_tpu.utils.ifile import crack

    kt = comparators.get_key_type("org.apache.hadoop.io.Text")
    part = gen.generate(str(tmp_path), JOB, SEED, records, maps)
    batches = [crack(open(os.path.join(tmp_path, JOB, m, "file.out"),
                          "rb").read()) for m in part.map_ids]
    want = native.frame_batch(merge_batches_host(batches, kt),
                              write_eof=False)
    got = ref.sorted_stream(str(tmp_path), JOB, part.map_ids)
    assert got.stream.tobytes() == want
    assert got.starts.size == records and got.stream.size == part.frame_bytes
    stream = np.frombuffer(want + b"\xff\xff", np.uint8)
    assert ref.compare(stream, got) is None


def test_the_reference_refuses_frames_that_are_not_the_configurations(tmp_path):
    good = gen.draw_map(SEED, 0, 50)
    raw = np.append(good, np.frombuffer(b"\xff\xff", np.uint8))
    assert ref.frame_starts(raw).size == 50
    assert ref.frame_starts(raw[-2:]).size == 0            # an empty map
    with pytest.raises(ref.ReferenceError, match="EOF marker"):
        ref.frame_starts(raw[:-1])
    with pytest.raises(ref.ReferenceError, match="chain"):
        ref.frame_starts(np.delete(raw, raw.size - 3))    # a short last frame
    # a byte lost inside the first word: the chain finds its way back
    # (the next frame, read one byte late, is one byte short), the key
    # it misreads does not pass for a Text
    path = str(tmp_path / "file.out")
    np.delete(raw, 5).tofile(path)
    with pytest.raises(ref.ReferenceError, match="not VInt"):
        ref.read_records(path)
    wide = raw.copy()
    wide[1] = 0x8F                        # a value length of several bytes
    with pytest.raises(ref.ReferenceError, match="several bytes"):
        ref.frame_starts(wide)
