"""The four-partition generator writes what the program's own readers
and a Hadoop spill index describe, and ``host_sort_parts`` is a stable
sort of exactly ONE partition's records."""

import os
import struct

import numpy as np
import pytest

from benchmark.gen import terasort_mofs_parts as gen
from benchmark.reference import host_sort_parts as ref

JOB, SEED, RECORDS, MAPS = "t", 2147483659, 1003, 4


def _drawn(m: int, p: int) -> np.ndarray:
    return gen.draw_part(SEED, m, p, gen.records_of_map(RECORDS, MAPS, m))


def test_index_triples_counts_and_program_readers(tmp_path):
    from uda_tpu.mofserver import read_index_file
    from uda_tpu.utils.ifile import IFileReader

    ids = gen.generate(str(tmp_path), JOB, SEED, RECORDS, MAPS)
    assert ids == gen.map_ids(JOB, MAPS) and gen.PARTITIONS == 4
    per_partition = [0] * gen.PARTITIONS
    for m, mid in enumerate(ids):
        mof = os.path.join(tmp_path, JOB, mid, "file.out")
        raw = open(mof, "rb").read()
        index = open(mof + ".index", "rb").read()
        triples = [struct.unpack(">qqq", index[i:i + 24])
                   for i in range(0, len(index), 24)]
        assert len(triples) == gen.PARTITIONS
        n = gen.records_of_map(RECORDS, MAPS, m)
        size = n * 102 + 2
        # back to back, each closed by its own EOF marker
        assert triples == [(p * size, size, size)
                           for p in range(gen.PARTITIONS)]
        assert len(raw) == gen.PARTITIONS * size
        recs = read_index_file(mof + ".index", mof)
        for p, rec in enumerate(recs):
            assert (rec.start_offset, rec.raw_length, rec.part_length) == \
                triples[p]
            part = raw[rec.start_offset:rec.start_offset + rec.raw_length]
            assert part[-2:] == b"\xff\xff"
            import io
            got = list(IFileReader(io.BytesIO(part)))
            frames = _drawn(m, p)
            assert got == [(fr[2:12].tobytes(), fr[12:].tobytes())
                           for fr in frames]
            keys = [k for k, _ in got]
            assert keys == sorted(keys)
            # a reduce task owns a key range: the p-th quarter
            assert all(k[0] >> 6 == p for k in keys)
            per_partition[p] += len(got)
    assert per_partition == [RECORDS] * gen.PARTITIONS    # exactly


def test_a_partition_depends_on_neither_the_map_count_nor_its_neighbours():
    a = gen.draw_part(SEED, 2, 1, 100)
    assert np.array_equal(a, gen.draw_part(SEED, 2, 1, 100))
    for other in (gen.draw_part(SEED + 1, 2, 1, 100),
                  gen.draw_part(SEED, 3, 1, 100),
                  gen.draw_part(SEED, 2, 0, 100)):
        assert not np.array_equal(a, other)


def test_a_maps_bytes_do_not_depend_on_the_map_count(tmp_path):
    few, many = tmp_path / "few", tmp_path / "many"
    n = 300
    gen.generate(str(few), JOB, SEED, 2 * n, 2)
    gen.generate(str(many), JOB, SEED, 5 * n, 5)
    mid = gen.map_ids(JOB, 5)[1]
    assert (few / JOB / mid / "file.out").read_bytes() == \
        (many / JOB / mid / "file.out").read_bytes()


def test_reference_on_a_hand_made_case(tmp_path):
    """Two maps, two partitions, six records written by hand: the
    reference of partition 1 is the stable sort of partition 1's four
    records — map order on the equal key — and holds none of partition
    0's."""
    def frame(key: bytes, tag: int) -> bytes:
        return bytes([10, 90]) + key.ljust(10, b"\0") + bytes([tag]) * 90

    k_low, k_mid, k_high = b"\x40a", b"\x40b", b"\x7fz"
    maps = {
        "m0": ([frame(b"\x01x", 1)], [frame(k_mid, 2), frame(k_high, 3)]),
        "m1": ([frame(b"\x02y", 4)], [frame(k_low, 5), frame(k_mid, 6)]),
    }
    for mid, parts in maps.items():
        d = tmp_path / JOB / mid
        d.mkdir(parents=True)
        blob, index, start = b"", b"", 0
        for frames in parts:
            part = b"".join(frames) + b"\xff\xff"
            blob += part
            index += struct.pack(">qqq", start, len(part), len(part))
            start += len(part)
        (d / "file.out").write_bytes(blob)
        (d / "file.out.index").write_bytes(index)
    got = ref.sorted_stream(str(tmp_path), JOB, ["m0", "m1"], 1)
    tags = got.reshape(-1, 102)[:, 12].tolist()
    assert tags == [5, 2, 6, 3]        # low, mid (m0 before m1), high
    assert ref.sorted_stream(str(tmp_path), JOB, ["m0", "m1"], 0) \
        .reshape(-1, 102)[:, 12].tolist() == [1, 4]

    stream = np.concatenate([got, np.frombuffer(b"\xff\xff", np.uint8)])
    assert ref.compare(stream, got) is None
    assert ref.compare_digest(stream, got.size, ref.digest(got)) is None
    crossed = stream.copy()
    crossed[102 * 2 + 50] ^= 1
    assert "record 2" in ref.compare(crossed, got)
    assert "digest" in ref.compare_digest(crossed, got.size, ref.digest(got))
    assert "expected" in ref.compare(stream[:-1], got)
    assert "expected" in ref.compare_digest(stream[:-1], got.size,
                                            ref.digest(got))
    with pytest.raises(ref.ReferenceError):
        ref.read_frames(str(tmp_path / JOB / "m0" / "file.out"), 2)


def test_reference_is_a_stable_sort_of_one_partitions_drawn_records(
        tmp_path):
    ids = gen.generate(str(tmp_path), JOB, SEED, RECORDS, MAPS)
    for p in (0, 3):
        got = ref.sorted_stream(str(tmp_path), JOB, ids, p).reshape(-1, 102)
        rows = [fr.tobytes() for m in range(MAPS) for fr in _drawn(m, p)]
        want = sorted(rows, key=lambda r: r[2:12])    # sorted() is stable
        assert [r.tobytes() for r in got] == want
