"""The generator writes the reference's map output format, readable by
the program's own readers, and the plain reference is a stable sort of
exactly those records."""

import os

import numpy as np

from benchmark.gen import terasort_mofs as gen
from benchmark.reference import host_sort

JOB, SEED, RECORDS, MAPS = "t", 9, 1003, 4


def _written(tmp_path):
    ids = gen.generate(str(tmp_path), JOB, SEED, RECORDS, MAPS)
    return ids, [gen.draw_map(SEED, m, gen.records_of_map(RECORDS, MAPS, m))
                 for m in range(MAPS)]


def test_split_is_as_even_as_whole_records_allow():
    sizes = [gen.records_of_map(RECORDS, MAPS, m) for m in range(MAPS)]
    assert sizes == [251, 251, 251, 250] and sum(sizes) == RECORDS
    big = [gen.records_of_map(10_500_000, 64, m) for m in range(64)]
    assert set(big) == {164062, 164063} and sum(big) == 10_500_000


def test_layout_and_program_readers(tmp_path):
    from uda_tpu.mofserver import read_index_file
    from uda_tpu.utils.ifile import IFileReader

    ids, drawn = _written(tmp_path)
    assert ids == [f"attempt_{JOB}_m_{m:06d}_0" for m in range(MAPS)]
    for mid, frames in zip(ids, drawn):
        mof = os.path.join(tmp_path, JOB, mid, "file.out")
        raw = open(mof, "rb").read()
        assert len(raw) == len(frames) * 102 + 2 and raw[-2:] == b"\xff\xff"
        (rec,) = read_index_file(mof + ".index", mof)
        assert (rec.start_offset, rec.raw_length, rec.part_length) == \
            (0, len(raw), len(raw))
        with open(mof, "rb") as f:
            got = list(IFileReader(f))
        want = [(fr[2:12].tobytes(), fr[12:].tobytes()) for fr in frames]
        assert got == want
        keys = [k for k, _ in got]
        assert keys == sorted(keys) and len(keys[0]) == 10
        assert all(len(v) == 90 for _, v in got)


def test_maps_do_not_depend_on_the_map_count():
    assert np.array_equal(gen.draw_map(SEED, 2, 100), gen.draw_map(SEED, 2, 100))
    assert not np.array_equal(gen.draw_map(SEED, 2, 100),
                              gen.draw_map(SEED + 1, 2, 100))


def test_reference_is_a_stable_sort_of_the_drawn_records(tmp_path):
    ids, drawn = _written(tmp_path)
    ref = host_sort.sorted_stream(str(tmp_path), JOB, ids).reshape(-1, 102)
    rows = [fr.tobytes() for frames in drawn for fr in frames]
    want = sorted(rows, key=lambda r: r[2:12])       # sorted() is stable
    assert [r.tobytes() for r in ref] == want
    stream = np.concatenate([ref.ravel(), np.frombuffer(b"\xff\xff", np.uint8)])
    assert host_sort.compare(stream, ref.ravel()) is None
    stream[102 * 7 + 50] ^= 1
    assert "record 7" in host_sort.compare(stream, ref.ravel())
    assert "expected" in host_sort.compare(stream[:-1], ref.ravel())


def test_ties_keep_map_order(tmp_path):
    frames = gen.draw_map(SEED, 0, 6)
    frames[:, 2:12] = frames[0, 2:12]                # one key everywhere
    for m, mid in enumerate(gen.map_ids(JOB, 2)):
        gen.write_map(str(tmp_path), JOB, mid, frames[3 * m:3 * m + 3])
    ref = host_sort.sorted_stream(str(tmp_path), JOB, gen.map_ids(JOB, 2))
    assert np.array_equal(ref.reshape(-1, 102), frames)
