"""The compressed inverted-index generator writes ``invindex_mofs``'s
map outputs, byte for byte, as SnappyCodec block streams the program's
own codec module reads; and the plain reference
``host_sort_text_compressed`` inflates them on its own and gives the
stream ``host_sort_text`` gives for the uncompressed twin of the same
seed — the tie between ``invindex_text_compressed`` and
``invindex_text``."""

import os
import struct

import numpy as np
import pytest

from benchmark.gen import invindex_mofs as plain_gen
from benchmark.gen import invindex_mofs_compressed as gen
from benchmark.reference import host_sort_text as plain_ref
from benchmark.reference import host_sort_text_compressed as ref

JOB, SEED = "t", 4600000021
HEADER = struct.Struct(">II")


def _blocks(path: str) -> list:
    """``(raw length, compressed body)`` of every block of a file."""
    with open(path, "rb") as f:
        data = f.read()
    out, pos = [], 0
    while pos < len(data):
        raw_len, comp_len = HEADER.unpack_from(data, pos)
        pos += HEADER.size
        out.append((raw_len, data[pos:pos + comp_len]))
        assert len(out[-1][1]) == comp_len
        pos += comp_len
    return out


def test_the_block_cut_is_snappy_codecs():
    assert gen.CODEC_BUFFER == 262144
    assert gen.BLOCK_RAW_MAX == 262144 - (262144 // 6 + 32) == 218422


def test_a_map_is_invindex_mofs_map_in_blocks_the_program_inflates(tmp_path):
    from uda_tpu import compress
    from uda_tpu.mofserver import read_index_file

    codec = compress.get_codec("org.apache.hadoop.io.compress.SnappyCodec")
    # 25,000 records a map: ~500 KB, three blocks
    part = gen.generate(str(tmp_path), JOB, SEED, 75_000, 3)
    twin = plain_gen.generate(str(tmp_path / "plain"), JOB, SEED, 75_000, 3)
    assert part.map_ids == twin.map_ids
    assert (part.records, part.frame_bytes, part.file_bytes,
            part.payload_bytes) == (twin.records, twin.frame_bytes,
                                    twin.file_bytes, twin.payload_bytes)
    wire = blocks = 0
    for m, mid in enumerate(part.map_ids):
        mof = os.path.join(tmp_path, JOB, mid, "file.out")
        want = plain_gen.draw_map(SEED, m, 25_000).tobytes() + b"\xff\xff"
        with open(os.path.join(tmp_path, "plain", JOB, mid, "file.out"),
                  "rb") as f:
            assert f.read() == want
        cut = _blocks(mof)
        assert [n for n, _ in cut[:-1]] == [gen.BLOCK_RAW_MAX] * (len(cut) - 1)
        assert 0 < cut[-1][0] <= gen.BLOCK_RAW_MAX and len(cut) == 3
        # the program's own codec object and whole-stream reader agree
        assert b"".join(codec.decompress(body, n) for n, body in cut) == want
        with open(mof, "rb") as f:
            assert compress.decompress_block_stream(f.read(), codec) == want
        (rec,) = read_index_file(mof + ".index", mof)
        assert (rec.start_offset, rec.raw_length, rec.part_length) == (
            0, len(want), os.path.getsize(mof))
        assert rec.part_length < rec.raw_length
        wire += rec.part_length
        blocks += len(cut)
        assert ref.inflate_file(mof).tobytes() == want
    assert (part.wire_bytes, part.blocks) == (wire, blocks)


def test_the_reference_is_host_sort_texts_on_the_uncompressed_twin(tmp_path):
    part = gen.generate(str(tmp_path / "c"), JOB, SEED + 1, 60_000, 6)
    twin = plain_gen.generate(str(tmp_path / "p"), JOB, SEED + 1, 60_000, 6)
    got = ref.sorted_stream(str(tmp_path / "c"), JOB, part.map_ids)
    want = plain_ref.sorted_stream(str(tmp_path / "p"), JOB, twin.map_ids)
    assert np.array_equal(got.stream, want.stream)
    assert np.array_equal(got.starts, want.starts)
    assert got.stream.size == part.frame_bytes
    assert ref.compare is plain_ref.compare
    assert ref.ReferenceError is plain_ref.ReferenceError


def _corrupt_raw_length(data: bytearray) -> None:
    raw_len, comp_len = HEADER.unpack_from(data, 0)
    HEADER.pack_into(data, 0, raw_len - 1, comp_len)


def _corrupt_compressed_length(data: bytearray) -> None:
    raw_len, comp_len = HEADER.unpack_from(data, 0)
    HEADER.pack_into(data, 0, raw_len, comp_len - 1)


def _cut_the_last_block_short(data: bytearray) -> None:
    del data[-5:]


def _flip_a_body_byte(data: bytearray) -> None:
    data[HEADER.size] ^= 0x55        # Snappy's own length preamble


@pytest.mark.parametrize("damage", [
    _corrupt_raw_length, _corrupt_compressed_length,
    _cut_the_last_block_short, _flip_a_body_byte], ids=lambda f: f.__name__)
def test_the_reference_refuses_a_block_that_is_not_what_its_header_says(
        tmp_path, damage):
    part = gen.generate(str(tmp_path), JOB, SEED, 2000, 1)
    mof = os.path.join(tmp_path, JOB, part.map_ids[0], "file.out")
    assert ref.inflate_file(mof).size == part.file_bytes
    with open(mof, "rb") as f:
        data = bytearray(f.read())
    before = len(data)
    damage(data)
    with open(mof, "wb") as f:
        f.write(data)
    if len(data) != before:                 # keep the index honest
        with open(mof + ".index", "wb") as f:
            f.write(struct.pack(">qqq", 0, part.file_bytes, len(data)))
    with pytest.raises(ref.ReferenceError):
        ref.sorted_stream(str(tmp_path), JOB, part.map_ids)


def test_neither_imports_the_program():
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    code = ("import sys; import benchmark.gen.invindex_mofs_compressed, "
            "benchmark.reference.host_sort_text_compressed; "
            "print(sorted(m for m in sys.modules if m.split('.')[0] in "
            "('uda_tpu', 'jax')))")
    out = subprocess.run([sys.executable, "-c", code], cwd=root, text=True,
                         capture_output=True, timeout=120)
    assert out.returncode == 0 and out.stdout.strip() == "[]", out
