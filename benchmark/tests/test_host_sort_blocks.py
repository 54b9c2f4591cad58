"""``reference/host_sort_blocks.py`` is ``host_sort.py`` computed in
blocks: the same size and the same digest on the same map outputs,
whatever the block, and a stream that differs anywhere is refused."""

import numpy as np
import pytest

from benchmark.gen import terasort_mofs
from benchmark.reference import host_sort, host_sort_blocks
from benchmark.reference.host_sort_parts import digest


@pytest.fixture(scope="module")
def maps(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("mofs"))
    ids = terasort_mofs.generate(root, "bench", 3000000007, 5000, 7)
    return root, ids


@pytest.mark.parametrize("block_records", (1, 333, 5000, 1 << 20))
def test_size_and_digest_are_the_plain_reference_s(maps, block_records):
    root, ids = maps
    plain = host_sort.sorted_stream(root, "bench", ids)
    assert host_sort_blocks.sorted_digest(root, "bench", ids,
                                          block_records) \
        == (plain.size, digest(plain))


def test_equal_keys_keep_map_then_row_order(tmp_path):
    """Two maps holding the same keys: the block gather must not
    reorder what the stable argsort decided."""
    root = str(tmp_path)
    frames = terasort_mofs.draw_map(5, 0, 400)
    frames[:, 2:12] = frames[::4, 2:12].repeat(4, axis=0)   # 4 rows a key
    ids = terasort_mofs.map_ids("bench", 2)
    twin = frames.copy()
    twin[:, 12:] ^= 0xFF
    terasort_mofs.write_map(root, "bench", ids[0], frames)
    terasort_mofs.write_map(root, "bench", ids[1], twin)
    plain = host_sort.sorted_stream(root, "bench", ids)
    assert host_sort_blocks.sorted_digest(root, "bench", ids, 37) \
        == (plain.size, digest(plain))


def test_compare_refuses_a_stream_that_differs(maps):
    root, ids = maps
    known = host_sort_blocks.sorted_digest(root, "bench", ids)
    plain = host_sort.sorted_stream(root, "bench", ids)
    stream = np.concatenate([plain, np.frombuffer(b"\xff\xff", np.uint8)])
    assert host_sort_blocks.compare_digest(stream, *known) is None
    swapped = stream.copy()
    swapped[:102], swapped[102:204] = stream[102:204], stream[:102]
    assert "digest" in host_sort_blocks.compare_digest(swapped, *known)
    assert "expected" in host_sort_blocks.compare_digest(stream[:-2], *known)
    stream[-1] = 0
    assert "EOF" in host_sort_blocks.compare_digest(stream, *known)


def test_a_map_output_that_is_not_terasort_frames_is_refused(tmp_path):
    root = str(tmp_path)
    ids = terasort_mofs.generate(root, "bench", 1, 50, 2)
    path = f"{root}/bench/{ids[1]}/file.out"
    raw = bytearray(open(path, "rb").read())
    raw[0] = 11                                  # a key length of 11
    open(path, "wb").write(raw)
    with pytest.raises(host_sort_blocks.ReferenceError):
        host_sort_blocks.sorted_digest(root, "bench", ids)
    open(path, "wb").write(raw[:-1])             # not whole frames
    with pytest.raises(host_sort_blocks.ReferenceError):
        host_sort_blocks.sorted_digest(root, "bench", ids)
