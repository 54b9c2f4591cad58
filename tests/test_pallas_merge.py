"""Pallas merge-path kernel vs host oracle (interpret mode on CPU)."""

import numpy as np
import pytest

from uda_tpu.ops import pallas_merge

slow = pytest.mark.slow  # interpret-mode Pallas kernels at merge sizes


def _sorted_run(n, w, num_keys, seed, dup_rate=0.0):
    rng = np.random.default_rng(seed)
    rows = rng.integers(0, 2**32, size=(n, w), dtype=np.uint32)
    if dup_rate:
        # force many duplicate keys to exercise tie-breaking
        rows[:, :num_keys] = rng.integers(0, 4, size=(n, num_keys),
                                          dtype=np.uint32)
    order = np.lexsort(tuple(rows[:, c] for c in reversed(range(num_keys))))
    return rows[order]


def _host_merge(a, b, num_keys):
    # stable merge: A rows before B rows on equal keys
    cat = np.concatenate([a, b])
    src = np.concatenate([np.zeros(len(a), np.int64),
                          np.ones(len(b), np.int64)])
    idx = np.concatenate([np.arange(len(a)), np.arange(len(b))])
    keys = tuple(cat[:, c] for c in reversed(range(num_keys)))
    order = np.lexsort((idx, src) + keys)
    return cat[order]


@slow
@pytest.mark.parametrize("na,nb", [(300, 500), (512, 512), (1, 1000),
                                   (1000, 1), (7, 5), (1024, 1024),
                                   (1, 4096), (255, 257), (769, 511)])
def test_merge_pair_matches_host(na, nb):
    num_keys, w = 3, 6
    a = _sorted_run(na, w, num_keys, seed=na)
    b = _sorted_run(nb, w, num_keys, seed=nb + 10_000)
    got = np.asarray(pallas_merge.merge_sorted_pair(
        a, b, num_keys, tile=256, interpret=True))
    want = _host_merge(a, b, num_keys)
    assert got.shape == want.shape
    assert (got == want).all()


def _keys_random(rows, num_keys, side):
    return rows


def _keys_few(rows, num_keys, side):
    rows[:, :num_keys] %= 3
    return rows


def _keys_equal(rows, num_keys, side):  # the tie-break row decides all
    rows[:, :num_keys] = 9
    return rows


def _keys_max(rows, num_keys, side):    # real +inf keys beside the padding
    rows[:, :num_keys] = 0xFFFFFFFF
    return rows


def _a_below_b(rows, num_keys, side):   # the split runs 0 -> na, then stays
    rows[:, 0] = side
    return rows


def _b_below_a(rows, num_keys, side):
    rows[:, 0] = 1 - side
    return rows


_KEY_CASES = {f.__name__.lstrip("_"): f for f in (
    _keys_random, _keys_few, _keys_equal, _keys_max, _a_below_b, _b_below_a)}


# fast tier: tile 128, a few tiles a case. Lengths one off a tile
# multiple on either side, one row against many, a tile against a tile.
@pytest.mark.parametrize("case", sorted(_KEY_CASES))
@pytest.mark.parametrize("na,nb", [(1, 700), (700, 1), (127, 129),
                                   (257, 383), (128, 128)])
def test_merge_pair_carried_split_matches_host(na, nb, case):
    num_keys, w = 2, 5
    rng = np.random.default_rng(na * 1000 + nb)

    def run(n, side):
        rows = _KEY_CASES[case](
            rng.integers(0, 2**32, size=(n, w), dtype=np.uint32), num_keys,
            side)
        return rows[np.lexsort(tuple(rows[:, c]
                                     for c in reversed(range(num_keys))))]

    a, b = run(na, 0), run(nb, 1)
    got = np.asarray(pallas_merge.merge_sorted_pair(
        a, b, num_keys, tile=128, interpret=True))
    np.testing.assert_array_equal(got, _host_merge(a, b, num_keys))
    # the kernel carries what merge_splits searches for: the rows of A
    # among the first d merged rows, at every tile boundary d
    splits = np.asarray(pallas_merge.merge_splits(a, b, 128, num_keys))
    from_a = np.concatenate([[0], np.cumsum(
        _host_merge(np.c_[a[:, :num_keys], np.zeros((na, 1), np.uint32)],
                    np.c_[b[:, :num_keys], np.ones((nb, 1), np.uint32)],
                    num_keys)[:, num_keys] == 0)])
    np.testing.assert_array_equal(splits, from_a[::128][:len(splits)])


@slow
def test_merge_pair_duplicate_keys_stable():
    num_keys, w = 2, 4
    a = _sorted_run(400, w, num_keys, seed=1, dup_rate=1.0)
    b = _sorted_run(300, w, num_keys, seed=2, dup_rate=1.0)
    got = np.asarray(pallas_merge.merge_sorted_pair(
        a, b, num_keys, tile=128, interpret=True))
    want = _host_merge(a, b, num_keys)
    assert (got == want).all()


@slow
def test_merge_pair_empty_side():
    a = _sorted_run(50, 4, 2, seed=3)
    empty = np.zeros((0, 4), np.uint32)
    out = np.asarray(pallas_merge.merge_sorted_pair(a, empty, 2,
                                                    interpret=True))
    assert (out == a).all()
    out2 = np.asarray(pallas_merge.merge_sorted_pair(empty, a, 2,
                                                     interpret=True))
    assert (out2 == a).all()


@slow
def test_merge_splits_diagonals():
    num_keys = 1
    a = np.asarray([[1], [3], [5], [7]], np.uint32)
    b = np.asarray([[2], [4], [6], [8]], np.uint32)
    splits = np.asarray(pallas_merge.merge_splits(a, b, 2, num_keys))
    # merged: 1 2 | 3 4 | 5 6 | 7 8 -> A rows before each tile: 0,1,2,3
    assert splits.tolist() == [0, 1, 2, 3]
    # ties: A first
    a2 = np.asarray([[5], [5]], np.uint32)
    b2 = np.asarray([[5], [5]], np.uint32)
    s2 = np.asarray(pallas_merge.merge_splits(a2, b2, 2, 1))
    assert s2.tolist() == [0, 2]


@slow
def test_pallas_tile_power_of_two_guard():
    a = np.zeros((4, 4), np.uint32)
    with pytest.raises(ValueError):
        pallas_merge.merge_sorted_pair(a, a, 2, tile=384)


@slow
def test_merge_pair_max_width_31():
    # W=31 fits: record words occupy rows 0..30, tie-break at row 31
    a = _sorted_run(40, 31, 2, seed=7)
    b = _sorted_run(30, 31, 2, seed=8)
    got = np.asarray(pallas_merge.merge_sorted_pair(a, b, 2,
                                                    interpret=True))
    assert (got == _host_merge(a, b, 2)).all()
    with pytest.raises(ValueError):
        pallas_merge.merge_sorted_pair(
            np.zeros((4, 32), np.uint32), np.zeros((4, 32), np.uint32), 2,
            interpret=True)
