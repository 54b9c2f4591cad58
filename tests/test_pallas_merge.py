"""Pallas merge-path kernel vs host oracle (interpret mode on CPU)."""

import numpy as np
import pytest

from uda_tpu.ops import pallas_merge

pytestmark = pytest.mark.slow  # interpret-mode Pallas kernels


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


@pytest.mark.parametrize("na,nb", [(300, 500), (512, 512), (1, 1000),
                                   (1000, 1), (7, 5), (1024, 1024)])
def test_merge_pair_matches_host(na, nb):
    num_keys, w = 3, 6
    a = _sorted_run(na, w, num_keys, seed=na)
    b = _sorted_run(nb, w, num_keys, seed=nb + 10_000)
    got = np.asarray(pallas_merge.merge_sorted_pair(
        a, b, num_keys, tile=256, interpret=True))
    want = _host_merge(a, b, num_keys)
    assert got.shape == want.shape
    assert (got == want).all()


def test_merge_pair_duplicate_keys_stable():
    num_keys, w = 2, 4
    a = _sorted_run(400, w, num_keys, seed=1, dup_rate=1.0)
    b = _sorted_run(300, w, num_keys, seed=2, dup_rate=1.0)
    got = np.asarray(pallas_merge.merge_sorted_pair(
        a, b, num_keys, tile=128, interpret=True))
    want = _host_merge(a, b, num_keys)
    assert (got == want).all()


def test_merge_pair_empty_side():
    a = _sorted_run(50, 4, 2, seed=3)
    empty = np.zeros((0, 4), np.uint32)
    out = np.asarray(pallas_merge.merge_sorted_pair(a, empty, 2,
                                                    interpret=True))
    assert (out == a).all()
    out2 = np.asarray(pallas_merge.merge_sorted_pair(empty, a, 2,
                                                     interpret=True))
    assert (out2 == a).all()


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


def test_pallas_tile_power_of_two_guard():
    a = np.zeros((4, 4), np.uint32)
    with pytest.raises(ValueError):
        pallas_merge.merge_sorted_pair(a, a, 2, tile=384)


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
