"""Pallas lanes-layout full sort vs host oracle (interpret mode)."""

import numpy as np
import pytest

from uda_tpu.ops import pallas_sort

pytestmark = pytest.mark.slow  # interpret-mode Pallas kernels


def _gen(n, num_keys=3, dup_rate=0.0, seed=0, payload_rows=None):
    rng = np.random.default_rng(seed)
    x = rng.integers(0, 2**32, size=(pallas_sort.ROWS, n),
                     dtype=np.uint32)
    if dup_rate:
        # few distinct keys -> many ties to exercise stability
        x[:num_keys] = rng.integers(0, 3, size=(num_keys, n),
                                    dtype=np.uint32)
    return x


def _oracle(x, num_keys):
    # stable ascending sort by key rows (records are columns)
    keys = tuple(x[r] for r in reversed(range(num_keys)))
    perm = np.lexsort(keys)  # lexsort is stable
    return x[:, perm], perm


def _check(n, tile, num_keys=3, dup_rate=0.0, seed=0):
    x = _gen(n, num_keys, dup_rate, seed)
    out = np.asarray(pallas_sort.sort_lanes(x, num_keys, tile=tile,
                                            interpret=True))
    want, perm = _oracle(x, num_keys)
    tb = pallas_sort.TB_ROW_DEFAULT
    # keys + payload rows (all but tb) must match the stable oracle
    for r in range(pallas_sort.ROWS):
        if r == tb:
            continue
        np.testing.assert_array_equal(out[r], want[r], err_msg=f"row {r}")
    # the tie-break row must hold the (stable) source permutation
    np.testing.assert_array_equal(out[tb].astype(np.int64), perm,
                                  err_msg="tie-break row != stable perm")


def test_single_tile():
    _check(512, tile=512)


def test_two_tiles_one_merge():
    _check(1024, tile=512, seed=1)


def test_eight_tiles_three_merges():
    _check(2048, tile=256, seed=2)


def test_many_duplicates_stability():
    _check(2048, tile=256, dup_rate=1.0, seed=3)


def test_presorted_and_reversed():
    n, tile, k = 1024, 256, 3
    x = _gen(n, k, seed=4)
    order = np.lexsort(tuple(x[r] for r in reversed(range(k))))
    for variant in (order, order[::-1]):
        xs = x[:, variant]
        out = np.asarray(pallas_sort.sort_lanes(xs, k, tile=tile,
                                                interpret=True))
        want, _ = _oracle(xs, k)
        np.testing.assert_array_equal(out[:k], want[:k])


def test_single_key_word():
    _check(1024, tile=256, num_keys=1, seed=5)


def test_roundtrip_layout_helpers():
    rng = np.random.default_rng(6)
    words = rng.integers(0, 2**32, size=(640, 26), dtype=np.uint32)
    lanes = np.asarray(pallas_sort.rows_to_lanes(words))
    assert lanes.shape == (pallas_sort.ROWS, 640)
    assert (lanes[26:] == 0).all()
    back = np.asarray(pallas_sort.lanes_to_rows(lanes, 26))
    np.testing.assert_array_equal(back, words)


def test_shape_validation():
    x = np.zeros((pallas_sort.ROWS, 768), np.uint32)  # 3 tiles: not pow2
    with pytest.raises(ValueError):
        pallas_sort.sort_lanes(x, 3, tile=256, interpret=True)
    with pytest.raises(ValueError):
        pallas_sort.sort_lanes(np.zeros((pallas_sort.ROWS, 512), np.uint32),
                               3, tile=192, interpret=True)
