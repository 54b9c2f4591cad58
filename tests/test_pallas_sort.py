"""Pallas lanes-layout full sort, and the merge of sorted runs, vs host
oracle (interpret mode)."""

import numpy as np
import pytest

from uda_tpu.ops import pallas_sort

slow = pytest.mark.slow  # interpret-mode Pallas kernels at sort sizes


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


@slow
def test_single_tile():
    _check(512, tile=512)


@slow
def test_two_tiles_one_merge():
    _check(1024, tile=512, seed=1)


@slow
def test_eight_tiles_three_merges():
    _check(2048, tile=256, seed=2)


@slow
def test_many_duplicates_stability():
    _check(2048, tile=256, dup_rate=1.0, seed=3)


@slow
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


@slow
def test_single_key_word():
    _check(1024, tile=256, num_keys=1, seed=5)


@slow
def test_roundtrip_layout_helpers():
    rng = np.random.default_rng(6)
    words = rng.integers(0, 2**32, size=(640, 26), dtype=np.uint32)
    lanes = np.asarray(pallas_sort.rows_to_lanes(words))
    assert lanes.shape == (pallas_sort.ROWS, 640)
    assert (lanes[26:] == 0).all()
    back = np.asarray(pallas_sort.lanes_to_rows(lanes, 26))
    np.testing.assert_array_equal(back, words)


@slow
def test_shape_validation():
    x = np.zeros((pallas_sort.ROWS, 768), np.uint32)  # 3 tiles: not pow2
    with pytest.raises(ValueError):
        pallas_sort.sort_lanes(x, 3, tile=256, interpret=True)
    with pytest.raises(ValueError):
        pallas_sort.sort_lanes(np.zeros((pallas_sort.ROWS, 512), np.uint32),
                               3, tile=192, interpret=True)


# -- the carried merge-path split (fast tier: 8 tiles of 128 lanes, three
# passes, so pairs stored descending in the first two) -------------------

def _sorted_lanes(x, k):
    return x[:, np.lexsort(tuple(x[r] for r in reversed(range(k))))]


_SORT_CASES = {
    "uniform": lambda x, k: x,
    "few_keys": lambda x, k: np.concatenate(
        [x[:k] % 3, x[k:]]),
    # the tie-break row alone decides every count
    "all_keys_equal": lambda x, k: np.concatenate(
        [np.full_like(x[:k], 7), x[k:]]),
    "all_keys_max": lambda x, k: np.concatenate(
        [np.full_like(x[:k], _INF), x[k:]]),
    # every run wholly below / above its partner: the split jumps
    "presorted": _sorted_lanes,
    "reversed": lambda x, k: _sorted_lanes(x, k)[:, ::-1],
}


@pytest.mark.parametrize("case", sorted(_SORT_CASES))
@pytest.mark.parametrize("rows,tb", [(8, 7), (32, 31)])
@pytest.mark.parametrize("tiles", [2, 8])
def test_sort_lanes_carried_split_is_the_stable_host_sort(tiles, rows, tb,
                                                          case):
    k = 3
    rng = np.random.default_rng(tiles * rows + len(case))
    x = rng.integers(0, 2**32, size=(rows, tiles * 128), dtype=np.uint32)
    x = np.ascontiguousarray(_SORT_CASES[case](x, k))
    out = np.asarray(pallas_sort.sort_lanes(x, k, tb_row=tb, tile=128,
                                            interpret=True))
    want, perm = _oracle(x, k)
    keep = [r for r in range(rows) if r != tb]
    np.testing.assert_array_equal(out[keep], want[keep])
    np.testing.assert_array_equal(out[tb].astype(np.int64), perm)


def test_pass_counts_are_the_loops_own():
    assert pallas_sort.sort_passes(1 << 24) == 14      # 2^24 lanes, tile 1024
    assert pallas_sort.sort_passes(1000) == 0          # one clamped tile
    assert pallas_sort.sort_passes(1025) == 1
    assert [pallas_sort.runs_passes(r) for r in (1, 2, 3, 4, 5, 8)] == [
        0, 1, 2, 2, 3, 3]


# -- merge_lanes_runs: the pipeline's merge-only entry (fast tier: a few
# tiles of 128 lanes a case) --------------------------------------------

_INF = 0xFFFFFFFF
_KEYS = 2                   # key words; the entry also compares a flag row
_TILE = 128


def _runs_matrix(counts, run_len, keys_of, seed):
    """R runs side by side as the fused step's receive side packs them
    (parallel/distributed._sort_valid_rows_lanes): rows [0, _KEYS) the
    key words, row _KEYS the invalid flag — 0 on a run's real lanes,
    ascending by key with equal keys in slot order; (+inf keys, 1) on
    the lanes behind them — and random payload everywhere."""
    rng = np.random.default_rng(seed)
    x = rng.integers(0, 2**32, size=(pallas_sort.ROWS, len(counts) * run_len),
                     dtype=np.uint32)
    for k, c in enumerate(counts):
        lanes = slice(k * run_len, (k + 1) * run_len)
        keys = np.full((_KEYS, run_len), _INF, np.uint32)
        real = keys_of(rng, c, k)
        keys[:, :c] = real[:, np.lexsort(real[::-1])]
        x[:_KEYS, lanes] = keys
        x[_KEYS, lanes] = np.arange(run_len) >= c
    return x


def _uniform_keys(rng, c, k):
    return rng.integers(0, 2**32, size=(_KEYS, c), dtype=np.uint32)


def _few_keys(rng, c, k):   # ties inside a run and across run boundaries
    return rng.integers(0, 3, size=(_KEYS, c), dtype=np.uint32)


def _one_key(rng, c, k):    # the tie-break row alone decides every count
    return np.full((_KEYS, c), 77, np.uint32)


def _max_keys(rng, c, k):   # real all-0xFFFFFFFF keys beside the padding
    return np.full((_KEYS, c), _INF, np.uint32)


def _run_below_run(rng, c, k):
    # run k wholly below run k-1: a pair's split jumps 0 -> L at the
    # tile where B' runs out, and the windows clamp at the run ends
    keys = _uniform_keys(rng, c, k)
    keys[0] = 1000 - k
    return keys


def _run_above_run(rng, c, k):
    keys = _uniform_keys(rng, c, k)
    keys[0] = k
    return keys


# run length (slots), how many of each run's slots are real, the keys
_RUN_CASES = {
    "tile_multiple_full": (2 * _TILE, lambda k, L: L, _uniform_keys),
    "tile_multiple_unequal": (2 * _TILE,
                              lambda k, L: (L, 0, 17, L - 1)[k % 4],
                              _uniform_keys),
    "not_tile_multiple_unequal": (200, lambda k, L: (L, 0, 17, 131)[k % 4],
                                  _uniform_keys),
    "under_a_tile": (48, lambda k, L: (L, 5, 0, 47)[k % 4], _few_keys),
    "ties_across_runs": (200, lambda k, L: (150, L, 3, 0)[k % 4], _few_keys),
    "all_keys_equal": (200, lambda k, L: (L, 90, 0, 199)[k % 4], _one_key),
    "real_max_keys_beside_padding": (200, lambda k, L: (60, L, 0, 7)[k % 4],
                                     _max_keys),
    "each_run_below_the_last": (3 * _TILE, lambda k, L: L, _run_below_run),
    "each_run_above_the_last": (3 * _TILE, lambda k, L: (L, L - 1)[k % 2],
                                _run_above_run),
    "one_tile_runs": (_TILE, lambda k, L: L, _few_keys),
    "one_off_a_tile_multiple": (2 * _TILE + 1,
                                lambda k, L: (L, L - 2, 1, L)[k % 4],
                                _few_keys),
}


@pytest.mark.parametrize("case", sorted(_RUN_CASES))
@pytest.mark.parametrize("runs", [1, 2, 3, 4, 5, 8])
def test_merge_lanes_runs_is_the_stable_host_sort(runs, case):
    run_len, count_of, keys_of = _RUN_CASES[case]
    counts = [count_of(k, run_len) for k in range(runs)]
    x = _runs_matrix(counts, run_len, keys_of, seed=100 * runs + len(case))
    out = np.asarray(pallas_sort.merge_lanes_runs(
        x, run_len, _KEYS + 1, tile=_TILE, interpret=True))
    # a stable sort by (keys, flag): equal keys by run, then by slot,
    # every real lane before every padding lane, whatever its key
    want, perm = _oracle(x, _KEYS + 1)
    tb = pallas_sort.TB_ROW_DEFAULT
    assert out.shape == x.shape
    rows = [r for r in range(pallas_sort.ROWS) if r != tb]
    np.testing.assert_array_equal(out[rows], want[rows])
    # the tie-break row holds each lane's index in x: run, then slot
    np.testing.assert_array_equal(out[tb].astype(np.int64), perm)
    assert (out[_KEYS, :sum(counts)] == 0).all()


def test_merge_lanes_runs_equals_sort_lanes_on_its_own_runs():
    # the entry IS the tail of the sort: runs cut out of sort_lanes'
    # input and sorted one by one merge to what sort_lanes makes of all
    n, run_len, k = 1024, 256, 3
    x = _gen(n, k, dup_rate=1.0, seed=9)
    runs = [x[:, i:i + run_len] for i in range(0, n, run_len)]
    runs = [r[:, np.lexsort(tuple(r[j] for j in reversed(range(k))))]
            for r in runs]
    got = np.asarray(pallas_sort.merge_lanes_runs(
        np.concatenate(runs, axis=1), run_len, k, tile=_TILE,
        interpret=True))
    want = np.asarray(pallas_sort.sort_lanes(x, k, tile=_TILE,
                                             interpret=True))
    tb = pallas_sort.TB_ROW_DEFAULT
    rows = [r for r in range(pallas_sort.ROWS) if r != tb]
    np.testing.assert_array_equal(got[rows], want[rows])


def test_merge_lanes_runs_shape_validation():
    x = np.zeros((pallas_sort.ROWS, 600), np.uint32)
    with pytest.raises(ValueError):         # not a whole number of runs
        pallas_sort.merge_lanes_runs(x, 256, 3, interpret=True)
    with pytest.raises(ValueError):
        pallas_sort.merge_lanes_runs(x, 200, 3, tile=192, interpret=True)
    with pytest.raises(ValueError):         # key rows reach the tie-break
        pallas_sort.merge_lanes_runs(x, 200, 3, tb_row=2, interpret=True)
