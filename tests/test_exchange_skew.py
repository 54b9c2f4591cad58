"""A multi-chip sort of keys of unknown distribution (the sort
benchmark's Daytona category): splitters sampled on the device,
partition on the whole key, and both routes — fused, and the windowed
rounds behind an overflowed fused attempt — against a plain host sort,
on the CPU's 8-device mesh (tests/test_exchange.py's fixtures)."""

import numpy as np
import pytest

from uda_tpu.parallel import (distributed_sort_step, make_mesh,
                              sample_splitters, uniform_splitters)
from uda_tpu.parallel import distributed
from uda_tpu.utils.metrics import metrics

AXIS = "shuffle"
KEYS, WIDTH = 3, 5          # 3 key words, the row number, one payload word
PER_CHIP = 192


def _ids(ranks):
    """TeraSort-shaped keys from integer ids: the id as a big-endian
    10-byte number, so every record shares its first 6 key bytes."""
    r = np.asarray(ranks, dtype=np.uint32)
    return np.stack([np.zeros_like(r), r >> 16, (r & 0xFFFF) << 16], axis=1)


def _keys(kind: str, n: int, rng) -> np.ndarray:
    if kind == "uniform":
        return rng.integers(0, 2**32, size=(n, KEYS), dtype=np.uint32)
    if kind == "zipf_ids":          # Zipf s=1 by the inverse CDF: K^u
        return _ids(np.floor(2 ** (10 * rng.random(n))))
    if kind == "all_equal":
        return _ids(np.full(n, 77))
    if kind == "two_keys":
        return _ids(rng.integers(0, 2, n) * 70000 + 5)
    if kind == "sorted":
        return _ids(np.sort(rng.integers(0, 1 << 20, n)))
    if kind == "reversed":
        return _ids(np.sort(rng.integers(0, 1 << 20, n))[::-1])
    raise AssertionError(kind)


def _records(kind: str, n: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    words = np.empty((n, WIDTH), np.uint32)
    words[:, :KEYS] = _keys(kind, n, rng)
    words[:, KEYS] = np.arange(n)                  # tells equal keys apart
    words[:, KEYS + 1] = rng.integers(0, 2**32, n)
    return words


def _shards(res, p):
    out = np.asarray(res.words).reshape(p, -1, WIDTH)
    nvalid = np.asarray(res.valid_counts).reshape(-1)
    return [out[d, :nvalid[d]] for d in range(p)]


def _stable_host_sort(words):
    return words[np.lexsort(tuple(words[:, c] for c in reversed(range(KEYS))))]


def _hottest_share(words) -> float:
    _, counts = np.unique(words[:, :KEYS], axis=0, return_counts=True)
    return counts.max() / len(words)


@pytest.mark.parametrize("route", ("fused", "rounds"))
@pytest.mark.parametrize("p", (2, 4, 8))
@pytest.mark.parametrize("kind", ("uniform", "zipf_ids", "all_equal",
                                  "two_keys", "sorted", "reversed"))
def test_sampled_sort_is_the_stable_host_sort(kind, p, route):
    mesh = make_mesh(p, AXIS)
    n = p * PER_CHIP
    words = _records(kind, n, seed=p)
    before = metrics.snapshot()
    if route == "fused":
        # a window that holds a source's whole shard never overflows
        res = distributed_sort_step(words, None, mesh, AXIS, capacity=n // p,
                                    num_keys=KEYS, multiround="never")
    else:
        # a window a twelfth of it: the fused attempt overflows and the
        # step runs again through the rounds, with the same splitters
        res = distributed_sort_step(words, None, mesh, AXIS,
                                    capacity=PER_CHIP // 12, num_keys=KEYS)
    res.check()
    after = metrics.snapshot()
    grew = {k: after[k] - before.get(k, 0) for k in after}
    assert grew["exchange.sample.keys"] == n        # n < SAMPLE_KEYS: all
    assert grew["exchange.fused.overflow_reruns"] == (route == "rounds")
    if route == "rounds":
        assert grew["exchange.rounds"] >= 2

    shards = _shards(res, p)
    # concatenated shards ARE the stable host sort, byte for byte: total
    # order, every record once, equal keys in input order
    np.testing.assert_array_equal(np.concatenate(shards),
                                  _stable_host_sort(words))
    # no key in two shards
    held = [s for s in shards if len(s)]
    for a, b in zip(held, held[1:]):
        assert tuple(a[-1, :KEYS]) < tuple(b[0, :KEYS])
    # balance: a key never straddles, so 1/p + the hottest key + error
    largest = max(len(s) for s in shards)
    assert largest <= n * (1 / p + _hottest_share(words) + 0.05)
    assert metrics.get_gauge("exchange.shard.max_permille") == \
        pytest.approx(1000 * largest / n)
    # the result says which range each shard holds
    spl = np.asarray(res.splitters)
    assert spl.shape == (p - 1, KEYS)
    for d, s in enumerate(shards):
        for key in (s[0, :KEYS], s[-1, :KEYS]) if len(s) else ():
            assert sum(tuple(x) <= tuple(key) for x in spl) == d

    if route == "rounds":
        fused = distributed_sort_step(words, None, mesh, AXIS,
                                      capacity=n // p, num_keys=KEYS,
                                      multiround="never")
        for a, b in zip(shards, _shards(fused, p)):
            np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(spl, np.asarray(fused.splitters))


@pytest.mark.parametrize("p", (2, 4, 8))
def test_device_sampled_splitters_equal_the_host_rule_on_the_same_sample(p):
    mesh = make_mesh(p, AXIS)
    n = p * PER_CHIP
    words = _records("zipf_ids", n, seed=40 + p)
    res = distributed_sort_step(words, None, mesh, AXIS, capacity=n // p,
                                num_keys=KEYS, multiround="never")
    rows = distributed._sample_rows(PER_CHIP, p)
    assert len(rows) == PER_CHIP and (np.diff(rows) > 0).all()
    sample = words.reshape(p, PER_CHIP, WIDTH)[:, rows, :KEYS]
    want = sample_splitters(sample.reshape(-1, KEYS), p)
    np.testing.assert_array_equal(np.asarray(res.splitters), want)
    # "always" samples by the same stage, outside the fused program
    multi = distributed_sort_step(words, None, mesh, AXIS, capacity=16,
                                  num_keys=KEYS, multiround="always")
    np.testing.assert_array_equal(np.asarray(multi.splitters), want)


def test_sample_rows_are_an_even_stride_and_a_function_of_the_shape():
    rows = distributed._sample_rows(1 << 24, 4)
    assert len(rows) == distributed.SAMPLE_KEYS // 4 == 25_000
    assert rows.dtype == np.int32 and rows[0] >= 0 and rows[-1] < 1 << 24
    assert set(np.diff(rows)) <= {671, 672}          # 2^24 / 25,000 = 671.1
    np.testing.assert_array_equal(rows, distributed._sample_rows(1 << 24, 4))
    assert len(distributed._sample_rows(100, 8)) == 100     # all it has


@pytest.mark.parametrize("p", (2, 4, 8))
def test_one_partition_gives_the_first_word_search_on_uniform_splitters(p):
    import jax.numpy as jnp

    rng = np.random.default_rng(p)
    words = rng.integers(0, 2**32, size=(4096, KEYS), dtype=np.uint32)
    edges = uniform_splitters(p)
    words[:p - 1, 0] = edges                  # keys ON an edge, and just
    words[p:2 * p - 1, 0] = edges - 1         # under one
    words[:p - 1, 1:] = 0
    whole = distributed._whole_keys(edges, KEYS)
    assert whole.shape == (p - 1, KEYS) and not whole[:, 1:].any()
    got = np.asarray(distributed._partition(jnp.asarray(words),
                                            jnp.asarray(whole), KEYS))
    want = np.searchsorted(edges, words[:, 0], side="right")
    np.testing.assert_array_equal(got, want)


def test_partition_compares_the_whole_key_where_the_first_words_tie():
    import jax.numpy as jnp

    keys = _ids([0, 5, 6, 70000, 70001, 1 << 19])
    spl = _ids([6, 70001])
    got = np.asarray(distributed._partition(jnp.asarray(keys),
                                            jnp.asarray(spl), KEYS))
    assert got.tolist() == [0, 0, 1, 1, 2, 2]


def test_sample_splitters_keeps_the_form_it_was_given():
    first = np.array([9, 1, 5, 3, 7, 1, 1, 8], np.uint32)
    assert sample_splitters(first, 4).tolist() == [1, 5, 8]
    whole = _ids([9, 1, 5, 3, 7, 1, 1, 8])
    np.testing.assert_array_equal(sample_splitters(whole, 4),
                                  _ids([1, 5, 8]))
    assert sample_splitters(whole[:0], 4).shape == (3, KEYS)


@pytest.mark.parametrize("kind", ("zipf_ids", "all_equal", "two_keys",
                                  "sorted"))
@pytest.mark.parametrize("p", (2, 4, 8))
@pytest.mark.parametrize("engine", ("carry", "lanes"))
def test_sampled_step_equals_the_scatter_then_sort_step(engine, p, kind):
    # the step that sorts first and merges the p received runs (lanes:
    # the Pallas merge passes, interpreted) against the step that
    # partitioned, permuted, exchanged and sorted (tests/
    # exchange_parent.py), both sampling their own splitters: the same
    # splitters, shards and counts, row for row — and the stable host
    # sort, so equal keys are in input order whichever chip sent them
    from tests.exchange_parent import scatter_then_sort_step

    mesh = make_mesh(p, AXIS)
    n = p * PER_CHIP
    words = _records(kind, n, seed=50 + p)
    before = metrics.snapshot()
    res = distributed_sort_step(words, None, mesh, AXIS, capacity=n // p,
                                num_keys=KEYS, multiround="never",
                                payload_path=engine)
    res.check()
    after = metrics.snapshot()
    assert after["exchange.merge.runs"] - before.get(
        "exchange.merge.runs", 0) == (p if engine == "lanes" else 0)
    want, want_nvalid, want_over, want_spl = scatter_then_sort_step(
        words, np.zeros((p - 1, KEYS), np.uint32), mesh, AXIS, n // p, KEYS,
        engine, interpret=engine == "lanes", sample=True)
    np.testing.assert_array_equal(np.asarray(res.splitters),
                                  np.asarray(want_spl))
    np.testing.assert_array_equal(np.asarray(res.valid_counts),
                                  np.asarray(want_nvalid))
    np.testing.assert_array_equal(np.asarray(res.words), np.asarray(want))
    assert int(np.asarray(want_over).sum()) == res.overflow() == 0
    np.testing.assert_array_equal(np.concatenate(_shards(res, p)),
                                  _stable_host_sort(words))


@pytest.mark.parametrize("engine", ("carry", "lanes"))
def test_sampled_sort_on_a_two_axis_mesh_matches_the_flat_mesh(engine):
    # the hierarchical body delivers the flat body's layout — block k of
    # the receive buffer source k's rows, slots kept — so on lanes its
    # blocks are the sorted runs the last stage merges
    from uda_tpu.parallel.mesh import mesh_from_config
    from uda_tpu.utils.config import Config

    mesh2 = mesh_from_config(Config({"uda.tpu.mesh.shape": "dcn:2,ici:4"}))
    names = tuple(mesh2.axis_names)
    n = 8 * PER_CHIP
    words = _records("zipf_ids", n, seed=3)
    flat = distributed_sort_step(words, None, make_mesh(8, AXIS), AXIS,
                                 capacity=n // 8, num_keys=KEYS,
                                 payload_path=engine)
    both = distributed_sort_step(words, None, mesh2, names,
                                 capacity=n // 8, num_keys=KEYS,
                                 payload_path=engine)
    np.testing.assert_array_equal(np.asarray(flat.splitters),
                                  np.asarray(both.splitters))
    for a, b in zip(_shards(flat, 8), _shards(both, 8)):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(np.concatenate(_shards(both, 8)),
                                  _stable_host_sort(words))


def test_distributed_terasort_names_its_two_categories():
    from uda_tpu.models import terasort

    mesh = make_mesh(4, AXIS)
    rng = np.random.default_rng(11)
    n = 4 * 256
    words = rng.integers(0, 2**32, size=(n, terasort.RECORD_WORDS),
                         dtype=np.uint32)
    words[:, :KEYS] = _ids(np.floor(2 ** (10 * rng.random(n))))
    indy = terasort.distributed_terasort(words, mesh, AXIS)
    indy.check()            # every key has first word 0: one shard, by
    assert np.asarray(indy.valid_counts).tolist() == [n, 0, 0, 0]  # rounds
    np.testing.assert_array_equal(
        np.asarray(indy.splitters),
        distributed._whole_keys(uniform_splitters(4), KEYS))
    daytona = terasort.distributed_terasort(words, mesh, AXIS,
                                            splitters="sampled")
    daytona.check()
    assert np.asarray(daytona.valid_counts).max() < 0.4 * n
    got = np.concatenate([np.asarray(daytona.words).reshape(4, -1, 26)[d, :c]
                          for d, c in enumerate(
                              np.asarray(daytona.valid_counts).reshape(-1))])
    np.testing.assert_array_equal(
        got, words[np.lexsort((words[:, 2], words[:, 1], words[:, 0]))])
    with pytest.raises(ValueError):
        terasort.distributed_terasort(words, mesh, AXIS, splitters="zipf")
