"""The skewed generator follows the law its configuration states, where
the law decides anything: the hot ids' shares and the half-mass point."""

import numpy as np
import pytest

from benchmark.gen import device_records_zipf as gen

N = 1 << 20


@pytest.fixture(scope="module")
def one_device():
    import jax
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(jax.devices()[0])


@pytest.fixture(scope="module")
def words(one_device):
    return np.asarray(gen.records(9, N, one_device))


def _ids(words):
    return (words[:, 1].astype(np.int64) << 16) | (words[:, 2] >> 16)


def test_layout_first_word_zero_and_row_word(words):
    assert words.shape == (N, 26) and words.dtype == np.uint32
    assert not words[:, 0].any()                  # 6 shared key bytes:
    assert not (words[:, 1] >> 4).any()           # ids stay under 2^20
    assert not (words[:, 2] & 0xFFFF).any()       # the key's 2 pad bytes
    assert (words[:, gen.ROW_WORD] == np.arange(N)).all()
    ids = _ids(words)
    assert ids.min() >= 1 and ids.max() < 1 << gen.RANKS_LOG2
    assert len(np.unique(words[:, 4])) > 0.99 * N     # payload differs


def test_hundred_hottest_shares_within_counting_error(words):
    counts = np.bincount(_ids(words), minlength=1 << gen.RANKS_LOG2)
    for rank in range(1, 101):
        share = np.log2(1 + 1 / rank) / gen.RANKS_LOG2
        sigma = np.sqrt(share * (1 - share) / N)
        assert abs(counts[rank] / N - share) < 5 * sigma, rank
    assert abs(counts[1] / N - 0.05) < 0.002          # the hottest: 5.0 %
    assert abs(counts[1:11].sum() / N - 0.173) < 0.003
    assert abs(counts[1:1024].sum() / N - 0.5) < 0.003   # half the mass


def test_arrival_is_not_ordered_and_seeds_differ(words, one_device):
    ids = _ids(words)
    assert abs(np.corrcoef(ids[:-1], ids[1:])[0, 1]) < 0.01
    other = np.asarray(gen.records(10, 4096, one_device))
    assert not np.array_equal(other, words[:4096])
    again = np.asarray(gen.records(9 + (1 << 32), 4096, one_device))
    assert np.array_equal(again, words[:4096])        # the seed's low 32 bits


def test_a_shards_rows_do_not_depend_on_the_mesh(words):
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    devices = jax.devices()
    if len(devices) < 4:
        pytest.skip("one device")
    mesh = Mesh(np.array(devices[:4]), ("ici",))
    sharded = gen.records(9, 1 << 14, NamedSharding(mesh,
                                                    PartitionSpec("ici")))
    assert len(sharded.addressable_shards) == 4
    assert np.array_equal(np.asarray(sharded), words[:1 << 14])
