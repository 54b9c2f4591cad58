"""The total-order verifier accepts a correct result whatever splitters
made it and names each way a wrong one is wrong."""

import numpy as np
import pytest

from benchmark.reference import exchange_verify_total as v

P, CAP, N = 4, 96, 200


def _case(cuts=(50, 100, 150), cap=CAP):
    """A correct result: ids with many duplicates, stable-sorted, cut
    into shards at key boundaries near ``cuts``."""
    rng = np.random.default_rng(5)
    words = rng.integers(0, 2**32, size=(N, 26), dtype=np.uint32)
    ids = np.floor(2 ** (6 * rng.random(N))).astype(np.uint32)
    words[:, 0], words[:, 1], words[:, 2] = 0, ids >> 16, (ids & 0xFFFF) << 16
    words[:, v.ROW_WORD] = np.arange(N)
    want = words[np.lexsort((words[:, 2], words[:, 1], words[:, 0]))]
    edges = [0]
    for c in cuts:          # move each cut up to the next key boundary
        while 0 < c < N and (want[c, :3] == want[c - 1, :3]).all():
            c += 1
        edges.append(c)
    edges.append(N)
    out = np.zeros((P, cap, 26), np.uint32)
    nvalid = np.zeros(P, np.int32)
    for d in range(P):
        part = want[edges[d]:edges[d + 1]]
        out[d, :len(part)] = part
        nvalid[d] = len(part)
    return words, out, nvalid


def _verdicts(words, out, nvalid):
    return (v.device_check(words, out.reshape(-1, 26), nvalid, P),
            v.byte_exact(words, out.reshape(-1, 26), nvalid))


@pytest.mark.parametrize("cuts", [(50, 100, 150), (40, 90, 160),
                                  (60, 60, 140)])
def test_any_valid_splitters_pass(cuts):
    device, host = _verdicts(*_case(cuts))      # (60, 60, ..): a shard empty
    assert not any(device.values()), device
    assert host is None


def _equal_pair(out, nvalid, d):
    """Index of two adjacent rows of shard d with one key."""
    k = out[d, :nvalid[d], :3]
    return int(np.flatnonzero((k[1:] == k[:-1]).all(axis=1))[0])


@pytest.mark.parametrize("fault,field", [
    ("swap_keys", "unsorted"), ("swap_equal", "unstable"),
    ("split_key", "straddled"), ("drop", "miscounted"),
    ("flip", "checksum"), ("overfull", "unbalanced")])
def test_each_fault_is_named(fault, field):
    words, out, nvalid = _case()
    if fault == "swap_keys":
        out[1, [0, nvalid[1] - 1]] = out[1, [nvalid[1] - 1, 0]]
    elif fault == "swap_equal":      # same key, input order reversed
        i = _equal_pair(out, nvalid, 0)
        out[0, [i, i + 1]] = out[0, [i + 1, i]]
    elif fault == "split_key":       # shard 1's first key also ends shard 0
        i = _equal_pair(out, nvalid, 1)
        run = out[1, :nvalid[1]].copy()
        out[0, nvalid[0]:nvalid[0] + i + 1] = run[:i + 1]
        out[1, :nvalid[1] - i - 1] = run[i + 1:]
        nvalid[0] += i + 1
        nvalid[1] -= i + 1
    elif fault == "drop":
        nvalid[2] -= 1
    elif fault == "flip":
        out[3, 2, 20] ^= 1
    else:                            # everything in one shard: sorted, whole
        words, out, nvalid = _case((0, 0, 0), cap=N)
        assert nvalid.tolist() == [0, 0, 0, N]
    device, host = _verdicts(words, out, nvalid)
    assert device[field] > 0, device
    assert host is not None


def test_the_bound_is_a_balanced_share_plus_the_hottest_key():
    assert v.shard_bound(1000, 4, 50) == 250 + 10 + 50
    words, out, nvalid = _case()
    _, counts = np.unique(words[:, :3], axis=0, return_counts=True)
    # all keys equal: one shard holds everything, and that is within it
    assert v.shard_bound(N, P, N) >= N
    assert int(nvalid.max()) <= v.shard_bound(N, P, int(counts.max()))
