"""The exchange step's verifier accepts a correct result and names each
way a wrong one is wrong."""

import numpy as np
import pytest

from benchmark.gen import device_records
from benchmark.reference import exchange_verify as v

P, CAP = 2, 48


def _case():
    import jax
    from jax.sharding import SingleDeviceSharding

    words = np.asarray(device_records.records(
        3, 64, SingleDeviceSharding(jax.devices()[0])))
    splitters = np.array([1 << 31], np.uint32)
    dest = np.searchsorted(splitters, words[:, 0], side="right")
    out = np.zeros((P, CAP, words.shape[1]), np.uint32)
    nvalid = np.zeros(P, np.int32)
    for d in range(P):
        part = words[dest == d]
        part = part[np.lexsort((part[:, 2], part[:, 1], part[:, 0]))]
        out[d, :len(part)] = part
        nvalid[d] = len(part)
    return words, out, nvalid, splitters


def test_generated_records_are_masked_and_seeded():
    words, *_ = _case()
    assert words.shape == (64, 26) and words.dtype == np.uint32
    assert not (words[:, 2] & 0xFFFF).any() and (words[:, 2] >> 16).any()
    assert len(np.unique(words[:, 0])) == 64


def test_correct_result_passes():
    words, out, nvalid, splitters = _case()
    assert not any(v.device_check(words, out.reshape(-1, 26), nvalid,
                                  splitters, P).values())
    assert v.byte_exact(words, out.reshape(-1, 26), nvalid, splitters) is None


@pytest.mark.parametrize("fault,field", [
    ("swap", "unsorted"), ("move", "misplaced"), ("drop", "miscounted"),
    ("flip", "checksum")])
def test_each_fault_is_named(fault, field):
    words, out, nvalid, splitters = _case()
    if fault == "swap":
        out[0, [0, 1]] = out[0, [1, 0]]
    elif fault == "move":
        out[0, nvalid[0] - 1] = out[1, 0]
    elif fault == "drop":
        nvalid[1] -= 1
    else:
        out[1, 3, 20] ^= 1
    verdict = v.device_check(words, out.reshape(-1, 26), nvalid, splitters, P)
    assert verdict[field] > 0, verdict
    assert v.byte_exact(words, out.reshape(-1, 26), nvalid,
                        splitters) is not None
