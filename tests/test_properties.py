"""Property-based tests for the L0 byte-level contracts.

Everything above L0 (device sorts, exchange, bridge, JVM) assumes these
byte formats are exact; property testing sweeps the corners example
tests miss (the reference had NO unit tests at all for its VInt/IFile
code, SURVEY §4 — "we must do better" was the stated test strategy).
"""

import io

import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from uda_tpu.compress.lzo import lzo1x_compress_py, lzo1x_decompress_py
from uda_tpu.utils import comparators, vint
from uda_tpu.utils.ifile import (IFileReader, IFileWriter, crack,
                                 crack_partial, write_records)

pytestmark = pytest.mark.slow  # property sweeps (hypothesis) dominate the suite

# CI-fast but NOT derandomized: a frozen example set would never
# explore new inputs across runs (reproduce failures via the printed
# @reproduce_failure blob / hypothesis example database)
settings.register_profile("uda", max_examples=80, deadline=None)
settings.load_profile("uda")


@given(st.integers(min_value=-(2 ** 63), max_value=2 ** 63 - 1))
def test_vlong_round_trip(value):
    buf = vint.encode_vlong(value)
    out, consumed = vint.decode_vlong(buf)
    assert (out, consumed) == (value, len(buf))
    # the (signed) first byte alone determines the encoded size
    signed = buf[0] - 256 if buf[0] > 127 else buf[0]
    assert vint.decode_vint_size(signed) == len(buf)


@given(st.lists(st.integers(min_value=-(2 ** 63), max_value=2 ** 63 - 1),
                max_size=50))
def test_vlong_stream_round_trip(values):
    arr = np.asarray(values, np.int64)
    blob = np.frombuffer(vint.encode_vlong_array(arr), np.uint8)
    out, _ = vint.decode_vlong_stream(blob, count=len(values))
    assert out.tolist() == values


_record = st.tuples(st.binary(min_size=0, max_size=40),
                    st.binary(min_size=0, max_size=60))


@pytest.mark.parametrize("use_native", [False, True])
@given(st.lists(_record, max_size=30))
def test_ifile_write_crack_round_trip(use_native, records):
    from uda_tpu.utils import ifile

    # pad one record so the blob crosses the native-dispatch threshold:
    # both the pure-Python and (when built) the C++ crack paths must
    # uphold the contract
    if use_native:
        if not ifile.native_enabled():
            pytest.skip("native codec not built")
        records = records + [(b"k" * 64, b"v" * 8192)]
    blob = write_records(records)
    batch = crack(blob, expect_eof=True)
    assert list(batch.iter_records()) == records


@given(st.lists(_record, min_size=1, max_size=12), st.data())
def test_crack_partial_at_any_split(records, data):
    # splitting the stream at ANY byte boundary must yield: a prefix of
    # complete records + a carry that, prepended to the rest, round-trips
    blob = write_records(records)
    cut = data.draw(st.integers(min_value=0, max_value=len(blob)))
    head, consumed, saw_eof = crack_partial(blob[:cut], expect_eof=False)
    got = list(head.iter_records())
    if saw_eof:
        # the whole stream (incl. EOF marker) fit in the prefix
        assert consumed == cut == len(blob)
    else:
        tail = crack(blob[:cut][consumed:] + blob[cut:], expect_eof=True)
        got += list(tail.iter_records())
    assert got == records


@given(st.lists(_record, max_size=20))
def test_ifile_writer_reader_agree_with_batch_path(records):
    buf = io.BytesIO()
    w = IFileWriter(buf)
    for k, v in records:
        w.append(k, v)
    w.close()
    assert list(IFileReader(io.BytesIO(buf.getvalue()))) == records
    assert (list(crack(buf.getvalue(), expect_eof=True).iter_records())
            == records)


@given(st.binary(max_size=30), st.binary(max_size=30))
def test_rawbytes_comparator_matches_memcmp(a, b):
    # independent oracle: hand-rolled byte loop + length tiebreak (NOT
    # Python's bytes comparison, which is what the implementation uses)
    def oracle(x, y):
        for xb, yb in zip(x, y):
            if xb != yb:
                return -1 if xb < yb else 1
        return (len(x) > len(y)) - (len(x) < len(y))

    kt = comparators.get_key_type("uda.tpu.RawBytes")
    want = oracle(a, b)
    got = kt.compare(a, b)
    assert (got > 0) == (want > 0) and (got < 0) == (want < 0) \
        and (got == 0) == (want == 0)


@given(st.binary(max_size=4096))
def test_lzo_pure_python_round_trip(data):
    assert lzo1x_decompress_py(lzo1x_compress_py(data), len(data)) == data


@settings(max_examples=8, deadline=None)
@given(st.integers(min_value=1, max_value=400), st.integers(0, 2 ** 32 - 1),
       st.floats(0.0, 1.0))
def test_sort_engines_agree(n, seed, dup_rate):
    # every engine must produce byte-identical output (stability
    # included) for arbitrary record counts, key distributions, and
    # duplicate rates
    import jax

    from uda_tpu.models import terasort

    words = np.asarray(terasort.teragen(jax.random.key(seed % 1000), n))
    words = words.copy()
    ndup = int(dup_rate * n / 2)
    if ndup:
        words[:ndup, :3] = words[n - ndup:, :3]  # forced duplicate keys
    want = np.asarray(terasort.single_chip_sort(words, path="carry"))
    for path in ("keys8", "lanes"):
        got = np.asarray(terasort.single_chip_sort(
            words, path=path, tile=256, interpret=True))
        np.testing.assert_array_equal(want, got, err_msg=path)
