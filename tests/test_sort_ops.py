"""Device sort/merge vs host comparator oracle (reference MergeQueue
semantics, src/Merger/MergeQueue.h:276-427)."""

import functools
import struct

import numpy as np
import pytest

from uda_tpu.ops import merge, packing, sort
from uda_tpu.utils import comparators, ifile, vint


def _batch(pairs):
    return ifile.crack(ifile.write_records(pairs))


def _raw():
    return comparators.get_key_type("uda.tpu.RawBytes")


def _host_order(batch, kt):
    idx = list(range(batch.num_records))
    return sorted(idx, key=functools.cmp_to_key(
        lambda i, j: kt.compare(batch.key(i), batch.key(j)) or (i > j) - (i < j)))


def _random_records(n, seed, max_key=24, max_val=40):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        klen = int(rng.integers(0, max_key))
        out.append((rng.bytes(klen), rng.bytes(int(rng.integers(0, max_val)))))
    return out


def test_device_sort_matches_host_random():
    recs = _random_records(500, seed=0)
    # inject adversarial keys: trailing NULs, shared prefixes past width
    recs += [(b"a", b"1"), (b"a\x00", b"2"), (b"a\x00\x00", b"3"),
             (b"prefix__prefix__AAAA", b"4"), (b"prefix__prefix__AAAB", b"5"),
             (b"prefix__prefix__", b"6"), (b"", b"7"), (b"\xff" * 30, b"8")]
    batch = _batch(recs)
    kt = _raw()
    order = merge.sorted_batch_order(batch, kt, width=16)
    host = _host_order(batch, kt)
    got = [batch.key(int(i)) for i in order]
    want = [batch.key(i) for i in host]
    assert got == want


def test_device_sort_stability_on_equal_keys():
    recs = [(b"dup", bytes([i])) for i in range(50)]
    batch = _batch(recs)
    order = merge.sorted_batch_order(batch, _raw(), width=8)
    # equal keys keep arrival order
    assert order.tolist() == list(range(50))


def test_text_keys_device_order():
    kt = comparators.get_key_type("org.apache.hadoop.io.Text")
    words = [b"pear", b"apple", b"fig", b"applesauce", b"app", b"", b"zzz",
             b"apple"]
    recs = [(vint.encode_vlong(len(w)) + w, b"v") for w in words]
    batch = _batch(recs)
    order = merge.sorted_batch_order(batch, kt, width=8)
    got = [kt.content(batch.key(int(i))) for i in order]
    assert got == sorted(words)


def test_int_writable_memcmp_semantics_on_device():
    kt = comparators.get_key_type("org.apache.hadoop.io.IntWritable")
    vals = [3, 1000, -5, 0, -(2**31), 2**31 - 1, 7]
    recs = [(struct.pack(">i", v), b"v") for v in vals]
    batch = _batch(recs)
    order = merge.sorted_batch_order(batch, kt, width=4)
    got = [struct.unpack(">i", batch.key(int(i)))[0] for i in order]
    # memcmp order: non-negatives ascending, then negatives ascending
    want = sorted([v for v in vals if v >= 0]) + sorted([v for v in vals if v < 0])
    assert got == want


def test_int_numeric_variant_on_device():
    kt = comparators.get_key_type("uda.tpu.IntNumeric")
    vals = [3, -5, 0, -(2**31), 2**31 - 1]
    recs = [(struct.pack(">i", v), b"v") for v in vals]
    batch = _batch(recs)
    order = merge.sorted_batch_order(batch, kt, width=4)
    got = [struct.unpack(">i", batch.key(int(i)))[0] for i in order]
    assert got == sorted(vals)


def test_merge_batches_device_vs_host():
    kt = _raw()
    runs = []
    for s in range(4):
        recs = sorted(_random_records(100, seed=10 + s), key=lambda r: r[0])
        runs.append(_batch(recs))
    dev = merge.merge_batches(runs, kt, width=16)
    host = merge.merge_batches_host(runs, kt)
    assert list(dev.iter_records()) == list(host.iter_records())


def test_merge_iter_host_streaming():
    kt = _raw()
    runs = []
    for s in range(3):
        recs = sorted(_random_records(50, seed=20 + s), key=lambda r: r[0])
        runs.append(_batch(recs))
    streamed = list(merge.merge_iter_host(runs, kt))
    bulk = list(merge.merge_batches_host(runs, kt).iter_records())
    assert streamed == bulk


def test_merge_runs_run_ids():
    kt = _raw()
    a = _batch([(b"a", b"0"), (b"c", b"0")])
    b = _batch([(b"b", b"1"), (b"d", b"1")])
    pa = packing.pack_keys(a, kt, 8)
    pb = packing.pack_keys(b, kt, 8)
    perm, run_id = sort.merge_runs([pa, pb])
    assert perm.tolist() == [0, 2, 1, 3]
    assert run_id.tolist() == [0, 1, 0, 1]


def test_fixed_stride_terasort_layout():
    # TeraSort: 10-byte keys, 90-byte values, fully device-resident
    rng = np.random.default_rng(42)
    n = 256
    recs = [(rng.bytes(10), rng.bytes(90)) for _ in range(n)]
    batch = _batch(recs)
    kt = _raw()
    packed = packing.pack_keys(batch, kt, width=12)
    payload = packing.pack_fixed_payload(batch, stride=90)
    sorted_payload, perm = sort.sort_records_fixed(packed, payload)
    perm = np.asarray(perm)
    want_order = _host_order(batch, kt)
    assert perm.tolist() == want_order
    vals = packing.unpack_fixed_payload(np.asarray(sorted_payload),
                                        batch.val_len[perm], 90)
    assert vals == [recs[i][1] for i in want_order]


def test_pack_fixed_payload_rejects_oversize():
    batch = _batch([(b"k", b"x" * 10)])
    with pytest.raises(Exception):
        packing.pack_fixed_payload(batch, stride=8)


def test_overflow_keys_rank_before_length():
    # regression: keys longer than the width sharing a prefix must order
    # by post-width bytes (rank), not by length — b"...Z" (17B) sorts
    # AFTER b"...AB" (18B)
    kt = _raw()
    recs = [(b"prefix__prefix__Z", b"1"), (b"prefix__prefix__AB", b"2"),
            (b"prefix__prefix__", b"3"), (b"prefix__prefix__A", b"4")]
    batch = _batch(recs)
    order = merge.sorted_batch_order(batch, kt, width=16)
    got = [batch.key(int(i)) for i in order]
    assert got == sorted(k for k, _ in recs)


def test_overflow_text_keys_rank_by_content_not_serialized():
    # regression: overflow ranks must compare comparator CONTENT, not the
    # serialized key — Text's VInt length prefix must not dominate
    kt = comparators.get_key_type("org.apache.hadoop.io.Text")
    contents = [b"0123456789012345Z",   # len 17, shorter VInt prefix
                b"0123456789012345AB",  # len 18 — must sort FIRST (A < Z)
                b"0123456789012345"]
    recs = [(vint.encode_vlong(len(c)) + c, b"v") for c in contents]
    batch = _batch(recs)
    order = merge.sorted_batch_order(batch, kt, width=16)
    got = [kt.content(batch.key(int(i))) for i in order]
    assert got == sorted(contents)


def test_overflow_equal_full_keys_stable():
    kt = _raw()
    recs = [(b"prefix__prefix__XX", bytes([i])) for i in range(5)]
    recs.insert(2, (b"prefix__prefix__W", b"w"))
    batch = _batch(recs)
    order = merge.sorted_batch_order(batch, kt, width=16)
    got = [(batch.key(int(i)), batch.value(int(i))) for i in order]
    want = sorted(recs, key=lambda r: r[0])
    # equal full keys keep arrival order (stable)
    assert got == want


def test_empty_batch():
    batch = _batch([])
    order = merge.sorted_batch_order(batch, _raw(), width=8)
    assert order.shape == (0,)
    merged = merge.merge_batches([batch, batch], _raw(), width=8)
    assert merged.num_records == 0


@pytest.mark.parametrize("backend,path,want", [
    ("cpu", "auto", "carry"), ("tpu", "auto", "lanes"),
    ("cpu", "carry", "carry"), ("cpu", "lanes", "lanes"),
    ("tpu", "keys8", "keys8")])
def test_resolve_sort_path(monkeypatch, backend, path, want):
    # the whole engine policy: auto is a function of the backend alone,
    # an explicit name is honoured on either backend
    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert sort.resolve_sort_path(path) == want


@pytest.mark.parametrize("stale", ["gather", "gather2", "carrychunk",
                                   "lanes2", "keys8f", "sideways"])
def test_resolve_sort_path_rejects(stale):
    # an engine name of an older deployment fails loudly, naming the
    # three that exist
    with pytest.raises(ValueError) as e:
        sort.resolve_sort_path(stale)
    assert all(name in str(e.value) for name in sort.SORT_PATHS)
