"""Multi-chip exchange on the 8-device CPU mesh (the multi-node-without-
a-cluster capability the reference never had, SURVEY §4.5)."""

import numpy as np
import pytest

from uda_tpu.parallel import (distributed_sort_step, exchange_record_batches,
                              exchange_round, make_mesh, prepare_layout,
                              sample_splitters, shuffle_exchange,
                              uniform_splitters)
from uda_tpu.utils.errors import TransportError
from uda_tpu.utils.ifile import RecordBatch, crack, write_records

AXIS = "shuffle"


def _mesh():
    return make_mesh(8, AXIS)


def _random_words(n, w, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 2**32, size=(n, w), dtype=np.uint32)


def test_prepare_layout_counts():
    mesh = _mesh()
    n, p = 64 * 8, 8
    words = _random_words(n, 4)
    dest = (words[:, 0] % p).astype(np.int32)
    layout = prepare_layout(words, dest, mesh, AXIS)
    counts = np.asarray(layout.counts)
    assert counts.shape == (p, p)
    # row i = histogram of dest among device i's shard
    shard = n // p
    for i in range(p):
        want = np.bincount(dest[i * shard:(i + 1) * shard], minlength=p)
        assert counts[i].tolist() == want.tolist()


def test_single_round_exchange_regroups():
    mesh = _mesh()
    p, shard = 8, 32
    words = _random_words(p * shard, 3, seed=1)
    dest = (words[:, 1] % p).astype(np.int32)
    layout = prepare_layout(words, dest, mesh, AXIS)
    cap = int(layout.counts.max())
    recv, recv_counts = exchange_round(layout, cap, 0)
    recv = np.asarray(recv).reshape(p, p, cap, 3)   # [dst, src, slot, w]
    recv_counts = np.asarray(recv_counts).reshape(p, p)
    got = {d: [] for d in range(p)}
    for d in range(p):
        for s in range(p):
            for i in range(recv_counts[d, s]):
                got[d].append(tuple(recv[d, s, i]))
    for d in range(p):
        want = sorted(map(tuple, words[dest == d]))
        assert sorted(got[d]) == want


def test_multi_round_skew_all_to_one():
    mesh = _mesh()
    p, shard = 8, 16
    words = _random_words(p * shard, 2, seed=2)
    dest = np.zeros(p * shard, np.int32)  # extreme skew: everything to 0
    results, layout = shuffle_exchange(words, dest, mesh, AXIS, capacity=4)
    assert len(results) == 4  # 16 per bucket / capacity 4
    collected = []
    for recv, counts in results:
        recv = np.asarray(recv).reshape(p, p, 4, 2)
        counts = np.asarray(counts).reshape(p, p)
        for s in range(p):
            for i in range(counts[0, s]):
                collected.append(tuple(recv[0, s, i]))
        # nothing lands on devices != 0
        assert counts[1:].sum() == 0
    assert sorted(collected) == sorted(map(tuple, words))


def test_shuffle_exchange_max_rounds_guard():
    mesh = _mesh()
    words = _random_words(64, 2, seed=3)
    dest = np.zeros(64, np.int32)
    with pytest.raises(TransportError):
        shuffle_exchange(words, dest, mesh, AXIS, capacity=1, max_rounds=2)


def test_distributed_sort_step_total_order():
    mesh = _mesh()
    p = 8
    n = p * 128
    words = _random_words(n, 5, seed=4)  # 3 key words + 2 payload words
    splitters = uniform_splitters(p)
    res = distributed_sort_step(words, splitters, mesh, AXIS,
                                capacity=n // p, num_keys=3)
    res.check()
    out = np.asarray(res.words).reshape(p, -1, 5)
    nvalid = np.asarray(res.valid_counts).reshape(-1)
    rows = [out[d, :nvalid[d]] for d in range(p)]
    got = np.concatenate(rows)
    assert got.shape[0] == n
    # global total order on the 3 key words
    keys = [tuple(r[:3]) for r in got]
    assert keys == sorted(keys)
    # the full multiset of records survived
    assert sorted(map(tuple, got)) == sorted(map(tuple, words))


def test_distributed_sort_step_overflow_detected():
    mesh = _mesh()
    p = 8
    words = _random_words(p * 64, 2, seed=5)
    words[:, 0] = 0  # all keys in partition 0 -> massive skew
    res = distributed_sort_step(words, uniform_splitters(p), mesh, AXIS,
                                capacity=8, num_keys=1, multiround="never")
    with pytest.raises(TransportError):
        res.check()


@pytest.mark.slow
def test_distributed_sort_auto_multiround_completes_skew():
    # same massive skew, default policy: the multi-round backlog path
    # must drain it completely with capacity << bucket size
    mesh = _mesh()
    p = 8
    n = p * 64
    words = _random_words(n, 3, seed=15)
    words[:, 0] = 0  # every record to partition 0
    res = distributed_sort_step(words, uniform_splitters(p), mesh, AXIS,
                                capacity=8, num_keys=1)
    res.check()
    out = np.asarray(res.words).reshape(p, -1, 3)
    nvalid = np.asarray(res.valid_counts).reshape(-1)
    assert nvalid[0] == n and nvalid[1:].sum() == 0
    got = out[0, :n]
    assert sorted(map(tuple, got)) == sorted(map(tuple, words))
    keys = got[:, 0].tolist()
    assert keys == sorted(keys)


@pytest.mark.slow
def test_multiround_matches_fused_exactly():
    # on non-overflowing data, "always" must produce the same per-shard
    # valid rows as the fused single-round program (incl. duplicate-key
    # (src, arrival) stability)
    mesh = _mesh()
    p = 8
    n = p * 64
    words = _random_words(n, 4, seed=16)
    words[: n // 2, 0] = words[n // 2:, 0]  # duplicate first key words
    spl = uniform_splitters(p)
    fused = distributed_sort_step(words, spl, mesh, AXIS, capacity=n // p,
                                  num_keys=2, multiround="never")
    fused.check()
    multi = distributed_sort_step(words, spl, mesh, AXIS, capacity=16,
                                  num_keys=2, multiround="always")
    multi.check()
    fw = np.asarray(fused.words).reshape(p, -1, 4)
    mw = np.asarray(multi.words).reshape(p, -1, 4)
    fv = np.asarray(fused.valid_counts).reshape(-1)
    mv = np.asarray(multi.valid_counts).reshape(-1)
    assert fv.tolist() == mv.tolist()
    for d in range(p):
        np.testing.assert_array_equal(fw[d, :fv[d]], mw[d, :mv[d]])


@pytest.mark.slow
def test_lanes_payload_path_matches_carry_exactly():
    # the Pallas lanes engine (interpret mode on the CPU mesh) must
    # reproduce the carry path byte-for-byte: identical sort key
    # (masked key words, invalid flag) and identical equal-key arrival
    # order — including the invalid tail rows and the non-power-of-two
    # shard sizes that exercise the +inf lane padding
    mesh = _mesh()
    p = 8
    n = p * 48  # cap = n//p = 48, so each shard sorts p*cap = 384 rows:
    #             not a power of two -> exercises the +inf lane padding
    words = _random_words(n, 5, seed=23)
    words[: n // 2, 0] = words[n // 2:, 0]  # duplicate first key words
    spl = uniform_splitters(p)
    kw = dict(capacity=n // p, num_keys=2, multiround="never")
    carry = distributed_sort_step(words, spl, mesh, AXIS,
                                  payload_path="carry", **kw)
    carry.check()
    lanes = distributed_sort_step(words, spl, mesh, AXIS,
                                  payload_path="lanes", **kw)
    lanes.check()
    np.testing.assert_array_equal(np.asarray(carry.valid_counts),
                                  np.asarray(lanes.valid_counts))
    np.testing.assert_array_equal(np.asarray(carry.words),
                                  np.asarray(lanes.words))


@pytest.mark.slow
def test_lanes_payload_path_multiround_skew():
    # lanes engine under the windowed multi-round accumulator sort
    mesh = _mesh()
    p = 8
    n = p * 64
    words = _random_words(n, 3, seed=24)
    words[:, 0] = 0  # every record to partition 0
    res = distributed_sort_step(words, uniform_splitters(p), mesh, AXIS,
                                capacity=8, num_keys=1,
                                payload_path="lanes")
    res.check()
    out = np.asarray(res.words).reshape(p, -1, 3)
    nvalid = np.asarray(res.valid_counts).reshape(-1)
    assert nvalid[0] == n and nvalid[1:].sum() == 0
    got = out[0, :n]
    assert sorted(map(tuple, got)) == sorted(map(tuple, words))
    assert got[:, 0].tolist() == sorted(got[:, 0].tolist())


def test_sample_splitters_balance():
    rng = np.random.default_rng(6)
    # skewed distribution: half the mass near zero
    w0 = np.concatenate([rng.integers(0, 1000, 5000),
                         rng.integers(0, 2**32, 5000)]).astype(np.uint32)
    spl = sample_splitters(w0, 8)
    assert spl.shape == (7,)
    assert (np.sort(spl) == spl).all()
    dest = np.searchsorted(spl, w0, side="right")
    counts = np.bincount(dest, minlength=8)
    assert counts.max() < 0.35 * w0.size  # vs 0.625 with uniform splitters


def test_exchange_record_batches_host():
    def batch(recs):
        return crack(write_records(recs))

    by_dest = [
        [batch([(b"a", b"1")]), batch([(b"b", b"2")])],
        [batch([(b"c", b"3")]), batch([])],
    ]
    out = exchange_record_batches(by_dest)
    assert [list(b.iter_records()) for b in out] == [
        [(b"a", b"1"), (b"c", b"3")],
        [(b"b", b"2")],
    ]


def test_lanes_engines_type_check_with_check_vma():
    # the real (interpret=False) lanes path must trace clean under
    # shard_map's strict varying-manual-axes checker — the r4 wholesale
    # bypass is now scoped to interpret mode only (the Pallas
    # interpreter's own grid dynamic_slice mis-types; committed repro:
    # scripts/repro_check_vma.py). eval_shape runs the vma check at
    # trace time without compiling any Mosaic kernel, so this pins the
    # property on CPU.
    from functools import partial

    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from uda_tpu.parallel import shard_map

    from uda_tpu.parallel import distributed as D

    mesh = make_mesh(8, AXIS)
    n = 8 * 4096  # multiple tiles per shard: the merge fori_loop engages
    spec = jax.ShapeDtypeStruct((n, 4), jnp.uint32)
    for eng in ("lanes", "keys8"):
        @partial(shard_map, mesh=mesh, in_specs=(P(AXIS),),
                 out_specs=P(AXIS), check_vma=True)
        def go(w, eng=eng):
            row = jnp.arange(w.shape[0], dtype=jnp.int32)
            return D._sort_valid_rows(w, row >= 0, 2, eng,
                                      interpret=False)

        out = jax.eval_shape(go, spec)
        assert out.shape == (n, 4)


@pytest.mark.slow
def test_two_axis_dcn_ici_mesh_matches_flat():
    # multi-pod shape: a (dcn=2, shuffle=4) mesh with rows sharded over
    # BOTH axes must produce byte-identical results to the flat 8-way
    # mesh (XLA routes the all_to_all per axis: ICI within a pod, DCN
    # across; the exchange logic only sees the linearized device index)
    import jax
    from jax.sharding import Mesh

    devs = np.array(jax.devices())
    if devs.size < 8:
        pytest.skip("needs 8 devices")
    mesh1 = Mesh(devs[:8].reshape(8), (AXIS,))
    mesh2 = Mesh(devs[:8].reshape(2, 4), ("dcn", AXIS))
    words = _random_words(1024, 4, seed=29)
    spl = uniform_splitters(8)
    r1 = distributed_sort_step(words, spl, mesh1, AXIS, capacity=256,
                               num_keys=2)
    r1.check()
    r2 = distributed_sort_step(words, spl, mesh2, ("dcn", AXIS),
                               capacity=256, num_keys=2)
    r2.check()
    np.testing.assert_array_equal(np.asarray(r1.words),
                                  np.asarray(r2.words))
    np.testing.assert_array_equal(np.asarray(r1.valid_counts),
                                  np.asarray(r2.valid_counts))
    # skew across both axes engages the multi-round path
    skew = _random_words(512, 3, seed=30)
    skew[:, 0] = 0
    r3 = distributed_sort_step(skew, spl, mesh2, ("dcn", AXIS),
                               capacity=16, num_keys=1)
    r3.check()
    nv = np.asarray(r3.valid_counts).reshape(-1)
    assert nv[0] == 512 and nv[1:].sum() == 0


@pytest.mark.slow
@pytest.mark.parametrize("seed", [41, 42, 43])
def test_distributed_sort_randomized_boundaries(seed):
    # randomized shapes/capacities around the rounding boundaries the
    # dryrun's tiny shapes never reach: per-device rows not divisible
    # by p, capacities exactly at / one under the max bucket, duplicate
    # keys, and 1-record buckets
    rng = np.random.default_rng(seed)
    mesh = _mesh()
    p = 8
    n = p * int(rng.integers(50, 400))
    w = int(rng.integers(2, 7))
    nk = int(rng.integers(1, min(3, w) + 1))
    words = _random_words(n, w, seed=seed)
    if seed % 2:
        # heavy duplication stresses stability + splitter ties
        words[:, 0] = rng.integers(0, 5, size=n).astype(np.uint32) << 29
    spl = uniform_splitters(p)
    # max bucket size determines the exact-fit capacity
    dest = np.searchsorted(spl, words[:, 0], side="right")
    shard = n // p
    counts = np.zeros((p, p), np.int64)
    for s in range(p):
        np.add.at(counts[s], dest[s * shard:(s + 1) * shard], 1)
    maxb = int(counts.max())
    for cap in (maxb, max(1, maxb - 1), max(1, maxb // 3)):
        res = distributed_sort_step(words, spl, mesh, AXIS, capacity=cap,
                                    num_keys=nk)
        res.check()
        out = np.asarray(res.words).reshape(p, -1, w)
        nv = np.asarray(res.valid_counts).reshape(-1)
        got = np.concatenate([out[d, :nv[d]] for d in range(p)])
        assert got.shape[0] == n, (cap, got.shape)
        keys = [tuple(r[:nk]) for r in got]
        assert keys == sorted(keys), f"cap={cap}: unsorted"
        assert sorted(map(tuple, got)) == sorted(map(tuple, words)), \
            f"cap={cap}: multiset changed"


def test_distributed_sort_realistic_size():
    # 64K x 6-word records over the 8-device mesh — two orders of
    # magnitude beyond the dryrun's 1,024-record shapes; checks order,
    # multiset survival and the per-device partition totality contract
    # (every record lands on exactly the device its key range owns,
    # reference MOFServlet.cc:28-96)
    mesh = _mesh()
    p, n, w = 8, 1 << 16, 6
    words = _random_words(n, w, seed=55)
    spl = uniform_splitters(p)
    res = distributed_sort_step(words, spl, mesh, AXIS,
                                capacity=2 * n // (p * p), num_keys=3)
    res.check()
    out = np.asarray(res.words).reshape(p, -1, w)
    nv = np.asarray(res.valid_counts).reshape(-1)
    edges = np.concatenate([[0], spl.astype(np.uint64), [1 << 32]])
    rows = []
    for d in range(p):
        shard = out[d, :nv[d]]
        rows.append(shard)
        if nv[d]:
            assert shard[:, 0].astype(np.uint64).min() >= edges[d]
            assert shard[:, 0].astype(np.uint64).max() < edges[d + 1]
    got = np.concatenate(rows)
    assert got.shape[0] == n
    keys = got[:, :3]
    assert np.array_equal(
        keys, keys[np.lexsort((keys[:, 2], keys[:, 1], keys[:, 0]))])
    # true ROW multiset check (per-column sorts would miss payload words
    # swapped between records — the gather-bug corruption class)
    def by_rows(a):
        return a[np.lexsort(tuple(a[:, c] for c in range(w - 1, -1, -1)))]

    assert np.array_equal(by_rows(got), by_rows(words))


@pytest.mark.parametrize("engine", ["carry", "lanes", "keys8"])
def test_distributed_sort_step_engine_matches_host_oracle(engine):
    # each engine behind the step against the host oracle: shard d is
    # range partition d in np.lexsort's (stable) order. Half the rows
    # share their whole key with an earlier row of another source
    # device, so equal-key order — source device, then arrival — is
    # checked; 384 rows a shard is not a power of two, so the Pallas
    # engines' +inf lane padding engages
    mesh = _mesh()
    p = 8
    n = p * 48
    words = _random_words(n, 5, seed=67)
    words[n // 2:, :2] = words[: n // 2, :2]
    spl = uniform_splitters(p)
    res = distributed_sort_step(words, spl, mesh, AXIS, capacity=n // p,
                                num_keys=2, multiround="never",
                                payload_path=engine)
    res.check()
    out = np.asarray(res.words).reshape(p, -1, 5)
    nvalid = np.asarray(res.valid_counts).reshape(-1)
    dest = np.searchsorted(spl, words[:, 0], side="right")
    for d in range(p):
        mine = words[dest == d]
        want = mine[np.lexsort((mine[:, 1], mine[:, 0]))]
        np.testing.assert_array_equal(out[d, :nvalid[d]], want,
                                      err_msg=f"shard {d}")


def _scatter_round_body(w, d, q, lo, axis, capacity):
    """The plain reference of ``window_round_body``: the send buffer
    scattered row by row at ``(destination, in-window slot)``, rows
    outside the window onto a drop row — how the round was written
    before its windows were read as slices (PR 33). It needs no order."""
    import jax.numpy as jnp
    from jax import lax

    p = lax.psum(1, axis)
    in_round = (q >= lo) & (q < lo + capacity)
    slot = jnp.where(in_round, q - lo, capacity)
    send = jnp.zeros((p, capacity + 1, w.shape[1]), w.dtype)
    send = send.at[d, slot].set(w, mode="drop")
    send_counts = jnp.bincount(
        jnp.where(in_round, d, p), length=p + 1)[:p].astype(jnp.int32)
    recv = lax.all_to_all(send[:, :capacity], axis, split_axis=0,
                          concat_axis=0, tiled=False)
    recv_counts = lax.all_to_all(send_counts[:, None], axis, split_axis=0,
                                 concat_axis=0, tiled=False).reshape(p)
    return recv.reshape(p * capacity, w.shape[1]), recv_counts


# (local rows, capacity, window index, how a device's rows pick their
# destinations)
_WINDOW_CASES = {
    "capacity_over_rows": (24, 40, 0, "uniform"),
    "bucket_over_capacity_window0": (48, 8, 0, "skew"),
    "bucket_over_capacity_window1": (48, 8, 1, "skew"),
    "bucket_over_capacity_window2": (48, 8, 2, "skew"),
    "empty_bucket": (32, 16, 0, "skip_last"),
    "all_to_one": (32, 12, 0, "one"),
    "all_to_one_window2": (32, 12, 2, "one"),
    "window_past_every_bucket": (16, 4, 5, "uniform"),
}


def _case_dest(kind, rng, n, p, device):
    if kind == "uniform":
        return rng.integers(0, p, size=n)
    if kind == "skew":      # over half to one destination: > 3 windows
        hot = (device + 1) % p
        return np.where(rng.random(n) < 0.6, hot, rng.integers(0, p, size=n))
    if kind == "skip_last":                 # nobody sends to p - 1
        return rng.integers(0, max(p - 1, 1), size=n)
    return np.full(n, p // 2)               # "one"


@pytest.mark.parametrize("case", sorted(_WINDOW_CASES))
@pytest.mark.parametrize("p", [1, 2, 4, 8])
def test_window_round_body_delivers_what_the_scatter_does(p, case):
    # flat WHOLE — the zeros past each peer's count included, they ride
    # through the sort as invalid rows' payload — and recv_counts, on
    # rows in _bucket_local's order (window_round_body's precondition)
    from functools import partial

    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from uda_tpu.parallel import shard_map
    from uda_tpu.parallel.exchange import window_round_body

    n, capacity, window, kind = _WINDOW_CASES[case]
    rng = np.random.default_rng(1000 * p + sorted(_WINDOW_CASES).index(case))
    dest = np.concatenate([_case_dest(kind, rng, n, p, device)
                           for device in range(p)]).astype(np.int32)
    words = rng.integers(1, 2**32, size=(p * n, 3), dtype=np.uint32)
    mesh = make_mesh(p, AXIS)
    # the order every caller hands the body: the program's own bucketing
    layout = prepare_layout(words, dest, mesh, AXIS)
    if kind == "skew":
        assert layout.counts.max() > 2 * capacity

    def run(body):
        @jax.jit
        @partial(shard_map, mesh=mesh,
                 in_specs=(P(AXIS), P(AXIS), P(AXIS), P()),
                 out_specs=(P(AXIS), P(AXIS)))
        def go(w, d, q, lo):        # lo traced, as the round programs do
            flat, counts = body(w, d, q, lo[0], AXIS, capacity)
            return flat, counts.reshape(1, -1)

        flat, counts = go(layout.words, layout.dest, layout.pos,
                          jnp.asarray([window * capacity], jnp.int32))
        return np.asarray(flat), np.asarray(counts)

    got_flat, got_counts = run(window_round_body)
    want_flat, want_counts = run(_scatter_round_body)
    np.testing.assert_array_equal(got_counts, want_counts)
    np.testing.assert_array_equal(got_flat, want_flat)
    assert got_flat.shape == (p * p * capacity, 3)
    # and the reference itself delivers what the counts say it should:
    # recv_counts[dst, src] = the part of bucket (src, dst) in the window
    np.testing.assert_array_equal(
        want_counts,
        np.clip(layout.counts.T - window * capacity, 0, capacity))


# -- the fused step sorts first and merges after (PR 35): held to the
# step that partitioned, permuted, exchanged and then sorted, kept as
# tests/exchange_parent.py ---------------------------------------------

_STEP_KEYS, _STEP_WIDTH, _STEP_ROWS = 2, 4, 48     # rows a chip


def _step_words(case, p, seed):
    """(words, capacity): 2 key words, the global row number (tells equal
    keys apart), one payload word."""
    rng = np.random.default_rng(seed)
    n = p * _STEP_ROWS
    words = rng.integers(0, 2**32, size=(n, _STEP_WIDTH), dtype=np.uint32)
    words[:, _STEP_KEYS] = np.arange(n)
    capacity = _STEP_ROWS           # a window holds a source's whole shard
    if case == "zipf":              # ids, one shared first word, hot keys
        words[:, 0] = 7
        words[:, 1] = np.floor(2 ** (10 * rng.random(n)))
    elif case == "one_key":
        words[:, :_STEP_KEYS] = 5
    elif case == "empty_bucket":    # nothing for the last chip
        words[:, 0] = rng.integers(0, 2**32 // p, size=n)
    elif case == "bucket_exactly_capacity":
        # every row of chip 0 goes to the last chip: a full window
        words[:, 0] = rng.integers(0, 2**32 // p, size=n)
        words[:capacity, 0] = 2**32 - 1 - rng.integers(0, 99, size=capacity)
    elif case == "ties_across_chips":   # every key on every source chip
        words[:, 0] = (np.arange(n) % 5) * (2**32 // 5)
        words[:, 1] = np.arange(n) % 3
    else:
        assert case == "uniform"
    return words, capacity


_STEP_CASES = ("uniform", "zipf", "one_key", "empty_bucket",
               "bucket_exactly_capacity", "ties_across_chips")


def _step_grew(before):
    from uda_tpu.utils.metrics import metrics

    after = metrics.snapshot()
    return {k: after[k] - before.get(k, 0) for k in after}


@pytest.mark.parametrize("case", _STEP_CASES)
@pytest.mark.parametrize("p", [1, 2, 4, 8])
@pytest.mark.parametrize("engine", ["carry", "lanes"])
def test_fused_step_equals_the_scatter_then_sort_step(engine, p, case):
    from tests.exchange_parent import scatter_then_sort_step
    from uda_tpu.parallel import distributed as D
    from uda_tpu.utils.metrics import metrics

    mesh = make_mesh(p, AXIS)
    words, capacity = _step_words(case, p, seed=10 * p + len(case))
    spl = uniform_splitters(p)
    before = metrics.snapshot()
    res = distributed_sort_step(words, spl, mesh, AXIS, capacity=capacity,
                                num_keys=_STEP_KEYS, multiround="never",
                                payload_path=engine)
    res.check()
    grew = _step_grew(before)
    # the runs a chip merged: one a source on lanes; 0, not nothing, on
    # an engine that sorts them again
    assert "exchange.merge.runs" in grew
    assert grew["exchange.merge.runs"] == (p if engine == "lanes" else 0)
    # merge passes whose kernel carried its split: the local sort's and
    # the receive side's log2(p); 0, not nothing, on carry
    from uda_tpu.ops.pallas_sort import runs_passes, sort_passes
    assert grew["sort.passes.carried"] == (
        sort_passes(len(words) // p) + runs_passes(p)
        if engine == "lanes" else 0)
    want, want_nvalid, want_over, want_spl = scatter_then_sort_step(
        words, D._whole_keys(spl, _STEP_KEYS), mesh, AXIS, capacity,
        _STEP_KEYS, engine, interpret=engine == "lanes")
    # row for row, the zero rows behind each shard's valid ones included
    np.testing.assert_array_equal(np.asarray(res.words), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(res.valid_counts),
                                  np.asarray(want_nvalid))
    np.testing.assert_array_equal(np.asarray(res.send_overflow),
                                  np.asarray(want_over))
    np.testing.assert_array_equal(np.asarray(res.splitters),
                                  np.asarray(want_spl))
    assert int(np.asarray(res.valid_counts).sum()) == len(words)
    if case == "bucket_exactly_capacity":
        dest = np.searchsorted(spl, words[:_STEP_ROWS, 0], side="right")
        assert (dest == p - 1).sum() == capacity


@pytest.mark.parametrize("p", [2, 4, 8])
@pytest.mark.parametrize("engine", ["carry", "lanes"])
def test_fused_step_overflow_reruns_with_the_same_splitters(engine, p):
    from tests.exchange_parent import scatter_then_sort_step
    from uda_tpu.parallel import distributed as D
    from uda_tpu.utils.metrics import metrics

    # a window a sixth of a source's rows: the fused attempt reports the
    # parent's overflow (WHICH rows it dropped is its own business: they
    # are the bucket's largest keys now, its last arrivals then), and the
    # rerun through the rounds gives what a window that fits gives
    mesh = make_mesh(p, AXIS)
    words, _ = _step_words("zipf", p, seed=p)
    spl = uniform_splitters(p)
    capacity = _STEP_ROWS // 6
    lost = distributed_sort_step(words, spl, mesh, AXIS, capacity=capacity,
                                 num_keys=_STEP_KEYS, multiround="never",
                                 payload_path=engine)
    _, _, want_over, _ = scatter_then_sort_step(
        words, D._whole_keys(spl, _STEP_KEYS), mesh, AXIS, capacity,
        _STEP_KEYS, engine, interpret=engine == "lanes")
    assert lost.overflow() == int(np.asarray(want_over).sum()) > 0
    np.testing.assert_array_equal(np.asarray(lost.send_overflow),
                                  np.asarray(want_over))
    before = metrics.snapshot()
    res = distributed_sort_step(words, spl, mesh, AXIS, capacity=capacity,
                                num_keys=_STEP_KEYS, payload_path=engine)
    res.check()
    grew = _step_grew(before)
    assert grew["exchange.fused.overflow_reruns"] == 1
    assert grew["exchange.merge.runs"] == (p if engine == "lanes" else 0)
    fits = distributed_sort_step(words, spl, mesh, AXIS, capacity=_STEP_ROWS,
                                 num_keys=_STEP_KEYS, multiround="never",
                                 payload_path=engine)
    np.testing.assert_array_equal(np.asarray(res.splitters),
                                  np.asarray(fits.splitters))
    nv = np.asarray(res.valid_counts).reshape(-1)
    np.testing.assert_array_equal(nv, np.asarray(fits.valid_counts))
    got = np.asarray(res.words).reshape(p, -1, _STEP_WIDTH)
    want = np.asarray(fits.words).reshape(p, -1, _STEP_WIDTH)
    for d in range(p):
        np.testing.assert_array_equal(got[d, :nv[d]], want[d, :nv[d]])


def _eqns(jaxpr):
    """Every equation of a jaxpr, those of its sub-jaxprs included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


@pytest.mark.parametrize("sample", [False, True])
def test_lanes_step_neither_sorts_nor_gathers_its_input_rows(sample):
    # sorted rows are in destination order as they stand: the traced
    # lanes step holds no XLA sort (no argsort of the destinations) and
    # no gather out of an [n, W] or a per-row [n] operand (no take of
    # the rows or of their destinations); the Pallas kernels and the
    # sample's 100,000-row reads are all that is left
    import jax
    import jax.numpy as jnp

    from uda_tpu.parallel import distributed as D

    p, n_local, w = 4, 32768, 6     # more rows a chip than it samples
    mesh = make_mesh(p, AXIS)
    words = jax.ShapeDtypeStruct((p * n_local, w), jnp.uint32)
    spl = jax.ShapeDtypeStruct((p - 1, 3), jnp.uint32)
    jaxpr = jax.make_jaxpr(
        lambda a, b: D._sort_step(a, b, mesh, AXIS, 2 * n_local // p, 3,
                                  "lanes", interpret=False, sample=sample))(
        words, spl)
    names = [e.primitive.name for e in _eqns(jaxpr.jaxpr)]
    assert "pallas_call" in names
    assert "sort" not in names
    for e in _eqns(jaxpr.jaxpr):
        if e.primitive.name == "gather":
            operand = e.invars[0].aval.shape
            assert operand not in ((n_local, w), (n_local,)) or \
                e.outvars[0].aval.shape[0] < n_local, (operand, e)
    # and the reference does hold them, so the walk sees what it claims
    from tests.exchange_parent import scatter_then_sort_step
    old = jax.make_jaxpr(
        lambda a, b: scatter_then_sort_step(a, b, mesh, AXIS,
                                            2 * n_local // p, 3, "lanes",
                                            interpret=False, sample=sample))(
        words, spl)
    old_names = [e.primitive.name for e in _eqns(old.jaxpr)]
    assert "sort" in old_names
    assert any(e.primitive.name == "gather"
               and e.invars[0].aval.shape == (n_local, w)
               and e.outvars[0].aval.shape == (n_local, w)
               for e in _eqns(old.jaxpr))
