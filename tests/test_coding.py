"""Survivable shuffle (ISSUE 8): k-of-n erasure-coded map outputs
(uda_tpu.coding — GF(2^8) Reed-Solomon codec, striped layout, v2
index, stripe-aware recovery), speculative dual-source fetch, and
supplier warm-restart with fetch-epoch handoff.

The ``faults``-marked rungs double as the chaos COMPLETION tier
(scripts/run_chaos.sh): a seeded supplier kill or bounce must end in a
byte-correct finished job — recovery counters > 0 and zero
FallbackSignals — not merely a clean fallback.
"""

import io
import itertools
import os
import random
import threading
import time

import numpy as np
import pytest

from tests.helpers import make_mof_tree, map_ids
from uda_tpu.coding import (CodingScheme, parse_scheme, shard_map_id,
                            parse_shard_id, stripe_host)
from uda_tpu.coding import gf256, rs
from uda_tpu.coding.recovery import StripeContext
from uda_tpu.merger import (HostRoutingClient, LocalFetchClient,
                            MergeManager, PenaltyBox, RecoveryLedger,
                            Segment)
from uda_tpu.mofserver import (DataEngine, DirIndexResolver, FetchResult,
                               ShuffleRequest, read_index_file,
                               write_index_file)
from uda_tpu.mofserver.writer import (write_map_output,
                                      write_striped_map_output)
from uda_tpu.utils.config import Config
from uda_tpu.utils.errors import (ConfigError, FallbackSignal,
                                  StorageError, TransportError)
from uda_tpu.utils.failpoints import failpoints
from uda_tpu.utils.ifile import IFileReader
from uda_tpu.utils.metrics import metrics
from uda_tpu.utils.retry import RetryPolicy, SpeculationPolicy

JOB = "job_coding"


# -- GF(2^8) + RS codec ------------------------------------------------------

def test_gf256_field_properties():
    # alpha = 2 generates the full multiplicative group of 255 elements
    assert len(set(gf256.EXP[:255].tolist())) == 255
    rng = random.Random(0)
    for _ in range(500):
        a = rng.randrange(256)
        b = rng.randrange(1, 256)
        c = rng.randrange(256)
        assert gf256.gf_mul(gf256.gf_mul(a, b), gf256.gf_inv(b)) == a
        # distributivity over XOR (the field's addition)
        assert gf256.gf_mul(a, b ^ c) == \
            gf256.gf_mul(a, b) ^ gf256.gf_mul(a, c)
    with pytest.raises(ZeroDivisionError):
        gf256.gf_inv(0)


def test_gf256_matrix_inverse():
    for k in (1, 2, 4, 7):
        a = rs.parity_matrix(k, 2 * k)  # a k x k Cauchy minor
        inv = gf256.inv_matrix(a)
        prod = gf256.matmul(a, inv)
        assert np.array_equal(prod, np.eye(k, dtype=np.uint8))
    with pytest.raises(np.linalg.LinAlgError):
        gf256.inv_matrix(np.zeros((2, 2), dtype=np.uint8))


@pytest.mark.parametrize("k,n", [(1, 1), (1, 3), (2, 3), (4, 6), (3, 3),
                                 (2, 5)])
def test_rs_roundtrip_every_erasure_pattern(k, n):
    """Any k of the n stripe chunks reconstruct the blob — exhaustively
    over every k-subset (the MDS property), over sizes that exercise
    empty, sub-chunk, unaligned and multi-chunk stripes."""
    rng = random.Random(42)
    for size in (0, 1, 17, 256, 1025):
        blob = rng.randbytes(size)
        chunks = {i: c for i, c in enumerate(rs.split_data(blob, k))}
        chunks.update({k + j: p for j, p in
                       enumerate(rs.encode_parity(blob, k, n))})
        assert len(chunks) == n
        for subset in itertools.combinations(range(n), k):
            got = rs.decode({i: chunks[i] for i in subset}, k, n, size)
            assert got == blob, (k, n, size, subset)


def test_rs_systematic_identity_and_failure_modes():
    blob = bytes(range(256)) * 3
    # n == k: no parity, decode of the data chunks is pure concat
    assert rs.encode_parity(blob, 4, 4) == []
    data = {i: c for i, c in enumerate(rs.split_data(blob, 4))}
    assert rs.decode(data, 4, 4, len(blob)) == blob
    # fewer than k chunks is typed, loud, and names the shortfall
    with pytest.raises(StorageError, match="unrecoverable"):
        rs.decode({0: data[0]}, 4, 6, len(blob))
    with pytest.raises(StorageError):
        rs.decode({0: data[0], 9: b"x"}, 4, 6, len(blob))  # bad index


def test_scheme_parsing():
    assert parse_scheme("") is None and parse_scheme(None) is None
    s = parse_scheme("rs:4:6")
    assert s == CodingScheme(4, 6) and s.parity == 2
    assert str(s) == "rs:4:6"
    for bad in ("rs:0:4", "rs:5:4", "xor:2:3", "rs:4", "rs:a:b"):
        with pytest.raises(ConfigError):
            parse_scheme(bad)


def test_shard_ids_and_placement():
    assert parse_shard_id(shard_map_id("m_01", 3)) == ("m_01", 3)
    assert parse_shard_id("m_01") is None
    hosts = ["a", "b", "c"]
    assert [stripe_host(hosts, "b", i) for i in range(4)] == \
        ["b", "c", "a", "b"]
    assert stripe_host([], "x", 2) == "x"  # degenerate: no universe


# -- v2 index + striped layout ----------------------------------------------

def test_index_v2_roundtrip_and_v1_back_compat(tmp_path):
    idx = str(tmp_path / "file.out.index")
    triples = [(0, 100, 100), (100, 57, 57)]
    locators = [[(200, 25), (225, 25)], [(250, 15), (265, 15)]]
    write_index_file(idx, triples, stripe=(4, 6, locators))
    recs = read_index_file(idx, "/mof")
    assert [(r.start_offset, r.raw_length, r.part_length) for r in recs] \
        == triples
    assert recs[0].stripe.k == 4 and recs[0].stripe.n == 6
    assert recs[1].stripe.parity == ((250, 15), (265, 15))
    # v1 files keep reading exactly as before, stripe-less
    write_index_file(idx, triples)
    recs = read_index_file(idx, "/mof")
    assert recs[0].stripe is None and recs[1].part_length == 57


def _records(num, seed=0, val=24):
    rng = np.random.default_rng(seed)
    return sorted((rng.bytes(10), rng.bytes(val)) for _ in range(num))


def test_parity_section_keeps_data_region_byte_identical(tmp_path):
    recs = [_records(80, 1), _records(50, 2)]
    plain, coded, chunked = (str(tmp_path / d) for d in ("p", "c", "k"))
    t_plain = write_map_output(plain, recs)
    t_coded = write_map_output(coded, recs, scheme=parse_scheme("rs:4:6"))
    t_chunk = write_map_output(chunked, recs, scheme=parse_scheme("rs:4:4"))
    assert t_plain == t_coded == t_chunk  # data triples untouched
    raw_plain = open(os.path.join(plain, "file.out"), "rb").read()
    raw_coded = open(os.path.join(coded, "file.out"), "rb").read()
    raw_chunk = open(os.path.join(chunked, "file.out"), "rb").read()
    # the data region is byte-identical; parity is strictly appended
    assert raw_coded[:len(raw_plain)] == raw_plain
    assert len(raw_coded) > len(raw_plain)
    # rs:k:k has zero parity -> the whole file is byte-identical
    assert raw_chunk == raw_plain


def test_resolver_synthesizes_shards_from_primary(tmp_path):
    """On the full-stripe holder no shard bytes exist on disk: data
    chunks resolve as slices of the partition range, parity chunks as
    parity-section ranges, and the served bytes equal the codec's."""
    scheme = parse_scheme("rs:3:5")
    recs = [_records(60, 3)]
    write_map_output(str(tmp_path / JOB / "m0"), recs, scheme=scheme)
    eng = DataEngine(DirIndexResolver(str(tmp_path)), Config())
    try:
        full = eng.fetch(ShuffleRequest(JOB, "m0", 0, 0, 1 << 20)).data
        data = rs.split_data(bytes(full), 3)
        parity = rs.encode_parity(bytes(full), 3, 5)
        for i in range(5):
            got = eng.fetch(ShuffleRequest(JOB, shard_map_id("m0", i),
                                           0, 0, 1 << 20))
            want = data[i] if i < 3 else parity[i - 3]
            assert bytes(got.data) == want, f"chunk {i}"
            assert got.raw_length == len(full)  # the decode-trim total
    finally:
        eng.stop()


def test_striped_fanout_places_chunks_on_peers(tmp_path):
    scheme = parse_scheme("rs:2:4")
    roots = [str(tmp_path / f"r{i}") for i in range(4)]
    recs = [_records(40, 4)]
    write_striped_map_output(roots, 1, JOB, "m7", recs, scheme)
    # primary root holds the full MOF (+ parity); peers hold shards
    assert os.path.exists(os.path.join(roots[1], JOB, "m7", "file.out"))
    blob = open(os.path.join(roots[1], JOB, "m7", "file.out"), "rb").read()
    data_len = read_index_file(
        os.path.join(roots[1], JOB, "m7", "file.out.index"),
        "x")[0].part_length
    data = rs.split_data(blob[:data_len], 2)
    parity = rs.encode_parity(blob[:data_len], 2, 4)
    # chunk i -> root (1 + i) % 4; chunk 0 stays on the primary
    # (synthesized, no shard dir)
    assert not os.path.exists(os.path.join(roots[1], JOB,
                                           shard_map_id("m7", 0)))
    for i, want in [(1, data[1]), (2, parity[0]), (3, parity[1])]:
        d = os.path.join(roots[(1 + i) % 4], JOB, shard_map_id("m7", i))
        got = open(os.path.join(d, "file.out"), "rb").read()
        assert got == want, f"chunk {i}"


# -- stripe-aware routing + reconstruction ----------------------------------

class _DeadClient(LocalFetchClient):
    """A supplier that answers every fetch with a transport fault (the
    dead-host shape, delivered async like a real dial failure)."""

    def start_fetch(self, req, on_complete):
        t = threading.Timer(0.002, on_complete, args=(
            TransportError(f"supplier down ({req.map_id})"),))
        t.daemon = True
        t.start()


def _striped_cluster(tmp_path, scheme_spec, num_maps, hosts):
    """num_maps maps striped over len(hosts) in-process suppliers ->
    (expected records, {host: engine}, [(host, map_id)] entries)."""
    scheme = parse_scheme(scheme_spec)
    roots = [str(tmp_path / f"root_{h}") for h in hosts]
    rng = np.random.default_rng(11)
    expected, maps = [], []
    for m in range(num_maps):
        mid = f"m_{m:04d}"
        recs = sorted((rng.bytes(10), rng.bytes(30)) for _ in range(90))
        expected += recs
        write_striped_map_output(roots, m % len(hosts), JOB, mid,
                                 [recs], scheme)
        maps.append((hosts[m % len(hosts)], mid))
    engines = {h: DataEngine(DirIndexResolver(r), Config())
               for h, r in zip(hosts, roots)}
    return expected, engines, maps


def test_stripe_aware_routing_reconstructs_through_dead_primary(tmp_path):
    """The acceptance shape in-process: rs:2:4 over 4 suppliers, one
    dead from the start — its maps reconstruct from any k shards on
    the survivors, the merge completes byte-correct, and the run never
    falls back."""
    hosts = ["h0", "h1", "h2", "h3"]  # sorted == canonical order
    expected, engines, maps = _striped_cluster(tmp_path, "rs:2:4", 4,
                                               hosts)
    clients = {h: LocalFetchClient(e) for h, e in engines.items()}
    clients["h2"] = _DeadClient(engines["h2"])  # dead supplier
    router = HostRoutingClient(lambda h: clients[h])
    cfg = Config({"uda.tpu.coding.scheme": "rs:2:4",
                  "uda.tpu.fetch.retries": 1})
    mm = MergeManager(router, "uda.tpu.RawBytes", cfg)
    blocks = []
    try:
        mm.run(JOB, maps, 0, lambda b: blocks.append(bytes(b)))
    finally:
        for e in engines.values():
            e.stop()
    got = list(IFileReader(io.BytesIO(b"".join(blocks))))
    assert sorted(got) == sorted(expected)
    assert metrics.get("coding.reconstructed.partitions") >= 1
    assert metrics.get("coding.shard.fetches") >= 2
    assert metrics.get("fallback.signals") == 0
    # the ledger journaled the whole story, structurally
    kinds = {e["kind"] for e in mm.ledger.events()}
    assert "reconstructed" in kinds and "fault" in kinds


def test_decode_under_penalty_single_host(tmp_path):
    """Single-supplier degenerate: the plain fetch path fails, every
    shard synthesizes from the primary's own parity section — the
    partition still reconstructs locally (no peers at all)."""
    scheme = parse_scheme("rs:4:6")
    recs = [_records(70, 6)]
    write_map_output(str(tmp_path / JOB / "m0"), recs, scheme=scheme)
    eng = DataEngine(DirIndexResolver(str(tmp_path)), Config())

    class FailPlain(LocalFetchClient):
        """Faults direct partition fetches; shard fetches pass."""

        def start_fetch(self, req, on_complete):
            if parse_shard_id(req.map_id) is None:
                on_complete(TransportError("primary path penalized"))
                return
            super().start_fetch(req, on_complete)

    seg = Segment(FailPlain(eng), JOB, "m0", 0, 1 << 20,
                  policy=RetryPolicy(retries=1),
                  stripe=StripeContext(scheme, [""]))
    try:
        seg.start()
        seg.wait(10.0)
        got = list(seg.record_batch().iter_records())
    finally:
        eng.stop()
    assert sorted(got) == recs[0]
    assert metrics.get("coding.reconstructed.partitions") == 1


def test_reconstruction_slots_in_below_decompression(tmp_path):
    """The stripe codes the ON-DISK (compressed) bytes; a compressed
    job's reconstruction decodes the stripe first and decompresses the
    rebuilt partition on the way up — the segment sees the same
    uncompressed domain a fetched stream would (byte-agnostic
    contract)."""
    from uda_tpu.compress import DecompressingClient, get_codec

    scheme = parse_scheme("rs:3:5")
    codec = get_codec("zlib")
    recs = [_records(80, 17, val=64)]
    write_map_output(str(tmp_path / JOB / "m0"), recs, codec=codec,
                     scheme=scheme)
    eng = DataEngine(DirIndexResolver(str(tmp_path)), Config())

    class FailPlain(LocalFetchClient):
        def start_fetch(self, req, on_complete):
            if parse_shard_id(req.map_id) is None:
                on_complete(TransportError("primary path down"))
                return
            super().start_fetch(req, on_complete)

    client = DecompressingClient(FailPlain(eng), codec)
    assert not client.resume_ok()  # stream state is never resumable
    seg = Segment(client, JOB, "m0", 0, 1 << 20,
                  policy=RetryPolicy(retries=1),
                  stripe=StripeContext(scheme, [""]))
    try:
        seg.start()
        seg.wait(10.0)
        got = list(seg.record_batch().iter_records())
    finally:
        eng.stop()
    assert sorted(got) == recs[0]
    assert metrics.get("coding.reconstructed.partitions") == 1
    assert metrics.get("decompress.bytes") > 0


def test_stale_shard_cannot_poison_reconstruction(tmp_path):
    """A shard left over from a DIFFERENT map attempt (different
    full-partition length) must not define the stripe baseline just by
    completing first: chunks group by identity and whichever identity
    collects k wins — even when the stale shard is the fastest."""
    scheme = parse_scheme("rs:2:4")
    recs = [_records(40, 33)]
    write_map_output(str(tmp_path / JOB / "m0"), recs, scheme=scheme)
    eng = DataEngine(DirIndexResolver(str(tmp_path)), Config())

    class StaleShard1(LocalFetchClient):
        """Plain fetch fails; shard 1 answers INSTANTLY with a stale
        attempt's bytes (wrong identity); real shards answer late."""

        def start_fetch(self, req, on_complete):
            shard = parse_shard_id(req.map_id)
            if shard is None:
                on_complete(TransportError("primary down"))
                return
            if shard[1] == 1:
                on_complete(FetchResult(b"Z" * 9, 999, 9, 0,
                                        "/stale", last=True))
                return

            def late(res):
                t = threading.Timer(0.05, on_complete, args=(res,))
                t.daemon = True
                t.start()

            super().start_fetch(req, late)

    seg = Segment(StaleShard1(eng), JOB, "m0", 0, 1 << 20,
                  policy=RetryPolicy(retries=0),
                  stripe=StripeContext(scheme, [""]))
    try:
        seg.start()
        seg.wait(10.0)
        got = list(seg.record_batch().iter_records())
    finally:
        eng.stop()
    assert sorted(got) == recs[0]
    assert metrics.get("coding.reconstructed.partitions") == 1


@pytest.mark.faults
def test_coding_decode_failpoint_makes_recovery_injectable(tmp_path):
    """The coding.decode site: an injected decode fault turns a
    would-have-recovered segment into the terminal (typed) error —
    chaos can reach the new path from day one (UDA003)."""
    scheme = parse_scheme("rs:2:3")
    recs = [_records(30, 7)]
    write_map_output(str(tmp_path / JOB / "m0"), recs, scheme=scheme)
    eng = DataEngine(DirIndexResolver(str(tmp_path)), Config())

    class FailPlain(LocalFetchClient):
        def start_fetch(self, req, on_complete):
            if parse_shard_id(req.map_id) is None:
                on_complete(TransportError("down"))
                return
            super().start_fetch(req, on_complete)

    seg = Segment(FailPlain(eng), JOB, "m0", 0, 1 << 20,
                  policy=RetryPolicy(retries=0),
                  stripe=StripeContext(scheme, [""]))
    try:
        with failpoints.scoped("coding.decode=error"):
            seg.start()
            with pytest.raises(StorageError, match="coding.decode"):
                seg.wait(10.0)
    finally:
        eng.stop()
    assert metrics.get("coding.recover.failures") == 1


# -- speculative dual-source fetch ------------------------------------------

class _SlowClient(LocalFetchClient):
    def __init__(self, engine, delay_s):
        super().__init__(engine)
        self.delay_s = delay_s

    def start_fetch(self, req, on_complete):
        def late(res):
            t = threading.Timer(self.delay_s, on_complete, args=(res,))
            t.daemon = True
            t.start()

        super().start_fetch(req, late)


@pytest.mark.faults
def test_speculation_won_switches_to_faster_source(tmp_path):
    """The straggler detector: a fetch stuck on a slow replica gets a
    duplicate on the PenaltyBox-ranked alternate; the duplicate wins,
    the segment switches sources, and the slow completion is discarded
    by the epoch machinery."""
    expected = make_mof_tree(str(tmp_path), JOB, 1, 1, 150, seed=8)
    eng = DataEngine(DirIndexResolver(str(tmp_path)), Config())
    clients = {"slow": _SlowClient(eng, 0.6),
               "fast": LocalFetchClient(eng)}
    router = HostRoutingClient(lambda h: clients[h])
    seg = Segment(router, JOB, map_ids(JOB, 1)[0], 0, 1 << 20,
                  host="slow", hosts=["slow", "fast"],
                  ledger=RecoveryLedger(PenaltyBox()),
                  speculation=SpeculationPolicy(pn=95, floor_ms=50),
                  policy=RetryPolicy(retries=1))
    t0 = time.perf_counter()
    try:
        seg.start()
        seg.wait(10.0)
    finally:
        eng.stop()
    assert seg.num_records == len(expected[0])
    assert seg.host == "fast"  # sticky win
    assert metrics.get("fetch.speculated") >= 1
    assert metrics.get("fetch.speculation.won") >= 1
    assert time.perf_counter() - t0 < 0.5  # did not wait out the slow path
    assert metrics.get_gauge("fetch.on_air") == 0  # loser fully settled


@pytest.mark.faults
def test_speculation_lost_late_completion_discarded(tmp_path):
    """The primary wins the race: the speculative duplicate's (slower)
    completion must be discarded as stale — exactly one ingest, no
    double-counted records, balanced on-air accounting."""
    expected = make_mof_tree(str(tmp_path), JOB, 1, 1, 120, seed=9)
    eng = DataEngine(DirIndexResolver(str(tmp_path)), Config())
    clients = {"primary": _SlowClient(eng, 0.12),
               "alt": _SlowClient(eng, 1.0)}
    router = HostRoutingClient(lambda h: clients[h])
    seg = Segment(router, JOB, map_ids(JOB, 1)[0], 0, 1 << 20,
                  host="primary", hosts=["primary", "alt"],
                  ledger=RecoveryLedger(PenaltyBox()),
                  speculation=SpeculationPolicy(pn=95, floor_ms=30),
                  policy=RetryPolicy(retries=1))
    try:
        seg.start()
        seg.wait(10.0)
        assert seg.num_records == len(expected[0])
        assert seg.host == "primary"
        assert metrics.get("fetch.speculated") >= 1
        assert metrics.get("fetch.speculation.won") == 0
        assert metrics.get("fetch.speculation.lost") >= 1
        # the loser's completion lands AFTER the win: stale-dropped
        time.sleep(1.1)
        assert metrics.get("fetch.stale_completions") >= 1
        assert seg.num_records == len(expected[0])  # no double ingest
        assert metrics.get_gauge("fetch.on_air") == 0
    finally:
        eng.stop()


@pytest.mark.faults
def test_both_racing_attempts_failing_still_retries(tmp_path):
    """Primary AND speculative duplicate both fail: the second failure
    must settle the attempt group and drive the retry ladder — never
    strand the segment with zero live attempts (the racing-failures
    path of Segment._drop_attempt)."""
    make_mof_tree(str(tmp_path), JOB, 1, 1, 30, seed=10)
    eng = DataEngine(DirIndexResolver(str(tmp_path)), Config())

    class FailAfter(LocalFetchClient):
        def __init__(self, engine, delay_s):
            super().__init__(engine)
            self.delay_s = delay_s

        def start_fetch(self, req, on_complete):
            t = threading.Timer(self.delay_s, on_complete, args=(
                TransportError(f"down ({req.host})"),))
            t.daemon = True
            t.start()

    clients = {"a": FailAfter(eng, 0.2), "b": FailAfter(eng, 0.01)}
    router = HostRoutingClient(lambda h: clients[h])
    seg = Segment(router, JOB, map_ids(JOB, 1)[0], 0, 1 << 20,
                  host="a", hosts=["a", "b"],
                  ledger=RecoveryLedger(PenaltyBox()),
                  speculation=SpeculationPolicy(pn=95, floor_ms=20),
                  policy=RetryPolicy(retries=1))
    try:
        seg.start()
        with pytest.raises(TransportError):
            seg.wait(5.0)  # fails PROMPTLY after the retry — a stranded
            # attempt group would hang until this timeout
        assert metrics.get("fetch.retries") >= 1
        assert metrics.get_gauge("fetch.on_air") == 0
    finally:
        eng.stop()


def test_speculation_gated_off_for_stateful_decompressing_client(tmp_path):
    """DecompressingClient claims a per-partition sequential stream
    token in start_fetch — a speculative DUPLICATE would steal it and
    fail the healthy primary's completion as stale, fabricating a
    fault. The straggler detector must not fire through it."""
    from uda_tpu.compress import DecompressingClient, get_codec

    codec = get_codec("zlib")
    recs = [_records(100, 19, val=48)]
    write_map_output(str(tmp_path / JOB / "m0"), recs, codec=codec)
    eng = DataEngine(DirIndexResolver(str(tmp_path)), Config())
    client = DecompressingClient(_SlowClient(eng, 0.1), codec)
    assert not client.speculate_ok()
    box = PenaltyBox(threshold=1, penalty_s=60.0)
    seg = Segment(client, JOB, "m0", 0, 1 << 20,
                  ledger=RecoveryLedger(box),
                  speculation=SpeculationPolicy(pn=95, floor_ms=10),
                  policy=RetryPolicy(retries=1))
    try:
        seg.start()
        seg.wait(10.0)
    finally:
        eng.stop()
    assert sorted(seg.record_batch().iter_records()) == recs[0]
    assert metrics.get("fetch.speculated") == 0  # gated, not raced
    assert metrics.get("fetch.penalties") == 0   # nobody punished


def test_handoff_record_survives_a_failed_start(tmp_path):
    """The handoff record is consumed by a SUCCESSFUL start only: a
    transient bind failure (port in use) must leave it in place so the
    supervisor's retry still comes up warm."""
    from uda_tpu.net import ShuffleServer

    eng, srv, cfg = _netted_supplier(tmp_path)
    port = srv.port
    srv.stop(drain=True)  # persists the record
    blocker = __import__("socket").socket()
    blocker.bind(("127.0.0.1", 0))
    blocker.listen(1)
    try:
        with pytest.raises(OSError):
            # same port as the blocker: bind fails BEFORE the record
            # would be consumed
            ShuffleServer(eng, cfg, host="127.0.0.1",
                          port=blocker.getsockname()[1]).start()
        srv2 = ShuffleServer(eng, cfg, host="127.0.0.1",
                             port=port).start()
        try:
            assert srv2.warm_restart  # the record was still there
        finally:
            srv2.stop()
    finally:
        blocker.close()
        eng.stop()


def test_speculation_policy_threshold_uses_histogram():
    pol = SpeculationPolicy(pn=95, floor_ms=40.0)
    assert pol.threshold_ms() == 40.0  # empty histogram -> floor
    metrics.enable_stats()
    for v in (10.0,) * 90 + (400.0,) * 10:
        metrics.observe("fetch.latency_ms", v)
    assert pol.threshold_ms() > 40.0  # p95 pulled it above the floor
    assert not SpeculationPolicy(pn=0).enabled


# -- structured cause + ledger ----------------------------------------------

def test_admin_fail_records_supplier_in_structured_cause(tmp_path):
    ledger = RecoveryLedger(PenaltyBox())
    eng = DataEngine(DirIndexResolver(str(tmp_path)), Config())
    seg = Segment(_SlowClient(eng, 5.0), JOB, "m9", 0, 1 << 20,
                  host="sick-host", ledger=ledger)
    try:
        seg.start()
        err = StorageError("watchdog rescue")
        assert seg.fail(err)
        assert err.supplier == "sick-host"  # structured, not a string
        events = ledger.events("admin_fail")
        assert events and events[0]["supplier"] == "sick-host"
        assert events[0]["error"] == "StorageError"
        # a SHARED stop-path error keeps its first attribution
        seg2 = Segment(_SlowClient(eng, 5.0), JOB, "m10", 0, 1 << 20,
                       host="other", ledger=ledger)
        seg2.start()
        assert seg2.fail(err)
        assert err.supplier == "sick-host"
        assert ledger.events("admin_fail")[1]["supplier"] == "other"
    finally:
        eng.stop()


def test_recovery_ledger_rank_and_snapshot():
    box = PenaltyBox(threshold=1, penalty_s=60.0)
    ledger = RecoveryLedger(box)
    box.punish("bad")
    assert ledger.rank(["bad", "good"]) == ["good", "bad"]
    v0 = ledger.version
    ledger.record("fault", supplier="bad", map_id="m",
                  error=TransportError("x"))
    assert ledger.version == v0 + 1
    snap = ledger.snapshot()
    assert snap["counts"]["fault"] == 1
    assert snap["events"][-1]["error"] == "TransportError"


# -- warm-restart + resume (the net handoff) --------------------------------

def _netted_supplier(tmp_path, handoff=True, port=0):
    cfg = Config({"uda.tpu.net.handoff.path":
                  str(tmp_path / "handoff.json") if handoff else ""})
    eng = DataEngine(DirIndexResolver(str(tmp_path)), cfg)
    from uda_tpu.net import ShuffleServer

    return eng, ShuffleServer(eng, cfg, host="127.0.0.1",
                              port=port).start(), cfg


@pytest.mark.faults
def test_completion_warm_restart_resumes_from_offset_ledger(tmp_path):
    """The bounced-supplier completion rung: stop(drain=True) persists
    the handoff, the restart advertises generation+1 warm, and the
    in-flight segment resumes from its own offset ledger — the job
    finishes without refetching already-served bytes and without a
    FallbackSignal."""
    expected = make_mof_tree(str(tmp_path), JOB, 1, 1, 2500, seed=12)
    eng, srv, cfg = _netted_supplier(tmp_path)
    port, gen1 = srv.port, srv.generation
    router = HostRoutingClient(config=Config())
    seg = Segment(router, JOB, map_ids(JOB, 1)[0], 0, 8192,
                  host=f"127.0.0.1:{port}",
                  policy=RetryPolicy(retries=8, backoff_ms=100),
                  resume=True)
    mid_fetch = threading.Event()
    orig_ingest = seg._ingest
    chunks = [0]

    def pacing_ingest(res):
        chunks[0] += 1
        if chunks[0] == 3:
            mid_fetch.set()
        if chunks[0] in (3, 4):
            time.sleep(0.15)  # hold the stream open across the bounce
        return orig_ingest(res)

    seg._ingest = pacing_ingest
    srv2 = None
    try:
        seg.start()
        assert mid_fetch.wait(10.0)
        srv.stop(drain=True)  # the graceful bounce: handoff persisted
        time.sleep(0.4)  # a real outage window: the segment's next
        # chunk fails against the down supplier and RETRIES (resume)
        from uda_tpu.net import ShuffleServer

        srv2 = ShuffleServer(eng, cfg, host="127.0.0.1",
                             port=port).start()
        assert srv2.generation == (gen1 + 1) & 0x7FFFFFFF
        assert srv2.warm_restart
        seg.wait(20.0)
    finally:
        if srv2 is not None:
            srv2.stop()
        router.stop()
        eng.stop()
    assert seg.record_batch().num_records == len(expected[0])
    assert metrics.get("fetch.resumed") >= 1
    assert metrics.get("fetch.resumed.bytes") > 0  # bytes NOT refetched
    assert metrics.get("net.handoff.persisted") >= 1
    assert metrics.get("net.handoff.loaded") >= 1
    assert metrics.get("fallback.signals") == 0


@pytest.mark.faults
def test_remote_pread_error_resumes_mid_partition(tmp_path):
    """A transient REMOTE StorageError — a typed ERR frame on a healthy
    stream (structured remote_kind stamp, net/wire.py) — must not cost
    a full refetch: every chunk ingested before it is valid, so the
    segment keeps its offset ledger and resumes. Under a periodic
    per-call error schedule a refetch-from-zero retry loop re-hits the
    fault at the same phase every attempt and exhausts any retry
    budget deterministically (the chaos-rung livelock this pins); with
    resume each attempt banks its progress and the fetch converges."""
    expected = make_mof_tree(str(tmp_path), JOB, 1, 1, 2500, seed=21)
    eng, srv, _ = _netted_supplier(tmp_path)
    router = HostRoutingClient(config=Config())
    seg = Segment(router, JOB, map_ids(JOB, 1)[0], 0, 8192,
                  host=f"127.0.0.1:{srv.port}",
                  policy=RetryPolicy(retries=8, backoff_ms=20),
                  resume=True)
    try:
        # every 3rd pread errors: < the partition's chunk count, so
        # without resume NO attempt can ever finish (the livelock)
        with failpoints.scoped("data_engine.pread=error:every:3"):
            seg.start()
            seg.wait(20.0)
    finally:
        srv.stop()
        router.stop()
        eng.stop()
    assert seg.record_batch().num_records == len(expected[0])
    assert metrics.get("fetch.resumed") >= 1
    assert metrics.get("fetch.resumed.bytes") > 0  # ground held


def test_cold_restart_revokes_resume(tmp_path):
    """Without a handoff record the restarted server mints a FRESH
    generation and advertises cold — the client revokes resume for
    retrying segments (their ledgers restart from zero)."""
    make_mof_tree(str(tmp_path), JOB, 1, 1, 20, seed=13)
    eng, srv, _ = _netted_supplier(tmp_path, handoff=False)
    port = srv.port
    from uda_tpu.net import RemoteFetchClient

    client = RemoteFetchClient("127.0.0.1", port, Config())
    try:
        res_box, done = [], threading.Event()
        client.start_fetch(
            ShuffleRequest(JOB, map_ids(JOB, 1)[0], 0, 0, 1 << 20),
            lambda r: (res_box.append(r), done.set()))
        assert done.wait(10.0) and isinstance(res_box[0], FetchResult)
        assert client.resume_ok()  # same generation so far
        srv.stop(drain=False)  # killed: no handoff record
        from uda_tpu.net import ShuffleServer

        srv = ShuffleServer(eng, Config(), host="127.0.0.1",
                            port=port).start()
        assert not srv.warm_restart
        done2, box2 = threading.Event(), []
        client.start_fetch(
            ShuffleRequest(JOB, map_ids(JOB, 1)[0], 0, 0, 1 << 20),
            lambda r: (box2.append(r), done2.set()))
        assert done2.wait(10.0)
        deadline = time.monotonic() + 5.0
        while client.resume_ok() and time.monotonic() < deadline:
            time.sleep(0.01)  # HELLO may trail the first data frame
        assert not client.resume_ok()  # cold restart observed
        assert metrics.get("net.generation.changes") >= 1
    finally:
        client.stop()
        srv.stop()
        eng.stop()


def _ifile_blob(records):
    from uda_tpu.utils.ifile import IFileWriter

    buf = io.BytesIO()
    w = IFileWriter(buf)
    for k, v in records:
        w.append(k, v)
    w.close()
    return buf.getvalue()


def test_resume_identity_check_restarts_on_changed_partition():
    """A resumed fetch whose first chunk reports a different partition
    identity (raw_length) must NOT splice two attempts' bytes: the
    identity check forces a full restart from zero, and the segment
    completes with the NEW attempt's records only."""
    recs_a = _records(12, 21)
    recs_b = _records(30, 22)
    part_a, part_b = _ifile_blob(recs_a), _ifile_blob(recs_b)
    assert len(part_a) != len(part_b)

    class SwappingClient(LocalFetchClient):
        """Serves 64-byte chunks of attempt A, faults once mid-stream,
        then serves attempt B (a different map attempt's output)."""

        def __init__(self):
            self.phase = 0

        def start_fetch(self, req, on_complete):
            blob = part_a if self.phase == 0 else part_b
            if self.phase == 0 and req.offset >= 64:
                self.phase = 1
                on_complete(TransportError("supplier bounced"))
                return
            chunk = blob[req.offset:req.offset + 64]
            on_complete(FetchResult(
                chunk, len(blob), len(blob), req.offset, "/x",
                last=req.offset + len(chunk) >= len(blob)))

    seg = Segment(SwappingClient(), JOB, "m0", 0, 64,
                  policy=RetryPolicy(retries=3), resume=True)
    seg.start()
    seg.wait(10.0)
    assert metrics.get("fetch.resumed") == 1
    assert metrics.get("fetch.resume.invalidated") == 1
    assert sorted(seg.record_batch().iter_records()) == recs_b


@pytest.mark.faults
def test_net_handoff_failpoint_degrades_to_cold(tmp_path):
    """An injected handoff-save fault must degrade the NEXT start to
    cold (counted, logged), never break the graceful stop itself."""
    make_mof_tree(str(tmp_path), JOB, 1, 1, 10, seed=14)
    eng, srv, cfg = _netted_supplier(tmp_path)
    port = srv.port
    with failpoints.scoped("net.handoff=error:match:save"):
        srv.stop(drain=True)  # save injected away; stop still clean
    from uda_tpu.net import ShuffleServer

    srv2 = ShuffleServer(eng, cfg, host="127.0.0.1", port=port).start()
    try:
        assert not srv2.warm_restart  # no record -> cold
        assert metrics.get("errors.swallowed") >= 1
    finally:
        srv2.stop()
        eng.stop()


# -- the chaos completion rung (sockets, seeded kill) ------------------------

@pytest.mark.faults
def test_completion_reconstruct_through_seeded_supplier_kill(tmp_path):
    """THE acceptance rung: rs:4:6 over six socket suppliers, a seeded
    supplier killed with no restart — the job completes with
    byte-correct merged output, coding.reconstructed.partitions > 0,
    and no FallbackSignal."""
    from uda_tpu.net import ShuffleServer

    seed = int(os.environ.get("UDA_TPU_CHAOS_SEED", "7"))
    num = 6
    scheme_spec = "rs:4:6"
    roots = [str(tmp_path / f"r{i}") for i in range(num)]
    engines = [DataEngine(DirIndexResolver(r), Config()) for r in roots]
    servers = [ShuffleServer(e, Config(), host="127.0.0.1", port=0).start()
               for e in engines]
    unsorted_hosts = [f"127.0.0.1:{s.port}" for s in servers]
    order = sorted(range(num), key=lambda i: unsorted_hosts[i])
    hosts = [unsorted_hosts[i] for i in order]       # canonical order
    roots_c = [roots[i] for i in order]
    servers_c = [servers[i] for i in order]
    scheme = parse_scheme(scheme_spec)
    rng = np.random.default_rng(seed)
    expected, maps = [], []
    for m in range(num):
        mid = f"m_{m:04d}"
        recs = sorted((rng.bytes(10), rng.bytes(30)) for _ in range(100))
        expected += recs
        write_striped_map_output(roots_c, m, JOB, mid, [recs], scheme)
        maps.append((hosts[m], mid))
    victim = seed % num
    cfg = Config({"uda.tpu.coding.scheme": scheme_spec,
                  "uda.tpu.fetch.retries": 1,
                  "mapred.rdma.fetch.retry.backoff.ms": 30,
                  "uda.tpu.net.connect.timeout.s": 2.0,
                  "mapred.rdma.buf.size": 16})
    router = HostRoutingClient(config=cfg)
    mm = MergeManager(router, "uda.tpu.RawBytes", cfg, seed=seed)
    blocks = []
    try:
        servers_c[victim].stop(drain=False)  # the kill: mid-shuffle
        # from the reducer's view (fetches racing the teardown)
        mm.run(JOB, maps, 0, lambda b: blocks.append(bytes(b)))
    finally:
        router.stop()
        for s in servers_c:
            try:
                s.stop()
            except Exception:  # noqa: BLE001 - double-stop on the
                pass           # victim is part of the scenario
        for e in engines:
            e.stop()
    got = list(IFileReader(io.BytesIO(b"".join(blocks))))
    assert sorted(got) == sorted(expected), "merged output not byte-correct"
    assert metrics.get("coding.reconstructed.partitions") > 0
    assert metrics.get("fallback.signals") == 0


# -- failure-domain-aware stripe placement (ISSUE 15) ------------------------

def test_parse_domains():
    from uda_tpu.coding import parse_domains

    assert parse_domains("") == {} and parse_domains(None) == {}
    assert parse_domains("a=r0, b=r0 ,c=r1") == \
        {"a": "r0", "b": "r0", "c": "r1"}
    for bad in ("a", "a=", "=r0", "a=r0,b"):
        with pytest.raises(ConfigError):
            parse_domains(bad)


def test_stripe_order_rotation_and_domain_interleave():
    from uda_tpu.coding import stripe_order

    # no domains: the PR 8 positional rotation, unchanged
    assert stripe_order(4, 1) == [1, 2, 3, 0]
    # domains: round-robin across domains, primary (and its domain)
    # first, rotation order within each domain
    #   hosts 0,1 -> rack0; hosts 2,3 -> rack1
    order = stripe_order(4, 0, ["r0", "r0", "r1", "r1"])
    assert order[0] == 0                      # primary stays chunk 0
    assert order == [0, 2, 1, 3]              # r0, r1, r0, r1
    # consecutive chunks land in distinct domains while any remain
    doms = ["r0", "r0", "r1", "r1"]
    for a, b in zip(order, order[1:]):
        assert doms[a] != doms[b]
    with pytest.raises(ConfigError):
        stripe_order(4, 0, ["r0"])            # label/count mismatch


def test_stripe_host_domains_spread_no_domain_holds_too_many():
    # THE satellite invariant: with declared failure domains, no
    # domain holds >= n-k+1 shards of one stripe (losing a whole
    # domain never makes a stripe unrecoverable) — checked over every
    # primary and a spread of (k, n, domain) configurations
    hosts = ["h0", "h1", "h2", "h3", "h4", "h5"]
    domains = {"h0": "rackA", "h1": "rackA", "h2": "rackB",
               "h3": "rackB", "h4": "rackC", "h5": "rackC"}
    for k, n in ((2, 4), (4, 6), (3, 5)):
        for primary in hosts:
            placed = [stripe_host(hosts, primary, i, domains=domains)
                      for i in range(n)]
            per_dom: dict = {}
            for h in placed:
                per_dom[domains[h]] = per_dom.get(domains[h], 0) + 1
            assert max(per_dom.values()) < n - k + 1, \
                (k, n, primary, placed, per_dom)
            assert placed[0] == primary
    # rotation (undeclared) keeps the historical placement
    assert [stripe_host(hosts[:3], "h1", i) for i in range(4)] == \
        ["h1", "h2", "h0", "h1"]
    # partially-declared hosts fall back to singleton domains
    part = {"h0": "rackA", "h1": "rackA"}
    placed = [stripe_host(hosts[:4], "h0", i, domains=part)
              for i in range(4)]
    assert placed[0] == "h0" and len(set(placed)) == 4


def test_striped_writer_and_recovery_agree_on_domain_placement(tmp_path):
    # writer fan-out and reduce-side StripeContext must derive the
    # SAME placement from the same domain declaration (no metadata
    # travels) — shards land exactly where host_of says they are
    from uda_tpu.coding import stripe_order

    roots = [str(tmp_path / f"s{i}") for i in range(4)]
    domains = {r: f"rack{i % 2}" for i, r in enumerate(roots)}
    scheme = parse_scheme("rs:2:4")
    parts = [[(b"k%d" % i, b"v" * i)] for i in range(3)]
    write_striped_map_output(roots, 1, "job", "m_0", parts, scheme,
                             domains=domains)
    ctx = StripeContext(scheme, roots, domains=domains)
    order = stripe_order(4, 1, [domains[r] for r in roots])
    for i in range(scheme.n):
        expect = roots[order[i % 4]]
        assert ctx.host_of(roots[1], i) == expect
        sdir = os.path.join(expect, "job", shard_map_id("m_0", i))
        if expect == roots[1]:
            assert not os.path.exists(sdir)   # synthesized, no bytes
        else:
            assert os.path.exists(os.path.join(sdir, "file.out"))


# -- background stripe scrub (ISSUE 15) --------------------------------------

def _write_coded_tree(tmp_path, nroots=3, scheme_spec="rs:2:3"):
    roots = [str(tmp_path / f"r{i}") for i in range(nroots)]
    scheme = parse_scheme(scheme_spec)
    parts = [[(b"key%03d" % i, bytes(range(i % 7)) * 5)]
             for i in range(4)]
    write_striped_map_output(roots, 0, "jobS", "m_000", parts, scheme)
    return roots, scheme


def test_scrub_clean_tree_counts_stripes(tmp_path):
    from uda_tpu.coding.scrub import scrub_roots

    roots, scheme = _write_coded_tree(tmp_path)
    metrics.reset()
    rep = scrub_roots(roots)
    assert rep["maps"] == 1 and rep["stripes"] == 4
    assert rep["parity_mismatches"] == 0 and rep["shard_faults"] == 0
    assert metrics.get("coding.scrub.stripes") == 4.0
    assert metrics.get("coding.scrub.repairs") == 0.0


def test_scrub_detects_lost_shard_dump_only_then_repairs(tmp_path):
    from uda_tpu.coding.scrub import scrub_roots

    roots, scheme = _write_coded_tree(tmp_path)
    # find a peer shard and destroy it
    victim = None
    for root in roots[1:]:
        for dirpath, _dirs, files in os.walk(root):
            if "file.out" in files:
                victim = os.path.join(dirpath, "file.out")
    assert victim is not None
    with open(victim, "rb") as f:
        original = f.read()
    os.remove(victim)
    metrics.reset()
    rep = scrub_roots(roots)                   # dump-only default
    assert rep["shard_faults"] >= 1 and rep["repaired"] == 0
    assert not os.path.exists(victim)          # bytes never touched
    assert metrics.get("coding.scrub.repairs") >= 1.0
    rep2 = scrub_roots(roots, repair=True)     # proactive rebuild
    assert rep2["repaired"] >= 1
    with open(victim, "rb") as f:
        assert f.read() == original            # byte-exact rebuild
    rep3 = scrub_roots(roots)
    assert rep3["shard_faults"] == 0           # tree healthy again


def test_scrub_detects_corrupt_shard_and_parity(tmp_path):
    from uda_tpu.coding.scrub import scrub_roots

    roots, scheme = _write_coded_tree(tmp_path)
    victim = None
    for root in roots[1:]:
        for dirpath, _dirs, files in os.walk(root):
            if "file.out" in files:
                victim = os.path.join(dirpath, "file.out")
    with open(victim, "r+b") as f:
        f.seek(0)
        b = f.read(1)
        f.seek(0)
        f.write(bytes([b[0] ^ 0xFF]))
    rep = scrub_roots(roots)
    assert rep["shard_faults"] >= 1
    rep2 = scrub_roots(roots, repair=True)
    assert rep2["repaired"] >= 1
    assert scrub_roots(roots)["shard_faults"] == 0


def test_maybe_scrub_interval_and_single_flight(tmp_path):
    from uda_tpu.coding import scrub as scrub_mod

    roots, _ = _write_coded_tree(tmp_path)
    scrub_mod.scrub_state_reset()
    cfg = Config({"uda.tpu.coding.scheme": "rs:2:3",
                  "uda.tpu.coding.scrub.s": 3600})
    assert scrub_mod.maybe_scrub(cfg, roots) is True
    # within the interval (and/or in flight): no second pass
    assert scrub_mod.maybe_scrub(cfg, roots) is False
    deadline = time.time() + 5
    while scrub_mod._SCRUB_ACTIVE and time.time() < deadline:
        time.sleep(0.01)
    assert not scrub_mod._SCRUB_ACTIVE
    # off switch and coding-off both decline
    scrub_mod.scrub_state_reset()
    assert scrub_mod.maybe_scrub(
        Config({"uda.tpu.coding.scheme": "rs:2:3"}), roots) is False
    assert scrub_mod.maybe_scrub(
        Config({"uda.tpu.coding.scrub.s": 10}), roots) is False


# -- coded jobs through the models/ map phase (ISSUE 15) ---------------------

def test_map_phase_writes_coded_layout_behind_scheme_flag(tmp_path):
    # the full-workload wiring: a sort job with uda.tpu.coding.scheme
    # set writes parity sections + v2 indexes (single root) and the
    # striped fan-out (multi root), with output validity intact
    from uda_tpu.coding.scrub import scrub_roots
    from uda_tpu.models.sort_job import run_sort
    from uda_tpu.utils.comparators import memcmp

    rng = np.random.default_rng(31)
    records = [(rng.bytes(int(rng.integers(1, 16))),
                rng.bytes(int(rng.integers(0, 32)))) for _ in range(64)]
    roots = [str(tmp_path / "w")] + [str(tmp_path / f"p{i}")
                                     for i in (1, 2)]
    cfg = Config({"uda.tpu.coding.scheme": "rs:2:3"})
    out = run_sort(records, num_maps=3, num_reducers=2, config=cfg,
                   work_dir=roots[0], supplier_roots=roots)
    got = []
    for r, recs in sorted(out.items()):
        keys = [k for k, _ in recs]
        assert all(memcmp(a, b) <= 0 for a, b in zip(keys, keys[1:]))
        got.extend(recs)
    assert sorted(got) == sorted(records)
    # the layout really is coded: v2 stripes scrub clean, shards exist
    rep = scrub_roots(roots)
    assert rep["maps"] == 3 and rep["stripes"] > 0
    assert rep["parity_mismatches"] == 0 and rep["shard_faults"] == 0


def test_scrub_min_age_skips_fresh_maps(tmp_path):
    # review hardening: a pass racing a live (non-atomic) striped
    # write must not book phantom faults — fresh maps are skipped
    # until the quiesce window passes (the daemon rung always sets it)
    from uda_tpu.coding.scrub import scrub_roots

    roots, _ = _write_coded_tree(tmp_path)
    rep = scrub_roots(roots, min_age_s=3600)
    assert rep["maps"] == 0 and rep["stripes"] == 0
    rep2 = scrub_roots(roots, min_age_s=0)
    assert rep2["maps"] == 1 and rep2["shard_faults"] == 0


def test_scrub_survives_damaged_primary(tmp_path):
    # review hardening (round 5): one torn/lost PRIMARY must be a
    # counted finding, never an aborted pass — the neighbor maps still
    # get scrubbed
    from uda_tpu.coding.scrub import scrub_roots

    roots = [str(tmp_path / f"r{i}") for i in range(3)]
    scheme = parse_scheme("rs:2:3")
    for mid in ("m_000", "m_001"):
        parts = [[(b"k", b"v" * 9)] for _ in range(2)]
        write_striped_map_output(roots, 0, "jobP", mid, parts, scheme)
    os.remove(os.path.join(roots[0], "jobP", "m_000", "file.out"))
    rep = scrub_roots(roots)
    assert rep["primary_faults"] == 1
    assert rep["maps"] == 1 and rep["stripes"] == 2   # m_001 scrubbed
    assert rep["shard_faults"] == 0


def test_scrub_corrupt_primary_never_repairs_healthy_shards(tmp_path):
    # review hardening (round 6): a parity mismatch marks the PRIMARY
    # untrusted — the shard pass (and especially repair) is skipped so
    # corrupt primary bytes can never overwrite the last good copies
    from uda_tpu.coding.scrub import scrub_roots

    roots, _ = _write_coded_tree(tmp_path)
    # flip a byte inside the PRIMARY's file.out data region
    primary = os.path.join(roots[0], "jobS", "m_000", "file.out")
    with open(primary, "r+b") as f:
        b = f.read(1)
        f.seek(0)
        f.write(bytes([b[0] ^ 0xFF]))
    shard_files = {}
    for root in roots[1:]:
        for dirpath, _dirs, files in os.walk(root):
            if "file.out" in files:
                p = os.path.join(dirpath, "file.out")
                with open(p, "rb") as f:
                    shard_files[p] = f.read()
    rep = scrub_roots(roots, repair=True)
    assert rep["parity_mismatches"] >= 1
    assert rep["repaired"] == 0 and rep["shard_faults"] == 0
    for p, want in shard_files.items():      # peer bytes untouched
        with open(p, "rb") as f:
            assert f.read() == want
