"""A chunk's fetch latency split where it is spent (ISSUE 36): supplier
park and serve (reported in the DATA head, only to a REQ that carried
the trace tail), wire (the remainder), the completion-dispatch queue,
the crack, ``feed()``'s backpressure — and the upcall thread's busy
seconds.

Every assertion is an inequality against an INJECTED delay or an
identity between counters; none is a tolerance on a CPU timing (the
suite runs six workers wide).
"""

from __future__ import annotations

import socket
import threading
import time

import numpy as np
import pytest

from tests.helpers import make_mof_tree, map_ids
from uda_tpu.merger import HostRoutingClient, MergeManager
from uda_tpu.merger.emitter import FramedEmitter
from uda_tpu.merger.overlap import OverlappedMerger
from uda_tpu.mofserver import (DataEngine, DirIndexResolver, FetchResult,
                               ShuffleRequest)
from uda_tpu.net import RemoteFetchClient, ShuffleServer, wire
from uda_tpu.utils import comparators, critpath
from uda_tpu.utils.config import Config
from uda_tpu.utils.failpoints import failpoints
from uda_tpu.utils.ifile import crack, write_records
from uda_tpu.utils.metrics import METRICS_REGISTRY, SPAN_REGISTRY, metrics

JOB = "jobStages"
CHUNK_COUNTERS = ("fetch.chunk.park_seconds", "fetch.chunk.serve_seconds",
                  "fetch.chunk.wire_seconds",
                  "fetch.chunk.dispatch_wait_seconds")
CLIENT_LOOP = "uda-net-client-loop"


# -- the DATA head -------------------------------------------------------------

def _parents_head(req_id, raw_length, part_length, offset, last, path, crc,
                  data_len) -> bytes:
    """``encode_result_head`` as the parent commit wrote it, rebuilt
    from the wire's primitives: the frame an untimed REQ must still
    get, byte for byte."""
    flags = (0x01 if last else 0) | (0x02 if crc is not None else 0)
    meta = wire._DATA.pack(raw_length, part_length, offset, flags)
    if crc is not None:
        meta += wire._CRC.pack(crc & 0xFFFFFFFF)
    meta += wire._pack_str(path)
    return wire.HEADER.pack(wire.MAGIC, wire.WIRE_VERSION, wire.MSG_DATA,
                            req_id, len(meta) + data_len) + meta


@pytest.mark.parametrize("take", (False, True), ids=("copy", "take"))
@pytest.mark.parametrize("crc", (None, 0xDEADBEEF), ids=("nocrc", "crc"))
@pytest.mark.parametrize("timing", (None, (1234, 56789), (0, 0)),
                         ids=("untimed", "timed", "zeros"))
def test_data_head_roundtrips_with_and_without_the_timing_block(
        timing, crc, take):
    data = b"r" * 777
    res = FetchResult(data, 12345, 2345, 512, "/mofs/file.out", last=True,
                      crc=crc, timing=timing)
    frame = wire.encode_result(9, res)
    payload = bytearray(frame[wire.HEADER.size:])
    got = (wire.decode_result_take(payload) if take
           else wire.decode_result(bytes(payload)))
    assert (bytes(got.data), got.raw_length, got.part_length, got.offset,
            got.path, got.last, got.crc, got.timing) == \
           (data, 12345, 2345, 512, "/mofs/file.out", True, crc, timing)
    # the block costs its 8 bytes and nothing else
    plain = wire.encode_result(9, FetchResult(
        data, 12345, 2345, 512, "/mofs/file.out", last=True, crc=crc))
    assert len(frame) - len(plain) == (8 if timing is not None else 0)


@pytest.mark.parametrize("crc", (None, 7), ids=("nocrc", "crc"))
def test_an_untimed_head_is_the_parents_bytes(crc):
    kw = dict(raw_length=1 << 40, part_length=999, offset=1 << 33,
              last=False, path="/a/b/file.out", crc=crc, data_len=4096)
    assert wire.encode_result_head(41, **kw) == _parents_head(41, **kw)
    assert wire.encode_result_head(41, timing=None, **kw) == \
        _parents_head(41, **kw)
    timed = wire.encode_result_head(41, timing=(5, 6), **kw)
    assert len(timed) == len(_parents_head(41, **kw)) + 8


def test_timing_saturates_and_a_flag_without_its_block_is_torn():
    head = wire.encode_result_head(
        1, raw_length=1, part_length=1, offset=0, last=True, path="p",
        data_len=0, timing=(1 << 40, -3))
    got = wire.decode_result(head[wire.HEADER.size:])
    assert got.timing == (0xFFFFFFFF, 0)
    flagged = bytearray(wire.encode_result_head(
        1, raw_length=1, part_length=1, offset=0, last=True, path="",
        data_len=0))
    flagged[wire.HEADER.size + 24] |= wire._FLAG_TIMING
    with pytest.raises(Exception, match="timing|truncated|string"):
        wire.decode_result(bytes(flagged[wire.HEADER.size:]))


# -- a loopback supplier -------------------------------------------------------

@pytest.fixture
def supplier(tmp_path):
    """A byte-path supplier (every REQ through the engine's pool, so a
    ``data_engine.pread`` failpoint reaches it) over three maps."""
    mof = tmp_path / "mof"
    mof.mkdir()
    make_mof_tree(str(mof), JOB, num_maps=3, num_reducers=1,
                  records_per_map=400, seed=11)
    engine = DataEngine(DirIndexResolver(str(mof)), Config())
    server = ShuffleServer(engine, Config({"uda.tpu.net.zerocopy": False}),
                           host="127.0.0.1", port=0)
    server.start()
    try:
        yield server
    finally:
        server.stop()
        engine.stop()


def _raw_fetch(server, trace):
    """One REQ over a plain socket -> the DATA frame's bytes."""
    sock = socket.create_connection(("127.0.0.1", server.port), timeout=10)
    try:
        sock.settimeout(10.0)
        assert wire.recv_frame(sock)[0] == wire.MSG_HELLO
        req = ShuffleRequest(JOB, map_ids(JOB, 1)[0], 0, 0, 1 << 20)
        sock.sendall(wire.encode_request(5, req, trace=trace))
        msg_type, req_id, payload = wire.recv_frame(sock)
        assert (msg_type, req_id) == (wire.MSG_DATA, 5)
        return bytes(payload)
    finally:
        wire.close_hard(sock)


def test_a_req_without_the_trace_tail_gets_the_parents_frame(supplier):
    """Spans off on the reduce side = no tail = the supplier's DATA
    frame is the parent's, byte for byte; the tail buys the block."""
    plain = _raw_fetch(supplier, None)
    res = wire.decode_result(plain)
    assert res.timing is None and res.is_last and res.data
    head = _parents_head(5, res.raw_length, res.part_length, res.offset,
                         res.last, res.path, res.crc, len(res.data))
    assert wire.HEADER.pack(wire.MAGIC, wire.WIRE_VERSION, wire.MSG_DATA,
                            5, len(plain)) + plain == head + res.data
    timed = wire.decode_result(_raw_fetch(supplier, (0xABC, 0xDEF)))
    assert timed.timing is not None and timed.data == res.data
    assert len(timed.timing) == 2 and min(timed.timing) >= 0
    # the supplier's own view of the same stages is always live
    for name in ("net.serve.park_seconds", "net.serve.serve_seconds",
                 "net.serve.send_seconds"):
        assert name in metrics.snapshot(), name
        assert metrics.get(name) >= 0.0


def _shuffle(server, cfg=None) -> bytes:
    """One whole reduce task over the loopback supplier, 8 KB chunks."""
    cfg = Config(dict({"mapred.rdma.buf.size": 8}, **(cfg or {})))
    router = HostRoutingClient(config=cfg)
    blocks: list = []
    try:
        mm = MergeManager(router, "uda.tpu.RawBytes", cfg)
        maps = [(f"127.0.0.1:{server.port}", m) for m in map_ids(JOB, 3)]
        mm.run(JOB, maps, 0, lambda b: blocks.append(bytes(b)))
    finally:
        router.stop()
    return b"".join(blocks)


def _remote_seconds() -> float:
    """Summed (frame decoded - posted) of the client's DATA frames, from
    the histogram the client observes with the same two stamps."""
    h = metrics.histogram_summaries()["net.frame.latency_ms{role=client}"]
    return h["sum"] / 1e3


def test_one_chunk_segments_are_cracked_on_the_upcall_thread(supplier):
    """A segment that arrives whole in its first chunk is cracked where
    it lands: a crack a chunk, inside the upcall thread's busy seconds,
    none deferred."""
    metrics.enable_stats()
    assert _shuffle(supplier, {"mapred.rdma.buf.size": 1024})
    chunks = metrics.get("fetch.chunks")
    assert chunks == 3              # one chunk a segment
    assert metrics.get("fetch.crack.deferred_segments") == 0
    assert 0.0 < metrics.get("fetch_crack_time") \
        <= metrics.get("net.dispatch.busy_seconds", loop=CLIENT_LOOP)
    cracks = [s for s in metrics.spans if s["name"] == "fetch_crack"]
    assert len(cracks) == chunks


def test_spans_on_every_chunk_is_timed_and_the_stages_add_up(supplier):
    metrics.enable_stats()          # histograms + spans
    assert _shuffle(supplier)
    chunks = metrics.get("fetch.chunks")
    assert chunks > 3               # several chunks a segment
    assert metrics.get("fetch.chunk.timed") == chunks
    stages = {name: metrics.get(name) for name in CHUNK_COUNTERS}
    assert min(stages.values()) >= 0.0, stages
    # wire is the remainder, so park + serve + wire IS posted -> decoded
    assert (stages["fetch.chunk.park_seconds"]
            + stages["fetch.chunk.serve_seconds"]
            + stages["fetch.chunk.wire_seconds"]) == \
        pytest.approx(_remote_seconds(), rel=1e-9)
    # ... and what the heads reported is what the supplier counted (the
    # heads carry whole microseconds, rounded down)
    assert stages["fetch.chunk.serve_seconds"] <= \
        metrics.get("net.serve.serve_seconds")
    assert metrics.get("net.serve.serve_seconds") \
        < stages["fetch.chunk.serve_seconds"] + 1e-6 * (chunks + 1)
    assert metrics.get("fetch_crack_time") > 0.0
    assert metrics.get("net.dispatch.upcalls", loop=CLIENT_LOOP) >= chunks
    # several chunks a segment: every crack was deferred to the thread
    # that materialized the segment, one crack a segment
    assert metrics.get("fetch.crack.deferred_segments") == 3

    # the spans: all in the task's trace, under the right parents
    spans = list(metrics.spans)
    root, = (s for s in spans if s["name"] == "reduce_task")
    by_name: dict = {}
    for s in spans:
        by_name.setdefault(s["name"], []).append(s)
    fetches = [s for s in by_name["net.fetch"] if s["trace"] == root["trace"]]
    assert len(fetches) == chunks
    assert all(s["attrs"]["park_us"] >= 0 and s["attrs"]["serve_us"] >= 0
               for s in fetches)
    fetch_ids = {s["id"] for s in fetches}
    waits = by_name["net.dispatch.wait"]
    assert len(waits) == chunks
    assert {s["parent"] for s in waits} <= fetch_ids
    segment_ids = {s["id"] for s in by_name["fetch.segment"]}
    cracks = by_name["fetch_crack"]
    assert len(cracks) == 3
    assert {s["parent"] for s in cracks} <= segment_ids
    assert {s["trace"] for s in waits + cracks} == {root["trace"]}


def test_spans_off_nothing_is_reported_and_wire_takes_it_all(supplier):
    metrics.enable_stats()
    metrics.disable_spans()         # histograms alone: the tail is off
    assert _shuffle(supplier)
    assert metrics.get("fetch.chunks") > 3
    assert metrics.get("fetch.chunk.timed") == 0
    assert metrics.get("fetch.chunk.park_seconds") == 0.0
    assert metrics.get("fetch.chunk.serve_seconds") == 0.0
    assert metrics.get("fetch.chunk.wire_seconds") == \
        pytest.approx(_remote_seconds(), rel=1e-9)
    # the always-live halves still count
    assert metrics.get("net.serve.serve_seconds") > 0.0
    assert metrics.get("fetch_crack_time") > 0.0
    assert metrics.get("net.dispatch.busy_seconds", loop=CLIENT_LOOP) > 0.0
    assert not metrics.spans


def test_a_delayed_read_shows_in_serve_not_in_wire(supplier):
    metrics.enable_spans()
    delay_s = 0.1
    with failpoints.scoped(f"data_engine.pread=delay:{delay_s * 1e3:g}"):
        assert _shuffle(supplier, {"mapred.rdma.buf.size": 1024})
    chunks = metrics.get("fetch.chunks")
    assert chunks == 3              # one chunk a map under a 1 MB buffer
    serve = metrics.get("fetch.chunk.serve_seconds")
    assert serve >= chunks * delay_s
    assert metrics.get("net.serve.serve_seconds") >= chunks * delay_s
    assert metrics.get("fetch.chunk.wire_seconds") < serve
    assert metrics.get("fetch.chunk.park_seconds") < serve


def test_a_slow_upcall_is_the_next_chunks_dispatch_wait(supplier):
    """Two fetches posted together; the first upcall holds the one
    dispatch thread for 50 ms AFTER the second frame was decoded, so
    the second chunk's queue wait and the thread's busy seconds both
    read at least that."""
    hold_s = 0.05
    client = RemoteFetchClient("127.0.0.1", supplier.port, Config())
    done = threading.Event()
    results: list = []

    def first(result) -> None:
        deadline = time.monotonic() + 10.0
        while client._pending and time.monotonic() < deadline:
            time.sleep(0.001)       # until the second frame is decoded
        time.sleep(hold_s)
        results.append(result)

    def second(result) -> None:
        results.append(result)
        done.set()

    try:
        mids = map_ids(JOB, 2)
        client.start_fetch(ShuffleRequest(JOB, mids[0], 0, 0, 1 << 20), first)
        client.start_fetch(ShuffleRequest(JOB, mids[1], 0, 0, 1 << 20),
                           second)
        assert done.wait(20.0)
    finally:
        client.stop()
    assert all(isinstance(r, FetchResult) for r in results), results
    assert metrics.get("fetch.chunk.dispatch_wait_seconds") >= hold_s
    assert metrics.get("net.dispatch.busy_seconds",
                       loop=CLIENT_LOOP) >= hold_s
    assert metrics.get("net.dispatch.busy_seconds") >= hold_s
    assert metrics.get("net.dispatch.upcalls", loop=CLIENT_LOOP) >= 2
    assert metrics.get("fetch.chunk.timed") == 0    # spans off: no tail


# -- feed()'s backpressure -----------------------------------------------------

def _big_batch(seed: int, nbytes: int):
    rng = np.random.default_rng(seed)
    recs = sorted((rng.bytes(10), rng.bytes(90))
                  for _ in range(nbytes // 100))
    return crack(write_records(recs))


def _feed_two(inflight_bytes: int, gated: bool) -> OverlappedMerger:
    """Feed two 0.7 MB batches. ``gated``: the first one's budget charge
    is held until the second feed is blocked on it (the deterministic
    form of 'staging lags')."""
    kt = comparators.get_key_type("uda.tpu.RawBytes")
    om = OverlappedMerger(kt, 16, engine="host",
                          inflight_bytes=inflight_bytes)
    batches = [_big_batch(s, 700_000) for s in (1, 2)]
    gate = threading.Event()
    if gated:
        release = om._release_charge

        def held_release(charge):
            assert gate.wait(20.0)
            release(charge)

        om._release_charge = held_release
    om.feed(0, batches[0])
    feeder = threading.Thread(target=om.feed, args=(1, batches[1]))
    feeder.start()
    if gated:
        deadline = time.monotonic() + 20.0
        while metrics.get("stage.backpressure_events") < 1 \
                and time.monotonic() < deadline:
            time.sleep(0.001)
        gate.set()
    feeder.join(30.0)
    assert not feeder.is_alive()
    out: list = []
    om.emit_stream(batches, FramedEmitter(1 << 16),
                   lambda blk: out.append(len(blk)))
    assert sum(out) > 1_400_000
    return om


def test_a_1mb_stage_budget_times_the_feed_wait():
    _feed_two(1 << 20, gated=True)
    assert metrics.get("stage.backpressure_events") == 1
    assert metrics.get("fetch_feed_wait_time") > 0.0


def test_an_ample_stage_budget_leaves_the_feed_wait_at_exactly_zero():
    _feed_two(64 << 20, gated=False)
    assert metrics.get("stage.backpressure_events") == 0
    snap = metrics.snapshot()
    assert snap["fetch_feed_wait_time"] == 0.0      # declared: 0, not nothing
    assert snap["fetch_crack_time"] == 0.0          # nothing fetched here


def test_the_feed_wait_span_lands_under_the_segments_span():
    """The upcall thread has no ambient span: the timer's span must
    take the fed segment's ``fetch.segment`` span as its parent, or
    critpath (which scopes by trace id) never sees it."""
    metrics.enable_spans()
    with metrics.span("reduce_task") as root:
        seg_span = metrics.start_span("fetch.segment", map="m0")
        seg_span.end()              # ended by _notify_done before feed()
    source = type("Seg", (), {"trace_span": seg_span})()
    with OverlappedMerger._feed_wait(source):
        pass
    span, = (s for s in metrics.spans if s["name"] == "fetch_feed_wait")
    assert span["parent"] == seg_span.span_id
    assert span["trace"] == root.trace_id


# -- the registries ------------------------------------------------------------

@pytest.mark.parametrize("name", CHUNK_COUNTERS + (
    "fetch.chunk.timed", "net.serve.park_seconds",
    "net.serve.serve_seconds", "net.serve.send_seconds",
    "net.dispatch.busy_seconds", "net.dispatch.upcalls"))
def test_the_new_counters_are_registered(name):
    assert METRICS_REGISTRY[name][0] == "counter"


def test_the_new_span_is_registered_and_bucketed():
    assert "net.dispatch.wait" in SPAN_REGISTRY
    assert critpath.SPAN_BUCKETS["net.dispatch.wait"] == "fetch"
    assert critpath.SPAN_BUCKETS["fetch_crack"] == "fetch"
    assert critpath.SPAN_BUCKETS["fetch_feed_wait"] == "wait"
