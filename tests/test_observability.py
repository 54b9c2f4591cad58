"""Cluster-scope observability tier-1 coverage (ISSUE 11).

Four layers:

1. wire plumbing: the optional REQ/SIZE_REQ trace-context tail
   (length-versioned, old shapes decode), HELLO capability bits old
   decoders ignore, MSG_STATS/MSG_STATS_REPLY frames, and the typed-ERR
   refusal of unknown frame types (no disconnects);
2. cross-process trace correlation end to end: a real
   server<->client shuffle whose supplier-side ``net.serve`` /
   ``engine.pread`` spans carry the reduce task's trace id with correct
   parentage, stitched into one Chrome trace by
   ``scripts/trace_merge.py``;
3. the live introspection plane: ``MSG_STATS`` round-trips live
   counters/gauges/percentiles, ResourceLedger obligations and the
   server conn table (the ``scripts/udatop.py`` scrape surface);
4. the flight recorder: ring bounds, dump contents, and the
   faults-marked guarantee that a forced FallbackSignal produces
   exactly ONE black-box dump containing the injected failpoint event
   and the terminal cause.
"""

from __future__ import annotations

import json
import socket
import subprocess
import sys
import threading
import time

import pytest

from tests.helpers import make_mof_tree, map_ids
from uda_tpu.merger import HostRoutingClient, LocalFetchClient, MergeManager
from uda_tpu.mofserver import DataEngine, DirIndexResolver
from uda_tpu.mofserver.data_engine import ShuffleRequest
from uda_tpu.net import ShuffleServer, wire
from uda_tpu.net.client import RemoteFetchClient, fetch_remote_stats
from uda_tpu.utils.config import Config
from uda_tpu.utils.errors import (FallbackSignal, ProtocolError,
                                  StorageError, TransportError)
from uda_tpu.utils.failpoints import failpoints
from uda_tpu.utils.flightrec import FlightRecorder, flightrec
from uda_tpu.utils.metrics import SPAN_REGISTRY, metrics
from uda_tpu.utils.stats import (StatsReporter, introspection_snapshot,
                                 register_stats_provider,
                                 unregister_stats_provider)

REPO = __file__.rsplit("/tests/", 1)[0]
JOB = "jobObs"


# -- wire: trace context + HELLO caps + stats frames -------------------------


def test_request_trace_tail_roundtrip():
    req = ShuffleRequest(JOB, "m_0", 3, 4096, 1 << 20)
    plain = wire.encode_request(7, req)
    traced = wire.encode_request(7, req, trace=(0xABCDEF0012345678, 42))
    assert len(traced) == len(plain) + 16
    for frame, want in ((plain, None),
                        (traced, (0xABCDEF0012345678, 42))):
        msg_type, req_id, length = wire.decode_header(
            frame[:wire.HEADER.size])
        assert (msg_type, req_id) == (wire.MSG_REQ, 7)
        got, trace = wire.decode_request_ex(frame[wire.HEADER.size:])
        assert got == req
        assert trace == want
    # the old decode surface is oblivious to the tail
    assert wire.decode_request(traced[wire.HEADER.size:]) == req


def test_size_request_trace_tail_roundtrip():
    plain = wire.encode_size_request(9, JOB, ["a", "b"], 1)
    traced = wire.encode_size_request(9, JOB, ["a", "b"], 1,
                                      trace=(5, 6))
    body, trace = wire.decode_size_request_ex(traced[wire.HEADER.size:])
    assert body == (JOB, ["a", "b"], 1) and trace == (5, 6)
    assert wire.decode_size_request(plain[wire.HEADER.size:]) == \
        (JOB, ["a", "b"], 1)


def test_trace_tail_wrong_length_is_torn_frame():
    req = ShuffleRequest(JOB, "m_0", 0, 0, 64)
    payload = wire.encode_request(1, req)[wire.HEADER.size:] + b"junk"
    with pytest.raises(TransportError, match="trailing"):
        wire.decode_request_ex(payload)


def test_hello_caps_bit_and_old_decoder_ignores_it():
    frame = wire.encode_hello(17, True)  # caps default CAP_TRACE
    payload = frame[wire.HEADER.size:]
    # the old (PR 8) decode surface: generation + warm only — the
    # capability bit must be invisible to it (same struct size)
    assert wire.decode_hello(payload) == (17, True)
    gen, warm, caps = wire.decode_hello_ex(payload)
    assert (gen, warm) == (17, True) and caps & wire.CAP_TRACE
    # a capability-less banner (old server shape)
    old = wire.encode_hello(3, False, caps=0)[wire.HEADER.size:]
    assert wire.decode_hello_ex(old)[2] & wire.CAP_TRACE == 0


def test_stats_frames_roundtrip():
    snap = {"counters": {"net.requests": 4}, "nested": {"p95": 1.5}}
    frame = wire.encode_stats_reply(11, snap)
    msg_type, req_id, _ = wire.decode_header(frame[:wire.HEADER.size])
    assert (msg_type, req_id) == (wire.MSG_STATS_REPLY, 11)
    assert wire.decode_stats_reply(frame[wire.HEADER.size:]) == snap
    req = wire.encode_stats_request(11)
    assert wire.decode_header(req[:wire.HEADER.size])[0] == wire.MSG_STATS


def test_unknown_type_in_reserved_range_passes_header():
    frame = wire.encode_frame(25, 1, b"")
    assert wire.decode_header(frame[:wire.HEADER.size])[0] == 25
    with pytest.raises(TransportError, match="unknown frame type"):
        wire.decode_header(wire.encode_frame(200, 1,
                                             b"")[:wire.HEADER.size])


# -- the live server plane ---------------------------------------------------


@pytest.fixture
def supplier(tmp_path):
    expected = make_mof_tree(str(tmp_path), JOB, num_maps=3,
                             num_reducers=1, records_per_map=40, seed=11)
    engine = DataEngine(DirIndexResolver(str(tmp_path)), Config())
    server = ShuffleServer(engine, Config(), host="127.0.0.1", port=0)
    server.start()
    yield expected, server
    server.stop()
    engine.stop()


def _fetch_sync(client, req, timeout=10.0):
    box, done = [], threading.Event()
    client.start_fetch(req, lambda res: (box.append(res), done.set()))
    assert done.wait(timeout), "fetch never completed"
    return box[0]


def test_msg_stats_roundtrip_returns_live_state(supplier):
    """The acceptance criterion: MSG_STATS against a supplier that has
    served traffic returns live counters/gauges/percentiles, the
    ResourceLedger summary and the conn table."""
    _, server = supplier
    metrics.enable_stats()  # histograms -> percentiles populated
    client = RemoteFetchClient("127.0.0.1", server.port, Config())
    try:
        for mid in map_ids(JOB, 3):
            res = _fetch_sync(client,
                              ShuffleRequest(JOB, mid, 0, 0, 1 << 20))
            assert not isinstance(res, Exception)
        # poll over the wire WHILE the fetch connection is still open:
        # the conn table must show it
        snap = fetch_remote_stats("127.0.0.1", server.port)
    finally:
        client.stop()
    assert snap["counters"]["net.requests"] >= 3
    assert snap["counters"]["supplier.bytes"] > 0
    assert "percentiles" in snap
    p = snap["percentiles"].get("supplier.read.latency_ms")
    if p is not None:  # zero-copy plans may skip the pool histogram
        assert p["p95"] >= 0
    led = snap["resledger"]
    assert {"armed", "outstanding", "by_pair",
            "leak_reports"} <= set(led)
    srv = snap["providers"]["net.server"]
    assert srv["generation"] == server.generation
    assert any(c["peer"] for c in srv["connections"])
    assert srv["loop"]["alive"]
    # the in-process multiplexed surface answers too
    client2 = RemoteFetchClient("127.0.0.1", server.port, Config())
    try:
        snap2 = client2.fetch_stats(timeout=10.0)
    finally:
        client2.stop()
    assert snap2 is not None and snap2["counters"]["net.stats.requests"] >= 1


def test_unknown_msg_type_gets_typed_err_without_disconnect(supplier):
    """A frame type the server does not handle is refused with a typed
    ERR on the same req id and the connection keeps working — the
    forward-compat acceptance criterion."""
    _, server = supplier
    sock = socket.create_connection(("127.0.0.1", server.port),
                                    timeout=10.0)
    try:
        sock.settimeout(10.0)
        msg_type, _, _ = wire.recv_frame(sock)  # the HELLO banner
        assert msg_type == wire.MSG_HELLO
        sock.sendall(wire.encode_frame(25, 77, b""))
        msg_type, req_id, payload = wire.recv_frame(sock)
        assert (msg_type, req_id) == (wire.MSG_ERR, 77)
        err = wire.decode_error(payload)
        assert isinstance(err, ProtocolError)
        # same connection still serves: a stats poll round-trips
        sock.sendall(wire.encode_stats_request(78))
        msg_type, req_id, payload = wire.recv_frame(sock)
        assert (msg_type, req_id) == (wire.MSG_STATS_REPLY, 78)
        assert "counters" in wire.decode_stats_reply(payload)
    finally:
        wire.close_hard(sock)


def test_old_peer_request_without_trace_fields_serves(supplier):
    """An old-version client (no trace tail, ignores the caps bit) must
    interoperate: a hand-rolled pre-observability REQ gets its DATA."""
    _, server = supplier
    sock = socket.create_connection(("127.0.0.1", server.port),
                                    timeout=10.0)
    try:
        sock.settimeout(10.0)
        assert wire.recv_frame(sock)[0] == wire.MSG_HELLO
        req = ShuffleRequest(JOB, map_ids(JOB, 1)[0], 0, 0, 1 << 20)
        sock.sendall(wire.encode_request(5, req))  # no trace kwarg
        msg_type, req_id, payload = wire.recv_frame(sock)
        assert (msg_type, req_id) == (wire.MSG_DATA, 5)
        assert wire.decode_result(payload).is_last
    finally:
        wire.close_hard(sock)


def test_udatop_once_renders_live_supplier(supplier):
    """The console script end to end: one --once --json sample against
    a live supplier parses and carries the snapshot."""
    _, server = supplier
    out = subprocess.run(
        [sys.executable, f"{REPO}/scripts/udatop.py",
         f"127.0.0.1:{server.port}", "--once", "--json"],
        capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    snap = json.loads(out.stdout.strip().splitlines()[-1])
    assert snap[f"127.0.0.1:{server.port}"]["counters"] is not None


# -- cross-process trace correlation (the tentpole e2e) ----------------------


def test_serve_spans_carry_reduce_trace_id_and_merge(tmp_path):
    """Two-bridge-shaped loopback e2e (the test_net pattern): a full
    MergeManager shuffle over RemoteFetchClient with spans on. The
    supplier-side ``net.serve`` spans must share the reduce task's
    trace id and parent under the reduce-side ``net.fetch`` spans
    (wire-carried trace context), ``engine.pread`` must hang under the
    serve spans, and ``scripts/trace_merge.py`` must stitch the
    \"two processes'\" span files into one valid Chrome trace."""
    mof = tmp_path / "mof"
    mof.mkdir()
    make_mof_tree(str(mof), JOB, num_maps=3, num_reducers=1,
                  records_per_map=50, seed=5)
    metrics.enable_spans()
    engine = DataEngine(DirIndexResolver(str(mof)), Config())
    # the byte path: the zero-copy fd path serves a resolved partition
    # inline with no engine.pread span, and the task's size probe (the
    # chip-wide HBM ledger's, at every merge approach) has resolved
    # every partition before its first fetch
    server = ShuffleServer(engine, Config({"uda.tpu.net.zerocopy": False}),
                           host="127.0.0.1", port=0)
    server.start()
    try:
        router = HostRoutingClient(config=Config())
        mm = MergeManager(router, "uda.tpu.RawBytes", Config())
        blocks: list[bytes] = []
        maps = [(f"127.0.0.1:{server.port}", m)
                for m in map_ids(JOB, 3)]
        mm.run(JOB, maps, 0, lambda b: blocks.append(bytes(b)))
        router.stop()
    finally:
        server.stop()
        engine.stop()
    assert blocks
    spans = list(metrics.spans)
    roots = [s for s in spans if s["name"] == "reduce_task"]
    assert len(roots) == 1
    trace = roots[0]["trace"]
    fetch_ids = {s["id"] for s in spans if s["name"] == "net.fetch"}
    serves = [s for s in spans if s["name"] == "net.serve"]
    # >= 1 supplier-side serve span in the reduce task's trace, with
    # correct parentage under a reduce-side net.fetch span
    assert any(s["trace"] == trace and s["parent"] in fetch_ids
               for s in serves), \
        f"no wire-stitched serve span (serves={len(serves)})"
    serve_ids = {s["id"] for s in serves}
    preads = [s for s in spans if s["name"] == "engine.pread"]
    assert any(s["trace"] == trace and s["parent"] in serve_ids
               for s in preads), "engine.pread not under net.serve"
    # every explicit span name this run produced is declared (the
    # UDA009 contract, observed live)
    assert {"reduce_task", "net.fetch", "net.serve",
            "engine.pread"} <= SPAN_REGISTRY.keys() & \
        {s["name"] for s in spans}

    # -- trace_merge over simulated per-process files --------------------
    all_jsonl = tmp_path / "all.jsonl"
    n = metrics.export_spans_jsonl(str(all_jsonl))
    assert n == len(spans)
    supplier_names = {"net.serve", "engine.pread", "supplier_read"}
    reducer_f = tmp_path / "reducer.jsonl"
    supplier_f = tmp_path / "supplier.jsonl"
    with open(all_jsonl) as f, open(reducer_f, "w") as rf, \
            open(supplier_f, "w") as sf:
        for line in f:
            rec = json.loads(line)
            if rec["name"] in supplier_names:
                rec["pid"] += 1  # the supplier "process"
                sf.write(json.dumps(rec) + "\n")
            else:
                rf.write(json.dumps(rec) + "\n")
    merged = tmp_path / "merged.json"
    out = subprocess.run(
        [sys.executable, f"{REPO}/scripts/trace_merge.py",
         str(reducer_f), str(supplier_f), "--out", str(merged),
         "--require-cross-process"],
        capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr + out.stdout
    trace_json = json.loads(merged.read_text())
    events = trace_json["traceEvents"]
    assert events and all(e["ph"] in ("X", "M") for e in events)
    stitched = [e for e in events
                if e.get("args", {}).get("cross_process_parent")]
    assert stitched, "merged trace lost the cross-process links"


def test_shard_streams_adopt_owning_fetch_span():
    """Satellite: coding/recovery.py shard streams issue from transport
    completion threads — every start_fetch (the chained candidates
    included) must run under the owning fetch span so transport spans
    join the trace tree instead of starting parentless roots."""
    from uda_tpu.coding import parse_scheme
    from uda_tpu.coding.recovery import StripeContext, start_recovery

    metrics.enable_spans()
    scheme = parse_scheme("rs:2:3")
    ctx = StripeContext(scheme, ["h1", "h2", "h3"])
    seen = []
    done = threading.Event()

    class FailingClient:
        def start_fetch(self, req, on_complete):
            seen.append(metrics.current_span())
            threading.Thread(target=on_complete,
                             args=(TransportError("shard down"),),
                             daemon=True).start()

    root = metrics.start_span("fetch.segment", map="m_0")
    with metrics.use_span(root):
        start_recovery(FailingClient(),
                       ShuffleRequest(JOB, "m_0", 0, 0, 1024, host="h1"),
                       ctx, lambda res: done.set())
    assert done.wait(5.0), "reconstruction never finished"
    root.end()
    assert len(seen) == 3  # every candidate was tried
    assert all(s is root for s in seen), \
        "a chained shard issue lost the owning fetch span"


# -- flight recorder ---------------------------------------------------------


def test_flightrec_ring_is_bounded_and_ordered():
    fr = FlightRecorder(capacity=16, enabled=True)
    for i in range(40):
        fr.record("tick", i=i)
    evs = fr.events()
    assert len(evs) == 16
    assert [e["i"] for e in evs] == list(range(24, 40))  # newest kept


def test_flightrec_disabled_is_noop(tmp_path):
    fr = FlightRecorder(enabled=False, dump_dir=str(tmp_path))
    fr.record("tick")
    assert fr.events() == [] and fr.dump("x") is None
    assert not list(tmp_path.iterdir())


def test_flightrec_dump_file_contents(tmp_path):
    fr = FlightRecorder(capacity=64, enabled=True,
                        dump_dir=str(tmp_path / "fr"))
    fr.record("segment.start", map="m_1")
    fr.record("failpoint", site="data_engine.pread", action="error")
    path = fr.dump("unit_test", extra={"why": "coverage"})
    assert path is not None
    rep = json.loads(open(path).read())
    assert rep["cause"] == "unit_test" and rep["extra"]["why"] == "coverage"
    kinds = [e["kind"] for e in rep["events"]]
    assert kinds == ["segment.start", "failpoint"]
    assert fr.dump_paths == [path] and len(fr.reports) == 1
    # no dir configured -> in-memory report only
    fr2 = FlightRecorder(enabled=True)
    fr2.record("tick")
    assert fr2.dump("mem_only") is None and len(fr2.reports) == 1


@pytest.mark.faults
def test_fallback_produces_exactly_one_dump_with_injected_fault(tmp_path):
    """Acceptance: a forced FallbackSignal dumps the black box exactly
    once, and the dump's event stream contains the injected failpoint
    event and the terminal cause."""
    mof = tmp_path / "mof"
    mof.mkdir()
    make_mof_tree(str(mof), JOB, num_maps=2, num_reducers=1,
                  records_per_map=20, seed=2)
    frdir = tmp_path / "fr"
    engine = DataEngine(DirIndexResolver(str(mof)), Config())
    cfg = Config({"uda.tpu.fetch.retries": 0,
                  "uda.tpu.flightrec.dir": str(frdir)})
    try:
        with failpoints.scoped("data_engine.pread=error"):
            mm = MergeManager(LocalFetchClient(engine),
                              "uda.tpu.RawBytes", cfg)
            with pytest.raises(FallbackSignal) as ei:
                mm.run(JOB, map_ids(JOB, 2), 0, lambda b: None)
        assert isinstance(ei.value.cause, StorageError)
    finally:
        engine.stop()
    dumps = sorted(frdir.glob("flightrec_*_fallback.json"))
    assert len(dumps) == 1, [p.name for p in dumps]
    rep = json.loads(dumps[0].read_text())
    assert rep["cause"] == "fallback"
    assert rep["extra"]["error"] == "StorageError"
    fired = [e for e in rep["events"] if e["kind"] == "failpoint"]
    assert fired and fired[0]["site"] == "data_engine.pread"
    # the terminal segment transition is in the stream too
    assert any(e["kind"] == "segment.done" and e["error"]
               for e in rep["events"])


# -- stats reporter satellites -----------------------------------------------


def test_reporter_percentiles_every_record_and_final_blocks():
    metrics.enable_stats()
    metrics.observe("fetch.latency_ms", 10.0)
    metrics.observe("fetch.latency_ms", 100.0)
    clock = [100.0]
    rep = StatsReporter(interval_s=1.0, out=open("/dev/null", "w"),
                        clock=lambda: clock[0])
    record = rep.report_once()
    p = record["percentiles"]["fetch.latency_ms"]
    assert set(p) == {"p50", "p95", "p99"} and p["p95"] >= p["p50"] > 0

    def provider():
        return {"penalty_box": {"boxed": ["h2"]},
                "ledger": {"counts": {"fault": 3}}}

    register_stats_provider("recovery.r7", provider)
    try:
        clock[0] = 101.0
        final = rep.report_once(final=True)
    finally:
        unregister_stats_provider("recovery.r7")
    assert final["recovery"]["recovery.r7"]["penalty_box"]["boxed"] == \
        ["h2"]
    assert "resledger" in final and "outstanding" in final["resledger"]
    assert "percentiles" in final


def test_introspection_snapshot_degrades_broken_provider():
    def broken():
        raise RuntimeError("component torn down")

    register_stats_provider("bad.provider", broken)
    try:
        snap = introspection_snapshot()
    finally:
        unregister_stats_provider("bad.provider")
    assert snap["providers"]["bad.provider"] == {"error": "RuntimeError"}
    assert {"counters", "gauges", "percentiles", "resledger",
            "pid"} <= set(snap)
