"""The deferred crack of a multi-chunk segment (merger/segment.py).

A segment whose first chunk is also its last is cracked where it lands,
by the parent's eager path. Any other segment keeps its chunks as they
come and ``record_batch()`` joins and cracks them once, on the thread
that materializes the segment. These tests hold the deferred half to
the eager half record for record, at every chunk-boundary class, and
through every path that reads the crack state mid-fetch: restart,
resume, speculation, reconstruction, ``fail()``, checkpoint export and
preload, push adoption, the streaming route's ``release()``."""

import functools
import io
import threading

import numpy as np
import pytest

from uda_tpu.merger import MergeManager
from uda_tpu.merger.emitter import FramedEmitter
from uda_tpu.merger.overlap import OverlappedMerger
from uda_tpu.merger.segment import HostRoutingClient, InputClient, Segment
from uda_tpu.mofserver import FetchResult
from uda_tpu.utils import comparators
from uda_tpu.utils.config import Config
from uda_tpu.utils.errors import (FallbackSignal, MergeError, StorageError,
                                  TransportError)
from uda_tpu.utils.ifile import (EOF_MARKER, IFileReader, crack,
                                 crack_partial, write_records)
from uda_tpu.utils.metrics import metrics
from uda_tpu.utils.retry import RetryPolicy, SpeculationPolicy

JOB = "job_defer"
DEFERRED = "fetch.crack.deferred_segments"


def _recs(n, seed=0, key_bytes=10, val_bytes=30):
    rng = np.random.default_rng(seed)
    return sorted((rng.bytes(key_bytes), rng.bytes(val_bytes))
                  for _ in range(n))


class _Chunks(InputClient):
    """Serves each map's payload cut where the TEST says (not at the
    request's length), inline, so that a chunk boundary can sit on any
    byte. ``faults`` maps the 1-based number of a ``start_fetch`` call
    to what that call does instead: an Exception is delivered as the
    completion, ``"hold"`` keeps the completion back (``held``)."""

    def __init__(self, payloads, cuts=(), faults=None):
        if isinstance(payloads, (bytes, bytearray)):
            payloads = {"m0": bytes(payloads)}
        self.payloads = payloads
        self.cuts = sorted(cuts)
        self.faults = dict(faults or {})
        self.calls: list = []
        self.held: list = []

    def result(self, req) -> FetchResult:
        data = self.payloads[req.map_id]
        end = next((c for c in self.cuts if c > req.offset), len(data))
        end = min(end, len(data))
        return FetchResult(data[req.offset:end], len(data), len(data),
                           req.offset, "p", last=end >= len(data))

    def start_fetch(self, req, on_complete):
        self.calls.append((req.map_id, req.offset))
        fault = self.faults.pop(len(self.calls), None)
        if fault == "hold":
            self.held.append((on_complete, self.result(req)))
        elif fault is not None:
            on_complete(fault)
        else:
            on_complete(self.result(req))

    def estimate_partition_bytes(self, job_id, map_ids, reduce_id):
        return sum(len(self.payloads[m[1] if isinstance(m, tuple) else m])
                   for m in map_ids)


def _segment(client, map_id="m0", **kw):
    kw.setdefault("policy", RetryPolicy(retries=3))
    return Segment(client, JOB, map_id, 0, 1 << 20, **kw)


def _fetched(client, **kw):
    seg = _segment(client, **kw)
    seg.start()
    seg.wait(10.0)
    return seg


def _same(a, b) -> None:
    """Two batches hold the same records, column for column."""
    assert a.num_records == b.num_records
    assert list(a.iter_records()) == list(b.iter_records())
    for col in ("key_len", "val_len"):
        assert np.array_equal(getattr(a, col), getattr(b, col))


PAYLOAD = write_records(_recs(60, seed=6))    # 60 records of 42 bytes, EOF
CUTS = (600, 1200, 1800)


def _reference():
    return crack(PAYLOAD)


# -- (a) record for record, at every boundary class ---------------------------

def _boundary_cases():
    recs = _recs(40, seed=1)
    payload = write_records(recs)
    starts = crack(payload).key_off - 2     # 10 B keys, 30 B values
    long_key = write_records([(b"k" * 300, b"v" * 5), (b"z", b"w")])
    yield "record_across_chunks", payload, [int(starts[7]) + 20,
                                            int(starts[23]) + 5]
    yield "cut_on_a_record_start", payload, [int(starts[11])]
    # a 300-byte key's length is a 3-byte VInt: cut inside it
    yield "vint_across_chunks", long_key, [1]
    yield "vint_across_chunks_twice", long_key, [1, 2]
    yield "eof_marker_alone_in_last_chunk", payload, \
        [len(payload) - len(EOF_MARKER)]
    yield "eof_marker_split", payload, [len(payload) - 1]
    yield "empty_partition_marker_split", write_records([]), [1]
    yield "every_byte_its_own_chunk", write_records(_recs(3, seed=2)), \
        list(range(1, 200))


@pytest.mark.parametrize("payload,cuts", [
    pytest.param(payload, cuts, id=name)
    for name, payload, cuts in _boundary_cases()])
def test_deferred_batch_equals_the_eager_one(payload, cuts):
    eager = _fetched(_Chunks(payload))
    assert metrics.get(DEFERRED) == 0       # one chunk: cracked on arrival
    assert eager.num_records == crack(payload).num_records
    client = _Chunks(payload, cuts)
    seg = _fetched(client)
    assert len(client.calls) == min(len(cuts), len(payload) - 1) + 1
    assert seg.num_records == 0 and not seg.batches     # nothing cracked yet
    _same(seg.record_batch(), eager.record_batch())
    assert seg.num_records == eager.num_records
    assert metrics.get(DEFERRED) == 1
    assert seg._raw is None                 # the chunk list is dropped


def test_a_dense_partition_is_cracked_whole_by_the_native_crack():
    """3-byte frames, the densest a value-only record gets, in a
    partition large enough to take the native crack: the one deferred
    call cracks them all."""
    recs = [(b"", bytes([i % 251])) for i in range(30_000)]
    payload = write_records(recs)
    assert len(payload) == 3 * len(recs) + len(EOF_MARKER)
    seg = _fetched(_Chunks(payload, [len(payload) // 2]))
    assert list(seg.record_batch().iter_records()) == recs
    assert seg.num_records == len(recs) and metrics.get(DEFERRED) == 1


def test_a_partition_of_no_bytes_in_two_chunks_is_empty():
    """raw_length 0 and no EOF marker, as foreign writers may produce
    for empty reducers — delivered as an empty non-final chunk and an
    empty final one, so the deferred path meets it too."""
    class _Empty(InputClient):
        calls = 0

        def start_fetch(self, req, on_complete):
            _Empty.calls += 1
            on_complete(FetchResult(b"", 0, 0, 0, "p",
                                    last=_Empty.calls > 1))

    seg = _fetched(_Empty())
    assert seg.record_batch().num_records == 0
    assert seg.num_records == 0 and metrics.get(DEFERRED) == 1


@pytest.mark.parametrize("chunk", [777, 1 << 20],
                         ids=["several_chunks", "one_chunk"])
def test_compressed_source_through_the_decompressing_client(tmp_path, chunk):
    from uda_tpu.compress import DecompressingClient, get_codec
    from uda_tpu.merger import LocalFetchClient
    from uda_tpu.mofserver import DataEngine, DirIndexResolver
    from uda_tpu.mofserver.writer import MOFWriter

    codec = get_codec("zlib")
    recs = _recs(150, seed=3, val_bytes=60)
    writer = MOFWriter(str(tmp_path), JOB, codec=codec)
    writer.write("m0", [recs])
    engine = DataEngine(DirIndexResolver(str(tmp_path)), Config())
    try:
        client = DecompressingClient(LocalFetchClient(engine), codec,
                                     comp_chunk_size=chunk)
        seg = Segment(client, JOB, "m0", 0, chunk)
        seg.start()
        seg.wait(10.0)
        assert list(seg.record_batch().iter_records()) == recs
    finally:
        engine.stop()
    assert seg.num_records == len(recs)
    one_chunk = chunk > 777
    assert (metrics.get("fetch.chunks") == 1) == one_chunk
    assert metrics.get(DEFERRED) == (0 if one_chunk else 1)


# -- (b) who takes which path, and what the counter books ---------------------

def test_one_chunk_is_eager_two_chunks_book_one_deferred_segment():
    metrics.enable_spans()
    payload = write_records(_recs(20, seed=4))
    one = _fetched(_Chunks(payload))
    assert one.num_records == 20 and len(one.batches) == 1  # on arrival
    assert one._raw is None
    cracks = [s for s in metrics.spans if s["name"] == "fetch_crack"]
    assert len(cracks) == 1 and metrics.get(DEFERRED) == 0
    crack_s = metrics.get("fetch_crack_time")
    assert crack_s > 0.0

    two = _fetched(_Chunks(payload, [len(payload) // 2]))
    assert metrics.get("fetch_crack_time") == crack_s   # nothing cracked
    assert metrics.get(DEFERRED) == 0                   # ... nor booked yet
    out: list = []
    t = threading.Thread(target=lambda: out.append(two.record_batch()),
                         name="a-stage-worker")
    t.start()
    t.join(10.0)
    assert metrics.get(DEFERRED) == 1
    assert metrics.get("fetch_crack_time") > crack_s    # the same counter
    cracks = [s for s in metrics.spans if s["name"] == "fetch_crack"]
    assert len(cracks) == 2
    # under the segment's own span, on the thread that asked
    segment_span, = (s for s in metrics.spans if s["name"] == "fetch.segment"
                     and s["id"] == cracks[1]["parent"])
    assert segment_span["id"] == two.trace_span.span_id
    assert cracks[1]["tid"] != cracks[0]["tid"]
    # cached: the finish pass pays nothing and books nothing
    assert two.record_batch() is out[0]
    assert metrics.get(DEFERRED) == 1
    assert len([s for s in metrics.spans
                if s["name"] == "fetch_crack"]) == 2


def test_many_threads_asking_at_once_crack_a_deferred_segment_once():
    import os
    import sys

    seg = _fetched(_Chunks(PAYLOAD, CUTS))
    workers = 2 * (os.cpu_count() or 4) + 1
    start = threading.Barrier(workers)
    out: list = []

    def ask():
        start.wait(10.0)
        out.append(seg.record_batch())

    threads = [threading.Thread(target=ask) for _ in range(workers)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(20.0)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert len(out) == workers and all(b is out[0] for b in out)
    assert metrics.get(DEFERRED) == 1 and seg.num_records == 60
    _same(out[0], _reference())


def test_the_counter_reads_zero_in_a_task_that_deferred_none():
    kt = comparators.get_key_type("uda.tpu.RawBytes")
    om = OverlappedMerger(kt, width=16)
    try:
        assert metrics.snapshot()[DEFERRED] == 0.0
    finally:
        om.abort()


def _task(payloads, cuts, cfg=None):
    client = _Chunks(payloads, cuts)
    cfg = Config(dict({"mapred.rdma.wqe.per.conn": 1}, **(cfg or {})))
    return client, MergeManager(client, "uda.tpu.RawBytes", cfg)


def _spy_segments(mm) -> list:
    """The segments the task's ``fetch_all`` returns, kept for the test."""
    held: list = []
    orig = mm.fetch_all

    def spy(*args, **kwargs):
        segs = orig(*args, **kwargs)
        held.extend(segs)
        return segs

    mm.fetch_all = spy
    return held


def _sorted_stream(blocks):
    return list(IFileReader(io.BytesIO(b"".join(blocks))))


@pytest.mark.parametrize("cuts,deferred", [((), 0), ((500, 1000), 3)],
                         ids=["one_chunk_segments", "three_chunk_segments"])
def test_a_whole_task_is_byte_identical_either_way(cuts, deferred):
    recs = {f"m{i}": _recs(40, seed=10 + i) for i in range(3)}
    client, mm = _task({m: write_records(r) for m, r in recs.items()}, cuts)
    held = _spy_segments(mm)
    blocks: list = []
    mm.run(JOB, list(recs), 0, lambda b: blocks.append(bytes(b)))
    kt = comparators.get_key_type("uda.tpu.RawBytes")
    want = sorted(sum(recs.values(), []), key=functools.cmp_to_key(
        lambda a, b: kt.compare(a[0], b[0])))
    assert _sorted_stream(blocks) == want
    assert metrics.get(DEFERRED) == deferred
    assert metrics.get("fetch.chunks") == 3 * (len(cuts) + 1)
    # the stream is out: the task holds none of the partition's bytes,
    # whenever the collector gets to its segments
    assert len(held) == 3
    assert all(s.batches == [] and s._raw is None for s in held)
    assert [s.num_records for s in held] == [40] * 3


# -- (c) corrupt framing: its class, and one delivery -------------------------

def _corrupt(payload: bytes, record: int) -> bytes:
    """A negative key length (-2) at the head of ``record``."""
    start = int(crack(payload).key_off[record]) - 2
    return payload[:start] + b"\xfe" + payload[start + 1:]


def test_corrupt_framing_in_a_deferred_segment_fails_at_finish():
    recs = {f"m{i}": _recs(40, seed=20 + i) for i in range(3)}
    payloads = {m: write_records(r) for m, r in recs.items()}
    payloads["m1"] = _corrupt(payloads["m1"], 25)       # in chunk 2 of 3
    client, mm = _task(payloads, (500, 1000))
    kt = comparators.get_key_type("uda.tpu.RawBytes")
    om = OverlappedMerger(kt, width=16)
    fed: list = []

    def feed(i, seg):
        fed.append(i)
        om.feed(i, seg)

    # one credit in the window: a fetch that kept one would never end
    segs = mm.fetch_all(JOB, list(recs), 0, on_segment=feed)
    assert sorted(fed) == [0, 1, 2]         # on_done fired once a segment
    assert all(s.ready for s in segs)       # the fetch itself is whole
    assert metrics.get_gauge("fetch.on_air") == 0
    with pytest.raises(StorageError):
        om.emit_stream(segs, FramedEmitter(1 << 16), lambda b: None)
    with pytest.raises(StorageError):       # and for whoever asks again
        segs[1].record_batch()
    assert sorted(fed) == [0, 1, 2]

    # the whole task: the engine's fallback signal, the cause in its class
    client, mm = _task(payloads, (500, 1000))
    with pytest.raises(FallbackSignal) as err:
        mm.run(JOB, list(recs), 0, lambda b: None)
    assert isinstance(err.value.cause, StorageError)
    assert metrics.get_gauge("fetch.on_air") == 0


def test_corrupt_framing_in_a_one_chunk_segment_fails_the_fetch_as_before():
    payload = _corrupt(write_records(_recs(40, seed=5)), 25)
    seg = _segment(_Chunks(payload))
    done: list = []
    seg.on_done = done.append
    seg.start()
    with pytest.raises(StorageError):
        seg.wait(10.0)
    assert done == [seg]


# -- (d) the recovery ladder over a half-fetched deferred segment -------------

def test_restart_from_zero_drops_the_kept_chunks():
    client = _Chunks(PAYLOAD, CUTS, faults={3: TransportError("blip")})
    seg = _fetched(client)                  # resume off: whole restart
    assert [off for _, off in client.calls] == \
        [0, 600, 1200, 0, 600, 1200, 1800]
    _same(seg.record_batch(), _reference())
    assert metrics.get("fetch.retries") == 1
    assert metrics.get(DEFERRED) == 1       # one segment, restarted or not


def test_a_restart_that_finds_the_segment_in_one_chunk_cracks_it_eagerly():
    class _WholeOnRetry(_Chunks):
        def result(self, req):
            if len(self.calls) > 2:         # the retry is served whole
                self.cuts = []
            return super().result(req)

    client = _WholeOnRetry(PAYLOAD, CUTS,
                           faults={2: TransportError("blip")})
    seg = _fetched(client)                  # chunk 1 kept, chunk 2 fails
    assert client.calls == [("m0", 0), ("m0", 600), ("m0", 0)]
    # nothing of the first attempt stays
    assert seg.num_records == 60 and seg._raw is None   # on arrival
    _same(seg.record_batch(), _reference())
    assert metrics.get(DEFERRED) == 0


def test_resume_at_offset_appends_to_the_kept_chunks():
    client = _Chunks(PAYLOAD, CUTS, faults={3: TransportError("blip")})
    seg = _fetched(client, resume=True)
    assert [off for _, off in client.calls] == [0, 600, 1200, 1200, 1800]
    assert metrics.get("fetch.resumed") == 1
    assert metrics.get("fetch.resumed.bytes") == 1200
    _same(seg.record_batch(), _reference())
    assert metrics.get(DEFERRED) == 1


def test_a_resume_onto_another_partition_restarts_and_keeps_nothing():
    """The resumed attempt's first chunk revalidates identity; a changed
    raw_length restarts from zero, and the first attempt's chunks go."""
    other = write_records(_recs(61, seed=7))
    client = _Chunks(PAYLOAD, CUTS, faults={3: TransportError("blip")})
    seg = _segment(client, resume=True)
    inner = client.result

    def swapped(req):
        if len(client.calls) >= 4:          # the supplier came back changed
            client.payloads = {"m0": other}
        return inner(req)

    client.result = swapped
    seg.start()
    seg.wait(10.0)
    assert metrics.get("fetch.resume.invalidated") == 1
    _same(seg.record_batch(), crack(other))


def test_speculation_win_mid_fetch_keeps_one_copy_of_every_chunk():
    class _Slow(_Chunks):
        def start_fetch(self, req, on_complete):
            if req.offset == 0:
                return super().start_fetch(req, on_complete)
            t = threading.Timer(1.0, super().start_fetch,
                                args=(req, on_complete))
            t.daemon = True
            t.start()

    clients = {"slow": _Slow(PAYLOAD, CUTS), "fast": _Chunks(PAYLOAD, CUTS)}
    router = HostRoutingClient(lambda h: clients[h])
    try:
        seg = _fetched(router, host="slow", hosts=["slow", "fast"],
                       speculation=SpeculationPolicy(pn=95, floor_ms=30))
    finally:
        router.stop()
    assert seg.host == "fast"
    assert metrics.get("fetch.speculation.won") == 1
    assert [off for _, off in clients["fast"].calls] == [600, 1200, 1800]
    _same(seg.record_batch(), _reference())
    assert metrics.get_gauge("fetch.on_air") == 0


def test_stripe_reconstruction_replaces_the_kept_chunks():
    class _Coded(_Chunks):
        def recover_partition(self, req, ctx, on_complete):
            data = self.payloads[req.map_id]
            on_complete(FetchResult(data, len(data), len(data), 0, "p",
                                    last=True))
            return True

    client = _Coded(PAYLOAD, CUTS, faults={3: TransportError("dead")})
    seg = _fetched(client, policy=RetryPolicy(retries=0), stripe=object())
    assert metrics.get("coding.recover.attempts") == 1
    # a whole partition in one result: cracked where it landed
    assert seg.num_records == 60 and seg._raw is None
    _same(seg.record_batch(), _reference())
    assert metrics.get(DEFERRED) == 0


def test_fail_on_a_half_fetched_deferred_segment():
    client = _Chunks(PAYLOAD, CUTS, faults={3: "hold"})
    seg = _segment(client)
    done: list = []
    seg.on_done = done.append
    seg.start()
    assert len(seg._raw) == 2 and seg._next_offset == 1200
    assert seg.fail(MergeError("stopped"))
    # the wedged attempt completes at last: stale, never appended
    on_complete, res = client.held[0]
    on_complete(res)
    assert len(seg._raw) == 2 and done == [seg]
    assert metrics.get("fetch.stale_completions") == 1
    with pytest.raises(MergeError, match="stopped"):
        seg.record_batch()
    assert metrics.get(DEFERRED) == 0 and metrics.get_gauge(
        "fetch.on_air") == 0


def test_the_watchdog_sees_a_deferred_fetch_advance_chunk_by_chunk():
    client = _Chunks(PAYLOAD, CUTS, faults={2: "hold", 3: "hold"})
    _, mm = _task({"m0": PAYLOAD}, CUTS)
    seg = _segment(client)
    mm._live_segments = [seg]
    seg.start()
    tokens = [mm._progress_token()]
    for _ in range(2):
        on_complete, res = client.held.pop(0)
        on_complete(res)
        tokens.append(mm._progress_token())
    assert len(set(tokens)) == 3
    seg.fail(MergeError("done here"))


# -- (e) checkpoint export and preload, push adoption -------------------------

def test_export_mid_fetch_preload_and_finish_round_trips():
    client = _Chunks(PAYLOAD, CUTS, faults={3: "hold"})
    seg = _segment(client)
    seg.start()                             # two chunks kept, third held
    state = seg.ckpt_export()
    assert sorted(state) == ["carry_len", "data", "next_offset",
                             "num_records", "raw_length"]
    assert state["next_offset"] == 1200
    assert state["raw_length"] == len(PAYLOAD)
    # today's format: framed whole records, then the carry tail
    framed = state["data"][:len(state["data"]) - state["carry_len"]]
    batch, consumed, _ = crack_partial(framed, expect_eof=False)
    assert consumed == len(framed)
    assert batch.num_records == state["num_records"] == 1200 // 42
    assert state["carry_len"] == 1200 % 42
    assert state["data"] == PAYLOAD[:1200]
    assert seg._raw is not None and len(seg._raw) == 2  # export took nothing
    seg.fail(MergeError("killed here"))

    # the next attempt: the eager ledger a preload builds exports the
    # same manifest, and the fetch finishes from the offset
    rest = _Chunks(PAYLOAD, CUTS)
    seg2 = _segment(rest)
    seg2.ckpt_preload(**state)
    assert seg2.ckpt_export() == state
    seg2.start()
    seg2.wait(10.0)
    assert [off for _, off in rest.calls] == [1200, 1800]
    _same(seg2.record_batch(), _reference())
    assert metrics.get("fetch.resumed.bytes") == 1200
    assert metrics.get(DEFERRED) == 0       # a preloaded segment is eager


def test_export_of_a_finished_or_untouched_deferred_segment_is_nothing():
    assert _segment(_Chunks(PAYLOAD, CUTS)).ckpt_export() is None
    seg = _fetched(_Chunks(PAYLOAD, CUTS))
    assert seg.ckpt_export() is None


class _Staged:
    """PushStaging's adoption surface: one map's staged prefix."""

    def __init__(self, kw):
        self.kw = kw
        self.job_id, self.reduce_id = JOB, 0

    def take(self, map_id):
        kw, self.kw = self.kw, None
        return kw

    def close(self):
        pass


def _prefix(nbytes):
    batch, consumed, _ = crack_partial(PAYLOAD[:nbytes], expect_eof=False)
    return dict(data=PAYLOAD[:nbytes], carry_len=nbytes - consumed,
                next_offset=nbytes, raw_length=len(PAYLOAD),
                num_records=batch.num_records)


def test_push_adopt_declines_when_a_ledger_is_further_along():
    client, mm = _task({"m0": PAYLOAD}, CUTS)
    seg = _segment(client)
    seg.ckpt_preload(**_prefix(1200))       # the checkpoint's ledger
    mm._push_staging = _Staged(_prefix(600))
    mm._push_adopt(seg)
    assert seg._next_offset == 1200 and metrics.get("push.adopted") == 0
    seg.start()
    seg.wait(10.0)
    _same(seg.record_batch(), _reference())

    # a fresh segment takes the pushed prefix, and is eager from there
    fresh = _segment(_Chunks(PAYLOAD, CUTS))
    mm._push_staging = _Staged(_prefix(600))
    mm._push_adopt(fresh)
    assert fresh._next_offset == 600 and metrics.get("push.adopted") == 1
    fresh.start()
    fresh.wait(10.0)
    assert fresh.num_records == 60 and fresh._raw is None
    _same(fresh.record_batch(), _reference())
    assert metrics.get(DEFERRED) == 0
    mm._push_staging = None


# -- (f) the streaming route --------------------------------------------------

def test_streaming_releases_the_raw_chunks_and_counts_the_records():
    recs = {f"m{i}": _recs(50, seed=30 + i) for i in range(4)}
    client, mm = _task({m: write_records(r) for m, r in recs.items()},
                       (700, 1400), {"uda.tpu.online.streaming": True})
    held = _spy_segments(mm)
    blocks: list = []
    # finish_streaming holds the spooled runs to the segments' record
    # count, asked once staging has drained: a count read before a stage
    # worker had cracked the last segment would fail the task here
    mm.run(JOB, list(recs), 0, lambda b: blocks.append(bytes(b)))
    assert len(held) == 4
    assert all(s.batches == [] and s._raw is None for s in held)
    assert [s.num_records for s in held] == [50] * 4
    with pytest.raises(MergeError):
        held[0].record_batch()
    assert len(_sorted_stream(blocks)) == 200
    assert metrics.get(DEFERRED) == 4


def test_a_segment_fetched_and_never_staged_fails_the_streaming_count():
    """The guard 'staged N of M records' keeps a count of its own: a
    deferred segment that no stage worker was handed is cracked for the
    count, so its records read as missing — not as never fetched."""
    recs = {f"m{i}": _recs(50, seed=40 + i) for i in range(4)}
    client, mm = _task({m: write_records(r) for m, r in recs.items()},
                       (700, 1400), {"uda.tpu.online.streaming": True})
    orig = mm.fetch_all

    def lossy(*args, on_segment=None, **kwargs):
        return orig(*args, on_segment=lambda i, seg: (
            None if i == 2 else on_segment(i, seg)), **kwargs)

    mm.fetch_all = lossy
    with pytest.raises(FallbackSignal) as err:
        mm.run(JOB, list(recs), 0, lambda b: None)
    assert isinstance(err.value.cause, MergeError)
    assert "staged 150 of 200 records" in str(err.value.cause)


def test_fetched_records_cracks_only_what_nobody_materialized():
    seg = _fetched(_Chunks(PAYLOAD, CUTS))
    assert seg.num_records == 0 and seg.fetched_records() == 60
    assert seg._raw is None and metrics.get(DEFERRED) == 1
    _same(seg.record_batch(), _reference())
    assert seg.fetched_records() == 60 and metrics.get(DEFERRED) == 1
    seg.release()
    assert seg.fetched_records() == 60      # the count survives release
    eager = _fetched(_Chunks(PAYLOAD))
    assert eager.fetched_records() == 60 and metrics.get(DEFERRED) == 1


# -- the in-memory route lets go of a finished task's bytes --------------------

def test_a_failed_task_releases_its_segments_and_keeps_its_error():
    recs = {f"m{i}": _recs(40, seed=50 + i) for i in range(3)}
    client, mm = _task({m: write_records(r) for m, r in recs.items()},
                       (500, 1000))
    held = _spy_segments(mm)

    class _ConsumerDown(RuntimeError):
        pass

    def consumer(block):
        raise _ConsumerDown("the reducer went away")

    with pytest.raises(_ConsumerDown):      # not the release's doing
        mm.run(JOB, list(recs), 0, consumer)
    assert len(held) == 3
    assert all(s.batches == [] and s._raw is None for s in held)
    with pytest.raises(MergeError, match="released"):
        held[0].record_batch()


def test_release_before_anyone_asked_frees_the_chunks():
    seg = _fetched(_Chunks(PAYLOAD, CUTS))
    assert len(seg._raw) == 4
    seg.release()
    assert seg._raw is None and seg.batches == []
    with pytest.raises(MergeError):
        seg.record_batch()
    assert metrics.get(DEFERRED) == 0
