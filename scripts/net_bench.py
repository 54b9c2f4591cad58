#!/usr/bin/env python
"""CPU-only loopback benchmark of the network shuffle data plane.

The net plane's perf trajectory without an accelerator: a
ShuffleServer over a synthetic MOF on 127.0.0.1,
measured three ways on the event-loop core (the ONLY core since the
legacy threaded baseline was deleted — its last measured point is
``BENCH_NET_r06.json``: 944 vs 323 MB/s single-stream, 2.92x):

1. **single-stream throughput** — one client, windowed pipelined chunk
   fetches of one large partition (the Segment steady-state shape);
   the headline number the zero-copy serve path must move;
2. **p99 frame latency** — sequential small (4 KB) request->response
   round trips; the TCP_NODELAY/sockbuf regression guard;
3. **256-connection fan-in** — 256 concurrent fetch clients against
   one server; must complete with zero errors and zero stall, the
   "dead at 10k" scale direction.

Emits a comparable JSON block (default ``BENCH_NET_r07.json``) with
throughput, latency percentiles, the zero-copy counters (sendfile
bytes, fd/byte-path serve split) and the process-wide traced
allocation peak (tracemalloc) — the flat-per-chunk-alloc evidence.

Exit code != 0 on any fan-in error/stall or a single-stream failure
(the ci.sh --quick gate); throughput itself is reported, not gated,
so a noisy shared host cannot flake CI.

Usage: scripts/net_bench.py [--quick] [--out PATH] [--sockbuf-kb N]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import threading
import time
import tracemalloc

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from uda_tpu.mofserver import DataEngine, ShuffleRequest  # noqa: E402
from uda_tpu.mofserver.index import IndexRecord  # noqa: E402
from uda_tpu.net import ShuffleServer  # noqa: E402
from uda_tpu.net.client import RemoteFetchClient  # noqa: E402
from uda_tpu.utils.config import Config  # noqa: E402
from uda_tpu.utils.metrics import metrics  # noqa: E402

JOB = "jobNetBench"
MAP = "attempt_jobNetBench_m_000000_0"


class _SyntheticResolver:
    """Every (job, map, reduce) resolves to one big pre-written file —
    the bench measures the wire, not index parsing."""

    def __init__(self, path: str, nbytes: int):
        self._rec = IndexRecord(start_offset=0, raw_length=nbytes,
                                part_length=nbytes, path=path)

    def resolve(self, job_id: str, map_id: str, reduce_id: int):
        return self._rec


def _make_data_file(tmp: str, nbytes: int) -> str:
    path = os.path.join(tmp, "bench.mof")
    block = os.urandom(1 << 20)
    with open(path, "wb") as f:
        left = nbytes
        while left > 0:
            f.write(block[:min(left, len(block))])
            left -= len(block)
    return path


def _cfg(sockbuf_kb: int) -> Config:
    return Config({"uda.tpu.net.sockbuf.kb": sockbuf_kb})


def run_single_stream(path: str, total: int, chunk: int,
                      window: int, sockbuf_kb: int) -> dict:
    """Windowed pipelined fetches of one `total`-byte partition."""
    metrics.reset()
    cfg = _cfg(sockbuf_kb)
    engine = DataEngine(_SyntheticResolver(path, total), Config())
    server = ShuffleServer(engine, cfg, host="127.0.0.1", port=0).start()
    client = RemoteFetchClient("127.0.0.1", server.port, cfg)
    lock = threading.RLock()
    done = threading.Event()
    state = {"next": 0, "inflight": 0, "got": 0, "err": None}

    def issue_locked() -> None:
        while state["inflight"] < window and state["next"] < total:
            off = state["next"]
            state["next"] = min(off + chunk, total)
            state["inflight"] += 1
            client.start_fetch(ShuffleRequest(JOB, MAP, 0, off, chunk),
                               on_complete)

    def on_complete(res) -> None:
        with lock:
            state["inflight"] -= 1
            if isinstance(res, Exception):
                state["err"] = res
                done.set()
                return
            state["got"] += len(res.data)
            if state["got"] >= total:
                done.set()
                return
            issue_locked()

    tracemalloc.start()
    t0 = time.perf_counter()
    with lock:
        issue_locked()
    ok = done.wait(timeout=600.0)
    secs = time.perf_counter() - t0
    peak = tracemalloc.get_traced_memory()[1]
    tracemalloc.stop()
    client.stop()
    server.stop()
    engine.stop()
    if not ok or state["err"] is not None:
        raise RuntimeError(f"single-stream failed: "
                           f"{state['err'] or 'stalled'}")
    return {"bytes": state["got"], "seconds": round(secs, 4),
            "mb_per_s": round(state["got"] / (1 << 20) / secs, 1),
            "chunk_kb": chunk // 1024, "window": window,
            "sendfile_bytes": int(metrics.get("net.sendfile.bytes")),
            "mmap_bytes": int(metrics.get("net.mmap.bytes")),
            "serve_fd": int(metrics.get("net.serve.fd")),
            "serve_copy": int(metrics.get("net.serve.copy")),
            "traced_peak_mb": round(peak / (1 << 20), 1)}


def run_latency(path: str, total: int, samples: int,
                sockbuf_kb: int) -> dict:
    """Sequential 4 KB round trips -> p50/p99 frame latency."""
    metrics.reset()
    cfg = _cfg(sockbuf_kb)
    engine = DataEngine(_SyntheticResolver(path, total), Config())
    server = ShuffleServer(engine, cfg, host="127.0.0.1", port=0).start()
    client = RemoteFetchClient("127.0.0.1", server.port, cfg)
    lats: list = []
    try:
        for i in range(samples):
            off = (i * 4096) % (total - 4096)
            box, got = [], threading.Event()
            t0 = time.perf_counter()
            client.start_fetch(ShuffleRequest(JOB, MAP, 0, off, 4096),
                               lambda r: (box.append(r), got.set()))
            if not got.wait(timeout=30.0):
                raise RuntimeError(f"latency fetch {i} stalled")
            if isinstance(box[0], Exception):
                raise RuntimeError(f"latency fetch {i} failed: "
                                   f"{box[0]}")
            lats.append((time.perf_counter() - t0) * 1e3)
    finally:
        client.stop()
        server.stop()
        engine.stop()
    lats.sort()
    return {"samples": samples,
            "p50_ms": round(lats[len(lats) // 2], 3),
            "p99_ms": round(lats[min(len(lats) - 1,
                                     int(len(lats) * 0.99))], 3)}


def run_fanin(path: str, total: int, connections: int, chunks: int,
              chunk: int, sockbuf_kb: int) -> dict:
    """N concurrent clients, each chaining `chunks` fetches — the
    fan-in scale test."""
    metrics.reset()
    cfg = _cfg(sockbuf_kb)
    engine = DataEngine(_SyntheticResolver(path, total), Config())
    server = ShuffleServer(engine, cfg, host="127.0.0.1", port=0).start()
    clients = [RemoteFetchClient("127.0.0.1", server.port, cfg)
               for _ in range(connections)]
    lock = threading.Lock()
    done = threading.Event()
    state = {"finished": 0, "bytes": 0, "errors": 0}

    def chain(ci: int, left: int) -> None:
        off = ((ci * 7919) + (chunks - left) * chunk) % max(total - chunk, 1)

        def on_complete(res, ci=ci, left=left) -> None:
            with lock:
                if isinstance(res, Exception):
                    state["errors"] += 1
                    state["finished"] += 1
                    if state["finished"] == connections:
                        done.set()
                    return
                state["bytes"] += len(res.data)
            if left > 1:
                chain(ci, left - 1)
            else:
                with lock:
                    state["finished"] += 1
                    if state["finished"] == connections:
                        done.set()

        clients[ci].start_fetch(
            ShuffleRequest(JOB, MAP, 0, off, chunk), on_complete)

    t0 = time.perf_counter()
    for ci in range(connections):
        chain(ci, chunks)
    ok = done.wait(timeout=600.0)
    secs = time.perf_counter() - t0
    for c in clients:
        c.stop()
    server.stop()
    engine.stop()
    return {"core": "evloop", "connections": connections,
            "chunks_per_conn": chunks, "chunk_kb": chunk // 1024,
            "completed": state["finished"], "errors": state["errors"],
            "stalled": not ok, "bytes": state["bytes"],
            "seconds": round(secs, 4),
            "agg_mb_per_s": round(state["bytes"] / (1 << 20)
                                  / max(secs, 1e-9), 1)}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true",
                    help="small sizes for the ci.sh gate")
    ap.add_argument("--out", default=os.path.join(REPO,
                                                  "BENCH_NET_r07.json"))
    ap.add_argument("--sockbuf-kb", type=int, default=4096,
                    help="uda.tpu.net.sockbuf.kb for every socket")
    ap.add_argument("--reps", type=int, default=3,
                    help="single-stream repetitions; the best "
                         "is reported (noisy-host discipline: the "
                         "minimum-interference run is the one that "
                         "measures the core, not the neighbors)")
    args = ap.parse_args()

    if args.quick:
        stream_mb, chunk_kb, window = 32, 1024, 6
        lat_samples, fanin_chunks, fanin_kb = 150, 2, 64
        args.reps = min(args.reps, 2)
    else:
        stream_mb, chunk_kb, window = 128, 4096, 6
        lat_samples, fanin_chunks, fanin_kb = 1000, 16, 64
    total = stream_mb << 20

    tmp = tempfile.mkdtemp(prefix="uda_net_bench_")
    path = _make_data_file(tmp, total)
    out: dict = {"bench": "net_loopback", "round": "r07",
                 "quick": args.quick,
                 "sockbuf_kb": args.sockbuf_kb,
                 # the deleted threaded core's last measured point, for
                 # trajectory comparisons (BENCH_NET_r06.json)
                 "threaded_baseline_r06_mb_per_s": 323,
                 "single_stream": {}, "frame_latency": {}}

    rc = 0
    runs = [run_single_stream(path, total, chunk_kb << 10,
                              window, args.sockbuf_kb)
            for _ in range(max(1, args.reps))]
    s = max(runs, key=lambda r: r["mb_per_s"])
    s["reps_mb_per_s"] = [r["mb_per_s"] for r in runs]
    out["single_stream"]["evloop"] = s
    print(f"single-stream: {s['mb_per_s']} MB/s best of "
          f"{s['reps_mb_per_s']} "
          f"({s['bytes'] >> 20} MB; sendfile "
          f"{s['sendfile_bytes'] >> 20} MB, mmap "
          f"{s['mmap_bytes'] >> 20} MB, traced peak "
          f"{s['traced_peak_mb']} MB)")
    lt = run_latency(path, total, lat_samples, args.sockbuf_kb)
    out["frame_latency"]["evloop"] = lt
    print(f"frame-latency: p50 {lt['p50_ms']} ms, "
          f"p99 {lt['p99_ms']} ms over {lt['samples']} fetches")

    fan = run_fanin(path, total, 256, fanin_chunks, fanin_kb << 10,
                    args.sockbuf_kb)
    out["fanin"] = fan
    print(f"fan-in: {fan['connections']} connections x "
          f"{fan['chunks_per_conn']} chunks -> {fan['agg_mb_per_s']} "
          f"MB/s aggregate, errors={fan['errors']}, "
          f"stalled={fan['stalled']}")
    if fan["errors"] or fan["stalled"] or \
            fan["completed"] != fan["connections"]:
        print("FAIL: fan-in saw errors or a stall", file=sys.stderr)
        rc = 1

    with open(args.out, "w") as f:
        json.dump(out, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"wrote {args.out}")
    try:
        os.remove(path)
        os.rmdir(tmp)
    except OSError:
        pass
    return rc


if __name__ == "__main__":
    sys.exit(main())
