#!/usr/bin/env python
"""Multi-tenant fairness bench: many jobs, one shuffle daemon.

The ROADMAP item-1 acceptance bench: one ShuffleServer on 127.0.0.1
runs as a multi-job daemon (``uda.tpu.tenant.enable``) serving T
tenants' jobs concurrently under a deliberately small shared credit
pool (``uda.tpu.tenant.wqe.total``), so the weighted-fair
CreditScheduler — not the clients' arrival order — decides who drains.
Three phases, all on the same daemon:

1. **identity** — every tenant fetches its whole job SOLO, then all
   tenants fetch concurrently; each job's concurrent digest must equal
   its solo digest (byte identity under contention is the hard gate,
   exit 3 — a fair-but-wrong scheduler is worthless);
2. **equal weights** — T pipelined drivers hammer the daemon for a
   fixed window; per-tenant goodput is the bytes completed inside the
   window. Reported ``fairness_ratio`` = min/max goodput; the full run
   gates it >= 0.7 (the acceptance bar — WDRR over equal weights must
   not let arrival luck starve anyone);
3. **2:1 weight** — tenant 0 re-registers at weight 2; its goodput
   over the mean of the weight-1 tenants must land ~2x (gated to the
   [1.4, 3.0] band in full mode; recorded in quick mode — CI hosts
   gate direction, not absolutes).

``--quick`` (the ci.sh gate) shrinks sizes/windows and gates identity
only. Emits BENCH_TENANT_r14.json with the session telemetry block
(tenant.sched.* / tenant.admission.* counters ride it).

Usage: scripts/tenant_bench.py [--quick] [--out PATH]
        [--tenants N] [--conns-per-tenant N]
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys
import tempfile
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from tests.helpers import make_mof_tree, map_ids  # noqa: E402
from uda_tpu.mofserver import (DataEngine, DirIndexResolver,  # noqa: E402
                               FetchResult, ShuffleRequest)
from uda_tpu.net import ShuffleServer  # noqa: E402
from uda_tpu.net.client import RemoteFetchClient  # noqa: E402
from uda_tpu.utils.config import Config  # noqa: E402
from uda_tpu.utils.stats import telemetry_block  # noqa: E402


def tenant_name(i: int) -> str:
    return f"tenant{i:02d}"


def job_name(i: int) -> str:
    return f"jobTen{i:02d}"


def client_cfg(i: int, weight: int = 1) -> Config:
    return Config({"uda.tpu.tenant.id": tenant_name(i),
                   "uda.tpu.tenant.weight": weight,
                   "uda.tpu.net.sockbuf.kb": 64})


def fetch_sync(client, req, timeout=30.0):
    box, done = [], threading.Event()

    def on_complete(res):
        box.append(res)
        done.set()

    client.start_fetch(req, on_complete)
    if not done.wait(timeout):
        raise RuntimeError("fetch never completed")
    return box[0]


def digest_job(client, job: str, num_maps: int, chunk: int) -> str:
    """Fetch the whole job (reducer 0), chunked, and digest the byte
    stream in (map, offset) order."""
    h = hashlib.sha256()
    for mid in map_ids(job, num_maps):
        offset = 0
        while True:
            res = fetch_sync(client,
                             ShuffleRequest(job, mid, 0, offset, chunk))
            if not isinstance(res, FetchResult):
                raise RuntimeError(f"fetch of {job}/{mid} failed: {res!r}")
            h.update(bytes(res.data))
            offset += len(res.data)
            if res.is_last:
                break
    return h.hexdigest()


def run_driver(args) -> int:
    """One tenant's load-generator SUBPROCESS (--driver): fairness
    only exists when arrival can outpace service, and in one
    interpreter the client and server share a GIL — the drivers must
    be separate processes so the daemon's loop is the contended
    resource and the WDRR queues actually form."""
    client = RemoteFetchClient(
        "127.0.0.1", args.port,
        Config({"uda.tpu.tenant.id": args.tenant,
                "uda.tpu.tenant.weight": args.weight}))
    client.bind_job(args.job)
    maps = map_ids(args.job, args.maps)
    state = {"bytes": 0, "errors": 0}
    stop = threading.Event()
    lock = threading.Lock()
    window = [float("inf"), float("-inf")]  # [t0, t1)

    def issue() -> None:
        client.start_fetch(
            ShuffleRequest(args.job, maps[state["bytes"] % len(maps)],
                           0, 0, args.chunk), on_done)

    def on_done(res) -> None:
        now = time.monotonic()
        with lock:
            if isinstance(res, FetchResult):
                if window[0] <= now < window[1]:
                    state["bytes"] += len(res.data)
            else:
                state["errors"] += 1
        if not stop.is_set():
            issue()

    for _ in range(args.depth):
        issue()
    time.sleep(args.warmup)
    with lock:
        window[0] = time.monotonic()
        window[1] = window[0] + args.window
    time.sleep(args.window + 0.05)
    stop.set()
    time.sleep(0.1)
    client.stop()
    print(json.dumps({"tenant": args.tenant,
                      "bytes": state["bytes"],
                      "errors": state["errors"],
                      "window_s": args.window}))
    return 0


def measure_window(port: int, tenants: int, num_maps: int, chunk: int,
                   depth: int, warmup_s: float, window_s: float,
                   weights=None) -> dict:
    """Spawn one driver PROCESS per tenant; collect each driver's own
    measured window (the warmup absorbs start skew)."""
    import subprocess

    weights = weights or {}
    procs = []
    for i in range(tenants):
        cmd = [sys.executable, os.path.abspath(__file__), "--driver",
               "--port", str(port), "--tenant", tenant_name(i),
               "--job", job_name(i), "--maps", str(num_maps),
               "--chunk", str(chunk), "--depth", str(depth),
               "--weight", str(weights.get(i, 1)),
               "--warmup", str(warmup_s), "--window", str(window_s)]
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        procs.append(subprocess.Popen(cmd, stdout=subprocess.PIPE,
                                      stderr=subprocess.DEVNULL,
                                      text=True, env=env))
    goodput, errors = {}, {}
    deadline = warmup_s + window_s + 60
    for i, p in enumerate(procs):
        out, _ = p.communicate(timeout=deadline)
        line = out.strip().splitlines()[-1] if out.strip() else "{}"
        rec = json.loads(line)
        goodput[rec.get("tenant", tenant_name(i))] = round(
            rec.get("bytes", 0) / window_s / (1 << 20), 3)
        errors[rec.get("tenant", tenant_name(i))] = rec.get("errors", 0)
    return {"goodput_mb_s": goodput, "errors": errors,
            "window_s": window_s, "driver_processes": tenants}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true",
                    help="small sizes/windows; identity-gate only "
                         "(ci.sh)")
    ap.add_argument("--out",
                    default=os.path.join(REPO, "BENCH_TENANT_r14.json"))
    ap.add_argument("--tenants", type=int, default=0,
                    help="concurrent jobs (0 = 8 full / 4 quick; "
                         "scale to what this host sustains)")
    ap.add_argument("--depth", type=int, default=16,
                    help="pipelined fetches per tenant driver")
    # the per-tenant load-generator subprocess (internal)
    ap.add_argument("--driver", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--port", type=int, help=argparse.SUPPRESS)
    ap.add_argument("--tenant", help=argparse.SUPPRESS)
    ap.add_argument("--job", help=argparse.SUPPRESS)
    ap.add_argument("--maps", type=int, help=argparse.SUPPRESS)
    ap.add_argument("--chunk", type=int, help=argparse.SUPPRESS)
    ap.add_argument("--weight", type=int, default=1,
                    help=argparse.SUPPRESS)
    ap.add_argument("--warmup", type=float, help=argparse.SUPPRESS)
    ap.add_argument("--window", type=float, help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.driver:
        return run_driver(args)

    tenants = args.tenants or (4 if args.quick else 8)
    # serve-dominated shape: whole-partition fetches of ~0.5 MB (full)
    # so the daemon's loop (sendfile + frame writes) is the contended
    # resource — with tiny chunks the round-trip dominates and the
    # scheduler has nothing to arbitrate
    if args.quick:
        num_maps, records, val_bytes, chunk = 1, 100, 500, 4 << 20
        warmup_s, window_s = 0.5, 1.2
    else:
        num_maps, records, val_bytes, chunk = 2, 500, 1000, 4 << 20
        warmup_s, window_s = 1.5, 4.0

    tmp = tempfile.mkdtemp(prefix="uda_tenant_bench_")
    for i in range(tenants):
        make_mof_tree(tmp, job_name(i), num_maps=num_maps,
                      num_reducers=1, records_per_map=records,
                      val_bytes=val_bytes, seed=100 + i)
    engine = DataEngine(DirIndexResolver(tmp), Config())
    # a deliberately SMALL shared pool + byte-path serves (zerocopy
    # off) + small socket buffers: a credit must be HELD for the
    # request's real service time (engine pool read + multi-round
    # frame write) — the inline zero-copy fast path settles a credit
    # synchronously on the loop thread, so the pool would never fill
    # and the scheduler would have nothing to arbitrate. Aggregate
    # demand (tenants x depth) far exceeds the pool, so the WDRR owns
    # the ordering.
    server = ShuffleServer(
        engine, Config({"uda.tpu.tenant.enable": True,
                        "uda.tpu.net.zerocopy": False,
                        "uda.tpu.net.sockbuf.kb": 64,
                        "uda.tpu.tenant.wqe.total":
                            max(2, tenants // 2)}),
        host="127.0.0.1", port=0).start()
    out: dict = {"bench": "tenant_fairness", "round": "r14",
                 "quick": args.quick, "tenants": tenants,
                 "jobs": tenants, "maps_per_job": num_maps,
                 "chunk_kb": chunk >> 10, "driver_depth": args.depth,
                 "credit_pool": server._sched.total}
    rc = 0
    try:
        # phase 1: byte identity — solo digests, then concurrent
        solo = {}
        for i in range(tenants):
            c = RemoteFetchClient("127.0.0.1", server.port,
                                  client_cfg(i))
            try:
                c.bind_job(job_name(i))
                solo[i] = digest_job(c, job_name(i), num_maps, chunk)
            finally:
                c.stop()
        conc: dict = {}
        errs: list = []

        def one(i: int) -> None:
            c = RemoteFetchClient("127.0.0.1", server.port,
                                  client_cfg(i))
            try:
                c.bind_job(job_name(i))
                conc[i] = digest_job(c, job_name(i), num_maps, chunk)
            except Exception as e:  # noqa: BLE001 - surfaced below
                errs.append((i, repr(e)))
            finally:
                c.stop()

        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(tenants)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        identical = not errs and conc == solo
        out["identity"] = {"concurrent_equals_solo": identical,
                           "errors": errs}
        print(f"identity: {tenants} concurrent jobs == solo runs: "
              f"{identical}")
        if not identical:
            print("FAIL: concurrent fetch diverged from solo bytes",
                  file=sys.stderr)
            rc = 3

        # phase 2: equal-weight fairness window
        eq = measure_window(server.port, tenants, num_maps, chunk,
                            args.depth, warmup_s, window_s)
        vals = list(eq["goodput_mb_s"].values())
        eq["fairness_ratio"] = round(min(vals) / max(max(vals), 1e-9), 3)
        out["equal_weight"] = eq
        print(f"equal weights: goodput {eq['goodput_mb_s']} MB/s -> "
              f"fairness ratio {eq['fairness_ratio']}")

        # phase 3: 2:1 weight — tenant 0 earns a double share
        wt = measure_window(server.port, tenants, num_maps, chunk,
                            args.depth, warmup_s, window_s,
                            weights={0: 2})
        g = wt["goodput_mb_s"]
        others = [v for k, v in g.items() if k != tenant_name(0)]
        wt["weights"] = {tenant_name(0): 2}
        wt["weighted_ratio"] = round(
            g[tenant_name(0)] / max(sum(others) / max(len(others), 1),
                                    1e-9), 3)
        out["weighted"] = wt
        print(f"2:1 weights: goodput {g} MB/s -> weighted ratio "
              f"{wt['weighted_ratio']} (want ~2)")

        if not args.quick:
            if eq["fairness_ratio"] < 0.7:
                print(f"FAIL: fairness ratio {eq['fairness_ratio']} "
                      f"< 0.7 under equal weights", file=sys.stderr)
                rc = rc or 4
            if not 1.4 <= wt["weighted_ratio"] <= 3.0:
                print(f"FAIL: weighted ratio {wt['weighted_ratio']} "
                      f"outside [1.4, 3.0]", file=sys.stderr)
                rc = rc or 4
    finally:
        server.stop()
        engine.stop()
    out["telemetry"] = telemetry_block()
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"wrote {args.out}")
    return rc


if __name__ == "__main__":
    sys.exit(main())
