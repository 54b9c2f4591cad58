#!/usr/bin/env python
"""Fused staging-pipeline A/B: pipelined vs serial stage path.

ISSUE 9's tentpole gate. Serial staging fed the device engine at
42-72 MB/s on the sandbox host (STAGING_BENCH_r05.json), far below any
device sort rate on record (git history; not measured on this machine).
The fix is the bounded stage pool + merge consumer in
uda_tpu.merger.overlap (uda.tpu.stage.pipeline). This bench proves both
halves of the claim on the CPU, where correctness is provable:

- **correctness gate** (always, and all of ``--quick``): the pipelined
  staging path is BYTE-IDENTICAL to the serial path across
  sorted/shuffled input, the in-memory and spooled (streaming) modes,
  and a compressed end-to-end MergeManager run;
- **throughput A/B** (full mode): staged MB/s of the pipelined pool vs
  the serial ``stage_sorted_x1`` baseline on the 64x64 MB deployment
  shape — gate: pipelined >= 1.5x serial, spool variants must not
  regress (>= 0.95x) — plus ``merge.wait_ms`` p95 (how long the merge
  waited for each run to become mergeable) for both paths in the same
  run: the pipeline must DROP it.

Usage: python scripts/bench_pipeline.py [--segs 64] [--seg-mb 64]
       [--quick] [--out BENCH_PIPELINE.json]
"""

from __future__ import annotations

import argparse
import io
import json
import os
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)


def _force_cpu() -> None:
    # staging is HOST work; the bench is valid on any backend, so it
    # holds no chip.
    import jax

    jax.config.update("jax_platforms", "cpu")


def _stage_once(batches, pipeline: bool, stagers: int, spool: bool,
                tmp: str) -> dict:
    """Stage every batch through one OverlappedMerger config; returns
    wall seconds + merge.wait_ms p95 (stats enabled per run)."""
    from uda_tpu.merger.overlap import OverlappedMerger
    from uda_tpu.merger.streaming import RunStore
    from uda_tpu.utils.comparators import get_key_type
    from uda_tpu.utils.metrics import metrics

    kt = get_key_type("uda.tpu.RawBytes")
    metrics.reset()
    metrics.enable_stats()
    store = RunStore([tmp], tag="pipebench") if spool else None
    om = OverlappedMerger(kt, 16, engine="host", run_store=store,
                          stagers=stagers, pipeline=pipeline)
    t0 = time.monotonic()
    for i, b in enumerate(batches):
        om.feed(i, b)
    om._drain()  # raises any staging error
    wall = time.monotonic() - t0
    p95 = metrics.percentile("merge.wait_ms", 95)
    if store is not None:
        assert store.total_records == sum(b.num_records for b in batches)
        store.cleanup()
    metrics.reset()
    return {"wall_s": wall, "wait_p95_ms": p95}


def _finish_bytes(batches, pipeline: bool, spool: bool, tmp: str) -> bytes:
    """Full staged merge -> emitted IFile bytes for identity checks."""
    from uda_tpu.merger.emitter import FramedEmitter
    from uda_tpu.merger.overlap import OverlappedMerger
    from uda_tpu.merger.streaming import RunStore
    from uda_tpu.utils.comparators import get_key_type

    kt = get_key_type("uda.tpu.RawBytes")
    store = RunStore([tmp], tag="pipeident") if spool else None
    om = OverlappedMerger(kt, 16, engine="host", run_store=store,
                          stagers=2 if pipeline else 1, pipeline=pipeline,
                          inflight_bytes=64 << 20)
    out = io.BytesIO()
    for i, b in enumerate(batches):
        om.feed(i, b)
    emitter = FramedEmitter(1 << 16)
    total = sum(b.num_records for b in batches)
    if spool:
        om.finish_streaming(emitter, lambda blk: out.write(bytes(blk)),
                            expected_records=total)
    else:
        om.emit_stream(batches, emitter,
                       lambda blk: out.write(bytes(blk)))
    return out.getvalue()


def _compressed_run_bytes(tmp: str, pipeline: bool) -> bytes:
    """Compressed end-to-end MergeManager run (zlib): fetch ->
    decompress -> pipelined/serial stage -> merge -> emit."""
    import numpy as np

    from uda_tpu.compress import DecompressingClient, get_codec
    from uda_tpu.merger import LocalFetchClient, MergeManager
    from uda_tpu.mofserver import DataEngine, DirIndexResolver
    from uda_tpu.mofserver.writer import MOFWriter
    from uda_tpu.utils.config import Config

    root = os.path.join(tmp, f"cmof_{int(pipeline)}")
    codec = get_codec("zlib")
    rng = np.random.default_rng(7)
    job = "pipebenchC"
    writer = MOFWriter(root, job, codec=codec)
    for m in range(4):
        recs = sorted((rng.bytes(10), rng.bytes(40)) for _ in range(300))
        writer.write(f"attempt_{job}_m_{m:06d}_0", [recs])
    cfg = Config({"uda.tpu.stage.pipeline": pipeline,
                  "mapred.rdma.buf.size": 8})
    engine = DataEngine(DirIndexResolver(root), cfg)
    try:
        mm = MergeManager(DecompressingClient(LocalFetchClient(engine),
                                              codec),
                          "uda.tpu.RawBytes", cfg)
        blocks: list[bytes] = []
        mm.run(job, writer.map_ids, 0, lambda b: blocks.append(bytes(b)))
    finally:
        engine.stop()
    return b"".join(blocks)


def _time_accounting_point(tmp: str) -> dict:
    """One pipelined MergeManager run with spans on -> the critpath
    ``time_accounting`` block (uda_tpu.utils.critpath). This is the
    time-accounting point perfwatch ingests next to the throughput
    numbers: bucket shares trend across rounds, and the buckets-sum-
    to-wall invariant is checked right here (exit gate in _run)."""
    from uda_tpu.utils.critpath import time_accounting_block
    from uda_tpu.utils.metrics import metrics

    metrics.reset()
    metrics.enable_stats()
    try:
        _compressed_run_bytes(os.path.join(tmp, "timeacct"), True)
        block = time_accounting_block()
    finally:
        metrics.reset()
    return block or {}


def identity_gate(tmp: str) -> dict:
    """Byte-identity of pipelined vs serial staging across input order,
    spool mode and compression — the CI correctness gate."""
    from scripts.bench_staging import make_segments

    checks = {}
    for sorted_input in (True, False):
        batches = make_segments(4, 1 << 20, sorted_input)
        tag = "sorted" if sorted_input else "shuffled"
        for spool in (False, True):
            a = _finish_bytes(batches, False, spool, tmp)
            b = _finish_bytes(batches, True, spool, tmp)
            key = f"{tag}{'_spool' if spool else ''}"
            checks[key] = (a == b and len(a) > 0)
    a = _compressed_run_bytes(tmp, False)
    b = _compressed_run_bytes(tmp, True)
    checks["compressed_e2e"] = (a == b and len(a) > 0)
    checks["all_identical"] = all(checks.values())
    return checks


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--segs", type=int, default=64)
    ap.add_argument("--seg-mb", type=int, default=64)
    ap.add_argument("--quick", action="store_true",
                    help="correctness gate + a small A/B (CI mode: "
                    "identity gated, throughput reported not gated)")
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    _force_cpu()
    tmp = tempfile.mkdtemp(prefix="uda_pipebench_")
    try:
        return _run(args, tmp)
    finally:
        import shutil

        shutil.rmtree(tmp, ignore_errors=True)


def _run(args, tmp: str) -> int:
    from scripts.bench_staging import make_segments

    result: dict = {"identity": identity_gate(tmp)}
    if not result["identity"]["all_identical"]:
        print(json.dumps(result))
        print("FAIL: pipelined staging is not byte-identical to serial",
              file=sys.stderr)
        return 3

    # the where-time-goes point: buckets must partition the task wall
    # (critical + idle == wall by construction; gate at 5% for the
    # acceptance record). A missing block (span layer broken) fails —
    # this bench is the time-accounting plane's own canary.
    ta = _time_accounting_point(tmp)
    result["time_accounting"] = ta
    ta_sum = (sum(b["critical_s"] for b in ta.get("buckets", {}).values())
              + ta.get("idle_s", 0.0))
    result["time_accounting_sums_to_wall"] = bool(
        ta.get("wall_s") and abs(ta_sum - ta["wall_s"])
        <= 0.05 * ta["wall_s"])
    if not result["time_accounting_sums_to_wall"]:
        print(json.dumps(result))
        print("FAIL: time_accounting buckets do not sum to task wall",
              file=sys.stderr)
        return 3

    segs = 6 if args.quick else args.segs
    seg_mb = 4 if args.quick else args.seg_mb
    seg_bytes = seg_mb << 20
    total_mb = segs * seg_mb
    result.update({"segs": segs, "seg_mb": seg_mb, "total_mb": total_mb,
                   "nproc": os.cpu_count(), "quick": bool(args.quick)})

    # A/B matrix: serial x1 is THE baseline (stage_sorted_x1 of
    # STAGING_BENCH_r05); pipelined = stage pool (auto width) + merge
    # consumer. Fresh batches per sortedness so page-cache state is
    # comparable between the two paths.
    for sorted_input in (True, False):
        batches = make_segments(segs, seg_bytes, sorted_input)
        tag = "sorted" if sorted_input else "shuffled"
        for spool in ((False, True) if sorted_input else (False,)):
            sp = "_spool" if spool else ""
            for name, pipeline, stagers in (("serial_x1", False, 1),
                                            ("pipelined", True, 0)):
                r = _stage_once(batches, pipeline, stagers, spool, tmp)
                key = f"{tag}{sp}_{name}"
                result[key + "_s"] = round(r["wall_s"], 2)
                result[key + "_MBps"] = round(total_mb / r["wall_s"], 1)
                if r["wait_p95_ms"] is not None:
                    result[key + "_wait_p95_ms"] = round(r["wait_p95_ms"], 1)
        del batches

    def ratio(num_key: str, den_key: str) -> float:
        return round(result[num_key] / max(result[den_key], 1e-9), 2)

    result["speedup_sorted"] = ratio("sorted_pipelined_MBps",
                                     "sorted_serial_x1_MBps")
    result["speedup_sorted_spool"] = ratio("sorted_spool_pipelined_MBps",
                                           "sorted_spool_serial_x1_MBps")
    result["speedup_shuffled"] = ratio("shuffled_pipelined_MBps",
                                       "shuffled_serial_x1_MBps")
    wait_s = result.get("sorted_serial_x1_wait_p95_ms")
    wait_p = result.get("sorted_pipelined_wait_p95_ms")
    result["wait_p95_drops"] = (wait_s is not None and wait_p is not None
                                and wait_p < wait_s)
    # gates: identity always; throughput only in full mode (a noisy
    # shared host must not flake CI — full runs ride BENCH artifacts)
    result["speedup_ok"] = result["speedup_sorted"] >= 1.5
    result["spool_ok"] = result["speedup_sorted_spool"] >= 0.95
    print(json.dumps(result))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1)
    if args.quick:
        return 0
    return 0 if (result["speedup_ok"] and result["spool_ok"]) else 2


if __name__ == "__main__":
    sys.exit(main())
