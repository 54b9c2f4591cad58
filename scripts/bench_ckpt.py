#!/usr/bin/env python
"""Checkpoint-overhead A/B: crash-consistent snapshots on vs off.

ISSUE 16's acceptance gate. Arming ``uda.tpu.ckpt.dir`` buys durable
resume (merger/checkpoint.py) at a cost of (a) fsync'd run spools +
``.off`` sidecars (RunStore fixed-dir mode), and (b) a manifest write
per snapshot trigger (run-spool boundary, rate-limited by
``uda.tpu.ckpt.interval.s``). This bench prices that:

- **identity + resume gate** (always, and all of ``--quick``): a
  checkpoint-armed end-to-end MergeManager run is BYTE-IDENTICAL to a
  checkpoint-off run; then a fault-killed attempt resumes
  byte-identical with ``ckpt.resumed`` counted and ZERO refetch of
  manifest-recorded runs — restart-from-scratch fails the bench;
- **overhead A/B** (full mode): the 64x64 MB pipelined spool shape of
  BENCH_PIPELINE_r09 (stage pool + run spool + streaming finish), run
  with the checkpoint plane off vs armed at the DEFAULT interval
  (30 s) — gate: overhead <= 5% wall.

Usage: python scripts/bench_ckpt.py [--segs 64] [--seg-mb 64]
       [--interval 30.0] [--quick] [--out BENCH_CKPT.json]
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
    import jax

    jax.config.update("jax_platforms", "cpu")


def _mof_tree(tmp: str, job: str, maps: int, recs_per_map: int):
    """A small deterministic MOF tree for the end-to-end gates."""
    import numpy as np

    from uda_tpu.mofserver.writer import MOFWriter

    root = os.path.join(tmp, f"mof_{job}")
    rng = np.random.default_rng(16)
    writer = MOFWriter(root, job)
    for m in range(maps):
        recs = sorted((rng.bytes(10), rng.bytes(30))
                      for _ in range(recs_per_map))
        writer.write(f"attempt_{job}_m_{m:06d}_0", [recs])
    return root, writer.map_ids


def _e2e_run(root, job, mids, ckdir: str, fault: str = "",
             interval: float = 0.0):
    """One MergeManager run; returns (bytes, ckpt.resumed delta) or
    raises FallbackSignal when the injected fault kills the attempt."""
    from uda_tpu.merger import LocalFetchClient, MergeManager
    from uda_tpu.mofserver import DataEngine, DirIndexResolver
    from uda_tpu.utils.config import Config
    from uda_tpu.utils.failpoints import failpoints
    from uda_tpu.utils.metrics import metrics

    cfg = Config({"uda.tpu.online.streaming": True,
                  "uda.tpu.ckpt.dir": ckdir,
                  "uda.tpu.ckpt.interval.s": interval,
                  "uda.tpu.fetch.retries": 0})
    engine = DataEngine(DirIndexResolver(root), cfg)
    out = io.BytesIO()
    r0 = metrics.snapshot().get("ckpt.resumed", 0)
    try:
        mm = MergeManager(LocalFetchClient(engine), "uda.tpu.RawBytes",
                          cfg)
        if fault:
            with failpoints.scoped(fault):
                mm.run(job, mids, 0, lambda b: out.write(bytes(b)))
        else:
            mm.run(job, mids, 0, lambda b: out.write(bytes(b)))
    finally:
        engine.stop()
    resumed = metrics.snapshot().get("ckpt.resumed", 0) - r0
    return out.getvalue(), resumed


def resume_gate(tmp: str) -> dict:
    """Identity + crash/resume correctness — the CI gate."""
    from uda_tpu.utils.errors import FallbackSignal

    job = "ckbench"
    root, mids = _mof_tree(tmp, job, 6, 2000)
    ref, _ = _e2e_run(root, job, mids, "")
    on, _ = _e2e_run(root, job, mids, os.path.join(tmp, "ck_id"))
    checks = {"ckpt_on_identical": (on == ref and len(ref) > 0)}
    ckdir = os.path.join(tmp, "ck_res")
    try:
        _e2e_run(root, job, mids, ckdir,
                 fault="segment.fetch=error:match:m_000004")
        checks["fault_killed_attempt"] = False
    except FallbackSignal:
        checks["fault_killed_attempt"] = True
    res, resumed = _e2e_run(root, job, mids, ckdir)
    checks["resume_identical"] = (res == ref)
    checks["resumed_not_restarted"] = (resumed >= 1)
    checks["all_ok"] = all(checks.values())
    return checks


def _spool_once(batches, tmp: str, ckpt_on: bool,
                interval: float) -> dict:
    """The BENCH_PIPELINE_r09 pipelined spool shape (feed -> stage pool
    -> run spool -> streaming k-way finish), with the checkpoint plane
    off or armed. Wall covers feed through emitted bytes — the whole
    reduce-side pipeline the overhead gate prices."""
    from uda_tpu.merger.checkpoint import RUN_EOF_LEN, TaskCheckpoint
    from uda_tpu.merger.emitter import FramedEmitter
    from uda_tpu.merger.overlap import OverlappedMerger
    from uda_tpu.merger.streaming import RunStore
    from uda_tpu.utils.comparators import get_key_type
    from uda_tpu.utils.metrics import metrics

    kt = get_key_type("uda.tpu.RawBytes")
    metrics.reset()
    ck = None
    if ckpt_on:
        ck = TaskCheckpoint(os.path.join(tmp, "ck_ab"), "ckbenchAB", 0,
                            interval_s=interval)
        store = RunStore(tag="ckbenchAB.r0", fixed_dir=ck.runs_dir)

        def collect():
            runs = {str(i): {"records": n, "bytes": b,
                             "length": b + RUN_EOF_LEN, "crc": c}
                    for i, (n, b, c) in store.manifest().items()}
            return ({"maps": [], "runs": runs, "ledgers": {},
                     "journal": [], "penalty": {}, "forest": {}}, {})

        on_spool = lambda i: ck.maybe_save(collect)  # noqa: E731
    else:
        store = RunStore([tmp], tag="ckbenchAB_off")
        on_spool = None
    om = OverlappedMerger(kt, 16, engine="host", run_store=store,
                          on_spool=on_spool)
    total = sum(b.num_records for b in batches)
    sink = {"n": 0}
    t0 = time.monotonic()
    for i, b in enumerate(batches):
        om.feed(i, b)
    om.finish_streaming(
        FramedEmitter(1 << 16),
        lambda blk: sink.__setitem__("n", sink["n"] + len(blk)),
        expected_records=total)
    wall = time.monotonic() - t0
    snaps = metrics.snapshot().get("ckpt.snapshots", 0)
    if ck is not None:
        ck.discard()
    else:
        store.cleanup()
    metrics.reset()
    return {"wall_s": wall, "snapshots": int(snaps),
            "out_bytes": sink["n"]}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--segs", type=int, default=64)
    ap.add_argument("--seg-mb", type=int, default=64)
    ap.add_argument("--interval", type=float, default=30.0,
                    help="snapshot interval for the armed variant "
                    "(default = the uda.tpu.ckpt.interval.s default)")
    ap.add_argument("--quick", action="store_true",
                    help="identity + resume gate plus a small A/B "
                    "(CI mode: overhead reported, not gated)")
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    _force_cpu()
    tmp = tempfile.mkdtemp(prefix="uda_ckbench_")
    try:
        return _run(args, tmp)
    finally:
        import shutil

        shutil.rmtree(tmp, ignore_errors=True)


def _run(args, tmp: str) -> int:
    from scripts.bench_staging import make_segments

    result: dict = {"bench": "ckpt_overhead",
                    "resume": resume_gate(tmp)}
    if not result["resume"]["all_ok"]:
        print(json.dumps(result))
        print("FAIL: checkpoint identity/resume gate", file=sys.stderr)
        return 3

    segs = 6 if args.quick else args.segs
    seg_mb = 4 if args.quick else args.seg_mb
    total_mb = segs * seg_mb
    result.update({"segs": segs, "seg_mb": seg_mb, "total_mb": total_mb,
                   "interval_s": args.interval,
                   "nproc": os.cpu_count(), "quick": bool(args.quick)})
    batches = make_segments(segs, seg_mb << 20, True)
    off = _spool_once(batches, tmp, False, args.interval)
    on = _spool_once(batches, tmp, True, args.interval)
    assert on["out_bytes"] == off["out_bytes"] > 0
    result["ckpt_off_s"] = round(off["wall_s"], 2)
    result["ckpt_on_s"] = round(on["wall_s"], 2)
    result["ckpt_off_MBps"] = round(total_mb / off["wall_s"], 1)
    result["ckpt_on_MBps"] = round(total_mb / on["wall_s"], 1)
    result["snapshots"] = on["snapshots"]
    result["overhead_pct"] = round(
        100.0 * (on["wall_s"] - off["wall_s"]) / off["wall_s"], 2)
    # gate only in full mode: a noisy shared host must not flake CI
    result["overhead_ok"] = result["overhead_pct"] <= 5.0
    print(json.dumps(result))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1)
    if args.quick:
        return 0
    return 0 if result["overhead_ok"] else 2


if __name__ == "__main__":
    sys.exit(main())
