#!/usr/bin/env python
"""Observability-plane overhead A/B: control tower on vs off.

ISSUE 17's acceptance gate. Arming the live telemetry plane
(utils/timeseries.py -> anomaly detectors + per-tenant SLI book) buys
recent-history rollups, online anomaly detection and SLO accounting at
the cost of one timer thread snapshotting the metrics hub every
``uda.tpu.ts.interval.s`` and running the detector pass per rollup.
This bench prices that on the BENCH_PIPELINE_r09 64x64 MB pipelined
spool shape (feed -> stage pool -> run spool -> streaming finish):

- **identity gate** (always): the armed run's emitted byte count must
  equal the disarmed run's — the plane observes, it must never touch
  the data path;
- **liveness gate** (always): the armed variant's ring must actually
  have sampled (a plane that priced at 0% because it never ran is not
  a result);
- **overhead gate** (full mode): the plane's measured time share —
  total wall spent inside ``TimeSeries.sample()`` (snapshot + delta +
  the detector/SLI listener pass, all of which run in the sampler
  thread) divided by the armed run's wall — gate: <= 1%.

The overhead gate is a direct measurement, not an A/B wall diff, by
necessity: on the shared hosts this runs on, run-to-run wall spread of
the IDENTICAL disarmed workload is 5-10% (CPU-frequency and co-tenant
drift; measured here and recorded as ``wall_spread_pct``), so a wall
A/B cannot resolve a 1% effect — it prices the host's mood, not the
plane. The instrumented share is exact to ~0.01% and captures
everything the plane does per tick; the A/B walls are still run
(identity needs both variants anyway) and reported as trend data.

Both variants run with the stats plane (histograms) ON so the numbers
isolate the tower itself, not the hub it reads.

Usage: python scripts/bench_obs.py [--segs 64] [--seg-mb 64]
       [--interval 1.0] [--reps 3] [--quick] [--out BENCH_OBS.json]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)

OVERHEAD_GATE_PCT = 1.0


def _force_cpu() -> None:
    import jax

    jax.config.update("jax_platforms", "cpu")


def _spool_once(batches, tmp: str, armed: bool, interval: float) -> dict:
    """One pipelined spool run (the BENCH_PIPELINE_r09 shape) with the
    observability plane armed or disarmed. Wall covers feed through
    emitted bytes — everything the timer thread could perturb."""
    # drain the PREDECESSOR run's dirty pages before the timer starts:
    # each run spools GBs through the page cache, and without a sync
    # whichever variant runs second pays the first one's writeback
    # inside its own timed window — on this host that bias alone
    # measured ~19% wall, dwarfing the <= 1% gate under test
    os.sync()
    from uda_tpu.merger.emitter import FramedEmitter
    from uda_tpu.merger.overlap import OverlappedMerger
    from uda_tpu.merger.streaming import RunStore
    from uda_tpu.utils.comparators import get_key_type
    from uda_tpu.utils.config import Config
    from uda_tpu.utils.metrics import metrics
    from uda_tpu.utils.timeseries import (arm_observability_plane,
                                          disarm_observability_plane,
                                          timeseries)

    kt = get_key_type("uda.tpu.RawBytes")
    metrics.reset()
    metrics.enable_stats()  # both variants: the A/B prices the tower,
    # not the histogram hub it reads
    samples = 0
    plane = {"s": 0.0}
    if armed:
        assert arm_observability_plane(Config({
            "uda.tpu.stats.enable": True,
            "uda.tpu.ts.interval.s": interval}))
        # instrument the sampler: every tick's full cost (hub snapshot,
        # delta fold, ring append AND the listener pass — detectors +
        # SLI book run inside sample()) accumulates into plane["s"]
        inner = timeseries.sample

        def timed_sample():
            t0 = time.monotonic()
            try:
                return inner()
            finally:
                plane["s"] += time.monotonic() - t0

        timeseries.sample = timed_sample  # instance attr, dropped below
    store = RunStore([tmp], tag=f"obsbench_{'on' if armed else 'off'}")
    om = OverlappedMerger(kt, 16, engine="host", run_store=store)
    total = sum(b.num_records for b in batches)
    sink = {"n": 0}
    t0 = time.monotonic()
    try:
        for i, b in enumerate(batches):
            om.feed(i, b)
        om.finish_streaming(
            FramedEmitter(1 << 16),
            lambda blk: sink.__setitem__("n", sink["n"] + len(blk)),
            expected_records=total)
        wall = time.monotonic() - t0
    finally:
        if armed:
            samples = timeseries.summary()["samples"]
            timeseries.__dict__.pop("sample", None)
            disarm_observability_plane()
        store.cleanup()
        metrics.reset()
    return {"wall_s": wall, "out_bytes": sink["n"],
            "ts_samples": int(samples), "plane_s": plane["s"]}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--segs", type=int, default=64)
    ap.add_argument("--seg-mb", type=int, default=64)
    ap.add_argument("--interval", type=float, default=1.0,
                    help="rollup interval for the armed variant "
                    "(default = the uda.tpu.ts.interval.s default)")
    ap.add_argument("--reps", type=int, default=3,
                    help="runs per variant; best wall is scored — disk "
                    "noise is one-sided (interference only ever slows "
                    "a run), so min estimates the clean wall (damps "
                    "shared-host noise under the tight 1%% gate)")
    ap.add_argument("--quick", action="store_true",
                    help="small shape, one rep; identity + liveness "
                    "gate only (overhead reported, not gated)")
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    _force_cpu()
    tmp = tempfile.mkdtemp(prefix="uda_obsbench_")
    try:
        return _run(args, tmp)
    finally:
        import shutil

        shutil.rmtree(tmp, ignore_errors=True)


def _run(args, tmp: str) -> int:
    from scripts.bench_staging import make_segments

    segs = 6 if args.quick else args.segs
    seg_mb = 4 if args.quick else args.seg_mb
    reps = 1 if args.quick else max(1, args.reps)
    # quick mode still needs >= 2 rollup intervals inside the run for
    # the liveness gate; the armed interval scales down with the shape
    interval = min(args.interval, 0.1) if args.quick else args.interval
    total_mb = segs * seg_mb
    result: dict = {"bench": "obs_overhead", "segs": segs,
                    "seg_mb": seg_mb, "total_mb": total_mb,
                    "interval_s": interval, "reps": reps,
                    "nproc": os.cpu_count(), "quick": bool(args.quick)}
    batches = make_segments(segs, seg_mb << 20, True)
    runs = {False: [], True: []}
    # interleaved reps with ALTERNATING order: drift (thermal, page
    # cache) lands on both variants, and neither variant owns the
    # first-slot advantage — with a fixed off->on order plus best-of
    # scoring, "off" always gets the cleanest slot and the measured
    # overhead is the host's positional bias, not the plane's cost
    for rep in range(reps):
        order = (False, True) if rep % 2 == 0 else (True, False)
        for armed in order:
            runs[armed].append(_spool_once(batches, tmp, armed,
                                           interval))
    off = min(runs[False], key=lambda r: r["wall_s"])
    on = min(runs[True], key=lambda r: r["wall_s"])
    identical = all(r["out_bytes"] == off["out_bytes"] > 0
                    for v in runs.values() for r in v)
    sampled = all(r["ts_samples"] >= 2 for r in runs[True])
    result["obs_off_s"] = round(off["wall_s"], 3)
    result["obs_on_s"] = round(on["wall_s"], 3)
    result["obs_off_MBps"] = round(total_mb / off["wall_s"], 1)
    result["obs_on_MBps"] = round(total_mb / on["wall_s"], 1)
    result["ts_samples"] = on["ts_samples"]
    result["identical"] = identical
    result["plane_sampled"] = sampled
    # trend data, NOT the gate: the wall diff of best-of reps, plus
    # the off variant's own rep-to-rep spread — the noise floor that
    # makes the wall diff unreadable at the 1% scale
    result["wall_overhead_pct"] = round(
        100.0 * (on["wall_s"] - off["wall_s"]) / off["wall_s"], 2)
    off_walls = [r["wall_s"] for r in runs[False]]
    result["wall_spread_pct"] = round(
        100.0 * (max(off_walls) - min(off_walls)) / min(off_walls), 2)
    # THE overhead gate: the plane's measured time share, worst armed
    # rep (sampler + detector + SLI cost over that rep's wall)
    result["overhead_pct"] = round(max(
        100.0 * r["plane_s"] / r["wall_s"] for r in runs[True]), 4)
    # gate only in full mode: a noisy shared host must not flake CI
    result["overhead_ok"] = result["overhead_pct"] <= OVERHEAD_GATE_PCT
    print(json.dumps(result))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1)
            f.write("\n")
    if not (identical and sampled):
        print("FAIL: observability A/B identity/liveness gate",
              file=sys.stderr)
        return 3
    if args.quick:
        return 0
    return 0 if result["overhead_ok"] else 2


if __name__ == "__main__":
    sys.exit(main())
