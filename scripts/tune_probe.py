#!/usr/bin/env python
"""tune_probe: the seeded probe that populates the online tuning
cache (uda_tpu/utils/tuncache.py).

Instead of a human reading a bench's output and exporting settings,
this probe measures on THIS host and persists the winner that the
batched host-I/O plane consults when it resolves its parameters. An
explicitly set flag still overrides the cache (tested in
tests/test_tuncache.py).

Domain probed:

- ``io.read``: a submit_batch burst A/B over coalesce-gap settings on
  a synthetic MOF (the io_bench hot-burst shape, in-process), one
  winner per platform: {batch, gap_kb, batch_max, backend}.

Re-probe rung: ``--reprobe-age S`` skips entries younger than S
seconds (the background-freshness contract: a cron/idle-time
invocation re-measures only what drifted stale; ``uda.tpu.tune.
reprobe.s`` is the in-process analogue via tuncache.ensure_fresh).
``--force`` re-measures everything. Probes count ``tune.probes`` —
the lifecycle test's "probe counter zero on the second run" gate rides
exactly this skip.

Usage::

    UDA_TPU_TUNE_CACHE=/path/tune.json python scripts/tune_probe.py --quick
    python scripts/tune_probe.py --cache /path/tune.json
"""

from __future__ import annotations

import argparse
import os
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

JOB = "jobTuneProbe"
MAP = "attempt_jobTuneProbe_m_000000_0"


def _fresh(cache, domain: str, key: str, reprobe_age: float,
           force: bool) -> bool:
    """True when the entry is fresh enough to SKIP re-probing."""
    if force:
        return False
    age = cache.age_s(domain, key)
    if age is None:
        return False
    if reprobe_age <= 0:
        return True  # a winner exists and no staleness horizon: keep it
    return age <= reprobe_age


def probe_io_read(cache, quick: bool, reprobe_age: float, force: bool,
                  seed: int) -> list:
    """Burst A/B over the batched read plane's parameters on a
    synthetic MOF: batch off vs on at each coalesce-gap rung, winner =
    the fastest configuration whose bytes matched the oracle."""
    from uda_tpu.mofserver.data_engine import DataEngine, ShuffleRequest
    from uda_tpu.mofserver.index import IndexRecord
    from uda_tpu.utils.config import Config
    from uda_tpu.utils.metrics import metrics

    key = sys.platform
    if _fresh(cache, "io.read", key, reprobe_age, force):
        return [(key, "fresh", None)]
    metrics.add("tune.probes", domain="io.read")

    class _Resolver:
        def __init__(self, path, n):
            self._rec = IndexRecord(start_offset=0, raw_length=n,
                                    part_length=n, path=path)

        def resolve(self, job_id, map_id, reduce_id):
            return self._rec

    import random

    total = (8 << 20) if quick else (64 << 20)
    chunk = 64 << 10
    burst = 64 if quick else 256
    tmp = tempfile.mkdtemp(prefix="uda_tune_probe_")
    path = os.path.join(tmp, "probe.mof")
    block = os.urandom(1 << 20)
    with open(path, "wb") as f:
        left = total
        while left > 0:
            f.write(block[:min(left, len(block))])
            left -= len(block)

    def burst_offsets():
        # the hot-burst shape: mostly-sequential chunks with jitter.
        # The rng is REBUILT per call so every configuration and every
        # repetition fetches the same ranges in the same order — a
        # shared advancing rng would hand each A/B arm a different
        # arrival order and bias which winner gets crowned
        offs = [(i * chunk) % (total - chunk) for i in range(burst)]
        random.Random(seed).shuffle(offs)
        return offs

    def run(cfg_over: dict, batched: bool) -> float:
        engine = DataEngine(_Resolver(path, total),
                            Config(dict(cfg_over)))
        offs = burst_offsets()
        reqs = [ShuffleRequest(JOB, MAP, 0, off, chunk) for off in offs]
        t0 = time.perf_counter()
        if batched:
            futs = engine.submit_batch(reqs)
        else:
            futs = [engine.submit(r) for r in reqs]
        with open(path, "rb") as oracle_f:
            for req, fut in zip(reqs, futs):
                res = fut.result(timeout=60.0)
                oracle_f.seek(req.offset)
                want = oracle_f.read(min(chunk, total - req.offset))
                assert bytes(res.data) == want, "probe identity broke"
        dt = time.perf_counter() - t0
        engine.stop()
        return dt

    reps = 2 if quick else 3
    results = {}
    results["off"] = min(run({}, batched=False) for _ in range(reps))
    gaps = (0, 64, 256)
    best = ("off", results["off"], {})
    for gap in gaps:
        name = f"gap{gap}"
        results[name] = min(
            run({"uda.tpu.read.coalesce.gap.kb": gap}, batched=True)
            for _ in range(reps))
        if results[name] < best[1]:
            best = (name, results[name],
                    {"batch": "on", "gap_kb": gap, "batch_max": 256})
    probe_engine = DataEngine(_Resolver(path, total), Config())
    winner = dict(best[2] or {"batch": "off"})
    winner["backend"] = probe_engine.io_backend
    probe_engine.stop()
    mbps = burst * chunk / (1 << 20) / best[1]
    cache.record("io.read", key, winner, metric=round(mbps, 2),
                 probe="tune_probe")
    try:
        os.remove(path)
        os.rmdir(tmp)
    except OSError:
        pass
    return [(key, "probed",
             f"{winner} ({ {k: round(v, 4) for k, v in results.items()} })")]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--cache", default="",
                    help="tuning-cache path (default: UDA_TPU_TUNE_CACHE"
                         " env, required one way or the other)")
    ap.add_argument("--quick", action="store_true",
                    help="small shapes (CI / test sizes)")
    ap.add_argument("--force", action="store_true",
                    help="re-measure even fresh entries")
    ap.add_argument("--reprobe-age", type=float, default=0.0,
                    help="re-measure entries older than this many "
                         "seconds (0 = existing winners are kept; "
                         "this is the background re-probe rung)")
    ap.add_argument("--seed", type=int, default=13)
    ap.add_argument("--list", action="store_true",
                    help="print the cache entries and exit")
    args = ap.parse_args()

    from uda_tpu.utils.metrics import metrics
    from uda_tpu.utils.tuncache import TuneCache, cache_path_from_env

    path = args.cache or cache_path_from_env()
    if not path:
        print("tune_probe: no cache path (--cache or UDA_TPU_TUNE_CACHE)",
              file=sys.stderr)
        return 2
    cache = TuneCache(path)
    if args.list:
        for k, v in sorted(cache.entries().items()):
            print(f"{k}: {v.get('winner')} (metric {v.get('metric')})")
        return 0
    reports = probe_io_read(cache, args.quick, args.reprobe_age,
                            args.force, args.seed)
    probes = int(metrics.get("tune.probes"))
    for key, status, winner in reports:
        line = f"tune_probe: {key}: {status}"
        if winner is not None:
            line += f" -> {winner}"
        print(line)
    print(f"tune_probe: {probes} probe(s) run, cache at {path}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
