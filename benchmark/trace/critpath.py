"""Critical seconds of each host stage in one task's span tree: the
benchmark's copy of ``uda_tpu/utils/critpath.py:analyze``'s wall
partition (the original stays the program's; the yardstick may not move
with it). Sweep the root span's timeline; at every instant exactly one
stage bucket is charged, the first of ``BUCKET_PRIORITY`` with a span
active; instants with none are ``idle``. Buckets plus idle sum to the
root's wall time."""

from __future__ import annotations

# span name -> stage bucket, as the program records them today
SPAN_BUCKETS = {
    "fetch": "fetch", "fetch.segment": "fetch", "net.fetch": "fetch",
    "net.size_probe": "fetch", "net.job_bind": "fetch",
    "wait_mem": "wait", "merge.wait": "wait",
    "overlap_pack": "decompress_pack", "pack": "decompress_pack",
    "run_spool": "decompress_pack",
    "overlap_stage": "device_put", "merge.device_put": "device_put",
    "merge": "merge", "overlap_device_merge": "merge",
    "device_sort": "merge", "lpq_spill": "merge", "lpq_phase": "merge",
    "rpq_phase": "merge",
    "net.serve": "serve", "engine.pread": "serve",
    "engine.read_batch": "serve", "supplier_read": "serve", "emit": "serve",
}
BUCKET_PRIORITY = ("merge", "device_put", "decompress_pack", "serve",
                   "fetch", "other", "wait")
ROOT = "reduce_task"


def bucket_of(name: str) -> str:
    return SPAN_BUCKETS.get(name, "other")


def critical_seconds(spans: list, root: dict) -> dict:
    """``{bucket: critical_s, ..., "idle": s, "wall": s}`` for the task
    whose root span is ``root``; ``spans`` are the recorded span dicts
    (name, ts, dur, trace, id)."""
    t0, t1 = root["ts"], root["ts"] + root["dur"]
    events = []
    for s in spans:
        if s is root or s.get("trace") != root.get("trace"):
            continue
        lo, hi = max(s["ts"], t0), min(s["ts"] + s["dur"], t1)
        if hi > lo:
            b = bucket_of(s["name"])
            events += [(lo, -1, b), (hi, 1, b)]     # opens sort first
    events.sort()
    out = dict.fromkeys(BUCKET_PRIORITY + ("idle",), 0.0)
    active = dict.fromkeys(BUCKET_PRIORITY, 0)
    prev = t0
    for t, kind, b in events + [(t1, 1, None)]:
        if t > prev:
            charged = next((p for p in BUCKET_PRIORITY if active[p]), "idle")
            out[charged] += t - prev
            prev = t
        if b is not None:
            active[b] -= kind
    out["wall"] = t1 - t0
    return out


def per_task(spans: list) -> list:
    """One :func:`critical_seconds` block per completed task, in order
    of completion."""
    spans = [s for s in spans if s.get("kind") is None
             and s.get("dur") is not None]
    roots = sorted((s for s in spans if s["name"] == ROOT),
                   key=lambda s: s["ts"] + s["dur"])
    return [critical_seconds(spans, r) for r in roots]
