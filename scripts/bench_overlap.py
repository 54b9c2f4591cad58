"""Overlap-forest vs post-hoc global sort: the network-levitated
property's perf datum.

The reference's headline property is that merging overlaps fetching, so
the post-last-fetch latency is small (reference MergeManager.cc:47-182).
This bench stages k pre-sorted segments into the OverlappedMerger run
forest exactly as fetch completions would, then measures:

- ``batch_sort_s``     — the post-hoc global device sort of everything
                         (merge_batches), the no-overlap strawman;
- ``overlap_total_s``  — feed()+finish() wall-clock (all merge work);
- ``overlap_finish_s`` — finish() alone after the forest has drained
                         every staged segment: the latency the reduce
                         actually waits after the LAST fetch lands —
                         the number the reference's design minimizes.

Runs on whatever backend is present (Pallas merge-path kernel on TPU;
on CPU the host engine, or UDA_TPU_OVERLAP_ENGINE=pallas for
interpret-mode smoke). One JSON line at the end for the notes table.

Usage: python scripts/bench_overlap.py
Env: UDA_TPU_OVERLAP_LOG2 (total records, default 22: ~0.4 GB),
     UDA_TPU_OVERLAP_SEGS (segment count, default 64),
     UDA_TPU_OVERLAP_ENGINE (auto|host|pallas)
"""

from __future__ import annotations

import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)

from uda_tpu.utils import compile_cache  # noqa: E402

compile_cache.enable()

import numpy as np  # noqa: E402


def make_segments(total: int, k: int, key_bytes=10, val_bytes=90, seed=0):
    """k segments of sorted TeraSort-shaped records as RecordBatches
    (vectorized: both lengths < 128 so the IFile framing is two 1-byte
    VInts, built as numpy columns)."""
    from uda_tpu.utils.ifile import EOF_MARKER, crack

    rng = np.random.default_rng(seed)
    per = total // k
    batches = []
    for _ in range(k):
        keys = np.frombuffer(rng.bytes(per * key_bytes), np.uint8
                             ).reshape(per, key_bytes)
        order = np.argsort(
            keys.view(np.dtype((np.void, key_bytes))).ravel())
        frame = np.empty((per, 2 + key_bytes + val_bytes), np.uint8)
        frame[:, 0] = key_bytes
        frame[:, 1] = val_bytes
        frame[:, 2:2 + key_bytes] = keys[order]
        frame[:, 2 + key_bytes:] = ord("v")
        batches.append(crack(frame.tobytes() + EOF_MARKER))
    return batches


class _SyncPoint:
    """A queue barrier: fed to the OverlappedMerger like a segment, its
    record_batch() runs on the merge thread AFTER every previously fed
    segment's stage+carry-merges completed (the queue is FIFO and
    single-threaded), sets the event, and contributes zero records."""

    def __init__(self):
        import threading

        self.reached = threading.Event()

    def record_batch(self):
        from uda_tpu.utils.ifile import EOF_MARKER, crack

        self.reached.set()
        return crack(EOF_MARKER)


def main() -> int:
    import jax

    from uda_tpu.merger.overlap import OverlappedMerger
    from uda_tpu.ops import merge as merge_ops
    from uda_tpu.utils.comparators import get_key_type
    from uda_tpu.utils.config import Config

    log2 = int(os.environ.get("UDA_TPU_OVERLAP_LOG2", 22))
    k = int(os.environ.get("UDA_TPU_OVERLAP_SEGS", 64))
    engine = os.environ.get("UDA_TPU_OVERLAP_ENGINE", "auto")
    total = 1 << log2
    kt = get_key_type("uda.tpu.RawBytes")
    width = Config().get("uda.tpu.key.width")
    backend = jax.default_backend()
    print(f"overlap bench: 2^{log2} records in {k} segments, "
          f"engine={engine} backend={backend}", flush=True)
    batches = make_segments(total, k)

    # ---- post-hoc global sort: warm at the FULL shape (the device
    # sort executable is shape-specialized), then time ----
    want = merge_ops.merge_batches(batches, kt, width)
    t0 = time.perf_counter()
    want = merge_ops.merge_batches(batches, kt, width)
    batch_sort_s = time.perf_counter() - t0
    print(f"batch global sort: {batch_sort_s:.3f}s", flush=True)

    # ---- overlap forest ----
    om = OverlappedMerger(kt, width, engine=engine)
    t0 = time.perf_counter()
    for i, b in enumerate(batches):
        om.feed(i, b)
    # deterministic drain barrier: the sync point's record_batch runs
    # after every staged segment's merge cascade completed
    sync = _SyncPoint()
    om.feed(len(batches), sync)
    sync.reached.wait()
    drained_at = time.perf_counter()
    got = om.finish(batches)
    t_end = time.perf_counter()
    overlap_total_s = t_end - t0
    overlap_finish_s = t_end - drained_at

    assert got.num_records == want.num_records
    assert bytes(got.key(0)) == bytes(want.key(0))
    assert bytes(got.key(got.num_records - 1)) == \
        bytes(want.key(want.num_records - 1))
    print(f"overlap total: {overlap_total_s:.3f}s  "
          f"finish-after-last-fetch: {overlap_finish_s:.3f}s  "
          f"(stats {om.stats})", flush=True)
    print(json.dumps({
        "bench": "overlap_vs_batch", "backend": backend,
        "records": total, "segments": k, "engine": om.engine,
        "batch_sort_s": round(batch_sort_s, 4),
        "overlap_total_s": round(overlap_total_s, 4),
        "overlap_finish_s": round(overlap_finish_s, 4),
        "finish_vs_batch": round(batch_sort_s / max(overlap_finish_s,
                                                    1e-9), 2),
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
