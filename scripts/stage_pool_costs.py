"""What a stage pool pays to stage a task's segments, numpy passes against
the one native pass (ops.merge.stage_run_rows), on the chip's host: wall
seconds for a pool of worker threads to take every segment of a task
from a queue, lease a row buffer, do the segment's key work (pack, order
check or sort, row fill) and hand the buffer back — what
merger/overlap.py:_prepare does under its ``overlap_pack`` timer, with no
fetch before it and no merge behind it (``*_w1_s``, ``*_w4_s``: one
worker, four) — and for the merger itself (``*_task_s``: an
OverlappedMerger, host engine, four stage workers, fed every segment
from one thread, until its forest has carried them all: the pool beside
its two other customers of the interpreter lock, the feeder and the
merge consumer).

Task shapes (rows a segment x segments): 1,280 x 1,024 (fanin1024),
5,120 x 256, 20,480 x 64 — the same 131 MB cut three ways — and
164,062 x 64 (wide64, 1.05 GB). TeraSort records (10 B key, 90 B value,
comparator uda.tpu.RawBytes, width 16 as the reduce cells run). Segment
kinds: ``presorted`` (every Hadoop map output), ``unsorted`` (the same
records in arrival order: an exchange bucket, a foreign writer) and
``compressed`` (presorted, but the worker first zlib-inflates the framed
bytes and cracks them: the probe's model of an inflate on the stage
pool, NOT what the served path does — there ``DecompressingClient``
inflates on the thread that completes the fetch, ``PERF.md`` section 7,
"What ``reduce_invindex_compressed`` opens"). The one-worker time is the work; what four workers take over a
quarter of it is the convoy on the interpreter lock.

    chiprun -- python3 scripts/stage_pool_costs.py

prints one JSON line a cell and writes chiprun_out/stage_pool_costs.json.
Host seconds only: the device is never touched (JAX is held to the CPU),
so the numbers are the machine's, whatever chip it carries."""
import json
import os
import queue
import statistics
import sys
import threading
import time
import zlib

import numpy as np

os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.getcwd())

from uda_tpu import native  # noqa: E402
from uda_tpu.merger.overlap import OverlappedMerger  # noqa: E402
from uda_tpu.ops import merge as merge_ops  # noqa: E402
from uda_tpu.utils import comparators  # noqa: E402

KT = comparators.get_key_type("uda.tpu.RawBytes")
WIDTH = 16
COLS = WIDTH // 4 + merge_ops.ROW_EXTRA_COLS
SHAPES = ((1280, 1024), (5120, 256), (20480, 64), (164062, 64))
KINDS = ("presorted", "unsorted", "compressed")
REPS = 3
MAX_DISTINCT_BYTES = 300 << 20   # of framed segments kept; cycled past it


def framed_segment(rng, rows: int, presorted: bool) -> bytes:
    """One TeraSort map output as IFile bytes: VInt(10) VInt(90) key
    value a record, EOF marker."""
    recs = np.empty((rows, 102), np.uint8)
    recs[:, 0], recs[:, 1] = 10, 90
    recs[:, 2:12] = rng.integers(0, 256, (rows, 10), dtype=np.uint8)
    recs[:, 12:] = 0x41
    if presorted:
        recs = recs[np.lexsort(tuple(recs[:, c] for c in range(11, 1, -1)))]
    return recs.tobytes() + b"\xff\xff"


class Deflated:
    """A compressed segment as the stage pool sees one: the worker that
    takes it inflates and cracks it (Segment.record_batch's place)."""

    def __init__(self, framed: bytes):
        self.raw_length = len(framed)
        self._z = zlib.compress(framed, 1)

    def record_batch(self):
        return native.crack_native(zlib.decompress(self._z))


def make_task(rows: int, segments: int, kind: str):
    """The task's sources: cracked batches, or (compressed) segments a
    worker must inflate and crack first."""
    rng = np.random.default_rng([rows, segments])
    distinct = max(1, min(segments, MAX_DISTINCT_BYTES // (rows * 102)))
    framed = [framed_segment(rng, rows, kind != "unsorted")
              for _ in range(distinct)]
    make = Deflated if kind == "compressed" else native.crack_native
    sources = [make(f) for f in framed]
    return [sources[i % distinct] for i in range(segments)]


def pool_seconds(task, kind: str, workers: int) -> float:
    """Wall seconds for ``workers`` bare threads to stage every segment."""
    pool = merge_ops.RowBufferPool()
    q: "queue.Queue" = queue.Queue()
    for item in enumerate(task):
        q.put(item)
    for _ in range(workers):
        q.put(None)
    errors = []

    def worker():
        try:
            while (item := q.get()) is not None:
                seg, source = item
                if kind == "compressed":
                    source = source.record_batch()
                lease = pool.lease(source.num_records, COLS)
                try:
                    merge_ops.stage_run_rows(lease, source, KT, WIDTH, seg)
                finally:
                    pool.release(lease)
        except BaseException as e:  # re-raised by the caller
            errors.append(e)

    threads = [threading.Thread(target=worker) for _ in range(workers)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    if errors:
        raise errors[0]
    return wall


def task_seconds(task) -> float:
    """Wall seconds for a host-engine merger with four stage workers to
    take every segment and carry it into its forest."""
    om = OverlappedMerger(KT, WIDTH, engine="host", stagers=4)
    try:
        t0 = time.perf_counter()
        for seg, source in enumerate(task):
            om.feed(seg, source)
        om._drain()     # every segment staged, every carry done
        return time.perf_counter() - t0
    finally:
        om.abort()      # the forest's leases go home


def main() -> None:
    assert native.build(), "the native library must build"
    native_pass = native.stage_segment_native
    out = {"host_cores": os.cpu_count(), "width": WIDTH, "reps": REPS,
           "cells": []}
    for rows, segments in SHAPES:
        for kind in KINDS:
            task = make_task(rows, segments, kind)
            rec = {"rows": rows, "segments": segments, "kind": kind}
            for path in ("numpy", "native"):
                # the numpy path: the native pass taken away, nothing else
                native.stage_segment_native = (
                    native_pass if path == "native" else lambda *a: None)
                pool_seconds(task[:8], kind, 4)     # warm
                for workers in (1, 4):
                    rec[f"{path}_w{workers}_s"] = statistics.median(
                        pool_seconds(task, kind, workers)
                        for _ in range(REPS))
                rec[f"{path}_task_s"] = statistics.median(
                    task_seconds(task) for _ in range(REPS))
            native.stage_segment_native = native_pass
            print(json.dumps(rec), flush=True)
            out["cells"].append(rec)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/stage_pool_costs.json", "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
