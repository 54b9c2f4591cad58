"""Shared test helpers: synthetic map-output generation, and the
framed bytes of a merged partition (the merger's emit and its oracle).

``make_mof_tree`` builds the on-disk layout the supplier serves (``<root>/<job>/<map>/
file.out[.index]``) the way a Hadoop mapper would: per-map records
partitioned by reducer, each partition sorted and IFile-framed, index
triples pointing into the concatenated MOF.
"""

from __future__ import annotations

import io
import os
from typing import Callable

import numpy as np

from uda_tpu.merger.emitter import FramedEmitter
from uda_tpu.mofserver.index import write_index_file
from uda_tpu.ops import merge as merge_ops
from uda_tpu.utils.ifile import IFileWriter


def default_partitioner(key: bytes, num_reducers: int) -> int:
    import zlib
    return zlib.crc32(key) % num_reducers


def make_mof_tree(root: str, job_id: str, num_maps: int, num_reducers: int,
                  records_per_map: int, seed: int = 0,
                  key_bytes: int = 10, val_bytes: int = 30,
                  partitioner: Callable[[bytes, int], int] = default_partitioner,
                  sort_key=None) -> dict[int, list[tuple[bytes, bytes]]]:
    """Write a full MOF tree; returns expected records per reducer
    (unsorted)."""
    rng = np.random.default_rng(seed)
    expected: dict[int, list[tuple[bytes, bytes]]] = {r: [] for r in range(num_reducers)}
    sort_key = sort_key or (lambda kv: kv[0])
    for m in range(num_maps):
        map_id = f"attempt_{job_id}_m_{m:06d}_0"
        parts: dict[int, list[tuple[bytes, bytes]]] = {r: [] for r in range(num_reducers)}
        for _ in range(records_per_map):
            k = rng.bytes(key_bytes)
            v = rng.bytes(val_bytes)
            r = partitioner(k, num_reducers)
            parts[r].append((k, v))
            expected[r].append((k, v))
        d = os.path.join(root, job_id, map_id)
        os.makedirs(d, exist_ok=True)
        mof = io.BytesIO()
        triples = []
        for r in range(num_reducers):
            start = mof.tell()
            w = IFileWriter(mof)
            for k, v in sorted(parts[r], key=sort_key):
                w.append(k, v)
            w.close()
            length = mof.tell() - start
            triples.append((start, length, length))
        with open(os.path.join(d, "file.out"), "wb") as f:
            f.write(mof.getvalue())
        write_index_file(os.path.join(d, "file.out.index"), triples)
    return expected


def map_ids(job_id: str, num_maps: int) -> list[str]:
    return [f"attempt_{job_id}_m_{m:06d}_0" for m in range(num_maps)]


def framed_bytes(batch) -> bytes:
    """A sorted batch as the emitter frames it (the block size cuts the
    stream into consumer calls; it does not change the bytes)."""
    out = io.BytesIO()
    FramedEmitter(1 << 14).emit_batch(batch,
                                      lambda blk: out.write(bytes(blk)))
    return out.getvalue()


def host_sort_bytes(batches, kt) -> bytes:
    """The oracle: one comparator sort of the concatenation on the
    host (stable: equal keys keep (segment, row) order), framed."""
    return framed_bytes(merge_ops.merge_batches_host(batches, kt))


def emit_stream_bytes(om, batches) -> bytes:
    """An in-memory ``OverlappedMerger``'s whole output: ``emit_stream``
    (drain, leftover merge, slab-wise gather and framing) into a
    buffer. Compare it with ``framed_bytes`` of an oracle's batch."""
    out = io.BytesIO()
    om.emit_stream(batches, FramedEmitter(1 << 14),
                   lambda blk: out.write(bytes(blk)))
    return out.getvalue()
