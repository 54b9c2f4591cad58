"""The plain reference of a TeraSort reduce task whose partition is too
large to hold twice: the same records, stably sorted on the host under
the bytewise comparator, in IFile framing (without the EOF marker) —
``host_sort``'s semantics, computed so that it fits. Only the key
columns (10 of every 102 bytes) and one stable argsort of them are ever
resident; the sorted stream itself is produced block by block, each
block gathered from the map output files — read, a range a map, never
mapped: a mapped file's pages count against the one-chip machine's
40 GiB as the task's own memory does — and fed to a 256-bit BLAKE2b
digest, and is known afterwards by its size and that digest
(``host_sort_parts.compare_digest`` decides ``correct`` against them).
Independent of the engine: a TeraSort record frames as 102 bytes, so
the map output files parse by reshape."""

from __future__ import annotations

import hashlib
import os

import numpy as np

from benchmark.reference.host_sort import ReferenceError
from benchmark.reference.host_sort_parts import compare_digest

__all__ = ["ReferenceError", "compare_digest", "map_records",
           "read_frames", "sorted_digest"]

BLOCK_RECORDS = 1 << 20       # 107 MB of stream resident at a time


def map_records(path: str) -> int:
    """Records of one map output, by its size and its EOF marker."""
    size = os.path.getsize(path)
    if size < 2 or (size - 2) % 102:
        raise ReferenceError(f"{path} is not 102-byte frames plus EOF")
    with open(path, "rb") as f:
        f.seek(size - 2)
        if f.read(2) != b"\xff\xff":
            raise ReferenceError(f"{path} does not end in the EOF marker")
    return (size - 2) // 102


def read_frames(path: str, first: int, count: int) -> np.ndarray:
    """``uint8[count, 102]``: frames ``first`` .. ``first + count`` of
    one map output, read."""
    frames = np.fromfile(path, np.uint8, count * 102, offset=first * 102)
    if frames.size != count * 102:
        raise ReferenceError(f"{path} is shorter than its size said")
    return frames.reshape(count, 102)


def sorted_digest(root: str, job: str, map_ids: list,
                  block_records: int = BLOCK_RECORDS) -> tuple:
    """``(bytes, digest)`` of what a correct reduce task emits before
    its EOF marker: ``host_sort.sorted_stream``'s size and
    ``host_sort_parts.digest`` of it. Equal keys keep arrival order
    (map, then row): the argsort is stable over the keys in map order."""
    paths = [os.path.join(root, job, m, "file.out") for m in map_ids]
    counts = np.asarray([map_records(p) for p in paths], np.int64)
    starts = np.concatenate([[0], np.cumsum(counts)])
    total = int(starts[-1])
    hi = np.empty(total, np.uint64)
    lo = np.empty(total, np.uint16)
    for path, n, a, b in zip(paths, counts, starts[:-1], starts[1:]):
        f = read_frames(path, 0, int(n))
        if not ((f[:, 0] == 10).all() and (f[:, 1] == 90).all()):
            raise ReferenceError("map outputs are not 10/90-byte records")
        hi[a:b] = np.ascontiguousarray(f[:, 2:10]).view(">u8").ravel()
        lo[a:b] = np.ascontiguousarray(f[:, 10:12]).view(">u2").ravel()
    # np.lexsort: last key primary, stable
    order = np.lexsort((lo, hi))
    del hi, lo
    digest = hashlib.blake2b(digest_size=32)
    block = np.empty((min(block_records, max(total, 1)), 102), np.uint8)
    for a in range(0, total, block_records):
        idx = order[a:a + block_records]
        out = block[:idx.shape[0]]
        # the block's records by the map that holds them: one read a
        # map, of the rows from its first to its last in the block (a
        # map is sorted, so they are neighbours), rows ascending
        by_map = np.argsort(idx, kind="stable")
        cuts = np.searchsorted(idx[by_map], starts)
        for m in np.flatnonzero(cuts[1:] > cuts[:-1]).tolist():
            dst = by_map[cuts[m]:cuts[m + 1]]
            rows = idx[dst] - starts[m]
            first = int(rows[0])
            out[dst] = read_frames(paths[m], first,
                                   int(rows[-1]) - first + 1)[rows - first]
        digest.update(memoryview(out).cast("B"))
    return total * 102, digest.digest()
