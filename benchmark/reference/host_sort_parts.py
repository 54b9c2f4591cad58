"""The plain reference of one reduce task of a TeraSort job whose map
outputs hold several partitions: the task's own partition — cut out of
every map output file by that file's spill index — stably sorted on the
host under the bytewise comparator, in IFile framing (without the EOF
marker). The configuration's plain reference, ``host_sort``'s for one
partition of several (it decides ``correct`` by the same ``compare``);
independent of the engine: a TeraSort record frames as 102 bytes, and a
spill index is ``(start, raw length, part length)`` triples of
big-endian int64, one per partition."""

from __future__ import annotations

import hashlib
import os

import numpy as np

from benchmark.reference.host_sort import ReferenceError, compare

__all__ = ["ReferenceError", "compare", "compare_digest", "digest",
           "read_frames", "sorted_stream"]


def read_frames(path: str, partition: int) -> np.ndarray:
    """The ``uint8[n, 102]`` frames of one partition of a map output."""
    index = np.fromfile(path + ".index", ">i8").reshape(-1, 3)
    if not 0 <= partition < len(index):
        raise ReferenceError(f"{path}.index has no partition {partition}")
    start, raw, part = (int(v) for v in index[partition])
    if raw != part or (raw - 2) % 102:
        raise ReferenceError(f"{path} partition {partition} is not "
                             f"uncompressed 102-byte frames plus EOF")
    with open(path, "rb") as f:
        f.seek(start)
        data = np.frombuffer(f.read(raw), np.uint8)
    if data.size != raw or data[-2:].tobytes() != b"\xff\xff":
        raise ReferenceError(f"{path} partition {partition} does not end "
                             f"in the EOF marker")
    return data[:-2].reshape(-1, 102)


def sorted_stream(root: str, job: str, map_ids: list,
                  partition: int) -> np.ndarray:
    """``uint8[records * 102]``: what a correct reduce task of
    ``partition`` emits before its EOF marker. Equal keys keep arrival
    order (map, then row)."""
    recs = np.concatenate([
        read_frames(os.path.join(root, job, m, "file.out"), partition)
        for m in map_ids])
    if not ((recs[:, 0] == 10).all() and (recs[:, 1] == 90).all()):
        raise ReferenceError("map outputs are not 10/90-byte records")
    hi = np.ascontiguousarray(recs[:, 2:10]).view(">u8").ravel()
    lo = np.ascontiguousarray(recs[:, 10:12]).view(">u2").ravel()
    # np.lexsort: last key primary, stable
    return recs[np.lexsort((lo, hi))].ravel()


def digest(stream: np.ndarray) -> bytes:
    """A 256-bit BLAKE2b digest of a stream's bytes: what stands for a
    reference where several cannot be held beside the tasks (a reduce
    slot's reference is as large as its partition)."""
    return hashlib.blake2b(memoryview(np.ascontiguousarray(stream)),
                           digest_size=32).digest()


def compare_digest(stream: np.ndarray, reference_bytes: int,
                   reference_digest: bytes) -> str | None:
    """:func:`compare` against a reference known by its size and its
    :func:`digest`: None when ``stream`` is byte for byte that
    reference plus the EOF marker (to 2^-256), else what differs —
    which byte, only :func:`compare` can say."""
    if stream.size != reference_bytes + 2:
        return f"{stream.size} bytes emitted, {reference_bytes + 2} expected"
    if stream[-2:].tobytes() != b"\xff\xff":
        return "stream does not end in the IFile EOF marker"
    if digest(stream[:-2]) != reference_digest:
        return "the stream's digest differs from the reference's"
    return None
