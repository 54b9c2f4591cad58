"""The plain reference of a TeraSort reduce task: the same records,
stably sorted on the host under the bytewise comparator, in IFile
framing (without the EOF marker). Independent of the engine: a TeraSort
record frames as 102 bytes, so the map output files parse by reshape.
(``chip_smoke.py:_host_reference`` is the original.)"""

from __future__ import annotations

import os

import numpy as np


class ReferenceError(Exception):
    """The map outputs are not what the configuration says they are."""


def read_frames(path: str) -> np.ndarray:
    raw = np.fromfile(path, np.uint8)
    if raw[-2:].tobytes() != b"\xff\xff" or (raw.size - 2) % 102:
        raise ReferenceError(f"{path} is not 102-byte frames plus EOF")
    return raw[:-2].reshape(-1, 102)


def sorted_stream(root: str, job: str, map_ids: list) -> np.ndarray:
    """``uint8[records * 102]``: what a correct reduce task emits before
    its EOF marker. Equal keys keep arrival order (map, then row)."""
    recs = np.concatenate([read_frames(os.path.join(root, job, m, "file.out"))
                           for m in map_ids])
    if not ((recs[:, 0] == 10).all() and (recs[:, 1] == 90).all()):
        raise ReferenceError("map outputs are not 10/90-byte records")
    hi = np.ascontiguousarray(recs[:, 2:10]).view(">u8").ravel()
    lo = np.ascontiguousarray(recs[:, 10:12]).view(">u2").ravel()
    # np.lexsort: last key primary, stable
    return recs[np.lexsort((lo, hi))].ravel()


def compare(stream: np.ndarray, reference: np.ndarray) -> str | None:
    """None when ``stream`` is the reference plus the EOF marker, else
    what differs."""
    if stream.size != reference.size + 2:
        return f"{stream.size} bytes emitted, {reference.size + 2} expected"
    if stream[-2:].tobytes() != b"\xff\xff":
        return "stream does not end in the IFile EOF marker"
    if not np.array_equal(stream[:-2], reference):
        bad = int(np.flatnonzero(stream[:-2] != reference)[0])
        return f"differs at byte {bad} (record {bad // 102})"
    return None
