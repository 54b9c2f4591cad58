"""The plain reference of a Text-keyed reduce task: the same records,
stably sorted on the host under ``org.apache.hadoop.io.Text``'s
comparator, in IFile framing (without the EOF marker). Independent of
the engine and of the generator: it parses the frames itself.

A frame is ``VInt(key bytes) VInt(value bytes) key value`` and a Text
key is ``VInt(len) content``; the comparator orders by the content's
bytes, a proper prefix first, and skips the VInt (reference
``src/Merger/CompareFunc.cc:82-86``). This configuration's VInts are
all one byte (a length under 128), which is checked, not assumed. With
one-byte lengths the frames of a map output are a chain — a record's
first two bytes say where the next starts — and the chain is followed
with numpy by pointer doubling: ``hop`` maps every byte offset to the
offset one frame on, squaring it doubles the stride, and the starts
found so far reach twice as far each round.

The order: content zero-padded to 48 bytes as six big-endian 64-bit
words, then the length — for content of at most 48 bytes that is the
comparator's order (where the padded bytes tie, one content is the
other plus zero bytes, and the shorter is smaller) — in one stable
``np.lexsort``, so equal words keep arrival order: map, then row.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
from typing import NamedTuple

import numpy as np

MAX_CONTENT = 48
EOF_MARKER = b"\xff\xff"
BLOCK = 1 << 20               # records gathered at a time into the stream


class ReferenceError(Exception):
    """The map outputs are not what the configuration says they are."""


class Sorted(NamedTuple):
    """``stream``: what a correct task emits before its EOF marker;
    ``starts``: the offset of every record in it, ascending."""
    stream: np.ndarray
    starts: np.ndarray


def frame_starts(raw: np.ndarray, path: str = "map output") -> np.ndarray:
    """Offsets of the frames of one map output file (``uint8``, EOF
    marker included), in file order."""
    end = raw.size - 2
    if end < 0 or raw[end:].tobytes() != EOF_MARKER:
        raise ReferenceError(f"{path} does not end in the EOF marker")
    if end == 0:
        return np.zeros(0, np.int64)
    hop = np.arange(2, end + 2)
    hop += raw[:end]
    hop += raw[1:end + 1]
    hop = np.append(np.minimum(hop, end), end)    # the marker stays put
    starts = np.zeros(1, np.int64)
    while True:
        reached = hop[starts]
        reached = reached[reached < end]
        if reached.size == 0:
            break
        starts = np.concatenate([starts, reached])
        hop = hop[hop]
    starts.sort()
    if (raw[starts] > 127).any() or (raw[starts + 1] > 127).any():
        raise ReferenceError(f"{path}: a frame length of several bytes")
    nxt = starts + 2 + raw[starts] + raw[starts + 1]
    if nxt[-1] != end or (nxt[:-1] != starts[1:]).any():
        raise ReferenceError(f"{path}: frames do not chain to the marker")
    return starts


def read_records(path: str):
    """One map output as ``(data, starts, sizes, words, lens)``: the
    file's bytes without the marker, every frame's offset and byte
    count in it, the key's content as six big-endian words and the
    content's length."""
    raw = np.fromfile(path, np.uint8)
    starts = frame_starts(raw, path)
    key_len = raw[starts].astype(np.int64)
    sizes = 2 + key_len + raw[starts + 1]
    lens = raw[starts + 2].astype(np.int64)   # a frame is 3 bytes at least
    if (lens > 127).any() or (lens + 1 != key_len).any():
        raise ReferenceError(f"{path}: a key is not VInt(len) + len bytes")
    if lens.max(initial=0) > MAX_CONTENT:
        raise ReferenceError(f"{path}: content of {lens.max()} bytes; this "
                             f"reference orders at most {MAX_CONTENT}")
    col = np.arange(MAX_CONTENT, dtype=np.int64)[None, :]
    padded = np.append(raw, np.zeros(MAX_CONTENT, np.uint8))
    content = padded[starts[:, None] + 3 + col]
    content[col >= lens[:, None]] = 0
    return raw[:-2], starts, sizes, content.view(">u8"), lens


def sorted_stream(root: str, job: str, map_ids: list,
                  threads: int = 8) -> Sorted:
    """Equal keys keep arrival order (map, then row)."""
    with ThreadPoolExecutor(threads) as pool:
        parts = list(pool.map(
            lambda m: read_records(os.path.join(root, job, m, "file.out")),
            map_ids))
    data, at, sizes, words, lens = (np.concatenate(c) for c in zip(*parts))
    base = np.zeros(len(parts), np.int64)
    np.cumsum([p[0].size for p in parts[:-1]], out=base[1:])
    at += np.repeat(base, [p[1].size for p in parts])
    # np.lexsort: last key primary, stable
    order = np.lexsort((lens,) + tuple(words[:, c] for c in range(5, -1, -1)))
    at, sizes = at[order], sizes[order]
    starts = np.zeros(sizes.size, np.int64)
    np.cumsum(sizes[:-1], out=starts[1:])
    stream = np.empty(data.size, np.uint8)
    for lo in range(0, order.size, BLOCK):
        hi = min(lo + BLOCK, order.size)
        first = starts[lo]
        end = starts[hi] if hi < order.size else stream.size
        # byte j of the block comes from data[j + (where its frame lies
        # in the maps) - (where it lies in the stream)]
        shift = np.repeat(at[lo:hi] - starts[lo:hi], sizes[lo:hi])
        stream[first:end] = data[shift + np.arange(first, end)]
    return Sorted(stream, starts)


def compare(stream: np.ndarray, reference: Sorted) -> str | None:
    """None when ``stream`` is the reference plus the EOF marker, else
    the first byte that differs and the record it lies in."""
    want = reference.stream
    if stream.size != want.size + 2:
        return f"{stream.size} bytes emitted, {want.size + 2} expected"
    if stream[-2:].tobytes() != EOF_MARKER:
        return "stream does not end in the IFile EOF marker"
    if not np.array_equal(stream[:-2], want):
        bad = int(np.flatnonzero(stream[:-2] != want)[0])
        record = int(np.searchsorted(reference.starts, bad, "right")) - 1
        at = int(reference.starts[record])
        return (f"differs at byte {bad} (record {record}, which the "
                f"reference frames as {want[at:at + 3 + want[at + 2]].tobytes()!r})")
    return None
