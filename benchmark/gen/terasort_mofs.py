"""Map output files of a TeraSort job, written in bulk from a seed.

One reduce partition's worth: ``maps`` per-map-sorted runs of 100-byte
records (10-byte uniform key, 90-byte value) in Hadoop IFile framing —
every record ``VInt(10) VInt(90) key value`` = 102 bytes (both VInts are
one byte), the stream closed by the EOF marker ``VInt(-1) VInt(-1)`` =
``ff ff`` — each beside a one-partition spill index (``start, raw
length, part length`` as three big-endian int64). The format is the
reference's (``scripts/regression/run_regression.py:_make_terasort_mofs``
writes the same bytes through the engine's codec); nothing of the
engine is used here.

Keys are drawn as one ``uint64`` and one ``uint16`` column and sorted
per map; values are raw random words; frames are laid out by slicing.
Map ``m`` draws from ``default_rng([seed, m])``, so a map's bytes do not
depend on how many maps there are.
"""

from __future__ import annotations

import os
import struct
from concurrent.futures import ThreadPoolExecutor

import numpy as np

KEY_BYTES, VALUE_BYTES = 10, 90
FRAME_BYTES = 2 + KEY_BYTES + VALUE_BYTES
EOF_MARKER = b"\xff\xff"


def map_ids(job: str, maps: int) -> list:
    return [f"attempt_{job}_m_{m:06d}_0" for m in range(maps)]


def records_of_map(records: int, maps: int, m: int) -> int:
    """``records`` split over ``maps`` as evenly as whole records allow,
    larger maps first."""
    base, extra = divmod(records, maps)
    return base + (m < extra)


def draw_map(seed: int, m: int, n: int) -> np.ndarray:
    """The ``uint8[n, 102]`` frames of map ``m``, sorted by key."""
    rng = np.random.default_rng([seed, m])
    hi = rng.integers(0, 1 << 64, n, dtype=np.uint64)
    lo = rng.integers(0, 1 << 16, n, dtype=np.uint16)
    order = np.lexsort((lo, hi))
    words = -(-n * VALUE_BYTES // 8)
    values = rng.integers(0, 1 << 64, words, dtype=np.uint64).view(np.uint8)
    frames = np.empty((n, FRAME_BYTES), np.uint8)
    frames[:, 0], frames[:, 1] = KEY_BYTES, VALUE_BYTES
    frames[:, 2:10] = hi[order].astype(">u8").view(np.uint8).reshape(n, 8)
    frames[:, 10:12] = lo[order].astype(">u2").view(np.uint8).reshape(n, 2)
    frames[:, 12:] = values[:n * VALUE_BYTES].reshape(n, VALUE_BYTES)
    return frames


def write_map(root: str, job: str, map_id: str, frames: np.ndarray) -> None:
    d = os.path.join(root, job, map_id)
    os.makedirs(d, exist_ok=True)
    with open(os.path.join(d, "file.out"), "wb") as f:
        f.write(frames.data)
        f.write(EOF_MARKER)
    size = frames.size + len(EOF_MARKER)
    with open(os.path.join(d, "file.out.index"), "wb") as f:
        f.write(struct.pack(">qqq", 0, size, size))


def generate(root: str, job: str, seed: int, records: int, maps: int,
             threads: int = 8) -> list:
    """Write the partition's map outputs under ``root``; returns the map
    ids in map order."""
    ids = map_ids(job, maps)

    def one(m: int) -> None:
        write_map(root, job, ids[m],
                  draw_map(seed, m, records_of_map(records, maps, m)))

    with ThreadPoolExecutor(threads) as pool:
        list(pool.map(one, range(maps)))
    return ids
