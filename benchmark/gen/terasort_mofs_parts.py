"""Map output files of a TeraSort job with several reduce partitions,
written in bulk from a seed.

What ``terasort_mofs`` writes for one partition, for ``PARTITIONS``
of them: every map output file holds the partitions back to back, each
a per-map-sorted run of 102-byte IFile frames closed by its own EOF
marker ``ff ff``, beside a spill index of one ``(start, raw length,
part length)`` triple of big-endian int64 per partition, in partition
order. A TeraSort reduce task owns a key range (its total-order
partitioner cuts the key space at sampled splitters): partition ``p``'s
keys are uniform in the ``p``-th quarter of the key space, so the top
two bits of a key name its partition and a record that crosses between
reduce tasks shows in the reference comparison.

Every partition holds exactly ``records`` records, split over the maps
as ``terasort_mofs.records_of_map`` splits them. Map ``m`` partition
``p`` draws from ``default_rng([seed, m, p])``: its bytes depend on
neither the number of maps nor the other partitions. Nothing of the
engine is used here.
"""

from __future__ import annotations

import os
import struct
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmark.gen.terasort_mofs import (EOF_MARKER, FRAME_BYTES, KEY_BYTES,
                                         VALUE_BYTES, map_ids,
                                         records_of_map)

PARTITIONS = 4
_RANGE_BITS = 62                     # 64 key bits less the two that name
#                                      the partition


def draw_part(seed: int, m: int, p: int, n: int) -> np.ndarray:
    """The ``uint8[n, 102]`` frames of map ``m``'s partition ``p``,
    sorted by key; every key lies in the ``p``-th quarter of the key
    space."""
    rng = np.random.default_rng([seed, m, p])
    hi = rng.integers(0, 1 << _RANGE_BITS, n, dtype=np.uint64)
    hi += np.uint64(p << _RANGE_BITS)
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


def write_map(root: str, job: str, map_id: str, parts: list) -> None:
    """One map output file: the partitions' frames in partition order,
    each closed by the EOF marker, and its spill index."""
    d = os.path.join(root, job, map_id)
    os.makedirs(d, exist_ok=True)
    index, start = [], 0
    with open(os.path.join(d, "file.out"), "wb") as f:
        for frames in parts:
            f.write(frames.data)
            f.write(EOF_MARKER)
            size = frames.size + len(EOF_MARKER)
            index.append(struct.pack(">qqq", start, size, size))
            start += size
    with open(os.path.join(d, "file.out.index"), "wb") as f:
        f.write(b"".join(index))


def generate(root: str, job: str, seed: int, records: int, maps: int,
             threads: int = 8) -> list:
    """Write the job's map outputs under ``root``: ``records`` records
    in each of the ``PARTITIONS`` partitions; returns the map ids in map
    order."""
    ids = map_ids(job, maps)

    def one(m: int) -> None:
        n = records_of_map(records, maps, m)
        write_map(root, job, ids[m],
                  [draw_part(seed, m, p, n) for p in range(PARTITIONS)])

    with ThreadPoolExecutor(threads) as pool:
        list(pool.map(one, range(maps)))
    return ids
