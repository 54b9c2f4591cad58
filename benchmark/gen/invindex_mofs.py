"""Map output files of an inverted-index job, written in bulk from a seed.

One reduce partition's worth of what ``uda_tpu/models/inverted_index.py``'s
mapper emits with no combiner: one ``<word, posting>`` record for every
word occurrence, ``maps`` per-map-sorted runs in Hadoop IFile framing.
The key is an ``org.apache.hadoop.io.Text`` (``VInt(len)`` + the word's
bytes), the value the posting ``struct.pack(">II", doc_id, pos)``, so a
record frames as ``VInt(1 + len) VInt(8) VInt(len) word posting`` =
11 + len bytes, every VInt one byte (len <= 48); the stream is closed by
the EOF marker ``ff ff`` and sits beside a one-partition spill index, as
``terasort_mofs`` writes it. Nothing of the engine is used here.

**The vocabulary** is a pure function of the term id (the same for every
seed: the language; a seed draws the occurrences). ``K = 2^20`` ids;
``mix`` is the splitmix64 finaliser; letters are lowercase ASCII,
letter ``k`` of stream ``x`` is ``'a' + mix(x * 64 + k) % 26``.

- an ordinary id has ``5 + floor(log2(id)) // 3 + mix(id) % 3`` letters
  (5-13: short for frequent ids, longer down the tail) of its own
  stream;
- the ids from 4,096 up fall in blocks of eight (``g = id >> 3``), and
  the block is a LONG block when ``mix(g ^ LONG_SALT) % 100 == 0``.
  Its eight terms share a 16-letter stem (stream ``g ^ STEM_SALT``):
  ``r = id & 7`` in 0-2 is ``stem + 'a'+r + own letters`` of 18-48
  bytes (equal first 16 bytes, different after: ordered by the whole
  content only), 3 is ``stem + 'd'`` (17 bytes: one past the carried
  width), 4 is ``stem + 'z' + block letters`` of 18-32 bytes and 5 is
  term 4 plus 1-16 letters more (a proper prefix among oversize keys),
  6 is the stem itself (16 bytes: exactly the carried width, and a
  proper prefix of the six above) and 7 the stem's first 5-12 letters.
  Terms 0-5 are longer than 16 bytes: 0.29 % of a partition's records.

**The occurrences**: map ``m`` draws from ``default_rng([seed, m])``, so
a map's bytes do not depend on how many maps there are. Term ids follow
Zipf's law with s = 1 over the K ids by the inverse CDF of its
continuous form, ``id = floor(K ** u)`` for uniform ``u``
(``gen/device_records_zipf.py``'s form): ``P(id) = log2(1 + 1/id) /
20`` — id 1 is 5.0 % of the records, every octave of ids 5 %. Record
``i`` of a map (in draw order, the order of the text) is word ``i %
250`` of document ``(m << 16) | (i // 250)``; the map's records are
then sorted under the Text comparator, stably, so equal words stay in
document order.
"""

from __future__ import annotations

import functools
from concurrent.futures import ThreadPoolExecutor
from typing import NamedTuple

import numpy as np

# the map ids, the split over maps, the file and its spill index are
# the TeraSort generator's: one layout on disk for every reduce cell
from benchmark.gen.terasort_mofs import (EOF_MARKER, map_ids, records_of_map,
                                         write_map)

RANKS_LOG2 = 20
K = 1 << RANKS_LOG2
MIN_BYTES, MAX_BYTES = 5, 48
CARRIED = 16                  # the stem: uda.tpu.key.width's default
VALUE_BYTES = 8
FRAME_EXTRA = 3 + VALUE_BYTES   # VInt(key) VInt(value) VInt(len) + value
WORDS_PER_DOC = 250
LONG_FROM = 1 << 12           # no long block among the 4,095 hottest ids
LONG_SALT = np.uint64(0x9E3779B97F4A7C15)
STEM_SALT = np.uint64(0xD1B54A32D192ED03)


class Partition(NamedTuple):
    """What ``generate`` wrote: the map ids in map order, and the bytes
    a task that fetches them delivers."""
    map_ids: list
    records: int
    frame_bytes: int          # the framed records, EOF markers left out

    @property
    def file_bytes(self) -> int:
        """The map output files, as the supplier sizes the partition."""
        return self.frame_bytes + len(self.map_ids) * len(EOF_MARKER)

    @property
    def payload_bytes(self) -> int:
        """Serialized keys and values: a frame less its two VInts."""
        return self.frame_bytes - 2 * self.records


def _mix(x: np.ndarray) -> np.ndarray:
    """splitmix64's finaliser over ``uint64`` (wraps, as it should)."""
    x = x.astype(np.uint64, copy=True)
    x ^= x >> np.uint64(30)
    x *= np.uint64(0xBF58476D1CE4E5B9)
    x ^= x >> np.uint64(27)
    x *= np.uint64(0x94D049BB133111EB)
    x ^= x >> np.uint64(31)
    return x


def _letters(stream: np.ndarray, count: int) -> np.ndarray:
    """``uint8[len(stream), count]``: the first letters of each stream."""
    k = np.arange(count, dtype=np.uint64)[None, :]
    at = stream.astype(np.uint64)[:, None] * np.uint64(64) + k
    return (_mix(at) % np.uint64(26)).astype(np.uint8) + np.uint8(ord("a"))


class Vocabulary:
    """Every term's bytes (``table``, zero past ``lens``), its length and
    its dense rank under the Text comparator (equal bytes, equal rank)."""

    def __init__(self):
        ids = np.arange(K, dtype=np.uint64)
        h = _mix(ids)
        octave = np.zeros(K, np.int64)
        octave[1:] = np.floor(np.log2(ids[1:].astype(np.float64)))
        lens = MIN_BYTES + octave // 3 + (h % np.uint64(3)).astype(np.int64)
        table = np.zeros((K, MAX_BYTES), np.uint8)
        short = int(lens.max())
        table[:, :short] = _letters(ids, short)

        block = ids >> np.uint64(3)
        long_ids = np.flatnonzero(
            (ids >= LONG_FROM)
            & (_mix(block ^ LONG_SALT) % np.uint64(100) == 0))
        g, r, hl = block[long_ids], (long_ids & 7), h[long_ids]
        own = _letters(ids[long_ids], MAX_BYTES)
        shared = _letters(g ^ STEM_SALT, MAX_BYTES)
        term4 = 18 + (_mix(g) % np.uint64(15)).astype(np.int64)
        llen = np.select(
            [r <= 2, r == 3, r == 4, r == 5, r == 6],
            [18 + (hl % np.uint64(31)).astype(np.int64), CARRIED + 1, term4,
             term4 + 1 + (hl % np.uint64(16)).astype(np.int64), CARRIED],
            default=MIN_BYTES + (hl % np.uint64(8)).astype(np.int64))
        col = np.arange(MAX_BYTES)[None, :]
        rows = np.where(col < CARRIED, shared, own)
        rows[:, CARRIED] = np.where(r <= 3, ord("a") + r, ord("z"))
        of_block = (r[:, None] >= 4) & (col > CARRIED) & (col < term4[:, None])
        rows = np.where(of_block, shared, rows)
        table[long_ids] = rows
        lens[long_ids] = llen
        table[col >= lens[:, None]] = 0
        self.table, self.lens = table, lens
        # the comparator's order: content bytes, shorter-is-smaller. No
        # letter is 0, so zero-padded bytes alone order the terms
        words = table.view(">u8")
        order = np.lexsort(tuple(words[:, c] for c in range(5, -1, -1)))
        ranked = table[order]
        new = np.ones(K, bool)
        new[1:] = (ranked[1:] != ranked[:-1]).any(axis=1)
        self.rank = np.empty(K, np.int64)
        self.rank[order] = np.cumsum(new) - 1


@functools.lru_cache(maxsize=1)
def vocabulary() -> Vocabulary:
    """Built once a process (a second of numpy), on first use."""
    return Vocabulary()


def draw_terms(seed: int, m: int, n: int) -> np.ndarray:
    """The term ids of map ``m``'s ``n`` records, in draw order."""
    u = np.random.default_rng([seed, m]).random(n)
    return np.clip(np.floor(np.exp2(RANKS_LOG2 * u)), 1, K - 1) \
        .astype(np.int64)


def draw_map(seed: int, m: int, n: int) -> np.ndarray:
    """The ``uint8`` frames of map ``m``, sorted under the Text
    comparator, equal words in draw order."""
    voc = vocabulary()
    ids = draw_terms(seed, m, n)
    order = np.argsort(voc.rank[ids], kind="stable")
    ids = ids[order]
    lens = voc.lens[ids]
    start = np.zeros(n, np.int64)
    np.cumsum(lens[:-1] + FRAME_EXTRA, out=start[1:])
    out = np.empty(int(lens.sum()) + n * FRAME_EXTRA, np.uint8)
    out[start], out[start + 1], out[start + 2] = lens + 1, VALUE_BYTES, lens
    col = np.arange(MAX_BYTES)[None, :]
    inside = col < lens[:, None]
    out[(start[:, None] + 3 + col)[inside]] = voc.table[ids][inside]
    posting = np.empty((n, 2), ">u4")
    posting[:, 0] = (m << 16) | (order // WORDS_PER_DOC)
    posting[:, 1] = order % WORDS_PER_DOC
    at = (start + 3 + lens)[:, None] + np.arange(VALUE_BYTES)[None, :]
    out[at] = posting.view(np.uint8).reshape(n, VALUE_BYTES)
    return out


def generate(root: str, job: str, seed: int, records: int, maps: int,
             threads: int = 8) -> Partition:
    """Write the partition's map outputs under ``root``."""
    ids = map_ids(job, maps)
    vocabulary()                     # once, not raced for by the pool

    def one(m: int) -> int:
        frames = draw_map(seed, m, records_of_map(records, maps, m))
        write_map(root, job, ids[m], frames)
        return frames.size

    with ThreadPoolExecutor(threads) as pool:
        frame_bytes = sum(pool.map(one, range(maps)))
    return Partition(ids, records, frame_bytes)
