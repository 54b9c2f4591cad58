"""``invindex_mofs``'s map outputs as a job with map-output compression
on writes them: ``mapreduce.map.output.compress`` = true,
``mapreduce.map.output.compress.codec`` =
``org.apache.hadoop.io.compress.SnappyCodec``.

A map's records are byte for byte ``invindex_mofs.draw_map(seed, m,
n)``'s. The IFile stream — the frames and the EOF marker ``ff ff`` — is
cut into blocks as Hadoop's ``BlockCompressorStream`` cuts it for
SnappyCodec: ``io.compression.codec.snappy.buffersize`` = 262,144, of
which ``buffersize / 6 + 32`` is kept back for the codec's worst case,
so a block holds at most 218,422 raw bytes; each block is written as
``[4 B big-endian raw length][4 B big-endian compressed length][the
block in Snappy's raw format]``, one compressed chunk a block. The
spill index holds ``(0, raw_length, part_length)``: the stream's bytes
before and after compression, now different.

Snappy comes from the host's ``libsnappy`` through this module's own
``ctypes`` binding (the C API of ``snappy-c.h``); nothing of the engine
is used here.
"""

from __future__ import annotations

import ctypes
import ctypes.util
import functools
import os
import struct
from concurrent.futures import ThreadPoolExecutor
from typing import NamedTuple

import numpy as np

from benchmark.gen.invindex_mofs import draw_map, vocabulary
from benchmark.gen.terasort_mofs import EOF_MARKER, map_ids, records_of_map

CODEC_BUFFER = 262144         # io.compression.codec.snappy.buffersize
BLOCK_RAW_MAX = CODEC_BUFFER - (CODEC_BUFFER // 6 + 32)     # 218,422
BLOCK_HEADER = struct.Struct(">II")   # raw length, compressed length


class Partition(NamedTuple):
    """What ``generate`` wrote. ``frame_bytes``, ``file_bytes`` and
    ``payload_bytes`` are ``invindex_mofs.Partition``'s, in uncompressed
    bytes, so the cell's goodput counts what ``reduce_invindex``'s
    does."""
    map_ids: list
    records: int
    frame_bytes: int          # the framed records, EOF markers left out
    wire_bytes: int           # the files as written: sum of part_length
    blocks: int               # compressed blocks in all

    @property
    def file_bytes(self) -> int:
        """The map outputs' raw_length summed, as the supplier sizes
        the partition."""
        return self.frame_bytes + len(self.map_ids) * len(EOF_MARKER)

    @property
    def payload_bytes(self) -> int:
        """Serialized keys and values: a frame less its two VInts."""
        return self.frame_bytes - 2 * self.records


@functools.lru_cache(maxsize=1)
def _snappy():
    path = ctypes.util.find_library("snappy") or "libsnappy.so.1"
    lib = ctypes.CDLL(path)
    lib.snappy_compress.restype = ctypes.c_int
    lib.snappy_compress.argtypes = [
        ctypes.c_void_p, ctypes.c_size_t, ctypes.c_void_p,
        ctypes.POINTER(ctypes.c_size_t)]
    lib.snappy_max_compressed_length.restype = ctypes.c_size_t
    lib.snappy_max_compressed_length.argtypes = [ctypes.c_size_t]
    return lib


def compress_stream(raw: np.ndarray) -> tuple:
    """``(the block-compressed stream, its block count)`` of one map
    output's bytes (``uint8``, C-contiguous, EOF marker included)."""
    lib = _snappy()
    room = lib.snappy_max_compressed_length(BLOCK_RAW_MAX)
    scratch = ctypes.create_string_buffer(room)
    out = bytearray()
    blocks = 0
    for lo in range(0, raw.size, BLOCK_RAW_MAX):
        block = raw[lo:lo + BLOCK_RAW_MAX]
        size = ctypes.c_size_t(room)
        rc = lib.snappy_compress(block.ctypes.data, block.size, scratch,
                                 ctypes.byref(size))
        if rc != 0:
            raise RuntimeError(f"snappy_compress returned {rc}")
        out += BLOCK_HEADER.pack(block.size, size.value)
        out += scratch[:size.value]
        blocks += 1
    return bytes(out), blocks


def write_map(root: str, job: str, map_id: str, frames: np.ndarray) -> tuple:
    """Write one compressed map output and its index; returns its
    ``(part_length, blocks)``."""
    raw = np.concatenate([frames, np.frombuffer(EOF_MARKER, np.uint8)])
    stream, blocks = compress_stream(raw)
    d = os.path.join(root, job, map_id)
    os.makedirs(d, exist_ok=True)
    with open(os.path.join(d, "file.out"), "wb") as f:
        f.write(stream)
    with open(os.path.join(d, "file.out.index"), "wb") as f:
        f.write(struct.pack(">qqq", 0, raw.size, len(stream)))
    return len(stream), blocks


def generate(root: str, job: str, seed: int, records: int, maps: int,
             threads: int = 8) -> Partition:
    """Write the partition's compressed map outputs under ``root``."""
    ids = map_ids(job, maps)
    vocabulary()                     # once, not raced for by the pool
    _snappy()

    def one(m: int) -> tuple:
        frames = draw_map(seed, m, records_of_map(records, maps, m))
        return (frames.size,) + write_map(root, job, ids[m], frames)

    with ThreadPoolExecutor(threads) as pool:
        sizes = list(pool.map(one, range(maps)))
    frame_bytes, wire_bytes, blocks = (sum(c) for c in zip(*sizes))
    return Partition(ids, records, frame_bytes, wire_bytes, blocks)
