"""The plain reference of a Text-keyed reduce task whose map outputs are
block-compressed (SnappyCodec): inflate every map output on its own,
then order exactly as ``host_sort_text`` orders the uncompressed job.
Independent of the engine and of the generator: it reads the block
framing and the spill index itself and inflates through its own
``ctypes`` binding of the host's ``libsnappy``.

A map output is a run of blocks ``[4 B big-endian raw length][4 B
big-endian compressed length][Snappy raw format]``; every header is
checked against the bytes it describes (the compressed length lies
inside the file, the block inflates to exactly the raw length, no
block is empty or larger than the codec's buffer) and the file against
its index (``raw_length`` = the inflated bytes, ``part_length`` = the
file). The
inflated map outputs — IFile streams, EOF marker included, what the
uncompressed configuration's files are — are written beside each other
in a temporary directory and sorted by ``host_sort_text.sorted_stream``:
one order for the two configurations.
"""

from __future__ import annotations

import ctypes
import ctypes.util
import functools
import os
import struct
import tempfile
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmark.reference import host_sort_text
from benchmark.reference.host_sort_text import (ReferenceError, Sorted,  # noqa: F401
                                                compare)

CODEC_BUFFER = 262144         # io.compression.codec.snappy.buffersize
BLOCK_HEADER = struct.Struct(">II")   # raw length, compressed length
INDEX = struct.Struct(">qqq")         # start, raw length, part length


@functools.lru_cache(maxsize=1)
def _snappy():
    path = ctypes.util.find_library("snappy") or "libsnappy.so.1"
    lib = ctypes.CDLL(path)
    lib.snappy_uncompress.restype = ctypes.c_int
    lib.snappy_uncompress.argtypes = [
        ctypes.c_void_p, ctypes.c_size_t, ctypes.c_void_p,
        ctypes.POINTER(ctypes.c_size_t)]
    return lib


def inflate_file(path: str) -> np.ndarray:
    """One map output inflated block by block: the IFile stream as
    ``uint8``, EOF marker included."""
    lib = _snappy()
    try:
        with open(path + ".index", "rb") as f:
            start, raw_length, part_length = INDEX.unpack(f.read())
    except (OSError, struct.error) as e:
        raise ReferenceError(f"{path}.index: {e}") from e
    data = np.fromfile(path, np.uint8)
    if start != 0 or part_length != data.size:
        raise ReferenceError(f"{path}: the index says ({start}, "
                             f"{part_length}) for a file of {data.size}")
    out = np.empty(raw_length, np.uint8)
    pos = filled = 0
    while pos < data.size:
        if pos + BLOCK_HEADER.size > data.size:
            raise ReferenceError(f"{path}: a block header cut short at {pos}")
        raw_len, comp_len = BLOCK_HEADER.unpack_from(data, pos)
        pos += BLOCK_HEADER.size
        if not 0 < raw_len <= CODEC_BUFFER or comp_len == 0 \
                or pos + comp_len > data.size \
                or filled + raw_len > raw_length:
            raise ReferenceError(
                f"{path}: block header ({raw_len}, {comp_len}) at "
                f"{pos - BLOCK_HEADER.size} does not fit the file or "
                f"the index")
        body = data[pos:pos + comp_len]
        # room for raw_len bytes and no more: a block that holds more
        # is refused by the codec, one that holds fewer by the count
        size = ctypes.c_size_t(raw_len)
        rc = lib.snappy_uncompress(body.ctypes.data, comp_len,
                                   out[filled:].ctypes.data,
                                   ctypes.byref(size))
        if rc != 0 or size.value != raw_len:
            raise ReferenceError(f"{path}: the block at {pos} does not "
                                 f"inflate to {raw_len} bytes ({rc})")
        pos += comp_len
        filled += raw_len
    if filled != raw_length:
        raise ReferenceError(f"{path}: {filled} bytes inflated, the index "
                             f"says {raw_length}")
    return out


def sorted_stream(root: str, job: str, map_ids: list,
                  threads: int = 8) -> Sorted:
    """Equal keys keep arrival order (map, then row)."""
    _snappy()
    with tempfile.TemporaryDirectory(prefix="uda_inflated_") as plain:

        def one(m: str) -> None:
            d = os.path.join(plain, job, m)
            os.makedirs(d)
            inflate_file(os.path.join(root, job, m, "file.out")) \
                .tofile(os.path.join(d, "file.out"))

        with ThreadPoolExecutor(threads) as pool:
            list(pool.map(one, map_ids))
        return host_sort_text.sorted_stream(plain, job, map_ids, threads)
