"""Compression path: block codecs + decompressing fetch client.

Equivalent of the reference's decompression input clients (reference
src/Merger/DecompressorWrapper.cc, LzoDecompressor.cc,
SnappyDecompressor.cc): map outputs may be block-compressed; the fetch
path pulls *compressed* bytes and decompresses on the fly in front of
the merge, behind the same InputClient interface the plain transport
implements (DecompressorWrapper.cc:80-114). Codec shared objects are
loaded at runtime with dlopen/dlsym exactly like the reference
(LzoDecompressor.cc:83-127 ``liblzo2.so``; SnappyDecompressor.cc:42-51
``libsnappy.so``), and gated on availability; zlib (Hadoop's
DefaultCodec) is always available through Python's zlib.

Block framing: each block is ``[4B BE uncompressed_len][4B BE
compressed_len][compressed bytes]`` — the (compressedLen,
uncompressedLen) block-header shape the reference's ``doDecompress``
consumes (DecompressorWrapper.cc:168-197). A segment's ``raw_length``
(index) is the total uncompressed size, ``part_length`` the on-disk
compressed size, matching Hadoop's spill index semantics.
"""

from __future__ import annotations

import ctypes
import ctypes.util
import struct
import threading
import time
import zlib
from typing import Callable, Dict, Optional

from uda_tpu.merger.segment import InputClient
from uda_tpu.mofserver.data_engine import FetchResult, ShuffleRequest
from uda_tpu.utils.errors import CompressionError, StorageError
from uda_tpu.utils.failpoints import failpoint
from uda_tpu.utils.logging import get_logger
from uda_tpu.utils.metrics import metrics

__all__ = ["Codec", "get_codec", "register_codec", "compress_block_stream",
           "decompress_block_stream", "DecompressingClient",
           "BLOCK_HEADER"]

log = get_logger()

BLOCK_HEADER = struct.Struct(">II")  # (uncompressed_len, compressed_len)

# one completion's counters go in with ONE locked update (add_keyed)
_K_INFLATE = metrics.timer_series("fetch_inflate")
_K_BYTES = metrics.series("decompress.bytes")
_K_BLOCKS = metrics.series("decompress.blocks")
_K_WIRE = metrics.series("decompress.wire_bytes")
_K_CARRY = metrics.series("decompress.carry_bytes")


class Codec:
    def __init__(self, name: str,
                 compress: Callable[[bytes], bytes],
                 decompress: Callable[[bytes, int], bytes]):
        self.name = name
        self.compress = compress
        self.decompress = decompress  # (data, uncompressed_len) -> bytes


def _zlib_codec() -> Codec:
    def decompress(data: bytes, uncompressed_len: int) -> bytes:
        out = zlib.decompress(data)
        # enforce the block header's length claim like the snappy codec
        # does — a corrupt header must fail at the block, not surface
        # later as a confusing record-framing error
        if len(out) != uncompressed_len:
            raise CompressionError(
                f"zlib length mismatch: {len(out)} != {uncompressed_len}")
        return out

    return Codec("zlib", lambda b: zlib.compress(b, 6), decompress)


_snappy_lock = threading.Lock()
_snappy_lib = None


def _load_snappy():
    """dlopen/dlsym libsnappy like the reference (SnappyDecompressor.cc:
    42-51); raises CompressionError when the library is absent."""
    global _snappy_lib
    with _snappy_lock:
        if _snappy_lib is not None:
            return _snappy_lib
        path = ctypes.util.find_library("snappy")
        if not path:
            raise CompressionError("libsnappy.so not found")
        lib = ctypes.CDLL(path)
        lib.snappy_compress.restype = ctypes.c_int
        lib.snappy_compress.argtypes = [ctypes.c_char_p, ctypes.c_size_t,
                                        ctypes.c_char_p,
                                        ctypes.POINTER(ctypes.c_size_t)]
        lib.snappy_uncompress.restype = ctypes.c_int
        lib.snappy_uncompress.argtypes = [ctypes.c_char_p, ctypes.c_size_t,
                                          ctypes.c_char_p,
                                          ctypes.POINTER(ctypes.c_size_t)]
        lib.snappy_max_compressed_length.restype = ctypes.c_size_t
        lib.snappy_max_compressed_length.argtypes = [ctypes.c_size_t]
        _snappy_lib = lib
        return lib


def _snappy_codec() -> Codec:
    lib = _load_snappy()

    def compress(data: bytes) -> bytes:
        out_len = ctypes.c_size_t(lib.snappy_max_compressed_length(len(data)))
        out = ctypes.create_string_buffer(out_len.value)
        rc = lib.snappy_compress(data, len(data), out, ctypes.byref(out_len))
        if rc != 0:
            raise CompressionError(f"snappy_compress failed: {rc}")
        return out.raw[: out_len.value]

    def decompress(data: bytes, uncompressed_len: int) -> bytes:
        out_len = ctypes.c_size_t(uncompressed_len)
        out = ctypes.create_string_buffer(max(uncompressed_len, 1))
        rc = lib.snappy_uncompress(data, len(data), out, ctypes.byref(out_len))
        if rc != 0:
            raise CompressionError(f"snappy_uncompress failed: {rc}")
        if out_len.value != uncompressed_len:
            raise CompressionError(
                f"snappy length mismatch: {out_len.value} != {uncompressed_len}")
        return out.raw[: out_len.value]

    return Codec("snappy", compress, decompress)


def _lzo_codec() -> Codec:
    from uda_tpu.compress.lzo import lzo_codec

    return lzo_codec()


# codec class-name registry: the createInputClient dispatch of reference
# reducer.cc:412-450 (Lzo/Snappy by Java class name; Default = zlib)
_REGISTRY: Dict[str, Callable[[], Codec]] = {
    "org.apache.hadoop.io.compress.DefaultCodec": _zlib_codec,
    "zlib": _zlib_codec,
    "org.apache.hadoop.io.compress.SnappyCodec": _snappy_codec,
    "snappy": _snappy_codec,
    "com.hadoop.compression.lzo.LzoCodec": _lzo_codec,
    "com.hadoop.compression.lzo.LzopCodec": _lzo_codec,
    "lzo": _lzo_codec,
}


def register_codec(class_name: str, factory: Callable[[], Codec]) -> None:
    _REGISTRY[class_name] = factory


def get_codec(class_name: str) -> Codec:
    factory = _REGISTRY.get(class_name)
    if factory is None:
        raise CompressionError(
            f"unsupported codec class for native merge: {class_name}")
    return factory()


def compress_block_stream(data: bytes, codec: Codec,
                          block_size: int = 256 * 1024) -> bytes:
    """Frame ``data`` as compressed blocks (see module docstring)."""
    out = bytearray()
    for start in range(0, len(data), block_size):
        raw = data[start:start + block_size]
        comp = codec.compress(raw)
        out += BLOCK_HEADER.pack(len(raw), len(comp))
        out += comp
    return bytes(out)


def _inflate_whole(data: bytes, codec: Codec) -> tuple:
    """-> (uncompressed bytes, blocks) of a whole block stream."""
    out = bytearray()
    pos = blocks = 0
    while pos < len(data):
        if pos + BLOCK_HEADER.size > len(data):
            raise CompressionError("truncated block header")
        raw_len, comp_len = BLOCK_HEADER.unpack_from(data, pos)
        pos += BLOCK_HEADER.size
        if pos + comp_len > len(data):
            raise CompressionError("truncated block body")
        out += codec.decompress(bytes(data[pos:pos + comp_len]), raw_len)
        pos += comp_len
        blocks += 1
    return bytes(out), blocks


def decompress_block_stream(data: bytes, codec: Codec) -> bytes:
    """Inverse of compress_block_stream (whole-buffer convenience)."""
    return _inflate_whole(data, codec)[0]


def _timed_inflate(span, fn, *args) -> tuple:
    """``fn(*args)`` inside the fetch_inflate timer -> (its result, the
    seconds the caller still has to book). With spans on it is
    metrics.timer under ``span`` — a span outside the task's trace is
    one critpath never sees — which books its own seconds; with spans
    off two stamps, and the seconds ride the caller's one locked
    counter update."""
    if metrics.record_spans:
        with metrics.use_span(span), metrics.timer("fetch_inflate"):
            return fn(*args), 0.0
    t0 = time.perf_counter()
    out = fn(*args)
    return out, time.perf_counter() - t0


class _StreamState:
    """Sequential decompression state for one partition fetch.

    ``mu`` serializes attempt issue and chunk ingest per stream;
    ``token`` identifies the stream's CURRENT fetch attempt, so a
    completion from a superseded attempt (the segment's per-attempt
    timeout fired and it re-issued) can never mutate state the new
    attempt depends on."""

    __slots__ = ("comp_offset", "carry", "delivered", "part_length",
                 "mu", "token")

    def __init__(self) -> None:
        self.comp_offset = 0
        self.carry = b""
        self.delivered = 0
        self.part_length: Optional[int] = None
        self.mu = threading.Lock()
        self.token: Optional[object] = None


class DecompressingClient(InputClient):
    """Wraps a transport, decompressing block streams on the fly —
    the DecompressorWrapper contract (same InputClient interface in
    front of the merge, compressed bytes on the wire).

    Segments fetch sequentially from offset 0; requests carry
    *uncompressed-domain* offsets while the inner fetches advance in the
    compressed domain; a partial trailing block is carried to the next
    chunk (the reference's handleNextRdmaFetch memmove of the partial
    block tail, DecompressorWrapper.cc:199-235).
    """

    def __init__(self, inner: InputClient, codec: Codec,
                 comp_chunk_size: Optional[int] = None):
        """``comp_chunk_size``: size of the compressed-domain inner
        fetches — the `ratio` share of each buffer pair that the
        reference dedicates to wire-compressed bytes (calculateMemPool,
        reducer.cc:453-496, conf mapred.rdma.compression.buffer.ratio).
        Defaults to the caller's uncompressed chunk size."""
        self.inner = inner
        self.codec = codec
        self.comp_chunk_size = comp_chunk_size
        self._streams: dict[tuple, _StreamState] = {}
        self._lock = threading.Lock()
        # a compressed task that inflated nothing reads 0, where a
        # program without these counters reads nothing
        metrics.add_keyed((_K_BYTES, 0.0), (_K_BLOCKS, 0.0), (_K_WIRE, 0.0),
                          (_K_CARRY, 0.0))
        metrics.add("decompress.fetches", 0)
        metrics.declare_timer("fetch_inflate")

    def estimate_partition_bytes(self, job_id: str, map_ids,
                                 reduce_id: int):
        """Forward to the wrapped transport: its estimate sums the
        spill index's raw_length (uncompressed record bytes), which is
        the domain this client delivers in — so the auto merge-approach
        policy sees real sizes for compressed jobs too."""
        return self.inner.estimate_partition_bytes(job_id, map_ids,
                                                   reduce_id)

    def resume_ok(self, host: str = "") -> bool:
        """Never resumable: an inner transport error pops the stream
        state (clean slate), so a mid-partition continuation would hit
        the non-sequential guard — the whole-segment restart IS this
        wrapper's recovery contract."""
        return False

    def speculate_ok(self) -> bool:
        """Never duplicate-safe: start_fetch claims the partition's
        sequential stream token, so a concurrent duplicate for the
        same (job, map, reduce) would steal it and fail the healthy
        attempt's completion as stale — fabricating a fault against a
        supplier that was merely slow."""
        return False

    def recover_partition(self, req, ctx, on_complete) -> bool:
        """k-of-n reconstruction BELOW the decompression (the stripe
        codes the on-disk/compressed bytes — uda_tpu.coding's
        byte-agnostic contract): delegate to the inner transport and
        decompress the rebuilt partition on the way up, delivering the
        same uncompressed domain a fetched stream would."""
        span = metrics.current_span()   # the recovering segment's

        def _done(res) -> None:
            if not isinstance(res, Exception):
                try:
                    (out, blocks), secs = _timed_inflate(
                        span, _inflate_whole, bytes(res.data), self.codec)
                    metrics.add_keyed(
                        (_K_INFLATE, secs), (_K_BYTES, len(out)),
                        (_K_BLOCKS, blocks), (_K_WIRE, len(res.data)))
                    res = FetchResult(out, len(out), res.part_length,
                                      0, res.path, last=True)
                except Exception as e:  # noqa: BLE001 - a corrupt
                    # reconstruction must surface as the segment's
                    # terminal error, not crash the recovery thread
                    res = e
            on_complete(res)

        return self.inner.recover_partition(req, ctx, _done)

    def start_fetch(self, req: ShuffleRequest, on_complete) -> None:
        key = (req.job_id, req.map_id, req.reduce_id)
        tok = object()
        with self._lock:
            st = self._streams.get(key)
            # new stream, or a restart after progress (a retrying
            # segment); NOT a continuation at offset 0 that simply
            # hasn't produced a complete block yet
            if st is None or (req.offset == 0 and st.delivered != 0):
                st = _StreamState()
                self._streams[key] = st
        with st.mu:
            # claim the stream for THIS attempt; any still-in-flight
            # older attempt's completion is now stale by token. The
            # ordering is safe either way: if that completion wins the
            # mutex first it ingests (it was still the owner) and this
            # attempt sees the advanced state below; if this claim wins,
            # the old completion is dropped without touching the state.
            st.token = tok
            err = None
            if req.offset != st.delivered:
                err = CompressionError(
                    f"non-sequential compressed fetch at {req.offset} "
                    f"(expected {st.delivered})")
            comp_offset = st.comp_offset
        if err is not None:
            on_complete(err)  # outside st.mu: the segment may re-issue
            return            # from this callback (same thread)
        inner_req = ShuffleRequest(req.job_id, req.map_id, req.reduce_id,
                                   comp_offset,
                                   self.comp_chunk_size or req.chunk_size,
                                   host=req.host)
        # the fetching segment's span (it issues under it): the parent
        # of this completion's fetch_inflate span, since the completion
        # thread has no ambient context
        span = metrics.current_span()
        metrics.add("decompress.fetches")

        def _done(res) -> None:
            # decide + mutate under st.mu, deliver after releasing it
            # (the segment chains its next fetch from this callback on
            # the same thread — holding st.mu across it would deadlock)
            with st.mu:
                with self._lock:
                    stale = (st.token is not tok
                             or self._streams.get(key) is not st)
                if stale:
                    # a superseded attempt must neither mutate nor pop
                    # the current owner's state; the segment's epoch
                    # guard drops this delivery as stale
                    res = CompressionError(
                        "stale compressed fetch completion "
                        "(attempt superseded)")
                elif isinstance(res, Exception):
                    with self._lock:
                        self._streams.pop(key, None)  # clean slate
                else:
                    crc = getattr(res, "crc", None)
                    if crc is not None and \
                            zlib.crc32(res.data) & 0xFFFFFFFF != crc:
                        # wire-domain integrity (uda.tpu.fetch.crc): the
                        # CRC covers the COMPRESSED chunk, so it must be
                        # validated here, not on the decompressed result;
                        # the segment recovers via whole-segment retry,
                        # which resets this stream cleanly
                        with self._lock:
                            self._streams.pop(key, None)
                        res = StorageError(
                            f"compressed chunk CRC mismatch at "
                            f"{req.map_id}:{res.offset}")
                    else:
                        try:
                            res = self._ingest(key, st, req, res, span)
                        except Exception as e:  # noqa: BLE001 - to segment
                            with self._lock:
                                self._streams.pop(key, None)
                            res = e
            on_complete(res)

        self.inner.start_fetch(inner_req, _done)

    def _ingest(self, key, st: _StreamState, req: ShuffleRequest,
                res: FetchResult, span) -> FetchResult:
        """st.mu held: one inner completion inflated, under the
        fetch_inflate timer as Segment._ingest's crack is under
        fetch_crack — its seconds and the completion's counters in one
        locked update."""
        wire = len(res.data)
        (out, blocks), secs = _timed_inflate(span, self._inflate, key, st,
                                             req, res)
        metrics.add_keyed((_K_INFLATE, secs), (_K_BYTES, len(out.data)),
                          (_K_BLOCKS, blocks), (_K_WIRE, wire),
                          (_K_CARRY, len(st.carry)))
        return out

    def _inflate(self, key, st: _StreamState, req: ShuffleRequest,
                 res: FetchResult) -> tuple:
        """-> (the uncompressed-domain FetchResult, blocks inflated)."""
        st.part_length = res.part_length
        st.comp_offset = res.offset + len(res.data)
        data = st.carry + res.data
        out = bytearray()
        pos = blocks = 0
        while pos + BLOCK_HEADER.size <= len(data):
            raw_len, comp_len = BLOCK_HEADER.unpack_from(data, pos)
            if pos + BLOCK_HEADER.size + comp_len > len(data):
                break
            body = bytes(data[pos + BLOCK_HEADER.size:
                              pos + BLOCK_HEADER.size + comp_len])
            # injectable per decoded block (keyed "<map>@<wire offset>"):
            # a decompress fault mid-pipeline must surface as this
            # stream's terminal error and drain the stage pool cleanly
            failpoint("decompress.block",
                      key=f"{req.map_id}@{res.offset}")
            out += self.codec.decompress(body, raw_len)
            pos += BLOCK_HEADER.size + comp_len
            blocks += 1
        st.carry = bytes(data[pos:])
        comp_done = st.comp_offset >= (st.part_length or 0)
        if comp_done and st.carry:
            raise CompressionError(
                f"{len(st.carry)} trailing bytes after last block")
        offset = st.delivered
        st.delivered += len(out)
        # uncompressed raw_length: exact once the compressed stream ends,
        # otherwise "more than delivered" so is_last stays False
        raw_length = st.delivered if comp_done else st.delivered + 1
        if comp_done:
            with self._lock:
                self._streams.pop(key, None)
        return FetchResult(bytes(out), raw_length, res.part_length,
                           offset, res.path, last=comp_done), blocks

    def stop(self) -> None:
        self.inner.stop()
