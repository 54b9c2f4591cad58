"""Framed emission: sorted records -> consumer blocks via the staging
arena.

The single place that implements the dataFromUda hand-off contract
(reference src/Merger/MergeManager.cc:155-182 + UdaPlugin.java:368-402):
records are IFile-framed into staging buffers of at most the configured
block size and handed to the consumer one filled block at a time, the
final block carrying the EOF marker. Both the online and the hybrid RPQ
paths emit through here (one framing implementation, no drift).

The staging buffers come from a 2-slot BufferArena — the reference's
2 x 1 MB KV staging pool (NETLEV_KV_POOL_EXPO, reference
src/include/NetlevComm.h:33, spawn_reduce_task reducer.cc:303-324). The
consumer receives a read-only memoryview of the slot, valid only for the
duration of the call (exactly the DirectByteBuffer contract: the Java
side copies out during dataFromUda); the double-buffering lets a
pipelined consumer still hold the previous block while the next fills.
"""

from __future__ import annotations

import io
from typing import Callable, Iterable, Optional, Tuple

from uda_tpu import native
from uda_tpu.merger.arena import BufferArena
from uda_tpu.utils.ifile import IFileWriter, RecordBatch
from uda_tpu.utils.metrics import metrics

__all__ = ["FramedEmitter", "emit_framed_records", "NUM_STAGE_BUFFERS"]

NUM_STAGE_BUFFERS = 2  # reference NUM_STAGE_MEM / 2x1MB kv pool

# records framed per native pass in emit_batch: bounds the transient
# framed-bytes copy to a few MB regardless of merge size
FRAME_CHUNK_RECORDS = 1 << 16


class FramedEmitter:
    """Reusable emitter bound to one arena + block size."""

    def __init__(self, block_size: int,
                 arena: Optional[BufferArena] = None):
        self.block_size = block_size
        self.arena = arena or BufferArena(NUM_STAGE_BUFFERS, block_size)

    def _stage(self, piece: bytes, held: list) -> memoryview:
        """Copy one <= block_size piece into an arena slot, releasing
        the previous slot one call late (double-buffer: a pipelined
        consumer may still hold the prior block). Callers time it under
        ``emit_deliver``."""
        slot = self.arena.acquire()
        held.append(slot)
        slot.write(piece)
        if len(held) > 1:
            self.arena.release(held.pop(0))
        return slot.view().data.toreadonly()

    @staticmethod
    def _hand_over(view: memoryview,
                   consumer: Callable[[memoryview], None]) -> int:
        """The consumer up-call, alone under the ``emit`` timer."""
        with metrics.timer("emit"):
            consumer(view)
        return len(view)

    def _deliver(self, piece: bytes, held: list,
                 consumer: Callable[[memoryview], None]) -> int:
        """Hand one <= block_size piece to the consumer through an arena
        slot."""
        with metrics.timer("emit_deliver"):
            view = self._stage(piece, held)
        return self._hand_over(view, consumer)

    def emit(self, records: Iterable[Tuple[bytes, bytes]],
             consumer: Callable[[memoryview], None]) -> int:
        """Frame ``records`` and stream to ``consumer``; returns bytes
        emitted. The memoryview passed to the consumer is only valid
        during the call."""
        out = io.BytesIO()
        writer = IFileWriter(out)
        total = 0
        held: list = []  # acquired slots not yet released (<= 2)

        def flush() -> None:
            nonlocal total
            block = out.getvalue()
            out.seek(0)
            out.truncate()
            # a single oversized record may exceed the block size; split
            # across as many consumer calls as needed (each <= block_size)
            for start in range(0, len(block), self.block_size):
                total += self._deliver(block[start:start + self.block_size],
                                       held, consumer)

        try:
            for key, value in records:
                writer.append(key, value)
                if out.tell() >= self.block_size:
                    flush()
            writer.close()  # EOF marker
            if out.tell():
                flush()
        finally:
            # a consumer exception must not strand slots: the arena is
            # task-lifetime (a leaked slot deadlocks the next emit)
            for slot in held:
                self.arena.release(slot)
        metrics.add("emit.bytes", total)
        return total

    def emit_framed(self, pieces: Iterable[bytes],
                    consumer: Callable[[memoryview], None]) -> int:
        """Stream an already-framed record stream (``pieces`` concatenate
        to the complete IFile stream INCLUDING the EOF marker) to the
        consumer in exactly-block_size slices. The stream concatenation
        contract is identical to emit(); blocks are not record-aligned,
        which emit() already allows for oversized records. Feeds both
        emit_batch (native chunk framing) and the native RPQ merge
        (uda_tpu.native.kway_merge_paths)."""
        total = 0
        held: list = []
        buf = bytearray()

        def next_block() -> memoryview:
            # everything between two consumer calls that is not the
            # making of a piece: slice, slot copy, remainder memmove
            with metrics.timer("emit_deliver"):
                view = self._stage(bytes(buf[:self.block_size]), held)
                del buf[:self.block_size]
            return view

        try:
            for piece in pieces:
                with metrics.timer("emit_deliver"):
                    buf += piece
                while len(buf) >= self.block_size:
                    total += self._hand_over(next_block(), consumer)
            while buf:
                total += self._hand_over(next_block(), consumer)
        finally:
            for slot in held:
                self.arena.release(slot)
        metrics.add("emit.bytes", total)
        return total

    def emit_batch(self, batch: RecordBatch,
                   consumer: Callable[[memoryview], None]) -> int:
        """Bulk emission of a RecordBatch: records are framed in native
        chunk passes (uda_tpu.native.frame_batch — the C++ twin of the
        reference's write_kv_to_stream hot loop, StreamRW.cc:151-225)
        instead of a per-record Python loop, then streamed through
        emit_framed."""
        def chunks():
            it = native.iter_framed_chunks(batch, FRAME_CHUNK_RECORDS,
                                           write_eof=True)
            while True:
                with metrics.timer("emit_frame"):
                    piece = next(it, None)
                if piece is None:
                    return
                yield piece

        return self.emit_framed(chunks(), consumer)


def emit_framed_records(records: Iterable[Tuple[bytes, bytes]],
                        block_size: int,
                        consumer: Callable[[memoryview], None]) -> int:
    """One-shot convenience wrapper."""
    return FramedEmitter(block_size).emit(records, consumer)
