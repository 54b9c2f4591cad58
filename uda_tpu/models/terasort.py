"""TeraSort: the flagship workload (BASELINE.json configs 2 and 5).

The reference's headline benchmark is TeraSort on a Hadoop+UDA cluster
(reference scripts/regression/executeTerasort.sh, analizeTerasort.sh):
10-byte keys, 90-byte values, shuffle+merge dominated. Here the whole
shuffle+merge is device-resident:

- records live as uint32[n, 26] rows: columns 0-2 the big-endian packed
  key (10 bytes + 2 constant pad bytes), columns 3-25 the 90-byte value
  (last 2 bytes pad);
- single-chip "merge": one stable lexicographic sort over the 3 key
  columns (uda_tpu.ops.sort semantics, fixed-width keys need no
  length/rank columns);
- multi-chip: the fused partition -> all_to_all -> local-sort step
  (uda_tpu.parallel.distributed), whose concatenated shards are the
  globally sorted dataset.

TeraGen-equivalent data is generated ON DEVICE (jax PRNG) — the host
never touches record bytes, mirroring how the real deployment stages
records into HBM once and keeps them there.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from uda_tpu.ops import pallas_sort
from uda_tpu.ops.sort import resolve_sort_path
from uda_tpu.parallel.distributed import (DistributedSortResult,
                                          distributed_sort_step,
                                          uniform_splitters)
from uda_tpu.parallel.mesh import SHUFFLE_AXIS

__all__ = ["KEY_WORDS", "RECORD_WORDS", "RECORD_BYTES", "teragen",
           "single_chip_sort", "distributed_terasort", "validate_sorted"]

KEY_WORDS = 3        # 10 key bytes -> 3 BE words (2 pad bytes, constant 0)
VALUE_WORDS = 23     # 90 value bytes -> 23 words (2 pad bytes)
RECORD_WORDS = KEY_WORDS + VALUE_WORDS
RECORD_BYTES = 100   # logical TeraSort record size


@partial(jax.jit, static_argnames=("n",))
def teragen(key: jax.Array, n: int) -> jax.Array:
    """Generate n TeraSort-shaped records on device.

    Keys are uniform random (the TeraGen keyspace); the 2 pad bytes of
    word 2 are zeroed so fixed-width memcmp order == 3-word lexicographic
    order. Values carry random payload bits.
    """
    kk, kv = jax.random.split(key)
    keys = jax.random.bits(kk, (n, KEY_WORDS), dtype=jnp.uint32)
    keys = keys.at[:, 2].set(keys[:, 2] & jnp.uint32(0xFFFF0000))
    vals = jax.random.bits(kv, (n, VALUE_WORDS), dtype=jnp.uint32)
    return jnp.concatenate([keys, vals], axis=1)


@jax.jit
def _single_chip_sort(words: jax.Array) -> jax.Array:
    cols = tuple(words[:, i] for i in range(words.shape[1]))
    return jnp.stack(lax.sort(cols, num_keys=KEY_WORDS, is_stable=True),
                     axis=1)


@partial(jax.jit, static_argnames=("path", "tile", "interpret"))
def _single_chip_sort_lanes(words: jax.Array, path: str, tile: int,
                            interpret: bool) -> jax.Array:
    """Lanes-engine body of single_chip_sort: pad the record count to a
    power-of-two multiple of ``tile`` with +inf-key lanes and run the
    Pallas pipeline. Padding lanes sit PAST every real lane, so even a
    real record whose keys are all 0xFFFFFFFF sorts before them (the
    tile-sort kernel's arrival-index tie-break is the lane index, and
    padding occupies the highest lanes); truncating to n drops exactly
    the padding."""
    n, w = words.shape
    m, tile = pallas_sort.pad_pow2(n, tile)
    if path == "keys8":
        # keys-only cascade (shared core: pallas_sort.keys8_sort_perm);
        # sorted keys come back from the cascade, so only the 23 value
        # rows cross the permutation gather
        keyr = jnp.full((KEY_WORDS, m), np.uint32(0xFFFFFFFF), jnp.uint32)
        keyr = lax.dynamic_update_slice(
            keyr, words[:, :KEY_WORDS].T.astype(jnp.uint32), (0, 0))
        sk, perm = pallas_sort.keys8_sort_perm(keyr, tile=tile,
                                               interpret=interpret)
        pay = jnp.take(words[:, KEY_WORDS:].T, perm[:n], axis=1,
                       unique_indices=True, mode="clip")
        return jnp.concatenate([sk[:, :n], pay], axis=0).T
    mat = jnp.full((pallas_sort.ROWS, m), np.uint32(0xFFFFFFFF),
                   jnp.uint32)
    mat = lax.dynamic_update_slice(mat, words.T.astype(jnp.uint32), (0, 0))
    out = pallas_sort.sort_lanes(mat, num_keys=KEY_WORDS, tile=tile,
                                 interpret=interpret)
    return pallas_sort.lanes_to_rows(out, w)[:n]


def single_chip_sort(words: jax.Array, path: str = "auto",
                     tile: int = 1024,
                     interpret: bool = False) -> jax.Array:
    """The single-chip shuffle+merge: stable lexicographic sort of whole
    records by their 3 key words (the device replacement of the
    reference's k-way PQ merge, src/Merger/MergeQueue.h:276-427).

    ``path`` is one of ops.sort.SORT_PATHS or "auto"
    (ops.sort.resolve_sort_path, at call time: "carry" on a CPU, "lanes"
    on a TPU). "lanes" and "keys8" run the Pallas bitonic pipeline with
    bounded compile; "carry" rides the 23 value words through a
    ``lax.sort`` network, whose compile time XLA grows superlinearly in
    operand count. The output is byte-identical across the three.
    """
    path = resolve_sort_path(path)
    if path == "carry":
        return _single_chip_sort(words)
    if int(words.shape[0]) == 0:
        return jnp.asarray(words, jnp.uint32)
    return _single_chip_sort_lanes(jnp.asarray(words, jnp.uint32), path,
                                   tile, interpret)


def distributed_terasort(words, mesh: Mesh, axis: str = SHUFFLE_AXIS,
                         capacity: Optional[int] = None,
                         splitters: str = "uniform"
                         ) -> DistributedSortResult:
    """Multi-chip TeraSort step over the mesh (BASELINE config 5 shape).

    ``splitters`` is the job's statement about its keys, the sort
    benchmark's two categories: ``"uniform"`` (Indy: TeraGen's keys,
    equal ranges of the keyspace balance the shards) or ``"sampled"``
    (Daytona: nothing assumed — ids, words, duplicates, shared
    prefixes; the step samples its own input on the device and
    partitions by the sample's quantiles,
    parallel/distributed.py:distributed_sort_step with
    ``splitters=None``). Either way the partition compares whole keys,
    equal keys leave in input order, and ``res.splitters`` says which
    range each shard holds.

    ``capacity`` defaults to 2x the balanced per-(src,dst) share. A
    bucket that overflows it — uniform splitters on skewed keys, or a
    key too hot for one window — sends the step through the windowed
    rounds, same splitters, same result: any distribution completes.
    """
    if splitters not in ("uniform", "sampled"):
        raise ValueError(f"unknown splitters {splitters!r}")
    p = int(np.prod(list(mesh.shape.values())))
    n = int(words.shape[0])
    if capacity is None:
        capacity = max(1, (2 * n) // (p * p))
    return distributed_sort_step(
        words, uniform_splitters(p) if splitters == "uniform" else None,
        mesh, axis, capacity=capacity, num_keys=KEY_WORDS)


@jax.jit
def _order_violations(words: jax.Array) -> jax.Array:
    """Count adjacent out-of-order key pairs on device (0 == sorted)."""
    a = words[:-1, :KEY_WORDS]
    b = words[1:, :KEY_WORDS]
    gt = ((a[:, 0] > b[:, 0])
          | ((a[:, 0] == b[:, 0]) & (a[:, 1] > b[:, 1]))
          | ((a[:, 0] == b[:, 0]) & (a[:, 1] == b[:, 1])
             & (a[:, 2] > b[:, 2])))
    return jnp.sum(gt.astype(jnp.int32))


@jax.jit
def _checksum(words: jax.Array) -> jax.Array:
    """Order-independent multiset fingerprint over row-matrix records: a
    DISTINCT odd multiplier per column couples a word to its column
    position, so torn records and column swaps change the sum; the
    outer sum over records is permutation-invariant. Column by column
    (no second [n, W] array — keeps the compiled program small)."""
    rec = None
    for c in range(words.shape[1]):
        m = words[:, c].astype(jnp.uint32) * jnp.uint32(
            (2 * c + 1) * 2654435761 & 0xFFFFFFFF)
        rec = m if rec is None else rec + m
    return jnp.sum(rec ^ jnp.uint32(0x9E3779B9))


def validate_sorted(sorted_words, input_words=None,
                    valid_count: Optional[int] = None) -> None:
    """Sort-validity gate (the TeraSort validity check of the reference's
    regression harness, scripts/regression/terasortAnallizer.sh):
    order violations == 0, and when the input is given, the record
    multiset is preserved (device checksum)."""
    sw = sorted_words if valid_count is None else sorted_words[:valid_count]
    violations = int(_order_violations(sw))
    if violations:
        raise AssertionError(f"{violations} adjacent order violations")
    if input_words is not None:
        if int(_checksum(sw)) != int(_checksum(input_words)):
            raise AssertionError("record multiset changed during sort")
