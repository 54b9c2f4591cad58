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
from uda_tpu.ops.sort import route_engine
from uda_tpu.parallel.distributed import (DistributedSortResult,
                                          distributed_sort_step,
                                          uniform_splitters)
from uda_tpu.parallel.mesh import SHUFFLE_AXIS

__all__ = ["KEY_WORDS", "RECORD_WORDS", "RECORD_BYTES", "teragen",
           "teragen_lanes", "single_chip_sort", "sort_lanes_keys8",
           "distributed_terasort", "validate_sorted"]

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


@partial(jax.jit, static_argnames=("n",))
def teragen_lanes(key: jax.Array, n: int) -> jax.Array:
    """Generate n TeraSort-shaped records directly in the lanes layout
    (uint32[pallas_sort.ROWS, n]): rows 0-2 the big-endian key words
    (pad bytes of row 2 zeroed), rows 3-25 the value words, rows 26-31
    zero (row 31 becomes the sort's stability tie-break). Generating in
    lanes form means the flagship path never pays a transpose."""
    kk, kv = jax.random.split(key)
    keys = jax.random.bits(kk, (KEY_WORDS, n), dtype=jnp.uint32)
    keys = keys.at[2].set(keys[2] & jnp.uint32(0xFFFF0000))
    vals = jax.random.bits(kv, (VALUE_WORDS, n), dtype=jnp.uint32)
    pad = jnp.zeros((pallas_sort.ROWS - RECORD_WORDS, n), jnp.uint32)
    return jnp.concatenate([keys, vals, pad], axis=0)


def _sort_record_cols(cols: tuple, path: str) -> tuple:
    """Stable lexicographic sort of SoA record columns by the first
    KEY_WORDS columns — the single source of truth for every lax.sort
    payload strategy (see bench_step for the trade-offs): "carry" rides
    all columns through the network; the rest compute a narrow-sort
    permutation and apply it with per-column gathers ("gather"), one
    minor-dim gather on the stacked value columns ("gather2"), or
    chunked carry sorts ("carrychunk")."""
    if path == "carry":
        return lax.sort(cols, num_keys=KEY_WORDS, is_stable=True)
    iota = lax.iota(jnp.int32, cols[0].shape[0])
    *sk, perm = lax.sort((*cols[:KEY_WORDS], iota),
                         num_keys=KEY_WORDS, is_stable=True)
    vals = cols[KEY_WORDS:]
    if path == "gather2":
        pay = jnp.take(jnp.stack(vals, axis=0), perm, axis=1,
                       unique_indices=True, mode="clip")
        return (*sk, *(pay[i] for i in range(len(vals))))
    if path == "carrychunk":
        from uda_tpu.ops.sort import apply_perm_chunked

        return (*sk, *apply_perm_chunked(perm, list(vals)))
    return (*sk, *(jnp.take(c, perm, axis=0) for c in vals))


@partial(jax.jit, static_argnames=("path",))
def _single_chip_sort(words: jax.Array, path: str) -> jax.Array:
    cols = tuple(words[:, i] for i in range(words.shape[1]))
    return jnp.stack(_sort_record_cols(cols, path), axis=1)


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
    if path in ("keys8", "keys8f"):
        # keys-only cascade (shared core: pallas_sort.keys8_sort_perm;
        # "keys8f" = the folded half-width variant); sorted keys come
        # back from the cascade, so only the 23 value rows cross the
        # permutation gather
        keyr = jnp.full((KEY_WORDS, m), np.uint32(0xFFFFFFFF), jnp.uint32)
        keyr = lax.dynamic_update_slice(
            keyr, words[:, :KEY_WORDS].T.astype(jnp.uint32), (0, 0))
        sk, perm = pallas_sort.keys8_sort_perm(keyr, tile=tile,
                                               interpret=interpret,
                                               folded=path == "keys8f")
        pay = jnp.take(words[:, KEY_WORDS:].T, perm[:n], axis=1,
                       unique_indices=True, mode="clip")
        return jnp.concatenate([sk[:, :n], pay], axis=0).T
    mat = jnp.full((pallas_sort.ROWS, m), np.uint32(0xFFFFFFFF),
                   jnp.uint32)
    mat = lax.dynamic_update_slice(mat, words.T.astype(jnp.uint32), (0, 0))
    out = pallas_sort.sort_lanes(mat, num_keys=KEY_WORDS, tile=tile,
                                 interpret=interpret,
                                 two_phase=path == "lanes2")
    return pallas_sort.lanes_to_rows(out, w)[:n]


def single_chip_sort(words: jax.Array, path: str = "auto",
                     tile: int = 1024,
                     interpret: bool = False) -> jax.Array:
    """The single-chip shuffle+merge: stable lexicographic sort of whole
    records by their 3 key words (the device replacement of the
    reference's k-way PQ merge, src/Merger/MergeQueue.h:276-427).

    Payload-movement strategy (see bench_step for the full trade-off):
    the lanes engines ("lanes"/"lanes2"/"keys8") run the Pallas
    bitonic pipeline with bounded compile; "carry" rides the 23 value
    words through a ``lax.sort`` network (fast at runtime, but XLA's
    variadic-sort compile time grows superlinearly in operand count —
    the CPU default);
    "gather"/"gather2"/"carrychunk" apply a narrow-sort permutation
    (per-column gathers / one minor-dim gather / chunked carry sorts —
    "carrychunk" is the TPU default via "auto": winner of the fly-off
    of 2026-07-31 on a backend that no longer exists, git history; not
    measured on this machine). "auto" resolves per the ambient
    backend — and the deployed UDA_TPU_SORT_PATH winner — at call time,
    with small batches steered off gather-bound engines
    (ops.sort.route_engine).
    """
    path = route_engine(int(words.shape[0]), path, lanes_ok=True)
    if path in ("lanes", "lanes2", "keys8", "keys8f"):
        if int(words.shape[0]) == 0:
            return jnp.asarray(words, jnp.uint32)
        return _single_chip_sort_lanes(jnp.asarray(words, jnp.uint32),
                                       path, tile, interpret)
    return _single_chip_sort(words, path)


def _keys8_parts(x: jax.Array, tile: int, interpret: bool,
                 folded: bool = False):
    """The keys8 engine: run the ENTIRE bitonic cascade on an 8-row
    keys-only array (one sublane tile: 3 key rows, 4 zero rows, the
    tie-break row) and move the 23 payload rows ONCE with a global
    XLA lane gather by the resulting permutation.

    Rationale (v5e stage profile, scripts/profile_lanes.py): the 32-row
    cascade is VPU-bound — every compare-exchange rolls/selects all 32
    rows, and every merge pass sweeps the full 128 B/record through HBM.
    The keys view cuts both by 4x; the single payload gather is the only
    full-width pass besides generation. Unlike the in-kernel two-phase
    gather (two_phase=True), the global gather is an XLA op — it lowers
    on every backend (scripts/probe_gather.py: no dynamic lane-gather
    formulation lowers in Mosaic on v5e).

    Returns (sorted [KEY_WORDS, n] key rows, gathered [VALUE_WORDS, n]
    payload, int32 permutation). Stability: the tie-break row holds the
    arrival index, so the permutation lists equal keys in arrival order.
    """
    sk, perm = pallas_sort.keys8_sort_perm(x[:KEY_WORDS], tile=tile,
                                           interpret=interpret,
                                           folded=folded)
    payload = jnp.take(x[KEY_WORDS:RECORD_WORDS], perm, axis=1,
                       unique_indices=True, mode="clip")
    return sk, payload, perm


def sort_lanes_keys8(x: jax.Array, tile: int = 1024,
                     interpret: bool = False,
                     folded: bool = False) -> jax.Array:
    """Stable TeraSort record sort in lanes layout via the keys8 engine.

    Drop-in equal to ``pallas_sort.sort_lanes(x, num_keys=KEY_WORDS,
    tile=tile)`` on teragen_lanes-shaped input (layout pad rows zero):
    same [ROWS, n] output, byte-identical including the arrival-index
    row — but the payload crosses HBM once instead of riding every
    compare-exchange stage. ``folded`` selects the half-width cascade
    (ops.pallas_fold; the keys8f engine).
    """
    sk, payload, perm = _keys8_parts(jnp.asarray(x, jnp.uint32), tile,
                                     interpret, folded=folded)
    n = x.shape[1]
    pad = jnp.zeros((pallas_sort.ROWS - RECORD_WORDS - 1, n), jnp.uint32)
    return jnp.concatenate(
        [sk, payload, pad, perm[None, :].astype(jnp.uint32)], axis=0)


def distributed_terasort(words, mesh: Mesh, axis: str = SHUFFLE_AXIS,
                         capacity: Optional[int] = None
                         ) -> DistributedSortResult:
    """Multi-chip TeraSort step over the mesh (BASELINE config 5 shape).

    ``capacity`` defaults to 2x the balanced per-(src,dst) share —
    uniform keys stay far under it; heavy skew should use
    parallel.exchange.shuffle_exchange's multi-round path instead.
    """
    p = int(np.prod(list(mesh.shape.values())))
    n = int(words.shape[0])
    if capacity is None:
        capacity = max(1, (2 * n) // (p * p))
    return distributed_sort_step(words, uniform_splitters(p), mesh, axis,
                                 capacity=capacity, num_keys=KEY_WORDS)


def _checksum_cols(cols) -> jax.Array:
    """Column-tuple form of the multiset fingerprint: distinct odd
    multiplier per column couples words within a row; the outer sum is
    permutation-invariant. Stays in SoA form (no [n, W] materialization
    — keeps the compiled program small)."""
    rec = None
    for c, col in enumerate(cols):
        m = col.astype(jnp.uint32) * jnp.uint32((2 * c + 1) * 2654435761 & 0xFFFFFFFF)
        rec = m if rec is None else rec + m
    return jnp.sum(rec ^ jnp.uint32(0x9E3779B9))


def _violations_cols(k0, k1, k2) -> jax.Array:
    gt = ((k0[:-1] > k0[1:])
          | ((k0[:-1] == k0[1:]) & (k1[:-1] > k1[1:]))
          | ((k0[:-1] == k0[1:]) & (k1[:-1] == k1[1:]) & (k2[:-1] > k2[1:])))
    return jnp.sum(gt.astype(jnp.int32))


@partial(jax.jit, static_argnames=("n", "k", "path", "tile", "interpret",
                                   "chunk_cols"))
def bench_step(seed: jax.Array, n: int, k: int, path: str = "lanes",
               tile: int = 1024, interpret: bool = False,
               chunk_cols: int | None = None):
    """Sustained-throughput benchmark kernel: k independent
    teragen->sort->validate rounds inside ONE device program (one host
    dispatch), so per-call host/RPC latency amortizes away and the
    result reflects device shuffle+merge throughput.

    Records are either 26 separate [n] columns (SoA) or the [32, n]
    lanes layout; nothing materializes an [n, 26] row matrix.

    Four device strategies:

    - ``path="lanes"`` (flagship): records live in the lanes layout and
      the full sort runs in the Pallas bitonic pipeline
      (pallas_sort.sort_lanes). Payload rides every compare-exchange as
      lane moves of the 32-row tile — streaming HBM access, no gathers
      — and compile cost is BOUNDED (two Mosaic kernels total,
      regardless of n and record width).
    - ``path="lanes2"``: the two-phase variant — each network runs on
      an 8-row keys view and the payload moves with one in-kernel lane
      gather (sort_lanes two_phase=True). Mosaic does not lower that
      gather (ops.sort.UNCOMPILED_ENGINES): interpret mode only.
    - ``path="keys8"``: the whole cascade runs on an 8-row keys-only
      array (4x less VPU and HBM work than the 32-row pipeline) and the
      payload moves ONCE via a global XLA lane gather (_keys8_parts) —
      the gather that Mosaic cannot lower in-kernel, hoisted to where
      XLA can.
    - ``path="gather2"``: keys8 with the permutation from the narrow
      4-operand ``lax.sort`` instead of the Pallas cascade (same single
      payload gather). Bounded compile; whichever permutation engine is
      faster on the ambient backend wins bench.py's fly-off.
    - ``path="carrychunk"``: gather-free — the permutation is inverted
      with a 2-operand sort and applied with ceil(23/6) narrow carry
      sorts. Payload moves through sort networks like "carry" but every
      sort stays far below the operand count where compile blows up.
    - ``path="carry"``: the payload rides the ``lax.sort`` network as
      extra operands, but XLA's variadic-sort compile time grows
      superlinearly in operand count (the 26-operand program compiles
      ONCE and persists in the compile cache afterwards).
    - ``path="gather"``: a 4-operand sort (3 key words + iota) computes
      the permutation, then per-column gathers apply it. Runtime is
      gather-bound — random per-element gathers were the slowest
      payload mover by far on the chip (2026-07 runs, git history; not
      measured on this machine), which is what motivated the lanes
      pipeline.

    bench.py times every candidate that compiles and reports the
    fastest.

    Returns (total order violations, input checksum, output checksum):
    consuming the sorted output in-graph keeps XLA from eliminating any
    round, and the caller asserts violations == 0 and checksum equality.
    """
    from uda_tpu.ops.sort import ALL_SORT_PATHS

    if path not in ALL_SORT_PATHS:
        raise ValueError(f"unknown bench path {path!r}")

    def body_keys8(i, acc):
        viol, ck_in, ck_out = acc
        x = teragen_lanes(jax.random.fold_in(seed, i), n)
        ck_in = ck_in + _checksum_cols(tuple(x[r]
                                             for r in range(RECORD_WORDS)))
        s8, payload, _ = _keys8_parts(x, tile, interpret,
                                      folded=path == "keys8f")
        out_cols = (*(s8[r] for r in range(KEY_WORDS)),
                    *(payload[r] for r in range(VALUE_WORDS)))
        ck_out = ck_out + _checksum_cols(out_cols)
        viol = viol + _violations_cols(s8[0], s8[1], s8[2])
        return (viol, ck_in, ck_out)

    def body_carrychunk(i, acc):
        # gather-free payload move (ops.sort.apply_perm_chunked):
        # payload crosses sort networks like "carry", compile stays
        # bounded
        from uda_tpu.ops.sort import apply_perm_chunked

        viol, ck_in, ck_out = acc
        x = teragen_lanes(jax.random.fold_in(seed, i), n)
        ck_in = ck_in + _checksum_cols(tuple(x[r]
                                             for r in range(RECORD_WORDS)))
        iota = lax.iota(jnp.int32, n)
        k0, k1, k2, perm = lax.sort((x[0], x[1], x[2], iota),
                                    num_keys=KEY_WORDS, is_stable=True)
        cols = apply_perm_chunked(
            perm, [x[r] for r in range(KEY_WORDS, RECORD_WORDS)],
            chunk_cols=chunk_cols)
        out_cols = (k0, k1, k2, *cols)
        ck_out = ck_out + _checksum_cols(out_cols)
        viol = viol + _violations_cols(k0, k1, k2)
        return (viol, ck_in, ck_out)

    def body_gather2(i, acc):
        # keys8's XLA-native twin: permutation from the narrow 4-operand
        # lax.sort (XLA's tuned on-chip sort), payload via the same
        # single minor-dim gather — no Pallas in the program at all
        viol, ck_in, ck_out = acc
        x = teragen_lanes(jax.random.fold_in(seed, i), n)
        ck_in = ck_in + _checksum_cols(tuple(x[r]
                                             for r in range(RECORD_WORDS)))
        iota = lax.iota(jnp.int32, n)
        k0, k1, k2, perm = lax.sort((x[0], x[1], x[2], iota),
                                    num_keys=KEY_WORDS, is_stable=True)
        payload = jnp.take(x[KEY_WORDS:RECORD_WORDS], perm, axis=1,
                           unique_indices=True, mode="clip")
        out_cols = (k0, k1, k2,
                    *(payload[r] for r in range(VALUE_WORDS)))
        ck_out = ck_out + _checksum_cols(out_cols)
        viol = viol + _violations_cols(k0, k1, k2)
        return (viol, ck_in, ck_out)

    def body_lanes(i, acc):
        viol, ck_in, ck_out = acc
        x = teragen_lanes(jax.random.fold_in(seed, i), n)
        ck_in = ck_in + _checksum_cols(tuple(x[r]
                                             for r in range(RECORD_WORDS)))
        out = pallas_sort.sort_lanes(x, num_keys=KEY_WORDS, tile=tile,
                                     interpret=interpret,
                                     two_phase=path == "lanes2")
        ck_out = ck_out + _checksum_cols(tuple(out[r]
                                               for r in range(RECORD_WORDS)))
        viol = viol + _violations_cols(out[0], out[1], out[2])
        return (viol, ck_in, ck_out)

    def body_cols(i, acc):
        viol, ck_in, ck_out = acc
        w = teragen(jax.random.fold_in(seed, i), n)
        cols = tuple(w[:, c] for c in range(RECORD_WORDS))
        ck_in = ck_in + _checksum_cols(cols)
        out = _sort_record_cols(cols, path)
        ck_out = ck_out + _checksum_cols(out)
        viol = viol + _violations_cols(out[0], out[1], out[2])
        return (viol, ck_in, ck_out)

    zero = jnp.uint32(0)
    body = {"lanes": body_lanes, "lanes2": body_lanes,
            "keys8": body_keys8, "keys8f": body_keys8,
            "gather2": body_gather2,
            "carrychunk": body_carrychunk}.get(path, body_cols)
    return lax.fori_loop(0, k, body, (jnp.int32(0), zero, zero))


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
    """Order-independent multiset fingerprint over row-matrix records —
    the same formula as _checksum_cols (a DISTINCT odd multiplier per
    column couples a word to its column position, so torn records and
    column swaps change the sum; the outer sum over records is
    permutation-invariant), so validate_sorted and bench_step agree."""
    return _checksum_cols(tuple(words[:, c] for c in range(words.shape[1])))


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
