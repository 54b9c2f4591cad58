"""Pallas full-record sort in the records-as-lanes layout.

The device-native replacement for the reference's whole merge pipeline
(reference src/Merger/MergeQueue.h:276-427 k-way PQ + StreamRW record
walk) built for how a TPU actually wants to touch memory:

- **Layout**: records are COLUMNS of a ``uint32[32, n]`` matrix ("lanes
  layout"): row r holds word r of every record, record i lives in lane
  i. Rows 0..num_keys-1 are the big-endian key words; one row is the
  stability tie-break (global arrival index, written by the tile-sort
  kernel); remaining rows are payload. Why: in ``[32, n]`` every
  compare-exchange is a lane-axis shift applied to all 32 rows at
  once, and every DMA window is lane-aligned (the Mosaic rule that
  rejects ``[n, 26]`` slicing). (HBM footprint is NOT the reason: on
  the v5e, libtpu 0.0.34, a ``uint32[n, 26]`` array is itself stored
  long-dimension-minor at 128 B/record — columns padded 26 -> 32 —
  and ``[n, 7]`` at 32 B/record; chip_smoke.py, 2026-09-26.)
- **Tile sort** (`_tile_sort_kernel`): a full bitonic sorting network
  over T lanes in VMEM; static strides lower to lane rotates. Tiles are
  emitted ASCENDING or DESCENDING by tile-index parity — the classic
  bitonic trick that makes every later merge input (asc ++ desc)
  bitonic *as stored*, so no kernel ever reverses data.
- **Merge passes** (`_merge_pass_kernel`): log2(n/T) passes; pass ℓ
  merges adjacent run pairs of length L into runs of 2L whose direction
  again alternates (the final pass emits ascending). Per output tile, a
  vectorized XLA binary search (merge-path) finds the pair diagonal;
  the kernel DMAs one lane-ALIGNED superwindow per side, aligns with a
  dynamic lane roll, masks out-of-window lanes to +inf positioned so
  the concatenation stays bitonic (ascending A with +inf tail, then
  +inf front on the stored-descending B window), and runs one
  log2(2T)-stage bitonic merge network in the tile's output direction.

Stability: the tie-break row makes all sort keys distinct, so the
(unstable) bitonic networks reproduce stable arrival order exactly.

``sort_lanes`` builds the whole pipeline (1 tile-sort + log2(n/T)
merge passes) in one traced, jit-compatible function.
``merge_lanes_runs`` is the pipeline's merge-only entry: input that is
ALREADY R sorted runs (what a chip receives when every sender sorted
before the exchange) is packed the way pass log2(run/T) would have
found it and takes the last log2(R) passes alone — no tile sort, no
pass below the run length. Unlike the
operand-carry ``lax.sort`` (whose TPU compile time grows superlinearly
in operand count, uda_tpu.ops.sort.SORT_PATHS), every kernel
here has a fixed small operand surface, so compile cost is bounded
regardless of record width.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["ROWS", "sort_lanes", "merge_lanes_runs", "rows_to_lanes",
           "lanes_to_rows", "keys8_sort_perm", "pad_pow2", "TB_ROW_DEFAULT"]

ROWS = 32               # sublane-padded row count of the lanes layout
TB_ROW_DEFAULT = 31     # default tie-break row (last)
_INF = np.uint32(0xFFFFFFFF)  # numpy scalar: kernels bake it in as a
                              # literal (a traced jnp constant would be
                              # rejected by pallas_call as a capture)
_LANE = 128             # TPU lane width: DMA lane offsets must be multiples


def rows_to_lanes(words, rows: int = ROWS):
    """[n, W] row-matrix records -> [rows, n] lanes layout (zero-padded
    rows). One transpose pass; prefer generating directly in lanes
    layout where possible."""
    w = jnp.asarray(words, jnp.uint32)
    n, cols = w.shape
    if cols > rows:
        raise ValueError(f"{cols} record words > {rows} layout rows")
    out = jnp.zeros((rows, n), jnp.uint32)
    return lax.dynamic_update_slice(out, w.T, (0, 0))


def lanes_to_rows(lanes, num_words: int):
    """[rows, n] lanes layout -> [n, num_words] row matrix."""
    return jnp.asarray(lanes)[:num_words, :].T


def _lex_lt(a_rows, b_rows):
    """Lexicographic a < b over equal-length lists of uint32 arrays."""
    lt = jnp.zeros(jnp.broadcast_shapes(a_rows[0].shape, b_rows[0].shape),
                   jnp.bool_)
    eq = jnp.ones(lt.shape, jnp.bool_)
    for a, b in zip(a_rows, b_rows):
        lt = lt | (eq & (a < b))
        eq = eq & (a == b)
    return lt


def _cmp_exchange(x, j: int, asc_mask, key_rows_idx):
    """One compare-exchange stage at static lane stride j.

    ``asc_mask``: [1, T] bool — True where the surrounding block sorts
    ascending. Lane i pairs with i^j; the "low" lane of a pair has bit
    j clear, so i+j never crosses a block boundary and the cyclic rolls
    never pair across a wrap (the wrapped values land on lanes whose
    mask points the other way)."""
    T = x.shape[1]
    idx = lax.broadcasted_iota(jnp.int32, (1, T), 1)
    low = (idx & j) == 0
    left = jnp.roll(x, -j, axis=1)   # lane i <- value of lane i+j
    right = jnp.roll(x, j, axis=1)   # lane i <- value of lane i-j
    other = jnp.where(low, left, right)
    lt = _lex_lt([x[r] for r in key_rows_idx],
                 [other[r] for r in key_rows_idx])[None, :]
    # this position should hold the pair minimum iff (ascending block)
    # == (low position); keep self iff that wish matches self<other
    # (keys are strictly ordered thanks to the tie-break row)
    take_min_here = asc_mask == low
    keep_self = take_min_here == lt
    return jnp.where(keep_self, x, other)


def _tile_sort_kernel(x_ref, o_ref, *, tile, num_keys, tb_row, alternate):
    t = pl.program_id(0)
    x = x_ref[...]
    lane = lax.broadcasted_iota(jnp.int32, (1, tile), 1)
    # stability: global arrival index into the tie-break row
    gidx = (lane + t * tile).astype(jnp.uint32)
    x = jnp.where(lax.broadcasted_iota(jnp.int32, x.shape, 0) == tb_row,
                  jnp.broadcast_to(gidx, x.shape), x)
    # whole-tile direction alternates by parity so merge inputs are
    # bitonic as stored (single-tile arrays sort ascending)
    if alternate:
        tile_asc = jnp.broadcast_to((t % 2) == 0, (1, tile))
    else:
        tile_asc = jnp.broadcast_to(jnp.bool_(True), (1, tile))

    key_rows_idx = list(range(num_keys)) + [tb_row]
    k = 2
    while k <= tile:
        if k == tile:
            asc = tile_asc
        else:
            # standard bitonic direction per k-block, flipped wholesale
            # for descending tiles
            asc = ((lane & k) == 0) == tile_asc
        j = k // 2
        while j >= 1:
            x = _cmp_exchange(x, j, asc, key_rows_idx)
            j //= 2
        k *= 2
    o_ref[...] = x


def _uint32_struct(shape, x):
    """uint32 out_shape struct carrying ``x``'s shard_map varying-manual-
    axes set, so the Pallas pipelines work as-is inside distributed
    shard_map bodies (the set is empty outside one)."""
    return jax.ShapeDtypeStruct(shape, jnp.uint32, vma=jax.typeof(x).vma)


@partial(jax.jit, static_argnames=("tile", "num_keys", "tb_row",
                                   "alternate", "interpret"))
def _tile_sort(x, tile: int, num_keys: int, tb_row: int, alternate: bool,
               interpret: bool = False):
    rows, n = x.shape
    return pl.pallas_call(
        partial(_tile_sort_kernel, tile=tile, num_keys=num_keys,
                tb_row=tb_row, alternate=alternate),
        grid=(n // tile,),
        in_specs=[pl.BlockSpec((rows, tile), lambda t: (0, t))],
        out_specs=pl.BlockSpec((rows, tile), lambda t: (0, t)),
        # vma propagates the caller's shard_map varying-axes set, so the
        # pipeline works as-is inside distributed shard_map bodies
        out_shape=_uint32_struct((rows, n), x),
        interpret=interpret,
    )(x)


def _pass_splits(x, run_len, final, tile: int, num_keys: int, tb_row: int):
    """Merge-path windows for one pass, in XLA.

    ``run_len`` (= L) and ``final`` may be TRACED scalars: every
    pass-dependent quantity is computed here and handed to the kernel as
    data, so ONE compiled kernel serves every pass (and the pass loop
    can be a ``lax.fori_loop``) — the whole pipeline costs two Mosaic
    kernel compiles regardless of n.

    Rank bookkeeping: per output tile, d_eff is the pair-local diagonal
    in ASCENDING rank space — for descending-output tiles the tile's
    ranks are [2L - d_local - T, 2L - d_local), counted from the top —
    and i0 is the number of A-run records among the first d_eff merged
    records (vectorized merge-path binary search). B is the
    stored-DESCENDING run read through its logical ascending view
    B'[m] = B[L-1-m]; ties go to A (arrival order) which the strict
    tie-break ordering decides naturally.

    Returns int32[num_tiles, 8] rows
    (a_blk, shift_a, thr_a, b_blk, shift_b, thr_b, out_asc, 0):
    per side an aligned superwindow start (in lane-block units), the
    non-negative cyclic lane shift in [0, win) that places the wanted
    first record at lane 0, and the invalid-lane threshold
    (A: lanes >= thr_a are past the run end; B: lanes < thr_b are below
    B'[j0]); see _merge_pass_kernel for how they are applied.
    """
    rows, n = x.shape
    L = jnp.asarray(run_len, jnp.int32)
    final = jnp.asarray(final, jnp.bool_)
    num_tiles = n // tile
    win = tile + _LANE
    t = jnp.arange(num_tiles, dtype=jnp.int32)
    pair = (t * tile) // (2 * L)
    d_local = t * tile - pair * 2 * L
    out_asc = final | ((pair % 2) == 0)
    d_eff = jnp.where(out_asc, d_local, 2 * L - (d_local + tile))
    a_base = pair * 2 * L
    b_base = a_base + L
    key_rows_idx = list(range(num_keys)) + [tb_row]

    def key_at(global_idx):
        return [x[r, global_idx] for r in key_rows_idx]

    lo = jnp.maximum(0, d_eff - L)
    hi = jnp.minimum(d_eff, L)
    # under shard_map's strict vma typing the carry must ENTER the loop
    # varying over the same manual axes it EXITS with: the body compares
    # against x (device-varying), so (lo, hi) become varying after one
    # iteration while their iota/run_len-derived inits are replicated.
    # pcast the inits to x's vma (a no-op outside shard_map, where vma
    # is empty) — this is what lets the distributed sort run the lanes
    # engines with check_vma=True (see parallel/distributed._sort_step)
    vma = tuple(sorted(jax.typeof(x).vma))
    lo, hi = lax.pcast((lo, hi), vma, to="varying")

    def body(_, carry):
        lo, hi = carry
        mid = (lo + hi + 1) // 2          # candidate: A-records taken
        j = d_eff - mid                   # B'-records taken
        a_idx = a_base + jnp.clip(mid - 1, 0, L - 1)
        b_idx = b_base + jnp.clip(L - 1 - j, 0, L - 1)  # B'[j] stored lane
        a_le_b = ~_lex_lt(key_at(b_idx), key_at(a_idx))
        ok = (mid <= 0) | (j >= L) | a_le_b
        lo = jnp.where(ok, mid, lo)
        hi = jnp.where(ok, hi, mid - 1)
        return lo, hi

    bits = max(2, int(np.log2(n)) + 2)    # covers any L <= n/2
    i0, _ = lax.fori_loop(0, bits, body, (lo, hi))
    j0 = d_eff - i0

    # ---- A: records [i0, i0+tile) of the ascending run ----
    a_start = a_base + i0
    a_align = jnp.minimum((a_start // _LANE) * _LANE, n - win)
    roll_a = a_start - a_align
    thr_a = L - i0                        # lanes >= thr_a: past run end
    # ---- B: stored lanes holding B'[j0+tile-1] ... B'[j0] ----
    # unclamped start b_base + L - j0 - tile undershoots b_base by
    # inv = max(0, j0 + tile - L); read from the clamped start and roll
    # RIGHT by inv so position r holds B'[j0 + tile - 1 - r] for r>=inv
    # and the first inv lanes are masked (+inf front)
    inv = jnp.maximum(0, j0 + tile - L)
    b_clamp = b_base + jnp.maximum(0, L - j0 - tile)
    b_align = jnp.minimum((b_clamp // _LANE) * _LANE, n - win)
    roll_b = inv - (b_clamp - b_align)
    # aligned starts ship as LANE-BLOCK indices; the kernel multiplies
    # by _LANE so Mosaic can statically prove the HBM slice offset is
    # lane-divisible (a raw traced offset fails its divisibility check).
    # Roll amounts are normalized to [0, win): hardware pltpu.roll
    # miscomputes NEGATIVE dynamic shifts (interpret mode is fine), so
    # only non-negative cyclic shifts may reach the kernel.
    shift_a = jnp.mod(-roll_a, win)
    shift_b = jnp.mod(roll_b, win)
    cols = [a_align // _LANE, shift_a, thr_a, b_align // _LANE, shift_b, inv,
            out_asc.astype(jnp.int32), jnp.zeros_like(a_align)]
    return jnp.stack([c.astype(jnp.int32) for c in cols], axis=1)


def _merge_pass_kernel(splits_ref, splits_nxt_ref, x_hbm, o_ref, a_bufs,
                       b_bufs, sem_a, sem_b, *, tile, num_keys, tb_row,
                       split_blk):
    """One output tile of one merge pass (see _pass_splits for the rank
    bookkeeping; every pass-dependent scalar arrives via splits_ref, so
    this kernel compiles once and serves all log2(n/tile) passes).

    DMA double buffering: the windows for tile t+1 (whose aligned starts
    arrive via splits_nxt_ref, the splits table shifted by one row) are
    DMA'd into the other scratch slot WHILE tile t's merge network runs,
    so HBM latency overlaps compute across sequential grid steps.

    Window construction: each side DMAs a lane-aligned superwindow of
    tile+128 lanes (align floor-clamped so it never leaves the array),
    then one dynamic cyclic roll places the wanted first record at lane
    0. Out-of-window lanes are masked to +inf *positionally* so the
    concatenation stays bitonic:

      [ A: ascending, +inf tail ] ++ [ B: +inf front, descending ]

    (ascending -> +inf plateau -> descending = bitonic). The +inf lanes
    always land in the discarded half of the merge: smallest-T taken
    for ascending output, largest-T (positions [T, 2T) of the
    descending-direction network) for descending output."""
    rows = a_bufs.shape[1]
    t = pl.program_id(0)
    nt = pl.num_programs(0)
    s = t % split_blk                    # this tile's row in the block
    slot = t % 2
    win = tile + _LANE

    def issue(spl, slot):
        a_cp = pltpu.make_async_copy(
            x_hbm.at[:, pl.ds(spl[s, 0] * _LANE, win)], a_bufs.at[slot],
            sem_a.at[slot])
        b_cp = pltpu.make_async_copy(
            x_hbm.at[:, pl.ds(spl[s, 3] * _LANE, win)], b_bufs.at[slot],
            sem_b.at[slot])
        a_cp.start()
        b_cp.start()

    @pl.when(t == 0)
    def _():
        issue(splits_ref, 0)

    @pl.when(t + 1 < nt)
    def _():
        issue(splits_nxt_ref, (t + 1) % 2)

    # wait for this tile's windows (issued at t-1, or just above for t=0)
    pltpu.make_async_copy(x_hbm.at[:, pl.ds(0, win)], a_bufs.at[slot],
                          sem_a.at[slot]).wait()
    pltpu.make_async_copy(x_hbm.at[:, pl.ds(0, win)], b_bufs.at[slot],
                          sem_b.at[slot]).wait()

    shift_a = splits_ref[s, 1]           # non-negative cyclic shifts only
    thr_a = splits_ref[s, 2]
    shift_b = splits_ref[s, 4]
    thr_b = splits_ref[s, 5]
    out_asc = splits_ref[s, 6] != 0

    r_idx = lax.broadcasted_iota(jnp.int32, (1, tile), 1)
    rowi = lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    is_key_row = (rowi < num_keys) | (rowi == tb_row)

    a_rows = pltpu.roll(a_bufs[slot], shift_a, 1)[:, :tile]
    a_invalid = r_idx >= thr_a             # tail lanes past the run end
    a_rows = jnp.where(is_key_row & a_invalid,
                       jnp.broadcast_to(_INF, a_rows.shape), a_rows)

    b_rows = pltpu.roll(b_bufs[slot], shift_b, 1)[:, :tile]
    b_invalid = r_idx < thr_b              # front lanes below B'[j0]
    b_rows = jnp.where(is_key_row & b_invalid,
                       jnp.broadcast_to(_INF, b_rows.shape), b_rows)

    seq = jnp.concatenate([a_rows, b_rows], axis=1)
    asc_mask = jnp.broadcast_to(out_asc, (1, 2 * tile))
    key_rows_idx = list(range(num_keys)) + [tb_row]
    j = tile
    while j >= 1:
        seq = _cmp_exchange(seq, j, asc_mask, key_rows_idx)
        j //= 2
    o_ref[...] = jnp.where(out_asc, seq[:, :tile], seq[:, tile:])


@partial(jax.jit, static_argnames=("tile", "num_keys", "tb_row", "interpret"))
def _merge_pass(x, splits, tile: int, num_keys: int, tb_row: int,
                interpret: bool = False):
    rows, n = x.shape
    # The splits table is BLOCKED into SMEM a few rows per grid step: a
    # whole-table scalar prefetch would put [num_tiles, 8] int32 in SMEM
    # with the minor dim padded to 128 lanes — 4 MB at n=8M vs the 1 MB
    # SMEM budget. An (8, 8) block is 256 bytes regardless of n (the
    # lowering wants the sublane block dim divisible by 8 or equal to
    # the array dim, hence 8 rows — the kernel picks its row by
    # program_id % 8).
    split_blk = min(8, n // tile)
    # splits shifted by one row: step t reads tile t+1's aligned starts
    # for the double-buffered prefetch (last row duplicated, never used)
    splits_nxt = jnp.concatenate([splits[1:], splits[-1:]], axis=0)
    blk = pl.BlockSpec((split_blk, 8), lambda t: (t // split_blk, 0),
                       memory_space=pltpu.SMEM)
    return pl.pallas_call(
        partial(_merge_pass_kernel, tile=tile, num_keys=num_keys,
                tb_row=tb_row, split_blk=split_blk),
        grid=(n // tile,),
        in_specs=[blk, blk, pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((rows, tile), lambda t: (0, t)),
        scratch_shapes=[
            pltpu.VMEM((2, rows, tile + _LANE), jnp.uint32),
            pltpu.VMEM((2, rows, tile + _LANE), jnp.uint32),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        out_shape=_uint32_struct((rows, n), x),
        interpret=interpret,
    )(splits, splits_nxt, x)


def pad_pow2(n: int, tile: int) -> tuple[int, int]:
    """The lane-padding rule every lanes-engine entry point shares:
    pad ``n`` lanes up to ``m`` (a power of two, at least one lane
    block) and clamp ``tile`` so sort_lanes' preconditions hold
    (m % tile == 0 with m/tile a power of two). Returns (m, tile)."""
    m = max(_LANE, 1 << max(0, n - 1).bit_length())
    return m, min(tile, m)


def keys8_sort_perm(keyrows, tile: int = 1024, interpret: bool = False):
    """The keys8 cascade core, shared by both keys8 surfaces (the
    single-chip sort, the distributed local sort):
    run the FULL bitonic pipeline on an 8-row keys-only matrix and
    return ``(sorted_key_rows, perm)`` — ``perm[j]`` is the source lane
    of sorted position j (int32), stable by arrival order among equal
    keys (the row-7 tie-break holds the lane index).

    ``keyrows``: uint32[k, m] with k <= 7 key rows, m a power-of-two
    multiple of ``tile``. Rows k..6 pad with zeros (never compared);
    row 7 is overwritten by the tile-sort kernel. Callers own their
    lane padding: pad lanes' key rows must sort after every real
    lane's (e.g. all-0xFFFFFFFF keys tie with real all-max keys, and
    the arrival tie-break then keeps real lanes first because padding
    occupies the highest lane indices)."""
    k, m = keyrows.shape
    if not 0 < k <= 7:
        raise ValueError(f"keys8 needs 1..7 key rows, got {k}")
    mat8 = jnp.concatenate(
        [jnp.asarray(keyrows, jnp.uint32),
         jnp.zeros((8 - k, m), jnp.uint32)], axis=0)
    out8 = sort_lanes(mat8, num_keys=k, tb_row=7, tile=tile,
                      interpret=interpret)
    return out8[:k], out8[7].astype(jnp.int32)


def sort_lanes(x, num_keys: int, tb_row: int = TB_ROW_DEFAULT,
               tile: int = 1024, interpret: bool = False):
    """Full stable sort of records in lanes layout.

    ``x``: uint32[ROWS, n] with key words in rows [0, num_keys); row
    ``tb_row`` is overwritten with the arrival index (stability) and
    holds it in the output. n must be a power-of-two multiple of
    ``tile`` (pad with +inf-key records otherwise).

    Returns the sorted [ROWS, n] array (ascending by keys, stable by
    arrival among equal keys).
    """
    x = jnp.asarray(x, jnp.uint32)
    rows, n = x.shape
    if tile & (tile - 1) or tile % _LANE:
        raise ValueError(f"tile={tile} must be a power of two multiple "
                         f"of {_LANE}")
    if n % tile or (n // tile) & (n // tile - 1):
        raise ValueError(f"n={n} must be a power-of-two multiple of "
                         f"tile={tile}")
    if not 0 < num_keys <= tb_row < rows:
        raise ValueError(f"bad num_keys={num_keys} / tb_row={tb_row}")
    levels = int(np.log2(n // tile))
    x = _tile_sort(x, tile, num_keys, tb_row, alternate=levels > 0,
                   interpret=interpret)
    if levels == 0:
        return x

    # One fori_loop body serving every pass: run_len/final are traced,
    # so the program holds exactly ONE merge pallas_call (and one tile
    # sort) no matter how many passes run — compile cost is bounded in
    # n, the property the operand-carry lax.sort path lacks.
    def body(lvl, x):
        run_len = jnp.int32(tile) << lvl
        final = lvl == levels - 1
        splits = _pass_splits(x, run_len, final, tile, num_keys, tb_row)
        return _merge_pass(x, splits, tile, num_keys, tb_row,
                           interpret=interpret)

    return lax.fori_loop(0, levels, body, x)


def merge_lanes_runs(x, run_len: int, num_keys: int,
                     tb_row: int = TB_ROW_DEFAULT, tile: int = 1024,
                     interpret: bool = False):
    """Stable merge of R sorted runs in lanes layout: the merge passes of
    ``sort_lanes`` from run length ``run_len`` up, and nothing below.

    ``x``: uint32[ROWS, R * run_len]; run k is lanes [k * run_len,
    (k + 1) * run_len), ASCENDING by the key rows [0, num_keys) with
    equal keys in the order they are to keep — a run shorter than
    ``run_len`` ends in lanes whose key rows sort after its real ones
    (+inf), as sort_lanes' callers pad. Any R >= 1, any run_len >= 1.
    Row ``tb_row`` is overwritten with the lane's index in ``x`` (run,
    then slot: the arrival index of sort_lanes) and holds it in the
    output, so equal keys come out by run, then by slot.

    The runs are stored the way a merge pass wants its input — each
    padded to a multiple of the tile, their count to a power of two,
    with lanes that are +inf in EVERY row, tie-break included, so they
    sort after a real all-0xFFFFFFFF key and are cut off again; runs of
    odd index reversed (bitonic as stored, padding at their front) —
    and log2(R) passes merge them, sort_lanes' loop from that level on.
    The stored matrix is written as ONE concatenation, out of place: in
    the fused multi-chip step (parallel/distributed.py) that is what
    lets the compiler keep the step's total where a full sort of the
    same buffer had it; reversing the odd runs in place cost it a
    second lanes buffer, 806 MB a chip at 2^25 lanes (PERF.md, PR 35).

    Returns the sorted [ROWS, R * run_len] array.
    """
    x = jnp.asarray(x, jnp.uint32)
    rows, n = x.shape
    if tile & (tile - 1) or tile % _LANE:
        raise ValueError(f"tile={tile} must be a power of two multiple "
                         f"of {_LANE}")
    if run_len <= 0 or n % run_len:
        raise ValueError(f"n={n} is not a whole number of runs of "
                         f"{run_len}")
    if not 0 < num_keys <= tb_row < rows:
        raise ValueError(f"bad num_keys={num_keys} / tb_row={tb_row}")
    runs = n // run_len
    x = lax.dynamic_update_slice(
        x, jnp.arange(n, dtype=jnp.uint32)[None], (tb_row, 0))
    if runs == 1:
        return x
    _, tile = pad_pow2(run_len, tile)
    blk = -(-run_len // tile) * tile
    nblk = 1 << (runs - 1).bit_length()
    if (nblk, blk) != (runs, run_len):
        x = jnp.pad(x.reshape(rows, runs, run_len),
                    ((0, 0), (0, nblk - runs), (0, blk - run_len)),
                    constant_values=_INF).reshape(rows, nblk * blk)
    x = jnp.concatenate(
        [jnp.flip(x[:, k * blk:(k + 1) * blk], axis=1) if k % 2
         else x[:, k * blk:(k + 1) * blk] for k in range(nblk)], axis=1)
    levels = nblk.bit_length() - 1

    def body(lvl, x):   # sort_lanes' pass, run lengths blk, 2 blk, ...
        splits = _pass_splits(x, jnp.int32(blk) << lvl, lvl == levels - 1,
                              tile, num_keys, tb_row)
        return _merge_pass(x, splits, tile, num_keys, tb_row,
                           interpret=interpret)

    return lax.fori_loop(0, levels, body, x)[:, :n]
