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
  again alternates (the final pass emits ascending). The grid walks a
  pair's output tiles in ascending rank order and CARRIES the pair's
  merge-path split (records taken from A, from B) from tile to tile in
  SMEM — (0, 0) at a pair's first tile, no search anywhere. Per tile the
  kernel DMAs one lane-ALIGNED superwindow per side, aligns it with a
  dynamic lane roll, and takes min(A_r, Bs_r) lane by lane (B as
  stored, descending): the first stage of the bitonic merge of the two
  windows, whose kept half — the tile's records, bitonic — goes through
  the remaining log2(T) stages in the tile's output direction. The
  count of lanes that kept A is what the tile consumed of A: it moves
  the split, and the next tile's windows are in flight before those
  stages run. A pair stored descending writes its tiles in reverse
  position (the output index map), so no kernel ever reverses data.

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
           "lanes_to_rows", "keys8_sort_perm", "pad_pow2", "sort_passes",
           "runs_passes", "TB_ROW_DEFAULT"]

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


def _pass_windows(sc_ref, pair, i, j, *, tile: int, n: int):
    """Scalar window arithmetic for one output tile: ``pair``'s runs are
    A = lanes [pair * 2L, +L) ascending and B = the next L lanes stored
    DESCENDING, read through its ascending view B'[m] = B[L-1-m]; the
    tile wants A[i, i+tile) and B'[j, j+tile).

    Returns (a_blk, shift_a, thr_a, b_blk, shift_b, thr_b): per side an
    aligned superwindow start (in lane-block units), the non-negative
    cyclic lane shift in [0, win) that places the wanted first record at
    lane 0, and the validity threshold (A: lanes >= thr_a are past the
    run end; B: lanes < thr_b are below B'[j])."""
    L = sc_ref[0]
    win = tile + _LANE
    last_blk = (n - win) // _LANE         # superwindows never leave x
    a_base = pair * 2 * L
    b_base = a_base + L
    # ---- A: records [i, i+tile) of the ascending run ----
    a_start = a_base + i
    a_blk = jnp.minimum(a_start // _LANE, last_blk)
    roll_a = a_start - a_blk * _LANE
    thr_a = L - i
    # ---- B: stored lanes holding B'[j+tile-1] ... B'[j] ----
    # unclamped start b_base + L - j - tile undershoots b_base by
    # thr_b = max(0, j + tile - L); read from the clamped start and roll
    # RIGHT by thr_b so position r holds B'[j + tile - 1 - r] for
    # r >= thr_b and the first thr_b lanes are not B's
    thr_b = jnp.maximum(0, j + tile - L)
    b_clamp = b_base + jnp.maximum(0, L - j - tile)
    b_blk = jnp.minimum(b_clamp // _LANE, last_blk)
    roll_b = thr_b - (b_clamp - b_blk * _LANE)
    # Roll amounts are normalized to [0, win): hardware pltpu.roll
    # miscomputes NEGATIVE dynamic shifts (interpret mode is fine), so
    # only non-negative cyclic shifts may reach it. roll_a is in
    # [0, win), roll_b in (-win, tile].
    shift_a = jnp.where(roll_a == 0, 0, win - roll_a)
    shift_b = jnp.where(roll_b < 0, roll_b + win, roll_b)
    return a_blk, shift_a, thr_a, b_blk, shift_b, thr_b


def _pair_ascending(sc_ref, pair):
    """Whether ``pair``'s merged run is stored ascending: every pair of
    the final pass, the even pairs of the others."""
    return (sc_ref[1] != 0) | (pair % 2 == 0)


def _out_tile(t, sc_ref):
    """Output tile position of grid step ``t``. A pair's tiles are
    computed in ASCENDING rank order whatever its output direction; a
    pair stored descending holds its lowest ranks in its LAST tile, so
    its steps write their tiles in reverse position."""
    tpp = sc_ref[2]                       # tiles a pair
    pair = t // tpp
    return jnp.where(_pair_ascending(sc_ref, pair), t,
                     (2 * pair + 1) * tpp - 1 - t)


def _merge_pass_kernel(sc_ref, x_hbm, o_ref, a_bufs, b_bufs, sem_a, sem_b,
                       st, *, tile, num_keys, tb_row):
    """One output tile of one merge pass. Every pass-dependent scalar
    arrives via ``sc_ref`` = (L, final, tiles a pair), so this kernel
    compiles once and serves all log2(n/tile) passes.

    The merge-path split is CARRIED, not searched: grid steps run in
    order, a pair's tiles in ascending rank order, and ``st`` (SMEM
    scratch: pair, tile-in-pair, i, j) holds how many records of A and
    of B' the pair's earlier tiles consumed — (0, 0) at a pair's first
    tile. The tile holds the ``tile`` smallest of A[i, i+tile) and
    B'[j, j+tile). With the B window as stored (descending: lane r holds
    Bs_r = B'[j + tile - 1 - r]) those are min(A_r, Bs_r), lane by lane
    — the first compare-exchange stage of the bitonic merge of
    [A ++ Bs], of which only this kept half is ever computed — and
    because A rises and Bs falls the lanes that keep A are a prefix:
    their count nA is what this tile consumes of A, tile - nA of B'.
    A lane past A's run end takes Bs and a lane below B'[j] takes A
    (every lane has a valid side: the runs' remainders sum to >= tile),
    BEFORE any key is compared — a run's +inf padding lanes
    (merge_lanes_runs) tie with each other in every row, and validity
    first is what keeps i and j within L.

    DMA double buffering: as soon as nA is known the windows of tile
    t+1 are DMA'd into the other scratch slot, WHILE tile t's remaining
    log2(tile) stages run, so HBM latency overlaps compute across
    sequential grid steps. Each side DMAs a lane-aligned superwindow of
    tile+128 lanes (align floor-clamped so it never leaves the array),
    then one dynamic cyclic roll places the wanted first record at lane
    0 (_pass_windows)."""
    t = pl.program_id(0)
    nt = pl.num_programs(0)
    slot = t % 2
    win = tile + _LANE
    windows = partial(_pass_windows, sc_ref, tile=tile, n=x_hbm.shape[1])

    def issue(a_blk, b_blk, slot):
        # aligned starts are LANE-BLOCK indices times _LANE so Mosaic can
        # statically prove the HBM slice offset is lane-divisible (a raw
        # traced offset fails its divisibility check)
        pltpu.make_async_copy(
            x_hbm.at[:, pl.ds(a_blk * _LANE, win)], a_bufs.at[slot],
            sem_a.at[slot]).start()
        pltpu.make_async_copy(
            x_hbm.at[:, pl.ds(b_blk * _LANE, win)], b_bufs.at[slot],
            sem_b.at[slot]).start()

    @pl.when(t == 0)
    def _():
        for k in range(4):
            st[k] = 0
        a_blk, _, _, b_blk, _, _ = windows(0, 0, 0)
        issue(a_blk, b_blk, 0)

    pair, s, i, j = st[0], st[1], st[2], st[3]
    _, shift_a, thr_a, _, shift_b, thr_b = windows(pair, i, j)
    out_asc = _pair_ascending(sc_ref, pair)

    # wait for this tile's windows (issued at t-1, or just above for t=0)
    pltpu.make_async_copy(x_hbm.at[:, pl.ds(0, win)], a_bufs.at[slot],
                          sem_a.at[slot]).wait()
    pltpu.make_async_copy(x_hbm.at[:, pl.ds(0, win)], b_bufs.at[slot],
                          sem_b.at[slot]).wait()

    a_rows = pltpu.roll(a_bufs[slot], shift_a, 1)[:, :tile]
    b_rows = pltpu.roll(b_bufs[slot], shift_b, 1)[:, :tile]
    key_rows_idx = list(range(num_keys)) + [tb_row]
    r_idx = lax.broadcasted_iota(jnp.int32, (1, tile), 1)
    a_lt_b = _lex_lt([a_rows[r] for r in key_rows_idx],
                     [b_rows[r] for r in key_rows_idx])[None, :]
    take_a = (r_idx < thr_a) & ((r_idx < thr_b) | a_lt_b)
    n_a = jnp.sum(take_a.astype(jnp.int32))

    # carry the split to the next tile and start its windows
    last = s + 1 == sc_ref[2]
    pair, s = jnp.where(last, pair + 1, pair), jnp.where(last, 0, s + 1)
    i, j = jnp.where(last, 0, i + n_a), jnp.where(last, 0, j + tile - n_a)
    st[0], st[1], st[2], st[3] = pair, s, i, j

    @pl.when(t + 1 < nt)
    def _():
        a_blk, _, _, b_blk, _, _ = windows(pair, i, j)
        issue(a_blk, b_blk, 1 - slot)

    x = jnp.where(take_a, a_rows, b_rows)  # bitonic: A's prefix, Bs' tail
    asc_mask = jnp.broadcast_to(out_asc, (1, tile))
    k = tile // 2
    while k >= 1:
        x = _cmp_exchange(x, k, asc_mask, key_rows_idx)
        k //= 2
    o_ref[...] = x


@partial(jax.jit, static_argnames=("tile", "num_keys", "tb_row", "interpret"))
def _merge_pass(x, run_len, final, tile: int, num_keys: int, tb_row: int,
                interpret: bool = False):
    """One merge pass: adjacent run pairs of length ``run_len`` (= L, a
    multiple of ``tile``; A ascending, B stored descending) merge into
    runs of 2L, stored ascending in even pairs and in every pair of the
    ``final`` pass, descending in the others. ``run_len`` and ``final``
    may be TRACED scalars, so ONE compiled kernel serves every pass
    (and the pass loop can be a ``lax.fori_loop``) — the whole pipeline
    costs two Mosaic kernel compiles regardless of n."""
    rows, n = x.shape
    L = jnp.asarray(run_len, jnp.int32)
    sc = jnp.stack([L, jnp.asarray(final, jnp.int32), 2 * L // tile])
    return pl.pallas_call(
        partial(_merge_pass_kernel, tile=tile, num_keys=num_keys,
                tb_row=tb_row),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n // tile,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((rows, tile),
                                   lambda t, sc: (0, _out_tile(t, sc))),
            scratch_shapes=[
                pltpu.VMEM((2, rows, tile + _LANE), jnp.uint32),
                pltpu.VMEM((2, rows, tile + _LANE), jnp.uint32),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((4,), jnp.int32),
            ]),
        # the carried split needs the grid's steps run in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        out_shape=_uint32_struct((rows, n), x),
        interpret=interpret,
    )(sc, x)


def pad_pow2(n: int, tile: int) -> tuple[int, int]:
    """The lane-padding rule every lanes-engine entry point shares:
    pad ``n`` lanes up to ``m`` (a power of two, at least one lane
    block) and clamp ``tile`` so sort_lanes' preconditions hold
    (m % tile == 0 with m/tile a power of two). Returns (m, tile)."""
    m = max(_LANE, 1 << max(0, n - 1).bit_length())
    return m, min(tile, m)


def sort_passes(n: int, tile: int = 1024) -> int:
    """Merge passes ``sort_lanes`` runs over ``n`` lanes padded by
    ``pad_pow2``."""
    m, tile = pad_pow2(n, tile)
    return (m // tile).bit_length() - 1


def runs_passes(runs: int) -> int:
    """Merge passes ``merge_lanes_runs`` runs over ``runs`` runs."""
    return (runs - 1).bit_length()


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
    levels = sort_passes(n, tile)
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
        return _merge_pass(x, run_len, final, tile, num_keys, tb_row,
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
    levels = runs_passes(runs)
    nblk = 1 << levels
    if (nblk, blk) != (runs, run_len):
        x = jnp.pad(x.reshape(rows, runs, run_len),
                    ((0, 0), (0, nblk - runs), (0, blk - run_len)),
                    constant_values=_INF).reshape(rows, nblk * blk)
    x = jnp.concatenate(
        [jnp.flip(x[:, k * blk:(k + 1) * blk], axis=1) if k % 2
         else x[:, k * blk:(k + 1) * blk] for k in range(nblk)], axis=1)

    def body(lvl, x):   # sort_lanes' pass, run lengths blk, 2 blk, ...
        return _merge_pass(x, jnp.int32(blk) << lvl, lvl == levels - 1,
                           tile, num_keys, tb_row, interpret=interpret)

    return lax.fori_loop(0, levels, body, x)[:, :n]
