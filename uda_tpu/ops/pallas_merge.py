"""Pallas merge: pairwise merge of sorted runs on the lanes engine.

The device-native replacement for the reference's network-levitated
incremental merge (reference src/Merger/MergeQueue.h:276-427: as each
segment lands it joins the k-way heap). Whole-run ``lax.sort`` is
O(n log n) and re-does all comparison work every time a new run lands;
merging two already-sorted runs is O(n).

Implementation: one merge PASS of the lanes bitonic pipeline
(uda_tpu.ops.pallas_sort). The two runs are packed into the
``uint32[32, 2L]`` lanes layout exactly the way the pipeline's tile
sort would have left them — A ascending in lanes [0, L), B stored
DESCENDING in lanes [L, 2L) (so the pair is bitonic as stored), with
the arrival index in the tie-break row and +inf-key padding lanes on
the ascending tail / descending front. ``_merge_pass`` then merges
them like any other pass, carrying the merge-path split from tile to
tile inside the kernel. This reuses the ONE merge kernel that is
validated on real TPU hardware; the earlier row-matrix merge kernel
variant was unloadable under Mosaic (minor-dim slices of a [tile, W]
block violate the 128-lane tiling rule — the same layout problem that
motivated the lanes design in the first place).

Rows travel as uint32[n, W] with the first ``num_keys`` columns the
big-endian key words (the uda_tpu.ops.packing layout); W <= 31.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from uda_tpu.ops import pallas_sort
from uda_tpu.ops.pallas_sort import _lex_lt, _merge_pass

__all__ = ["merge_sorted_pair", "merge_splits"]

_INF = np.uint32(0xFFFFFFFF)

# lexicographic a < b over tuples of uint32 arrays — shared with the
# lanes kernels (single implementation of the compare semantics)
_key_less = _lex_lt


@partial(jax.jit, static_argnames=("tile", "num_keys"))
def merge_splits(a, b, tile: int, num_keys: int):
    """For each output tile boundary d = t*tile, the number of A rows in
    the first d merged rows (merge-path diagonal intersection). Returns
    int32[num_tiles]. Vectorized binary search, 32 fixed iterations.

    (Host-callable analysis utility, and the tests' oracle of the
    split the merge kernel carries from tile to tile: its i at tile t
    is ``merge_splits(...)[t]``.)"""
    na, nb = a.shape[0], b.shape[0]
    num_tiles = (na + nb + tile - 1) // tile
    d = jnp.arange(num_tiles, dtype=jnp.int32) * tile

    def key_at(arr, idx):
        idx = jnp.clip(idx, 0, arr.shape[0] - 1)
        return tuple(arr[idx, c] for c in range(num_keys))

    lo = jnp.maximum(0, d - nb)
    hi = jnp.minimum(d, na)

    def body(_, carry):
        lo, hi = carry
        mid = (lo + hi + 1) // 2  # candidate i: rows of A taken
        j = d - mid               # rows of B taken
        # valid split needs A[mid-1] <= B[j]  (A wins ties)
        a_key = key_at(a, mid - 1)
        b_key = key_at(b, jnp.clip(j, 0, nb - 1))
        a_le_b = ~_key_less(b_key, a_key)      # A[mid-1] <= B[j]
        ok = (mid <= 0) | (j >= nb) | a_le_b
        lo = jnp.where(ok, mid, lo)
        hi = jnp.where(ok, hi, mid - 1)
        return lo, hi

    lo, hi = lax.fori_loop(0, 32, body, (lo, hi))
    return lo.astype(jnp.int32)


def _pack_bitonic_pair(a, b, ncols: int, nrows: int, tb: int, L: int):
    """Two [n, W] sorted runs -> one [nrows, 2L] bitonic-as-stored lanes
    pair: the leading ``ncols`` columns of each run in rows [0, ncols),
    the GLOBAL arrival index (= row id into concat(a, b)) in row ``tb``,
    +inf keys/tie-break in the L-n padding lanes (payload rows of
    padding lanes are never read). B is stored DESCENDING (flip) so the
    concatenation is bitonic as stored and padding sits at its front."""
    na, nb = a.shape[0], b.shape[0]

    def run_lanes(r, n, base, descending):
        lanes = jnp.full((nrows, L), _INF, jnp.uint32)
        lanes = lax.dynamic_update_slice(
            lanes, r[:, :ncols].T.astype(jnp.uint32), (0, 0))
        idx = jnp.arange(L, dtype=jnp.uint32)
        lanes = lanes.at[tb].set(jnp.where(idx < n, base + idx, _INF))
        return jnp.flip(lanes, axis=1) if descending else lanes

    return jnp.concatenate([run_lanes(a, na, 0, False),
                            run_lanes(b, nb, na, True)], axis=1)


def _ceil_runs(na: int, nb: int, tile: int) -> int:
    # a single merge pass only needs L % tile == 0 (sort_lanes' pass
    # CASCADE is what needs powers of two), so ceil-to-tile padding
    # avoids up-to-2x wasted lanes on the overlapped merger's hot path
    return max(tile, -(-max(na, nb) // tile) * tile)


@partial(jax.jit, static_argnames=("num_keys", "tile", "interpret"))
def _merge_sorted_pair_jit(a, b, num_keys: int, tile: int, interpret: bool):
    """Shape-specialized core: jit so repeat calls at the same (na, nb)
    hit the executable cache instead of re-tracing the pallas_call
    (the overlapped merger calls this many times per job)."""
    na, nb, wcols = a.shape[0], b.shape[0], a.shape[1]
    tb = pallas_sort.TB_ROW_DEFAULT
    L = _ceil_runs(na, nb, tile)
    x = _pack_bitonic_pair(a, b, wcols, pallas_sort.ROWS, tb, L)
    out = _merge_pass(x, L, True, tile, num_keys, tb, interpret=interpret)
    return out[:wcols, :na + nb].T


def merge_sorted_pair(a, b, num_keys: int, tile: int = 512,
                      interpret: bool = False):
    """Merge two key-sorted row matrices into one (stable: A's rows
    precede B's on equal keys). ``a``/``b``: uint32[n, W] with key words
    in the leading ``num_keys`` columns, W <= 31. The output has
    a.shape[0]+b.shape[0] rows."""
    if tile <= 0 or (tile & (tile - 1)) != 0 or tile % 128:
        raise ValueError(f"tile must be a power of two multiple of 128, "
                         f"got {tile} (the lanes merge kernel requires "
                         "it)")
    a = jnp.asarray(a, jnp.uint32)
    b = jnp.asarray(b, jnp.uint32)
    if a.shape[1] > pallas_sort.TB_ROW_DEFAULT:
        raise ValueError(f"{a.shape[1]} record words do not fit the "
                         f"{pallas_sort.ROWS}-row lanes layout")
    if a.shape[0] == 0:
        return b
    if b.shape[0] == 0:
        return a
    return _merge_sorted_pair_jit(a, b, num_keys, tile, interpret)
