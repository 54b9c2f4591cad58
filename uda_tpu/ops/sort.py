"""Device sort/merge over packed key columns.

Replaces the reference's reduce-side k-way priority-queue merge (reference
src/Merger/MergeQueue.h:126-427 ``PriorityQueue``/``MergeQueue``,
consumed record-at-a-time by ``write_kv_to_stream``,
src/Merger/StreamRW.cc:151-225) with whole-run device sorts:

- ``sort_permutation``: one multi-operand lexicographic ``lax.sort`` over
  (key words..., content length, overflow rank) yielding the record
  permutation. XLA lowers this to its tuned on-chip sort; there is no
  per-record host loop anywhere.
- ``merge_runs``: k pre-sorted runs are concatenated and re-sorted. A
  k-way merge is O(n log k) vs O(n log n), but on TPU the constant factor
  of XLA's vectorized bitonic sort beats scalar heap walks by orders of
  magnitude; a Pallas merge-path kernel is the planned upgrade and slots
  in behind the same API (see uda_tpu/ops/pallas_merge.py).
- ``sort_records_fixed``: fully device-resident variant that carries a
  fixed-stride payload through the same sort (TeraSort layout).

All functions are jit-compiled with static column counts; shapes are
static per (run length, key width) pair so XLA caches one executable per
configuration, analogous to the reference sizing its buffer pools once
per job (reference src/Merger/reducer.cc:56-133).
"""

from __future__ import annotations

from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from uda_tpu.ops.packing import PackedKeys

__all__ = ["sort_permutation", "merge_runs", "sort_records_fixed",
           "concat_packed", "resolve_sort_path", "SORT_PATHS"]

# The engine names, in one place. Every engine implements the same
# stable order (equal keys keep arrival order), so outputs are
# byte-identical across them.
# - "carry": XLA's operand-carry ``lax.sort`` — all record columns ride
#   the sort network. Compile time grows superlinearly in operand count
#   (minutes on a TPU at TeraSort's 26 words), cheap on a CPU.
# - "lanes": the Pallas lanes pipeline (ops.pallas_sort.sort_lanes) —
#   two Mosaic kernels whatever n and the record width; records up to
#   the 32-row layout.
# - "keys8": the same pipeline on an 8-row keys-only view plus one XLA
#   payload gather; no record-width limit. Explicit only.
SORT_PATHS = ("carry", "lanes", "keys8")


def resolve_sort_path(path: str) -> str:
    """The whole engine policy: "auto" is "carry" on a CPU backend and
    "lanes" on a TPU; an explicit name of SORT_PATHS is honoured.
    Resolution happens EAGERLY, never inside a jitted trace: a
    trace-time choice would be baked into the jit cache and survive a
    later platform switch."""
    if path == "auto":
        backend = jax.default_backend()
        if backend == "cpu":
            return "carry"
        if backend == "tpu":
            return "lanes"
        raise ValueError(f"no default sort path for backend "
                         f"{backend!r} (cpu and tpu are supported)")
    if path not in SORT_PATHS:
        raise ValueError(f"unknown sort path {path!r}; the engines are "
                         f"{SORT_PATHS}")
    return path


@partial(jax.jit, static_argnames=("num_key_words",))
def _sort_perm(columns: tuple, num_key_words: int):
    n = columns[0].shape[0]
    iota = lax.iota(jnp.int32, n)
    operands = (*columns, iota)
    out = lax.sort(operands, num_keys=num_key_words + 2, is_stable=True)
    return out[-1]


def _as_columns(keys: PackedKeys) -> tuple:
    # Operand order matters: (prefix words..., overflow rank, content
    # length). Rank must precede length — for two keys that BOTH overflow
    # the carried width with equal prefixes, their order is decided by the
    # bytes past the width (the rank), not by their lengths (e.g.
    # b"P...P_Z" (17B) vs b"P...P_AB" (18B) with width 16: AB-key first
    # despite being longer). Length then orders the remaining ties:
    # fitting keys among themselves (shorter-is-smaller memcmp rule) and
    # fitting-vs-overflowing (the fitting key is a strict prefix, and its
    # rank is 0 <= any overflow rank, falling through to length which is
    # necessarily smaller).
    cols = tuple(jnp.asarray(keys.key_words[:, i])
                 for i in range(keys.key_words.shape[1]))
    return (*cols, jnp.asarray(keys.ranks), jnp.asarray(keys.key_lens))


def sort_permutation(keys: PackedKeys) -> np.ndarray:
    """Stable sort permutation of one run, computed on device.

    Sort key = (key words lexicographic, overflow rank, content length);
    stability preserves arrival order among equal keys, which is the
    merge-queue contract equal keys get in the reference (segments are
    advanced in heap order; Hadoop guarantees grouping, not order, so
    stable-by-arrival is a strict strengthening).
    """
    if keys.num_records == 0:
        return np.zeros(0, np.int64)
    perm = _sort_perm(_as_columns(keys), keys.key_words.shape[1])
    return np.asarray(perm, dtype=np.int64)


def concat_packed(runs: Sequence[PackedKeys]) -> PackedKeys:
    """Concatenate packed runs (the host-side prelude to merge_runs)."""
    return PackedKeys(
        np.concatenate([r.key_words for r in runs], axis=0),
        np.concatenate([r.key_lens for r in runs]),
        np.concatenate([r.ranks for r in runs]),
    )


def merge_runs(runs: Sequence[PackedKeys]) -> tuple[np.ndarray, np.ndarray]:
    """Merge k sorted runs into one global order.

    Returns ``(perm, run_id)`` where ``perm`` indexes into the
    concatenation of the runs and ``run_id[i]`` is the source run of
    output position i (the analogue of the reference's per-segment
    provenance, used to pull the right value bytes at emission).

    Overflow-rank caveat: each run's ranks were computed within that run;
    merging reuses them only when rank columns are compatible. The merge
    engine recomputes ranks across runs at staging time (see
    uda_tpu.merger), so here ranks are taken as-is.
    """
    if not runs:
        return np.zeros(0, np.int64), np.zeros(0, np.int64)
    cat = concat_packed(runs)
    perm = sort_permutation(cat)
    sizes = np.asarray([r.num_records for r in runs], dtype=np.int64)
    bounds = np.cumsum(sizes)
    run_id = np.searchsorted(bounds, perm, side="right")
    return perm, run_id


@partial(jax.jit, static_argnames=("num_key_words",))
def _sort_fixed(columns: tuple, payload, num_key_words: int):
    n = columns[0].shape[0]
    iota = lax.iota(jnp.int32, n)
    pay_cols = tuple(payload[:, i] for i in range(payload.shape[1]))
    out = lax.sort((*columns, iota, *pay_cols), num_keys=num_key_words + 2,
                   is_stable=True)
    perm = out[len(columns)]
    sorted_payload = jnp.stack(out[len(columns) + 1:], axis=1)
    return sorted_payload, perm


def sort_records_fixed(keys: PackedKeys, payload: jnp.ndarray | np.ndarray):
    """Device-resident sort of (keys, fixed-stride payload words).

    The payload words are carried through the sort network as extra
    operands rather than gathered by the output permutation afterwards:
    on TPU a row gather of wide payloads runs ~5x slower than the
    operand-carried sort (random HBM access vs streaming
    compare-exchange). Returns ``(sorted_payload, perm)`` as device
    arrays.
    """
    return _sort_fixed(_as_columns(keys), jnp.asarray(payload),
                       keys.key_words.shape[1])
