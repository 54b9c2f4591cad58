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

import os
from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from uda_tpu.ops.packing import PackedKeys

__all__ = ["sort_permutation", "merge_runs", "sort_records_fixed",
           "concat_packed", "resolve_sort_path", "apply_perm_chunked",
           "route_engine", "LANES_ENGINES", "FLYOFF_ENGINES",
           "BENCH_FLYOFF", "ALL_SORT_PATHS", "UNCOMPILED_ENGINES",
           "SELECTABLE_SORT_PATHS", "GATHER_BOUND_ENGINES",
           "CC_LADDER", "SMALL_BATCH_ROWS"]

# The single source of truth for engine path names. LANES_ENGINES are
# the Pallas-pipeline variants (bounded compile; interpret mode on CPU
# meshes): "lanes" carries payload through the network, "lanes2" uses
# the in-kernel two-phase gather, "keys8" runs the cascade on an 8-row
# keys view + one global XLA payload gather. "gather2" is keys8's
# XLA-native twin: the permutation comes from a narrow lax.sort
# instead of the Pallas cascade, the payload moves with the same
# single minor-dim gather (differs from "gather", which does one
# gather PER COLUMN on [n] arrays). The remaining lax.sort paths are
# "carry" (operand-carry) and "gather". bench.py, parallel.distributed
# and models.terasort all import these — adding an engine means
# extending ONE tuple.
# "carrychunk" applies the narrow-sort permutation with a few SMALL
# operand-carry sorts (invert the permutation with a 2-operand sort,
# then re-sort payload chunks of ~6 columns by it): no gathers, no
# Pallas, and every sort stays far below the operand count where XLA's
# variadic-sort compile time blows up. "keys8f" is keys8 with the
# FOLDED cascade (ops.pallas_fold: two element-halves share the 8-row
# tile, halving per-stage work) — it needs the compare set to fit a
# 4-row slot, so it is a narrow-key specialization (<= 3 compare rows
# + tie-break; the TeraSort flagship shape) and joins the bench
# fly-off but not the general-purpose engine set.
# carrychunk's payload-chunk width; overridable for deployment tuning
# (resolved once at import — see apply_perm_chunked)
DEFAULT_CHUNK_COLS = int(os.environ.get("UDA_TPU_CHUNK_COLS", "6"))

# The engine the "auto" policy deploys — how a fly-off/sweep winner
# reaches every production call site at once (the engine analogue of
# UDA_TPU_CHUNK_COLS). Empty = the built-in per-backend defaults
# below. Read ONCE at import, never inside a jitted trace. A deployed
# LANES engine applies only to lanes-capable callers (lanes_ok=True);
# others keep the built-in default rather than failing — the deploy
# var must never break a pure-XLA code path.
DEPLOYED_SORT_PATH = os.environ.get("UDA_TPU_SORT_PATH", "")

LANES_ENGINES = ("lanes", "lanes2", "keys8", "keys8f")
FLYOFF_ENGINES = ("lanes", "keys8", "gather2", "carrychunk")
BENCH_FLYOFF = FLYOFF_ENGINES + ("keys8f",)
# Engines whose kernels Mosaic refuses (chip_smoke.py Phase B records
# the compiler's message): known by name, so interpret-mode tests can
# still diff them, but in no fly-off and selectable by no policy — a
# deployed or cached winner naming one is rejected like an unknown
# engine. "lanes2": the in-kernel lane gather does not lower.
UNCOMPILED_ENGINES = ("lanes2",)
SELECTABLE_SORT_PATHS = ("carry", "gather") + BENCH_FLYOFF
ALL_SORT_PATHS = SELECTABLE_SORT_PATHS + UNCOMPILED_ENGINES

# Engines whose payload movement is one (or more) global HBM gathers.
# A take-ramp probe of 2026-07-31, on a backend that no longer exists
# (git history; not measured on this machine), found the gather
# LATENCY-bound below SMALL_BATCH_ROWS — fixed per-row random-access
# cost dominates before the streaming rate amortizes it — so small
# batches route to a gather-free engine (route_engine below).
GATHER_BOUND_ENGINES = ("gather", "gather2", "keys8", "keys8f")
SMALL_BATCH_ROWS = 1 << 20

# carrychunk chunk-width ladder (words per payload-chunk sort). For the
# TeraSort shape's 23 payload words: cc=6 -> 4 chunk sorts moving 27
# operand-words/record, cc=8 -> 3 (26), cc=12 -> 2 (25), cc=23 -> the
# single-sort extreme (24 words/record — the ROADMAP "27->24" lever).
# Larger cc strictly reduces sort-network traffic, bounded by XLA's
# superlinear variadic-sort compile time; a sweep's winner deploys via
# UDA_TPU_CHUNK_COLS.
CC_LADDER = (8, 12, 23)


def resolve_sort_path(path: str, lanes_ok: bool = False) -> str:
    """Resolve a payload-movement strategy name. "auto" picks
    operand-carry on CPU (compile is cheap there) and "carrychunk" on
    TPU — the winner of the fly-off of 2026-07-31 on a backend that no
    longer exists (git history; not measured on this machine), with
    bounded compile (no sort exceeds chunk_cols+1 operands; XLA's
    variadic-sort compile time grows superlinearly in operand count)
    and no record-width limit.
    ``lanes_ok`` additionally admits the Pallas-pipeline engines
    (LANES_ENGINES) for callers that implement them; the pure-XLA
    strategies (carry/gather/gather2/carrychunk) are valid everywhere.
    Resolution happens EAGERLY, never inside a jitted trace: a
    trace-time choice would be baked into the jit cache and survive a
    later platform switch."""
    valid = (ALL_SORT_PATHS if lanes_ok
             else tuple(p for p in ALL_SORT_PATHS
                        if p not in LANES_ENGINES))
    if path == "auto":
        if DEPLOYED_SORT_PATH:
            if DEPLOYED_SORT_PATH not in SELECTABLE_SORT_PATHS:
                raise ValueError(
                    f"UDA_TPU_SORT_PATH={DEPLOYED_SORT_PATH!r} is not a "
                    f"selectable sort path {SELECTABLE_SORT_PATHS}")
            if DEPLOYED_SORT_PATH in valid:
                return DEPLOYED_SORT_PATH
            # deployed lanes engine, lanes-incapable caller: keep the
            # built-in default
        backend = jax.default_backend()
        if backend == "cpu":
            path = "carry"
        elif backend == "tpu":
            path = "carrychunk"
        else:
            raise ValueError(f"no default sort path for backend "
                             f"{backend!r} (cpu and tpu are supported)")
    if path not in valid:
        raise ValueError(f"unknown sort path {path!r}")
    return path


def _cached_engine(n_rows: int, lanes_ok: bool) -> "str | None":
    """The tuning-cache consult for "auto" routing (utils/tuncache.py):
    a fly-off winner persisted per (backend, row-bucket, lanes
    capability) by scripts/tune_probe.py. Returns None — today's
    built-in default — on a cold cache, an unreadable file, or a
    winner this caller cannot run (validation here, so a stale or
    hand-edited cache can never force an invalid engine name onto a
    production sort surface). Precedence is env > cache > built-in:
    callers consult this only when UDA_TPU_SORT_PATH is unset."""
    from uda_tpu.utils.tuncache import rows_bucket, tune_cache

    backend = jax.default_backend()
    key = f"{backend}|rows{rows_bucket(n_rows)}|lanes{int(lanes_ok)}"
    rec = tune_cache.lookup("sort.engine", key)
    if rec is None:
        return None
    engine = (rec.get("winner") or {}).get("engine")
    valid = (SELECTABLE_SORT_PATHS if lanes_ok
             else tuple(p for p in SELECTABLE_SORT_PATHS
                        if p not in LANES_ENGINES))
    if engine not in valid:
        return None
    return engine


def route_engine(n_rows: int, path: str = "auto",
                 lanes_ok: bool = False) -> str:
    """Batch-size-aware engine routing: resolve ``path`` like
    :func:`resolve_sort_path` — consulting the persisted tuning cache
    for "auto" when no env winner is deployed (env > cache > built-in;
    a cold cache is byte-for-byte today's defaults) — then, for "auto"
    only, steer batches below :data:`SMALL_BATCH_ROWS` away from
    :data:`GATHER_BOUND_ENGINES` onto "carrychunk" on TPU (its
    permutation apply rides small sort networks, no global gather —
    the only engine shape that holds up in the latency-bound take-ramp
    regime). The steering applies to deployed AND cached winners
    alike: a gather-bound fly-off champion (keys8f/gather2/...) must
    not be routed into the regime the take-ramp datum says it loses.
    An EXPLICIT path is always honored: routing refines the default,
    it never overrides the operator. This is the resolution entry for
    the production sort surfaces (models.terasort.single_chip_sort,
    parallel.distributed). Resolution is eager, never inside a jitted
    trace."""
    if path != "auto":
        return resolve_sort_path(path, lanes_ok)
    resolved = resolve_sort_path("auto", lanes_ok)
    if not DEPLOYED_SORT_PATH:
        cached = _cached_engine(n_rows, lanes_ok)
        if cached is not None:
            resolved = cached
    if (n_rows < SMALL_BATCH_ROWS and jax.default_backend() == "tpu"
            and resolved in GATHER_BOUND_ENGINES):
        return "carrychunk"
    return resolved


def apply_perm_chunked(perm, cols, chunk_cols: int | None = None) -> list:
    """Apply ``perm`` to columns WITHOUT gathers: ``out[c][j] ==
    cols[c][perm[j]]``. Inverts the permutation with a 2-operand sort
    (iota carried through a sort BY perm lands at the inverse), then
    re-sorts payload chunks of ``chunk_cols`` columns by it — every
    sort stays far below the operand count where XLA's variadic-sort
    compile time blows up. The single implementation behind the
    "carrychunk" engine (terasort bench and the distributed step).

    ``chunk_cols=None`` resolves ``UDA_TPU_CHUNK_COLS`` so a
    sweep-tuned value reaches every production call site at once. The
    env var is read ONCE at import (module constant), never inside a
    jitted trace — a trace-time read would bake into the jit cache
    without being part of its key."""
    if chunk_cols is None:
        chunk_cols = DEFAULT_CHUNK_COLS
    n = perm.shape[0]
    iota = lax.iota(jnp.int32, n)
    # perm keys are distinct, so unstable sorts are exact
    _, inv = lax.sort((perm.astype(jnp.int32), iota), num_keys=1,
                      is_stable=False)
    out_cols: list = []
    for base in range(0, len(cols), chunk_cols):
        chunk = tuple(cols[base:base + chunk_cols])
        out = lax.sort((inv, *chunk), num_keys=1, is_stable=False)
        out_cols.extend(out[1:])
    return out_cols


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
