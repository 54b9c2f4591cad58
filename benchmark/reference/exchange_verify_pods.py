"""What a correct sort step across pods leaves behind, checked without
the engine (nothing of ``uda_tpu`` is imported here): on the device at
full size (sortedness, range partition, counts, multiset), and byte for
byte against ``np.lexsort`` at a size the host can hold.

``terasort_exchange_pods``' own copy of ``exchange_verify``'s contract,
stated for its mesh: rows are sharded over the axis pair ``(dcn, ici)``,
so block d of the global output is the shard of the d-th device in
POD-MAJOR order — pod ``d // chips_per_pod``, chip ``d %
chips_per_pod`` — and that shard must hold key range d. A body that
delivered a pod pair's tile to the right pod and the wrong chip of it,
or tiles in pod-minor order, fails ``misplaced`` and ``miscounted``
here and the byte comparison in set-up."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

KEY_WORDS = 3


def device_check(words, out, nvalid, splitters, p: int):
    """Device-side verdict of one step as a dict of host integers, all of
    which must be 0: ``unsorted`` (adjacent valid rows out of order),
    ``misplaced`` (valid rows outside their shard's key range),
    ``miscounted`` (shards whose valid count differs from the input's
    histogram over the splitters), ``checksum`` (1 when the multiset of
    records changed). ``words``: the step's input, ``uint32[n, W]``;
    ``out``: its output, ``uint32[p * cap, W]``, shard d = rows
    ``[d * cap, (d + 1) * cap)``, its valid rows first; ``nvalid``:
    ``int32[p]``; ``p`` = pods x chips a pod."""
    verdict = _device_check(words, out, nvalid, splitters, p)
    return {k: int(v) for k, v in verdict.items()}


def _row_hash(w):
    odd = (2 * np.arange(w.shape[-1], dtype=np.uint64) + 1) * 2654435761
    mixed = w * jnp.asarray((odd & 0xFFFFFFFF).astype(np.uint32))
    return jnp.sum(mixed, axis=-1, dtype=jnp.uint32) ^ jnp.uint32(0x9E3779B9)


def _descends(k):
    """Adjacent rows of ``[shard, row]`` key columns out of order."""
    gt = k[-1][:, :-1] > k[-1][:, 1:]
    for col in reversed(k[:-1]):
        gt = (col[:, :-1] > col[:, 1:]) | ((col[:, :-1] == col[:, 1:]) & gt)
    return gt


@partial(jax.jit, static_argnames=("p",))
def _device_check(words, out, nvalid, splitters, p):
    dest_in = jnp.searchsorted(splitters, words[:, 0], side="right")
    counts_in = jnp.bincount(dest_in, length=p)
    sum_in = jnp.sum(_row_hash(words), dtype=jnp.uint32)
    shards = out.reshape(p, -1, out.shape[-1])
    row = jnp.arange(shards.shape[1])[None, :]
    valid = row < nvalid[:, None]
    k = [shards[:, :, c] for c in range(KEY_WORDS)]
    dest_out = jnp.searchsorted(splitters, k[0], side="right")
    sum_out = jnp.sum(jnp.where(valid, _row_hash(shards), 0),
                      dtype=jnp.uint32)
    return {
        "unsorted": jnp.sum(_descends(k) & valid[:, 1:]),
        "misplaced": jnp.sum(valid & (dest_out != jnp.arange(p)[:, None])),
        "miscounted": jnp.sum(nvalid != counts_in),
        "checksum": (sum_in != sum_out).astype(jnp.int32),
    }


def byte_exact(words: np.ndarray, out: np.ndarray, nvalid: np.ndarray,
               splitters: np.ndarray) -> str | None:
    """None when every shard of ``out`` — in pod-major device order — is
    exactly its range partition of ``words`` in ``np.lexsort`` order by
    the three key words (stable: equal keys in input order), else what
    differs. All host arrays."""
    p = len(nvalid)
    shards = out.reshape(p, -1, out.shape[-1])
    ordered = words[np.lexsort((words[:, 2], words[:, 1], words[:, 0]))]
    dest = np.searchsorted(splitters, ordered[:, 0], side="right")
    for d in range(p):
        want = ordered[dest == d]
        if int(nvalid[d]) != len(want):
            return (f"shard {d} holds {int(nvalid[d])} rows, "
                    f"{len(want)} expected")
        if not np.array_equal(shards[d, :len(want)], want):
            return f"shard {d} differs from its sorted range partition"
    return None
