"""What a correct distributed sort of keys of ANY distribution leaves
behind, checked without the engine and without the splitters it chose:
any valid splitters are a right answer, so this holds a step to the
sort's contract, not to the choice.

The contract: the shards' valid rows, concatenated in shard order, are
the input in stable order by the three key words (equal keys in input
order — the generator's word 3 is the row number, so between equal keys
it ascends); no key value lies in two shards; every record is there
once; the largest shard is within ``n * (1/p + h + 0.01)``, ``h`` the
share of the most frequent key (a key never straddles, so no range
partition can promise less).

On the device at full size (``device_check``), and byte for byte
against ``np.lexsort`` at a size the host holds (``byte_exact``)."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.exchange_verify import _row_hash

KEY_WORDS = 3
ROW_WORD = 3                # payload word holding the input row number
BALANCE_SLACK = 0.01


def shard_bound(n: int, p: int, hottest: int) -> int:
    """The most rows one shard may hold."""
    return int(n * (1 / p + BALANCE_SLACK)) + hottest


def device_check(words, out, nvalid, p: int):
    """Verdict of one step at full size as a dict of host integers, all
    of which must be 0: ``unsorted`` (adjacent valid rows of a shard out
    of key order), ``unstable`` (adjacent equal keys whose row numbers
    do not ascend), ``straddled`` (shards whose first key is not
    strictly above the last key of the shard before), ``miscounted``
    (1 when the valid counts do not sum to the input's rows),
    ``checksum`` (1 when the multiset of records changed),
    ``unbalanced`` (rows of the largest shard past the bound).
    ``words``: the step's input, ``uint32[n, W]``; ``out``: its output,
    ``uint32[p * cap, W]``, each shard's valid rows first; ``nvalid``:
    ``int32[p]``. The rows are read on the device; only each shard's
    first and last key, the counts and the sums come to the host."""
    verdict, first, last, hottest = jax.device_get(
        _device_check(words, out, nvalid, p))
    counts = np.asarray(nvalid).reshape(-1)
    held = [d for d in range(p) if counts[d]]
    verdict = {k: int(v) for k, v in verdict.items()}
    verdict["straddled"] = sum(int(tuple(last[a]) >= tuple(first[b]))
                               for a, b in zip(held, held[1:]))
    verdict["unbalanced"] = max(0, int(counts.max()) - shard_bound(
        int(words.shape[0]), p, int(hottest)))
    return verdict


@partial(jax.jit, static_argnames=("p",))
def _device_check(words, out, nvalid, p):
    shards = out.reshape(p, -1, out.shape[-1])
    row = jnp.arange(shards.shape[1])[None, :]
    valid = row < nvalid[:, None]
    keys = shards[:, :, :KEY_WORDS]
    lt = jnp.zeros(keys.shape[:2], jnp.bool_)[:, 1:]    # row i < row i+1
    eq = ~lt
    for c in range(KEY_WORDS):
        a, b = keys[:, :-1, c], keys[:, 1:, c]
        lt, eq = lt | (eq & (a < b)), eq & (a == b)
    both = valid[:, 1:]
    rows = shards[:, :, ROW_WORD]
    # the longest run of one key: with nothing else wrong, the count of
    # the most frequent key (no key straddles, so no run is cut)
    opens = jnp.concatenate([jnp.ones((p, 1), jnp.bool_), ~eq], axis=1)
    run_start = jax.lax.cummax(jnp.where(opens, row, 0), axis=1)
    hottest = jnp.max(jnp.where(valid, row - run_start + 1, 0))
    last_row = jnp.maximum(nvalid - 1, 0)[:, None, None]
    sum_in = jnp.sum(_row_hash(words), dtype=jnp.uint32)
    sum_out = jnp.sum(jnp.where(valid, _row_hash(shards), 0),
                      dtype=jnp.uint32)
    verdict = {
        "unsorted": jnp.sum(both & ~lt & ~eq),
        "unstable": jnp.sum(both & eq & (rows[:, :-1] >= rows[:, 1:])),
        "miscounted": (jnp.sum(nvalid) != words.shape[0]).astype(jnp.int32),
        "checksum": (sum_in != sum_out).astype(jnp.int32),
    }
    return (verdict, keys[:, 0],
            jnp.take_along_axis(keys, last_row, axis=1)[:, 0], hottest)


def byte_exact(words: np.ndarray, out: np.ndarray,
               nvalid: np.ndarray) -> str | None:
    """None when the shards' valid rows, in shard order, are exactly
    ``words`` in ``np.lexsort`` order by the three key words (stable:
    equal keys in input order), no key lies in two shards and the
    largest shard is within the bound; else what differs. All host
    arrays."""
    p, n = len(nvalid), len(words)
    shards = out.reshape(p, -1, out.shape[-1])
    if int(nvalid.sum()) != n:
        return f"shards hold {int(nvalid.sum())} rows of {n}"
    held = [shards[d, :nvalid[d]] for d in range(p) if nvalid[d]]
    want = words[np.lexsort((words[:, 2], words[:, 1], words[:, 0]))]
    if not np.array_equal(np.concatenate(held), want):
        return "the shards in order differ from the stable host sort"
    for a, b in zip(held, held[1:]):
        if np.array_equal(a[-1, :KEY_WORDS], b[0, :KEY_WORDS]):
            return f"key {a[-1, :KEY_WORDS].tolist()} lies in two shards"
    _, counts = np.unique(words[:, :KEY_WORDS], axis=0, return_counts=True)
    bound = shard_bound(n, p, int(counts.max()))
    if int(nvalid.max()) > bound:
        return (f"largest shard holds {int(nvalid.max())} rows, over the "
                f"bound {bound}")
    return None
