"""TeraSort records with skewed id keys, made on the device from a seed,
already row-sharded: ``uint32[n, 26]``, the layout of
``device_records`` with other keys.

The key is an integer id drawn from Zipf's law with s = 1 over
K = 2^20 ranks, written as a 10-byte big-endian number — word 0 is 0,
word 1 ``id >> 16``, word 2 ``(id & 0xFFFF) << 16`` — so every record
shares its first 6 key bytes and the keys repeat. The law by the
inverse CDF of its continuous form: from a uniform ``u`` in [0, 1) a
row, ``id = floor(K ** u)``, which gives ``P(id) = log2(1 + 1/id) /
20``: id 1 is 5.0 % of the records, the ten hottest 17.3 %, the 1,023
hottest half. ``u`` is 24 bits of a mix of (seed, row) in float32, so
the coldest ids come out coarse; the hot end, which is what skews a
sort, is exact to counting error.

Arrival is iid (the row's id depends on nothing but seed and row), a
shard's rows do not depend on the mesh, word 3 is the global row number
(it tells equal keys apart: a stable sort leaves them ascending), and
the other 22 payload words are a mix of (seed, row, column)."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from benchmark.gen.device_records import KEY_WORDS, RECORD_WORDS, _mix

RANKS_LOG2 = 20                       # K = 2^20 ids
ROW_WORD = KEY_WORDS                  # the payload word that holds the row


@partial(jax.jit, static_argnames=("n", "sharding"))
def _make(seed, n, sharding):
    row = jnp.arange(n, dtype=jnp.uint32)
    base = _mix(row ^ _mix(seed))
    u = (_mix(base ^ jnp.uint32(0x5BD1E995)) >> 8).astype(jnp.float32) \
        * jnp.float32(2.0 ** -24)
    ids = jnp.clip(jnp.floor(jnp.exp2(RANKS_LOG2 * u)), 1,
                   2 ** RANKS_LOG2 - 1).astype(jnp.uint32)
    col = jnp.arange(RECORD_WORDS, dtype=jnp.uint32)[None, :]
    words = _mix(base[:, None] + col * jnp.uint32(0x9E3779B9))
    words = words.at[:, 0].set(0)
    words = words.at[:, 1].set(ids >> 16)
    words = words.at[:, 2].set((ids & 0xFFFF) << 16)
    words = words.at[:, ROW_WORD].set(row)
    return jax.lax.with_sharding_constraint(words, sharding)


def records(seed: int, n: int, sharding):
    """``uint32[n, 26]`` under ``sharding`` (rows over the mesh)."""
    return _make(jnp.uint32(seed & 0xFFFFFFFF), n, sharding)
