"""TeraSort records made on the device from a seed, already row-sharded:
``uint32[n, 26]`` (the 100-byte record in words; the first three words
are the 10-byte key, the third masked to its top 16 bits). Every word is
a mix of (seed, row, column), so a shard's rows do not depend on the
mesh, nothing crosses chips, and one jitted call makes the whole input
in the type the step takes."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

RECORD_WORDS, KEY_WORDS = 26, 3


def _mix(x):
    """A 32-bit finalizer (murmur3's): every input bit reaches every
    output bit."""
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    return x ^ (x >> 16)


@partial(jax.jit, static_argnames=("n", "sharding"))
def _make(seed, n, sharding):
    row = jnp.arange(n, dtype=jnp.uint32)[:, None]
    col = jnp.arange(RECORD_WORDS, dtype=jnp.uint32)[None, :]
    words = _mix(_mix(row ^ _mix(seed)) + col * jnp.uint32(0x9E3779B9))
    mask = np.full(RECORD_WORDS, 0xFFFFFFFF, np.uint32)
    mask[KEY_WORDS - 1] = 0xFFFF0000
    return jax.lax.with_sharding_constraint(words & jnp.asarray(mask)[None, :],
                                            sharding)


def records(seed: int, n: int, sharding):
    """``uint32[n, 26]`` under ``sharding`` (rows over the mesh)."""
    return _make(jnp.uint32(seed & 0xFFFFFFFF), n, sharding)
