"""The fused exchange step as it stood before it sorted first (up to PR
34): partition the rows as they arrive, permute them into destination
order (a stable argsort of the destinations and a ``take``), exchange
one window a destination, then SORT the whole receive buffer. Kept as
the plain reference of ``distributed._sort_step``, which sorts each
chip's rows first and merges the P runs it receives: same shards, same
counts, same overflow, row for row (tests/test_exchange.py,
tests/test_exchange_skew.py)."""

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from uda_tpu.parallel import distributed as D
from uda_tpu.parallel import shard_map
from uda_tpu.parallel.exchange import window_round_body


@partial(jax.jit, static_argnames=("mesh", "axis", "capacity", "num_keys",
                                   "payload_path", "interpret", "sample"))
def scatter_then_sort_step(words, splitters, mesh, axis, capacity, num_keys,
                           payload_path="carry", interpret=False,
                           sample=False):
    """``(shards [P * P * capacity, W], valid rows [P], overflow [P],
    splitters [P - 1, num_keys])`` of the flat single-round step."""

    @partial(shard_map, mesh=mesh, in_specs=(P(axis), P()),
             out_specs=(P(axis), P(axis), P(axis), P(axis)),
             check_vma=not interpret)
    def go(w, spl):
        p = lax.psum(1, axis)
        spl = D._sampled_splitters(w, axis, num_keys, payload_path,
                                   interpret) if sample else spl[0]
        dest = D._partition(w, spl, num_keys)
        order = jnp.argsort(dest, stable=True)
        sd = jnp.take(dest, order)
        sw = jnp.take(w, order, axis=0)
        counts = jnp.bincount(sd, length=p).astype(jnp.int32)
        overflow = jnp.sum(jnp.maximum(counts - capacity, 0))
        flat, recv_counts = window_round_body(sw, sd, None, 0, axis,
                                              capacity)
        row = jnp.arange(p * capacity, dtype=jnp.int32)
        valid = (row % capacity) < jnp.take(recv_counts, row // capacity)
        out = D._sort_valid_rows(flat, valid, num_keys, payload_path,
                                 interpret)
        return out, jnp.sum(recv_counts)[None], overflow[None], spl[None]

    out, nvalid, overflow, spl = go(words, splitters[None])
    return out, nvalid, overflow, spl[0]
