"""Breakdown profiling of the lanes TeraSort bench on the real chip.

Times each pipeline slice (teragen+checksum only, tile-sort only, full
sort at several tile sizes) with the same amortized-dispatch protocol as
bench.py, so the deltas attribute wall-clock to generation/validation,
the tile-sort kernel, and the merge-pass cascade. Every round uses a
fresh PRNG key; the timed region ends in the readback of the result
scalars it validates.

Usage: python scripts/profile_lanes.py [log2_records] [rounds]
"""

from __future__ import annotations

import os
import sys
import time
from functools import partial

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from uda_tpu.utils import compile_cache  # noqa: E402

compile_cache.enable()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax import lax  # noqa: E402

from uda_tpu.models import terasort  # noqa: E402
from uda_tpu.ops import pallas_sort  # noqa: E402

LOG2 = int(sys.argv[1]) if len(sys.argv) > 1 else 23
K = int(sys.argv[2]) if len(sys.argv) > 2 else 4
N = 1 << LOG2
GB = N * terasort.RECORD_BYTES * K / 1e9


@partial(jax.jit, static_argnames=("n", "k", "stage", "tile"))
def step(seed, n, k, stage, tile):
    """k rounds of teragen -> [stage] -> checksum/violations."""

    def body(i, acc):
        viol, ck = acc
        x = terasort.teragen_lanes(jax.random.fold_in(seed, i), n)
        if stage == "gen":
            out = x
        elif stage == "tilesort":
            out = pallas_sort._tile_sort(x, tile, terasort.KEY_WORDS,
                                         pallas_sort.TB_ROW_DEFAULT,
                                         alternate=True)
        elif stage in ("keys8", "keys8f"):
            out = terasort.sort_lanes_keys8(x, tile=tile,
                                            folded=stage == "keys8f")
        elif stage in ("keys8sort", "keys8fsort"):
            # the keys cascade alone: _keys8_parts returns the sorted
            # KEY rows; the payload gather's output is unused below
            # (checksum over zero pad rows), so XLA DCEs it
            sk = terasort._keys8_parts(x, tile, False,
                                       folded=stage == "keys8fsort")[0]
            out = jnp.concatenate(
                [sk, jnp.zeros((pallas_sort.ROWS - terasort.KEY_WORDS,
                                x.shape[1]), jnp.uint32)], axis=0)
        else:
            out = pallas_sort.sort_lanes(x, num_keys=terasort.KEY_WORDS,
                                         tile=tile)
        ck = ck + terasort._checksum_cols(
            tuple(out[r] for r in range(terasort.RECORD_WORDS)))
        viol = viol + terasort._violations_cols(out[0], out[1], out[2])
        return viol, ck

    return lax.fori_loop(0, k, body, (jnp.int32(0), jnp.uint32(0)))


def time_stage(stage, tile=1024, dispatches=2):
    # warmup/compile
    viol, ck = step(jax.random.key(99), N, K, stage, tile)
    int(viol)
    best = float("inf")
    for i in range(dispatches):
        t0 = time.perf_counter()
        viol, ck = step(jax.random.key(i), N, K, stage, tile)
        int(viol), int(ck)  # host readback = sync
        best = min(best, time.perf_counter() - t0)
    print(f"{stage:>10} tile={tile:<5} best {best*1e3:8.1f} ms "
          f"({GB/best:6.2f} GB/s)", flush=True)
    return best


if __name__ == "__main__":
    print(f"n=2^{LOG2} k={K} ({GB:.2f} GB/dispatch) on "
          f"{jax.devices()[0].platform}")
    t_gen = time_stage("gen")
    t_tile = time_stage("tilesort", 1024)
    # keys8f's slim [4, n] layout halves merge-kernel VMEM, so its
    # sweep extends to 32768 (fewer passes at half the DMA bytes)
    for stage, tiles in (("full", (1024, 2048, 4096)),
                         ("keys8sort", (4096, 8192, 16384)),
                         ("keys8fsort", (4096, 8192, 16384, 32768)),
                         ("keys8", (4096, 8192, 16384)),
                         ("keys8f", (4096, 8192, 16384, 32768))):
        for tile in tiles:
            if (N % tile) or ((N // tile) & (N // tile - 1)):
                continue
            try:
                time_stage(stage, tile)
            except Exception as e:  # noqa: BLE001 - report, continue sweep
                print(f"      {stage} tile={tile}: FAILED "
                      f"{type(e).__name__}: {str(e)[:200]}", flush=True)
