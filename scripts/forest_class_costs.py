"""The two costs behind overlap.DEVICE_MIN_BUCKET, on the chip's host:
per size class (rows of the OUTPUT's class), the native row merge of two
runs filled to 62.5 % (1,280 of 2,048, as fanin1024's) against one device
merge asked of the chip (device_put of each input + the jitted Pallas
merge call), host seconds per call. One process, one thread.

    chiprun -- python3 scripts/forest_class_costs.py

prints one JSON line a class and writes chiprun_out/forest_class_costs.json.
The numbers beside the constant (and in PERF.md section 3) are this
script's on a TPU v5 lite machine; on the CPU backend the "device" half
is the interpreted kernel and says nothing."""
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.getcwd())
import jax  # noqa: E402

from uda_tpu import native  # noqa: E402
from uda_tpu.ops import merge as m  # noqa: E402
from uda_tpu.utils import compile_cache  # noqa: E402

compile_cache.enable()
assert native.build()
rng = np.random.default_rng(7)
COLS = 7
out = {"device": str(jax.devices()[0]), "classes": []}


def run(n):
    r = rng.integers(0, 2**32, size=(n, COLS), dtype=np.uint32)
    r[:, 3] = 10
    return np.ascontiguousarray(r[np.lexsort(tuple(r[:, c] for c in range(COLS - 1, -1, -1)))])


for log2 in range(11, 20):
    bucket = 1 << log2          # output class
    cap = bucket // 2           # each input's capacity
    valid = cap * 5 // 8
    reps = max(8, min(400, (1 << 22) // bucket))
    pairs = [(run(valid), run(valid)) for _ in range(min(reps, 16))]
    outs = [np.empty((2 * valid, COLS), np.uint32) for _ in range(4)]
    # host
    for i in range(3):
        native.merge_rows_native_into(*pairs[i % len(pairs)], outs[i % 4])
    t0 = time.perf_counter()
    for i in range(reps):
        a, b = pairs[i % len(pairs)]
        native.merge_rows_native_into(a, b, outs[i % 4])
    host_s = (time.perf_counter() - t0) / reps
    # device: padded inputs
    padded = []
    for a, b in pairs:
        pa = np.full((cap, COLS), 0xFFFFFFFF, np.uint32); pa[:valid] = a
        pb = np.full((cap, COLS), 0xFFFFFFFF, np.uint32); pb[:valid] = b
        padded.append((pa, pb))
    da, db = (jax.device_put(x) for x in padded[0])
    jax.block_until_ready(m.merge_row_pair(da, db, valid, valid, "pallas"))
    put_s = call_s = done_s = 0.0
    dreps = max(8, min(100, reps))
    for i in range(dreps):
        pa, pb = padded[i % len(padded)]
        t0 = time.perf_counter()
        da = jax.device_put(pa); jax.block_until_ready(da)
        db = jax.device_put(pb); jax.block_until_ready(db)
        t1 = time.perf_counter()
        o = m.merge_row_pair(da, db, valid, valid, "pallas")
        t2 = time.perf_counter()
        jax.block_until_ready(o)
        t3 = time.perf_counter()
        put_s += (t1 - t0) / 2; call_s += t2 - t1; done_s += t3 - t1
    rec = {"class_rows": bucket, "out_rows": 2 * valid, "reps": reps,
           "host_merge_us": host_s * 1e6, "host_ns_per_row": host_s * 1e9 / (2 * valid),
           "device_put_us": put_s / dreps * 1e6, "device_call_us": call_s / dreps * 1e6,
           "device_call_done_us": done_s / dreps * 1e6}
    print(json.dumps(rec), flush=True)
    out["classes"].append(rec)
os.makedirs("chiprun_out", exist_ok=True)
json.dump(out, open("chiprun_out/forest_class_costs.json", "w"), indent=1)
