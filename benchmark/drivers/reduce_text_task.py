"""Driver ``reduce_text_task``: ``reduce_task``'s run for a job whose
records are not TeraSort's — the served reduce path, one task after
another, with everything that driver fixes to 102-byte frames taken
from the data instead.

The same MOFSupplier child, the same ``ReducerCallable``, the same
fresh NetMerger-role ``UdaBridge`` a task taking reference-layout
INIT/FETCH/FINAL with every flag at its default; INIT's key class is the
configuration's ``comparator``. What differs: the generator says how
many bytes the partition frames to (the stream a task must emit is that
plus the EOF marker) and how many of them are serialized keys and values
(what ``goodput_MBps`` counts: a frame less its two length VInts, as the
TeraSort cells count 100 of a record's 102); ``hbm_model_ratio`` is the
admission model on the partition's own bytes, as the supplier sizes it.
The reference sorts BEFORE the warm-up task, not beside it: each is
tens of seconds of host work here, and side by side on the one-chip
machine's shared cores they made ``setup_s`` spread by 12 % (ledger,
PR 40).
"""

from __future__ import annotations

import contextlib
import importlib
import os
import subprocess
import sys
import threading
import time

import numpy as np

from benchmark.drivers.reduce_task import ReducerCallable, Supplier
from benchmark.harness import platform
from benchmark.harness.loop import (DeviceTrace, SetupError, closed_loop,
                                    outcome)
from benchmark.trace import critpath
from benchmark.trace import reduce as trace_reduce


def run(ctx) -> dict:
    cfg, traffic = ctx.config, ctx.traffic
    shape = traffic["rehearsal"] if ctx.rehearse else traffic
    job = cfg["job"]

    native_dir = os.path.join(ctx.root, "uda_tpu", "native")
    if subprocess.run(["make", "-C", native_dir],
                      stdout=sys.stderr).returncode:
        raise SetupError("native build failed")
    gen = importlib.import_module(f"benchmark.gen.{traffic['generator']}")
    reference = importlib.import_module(
        f"benchmark.reference.{cfg['reference']}")
    mof_root = os.path.join(ctx.work_dir, "mofs")
    phases = {"build_s": time.perf_counter() - ctx.t_start}
    supplier = Supplier(ctx.root, mof_root)   # before this process meets JAX
    try:
        t0 = time.perf_counter()
        partition = gen.generate(mof_root, job, ctx.seed, shape["records"],
                                 shape["maps"])
        phases["generate_s"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        try:
            ref = reference.sorted_stream(mof_root, job, partition.map_ids)
        except reference.ReferenceError as e:
            raise SetupError(f"reference: {e}") from e
        phases["reference_s"] = time.perf_counter() - t0
        supplier.wait_ready()
        return _measure(ctx, supplier, reference, ref, partition, phases)
    finally:
        word = supplier.stop()
        if word["failed"]:
            print(f"benchmark: supplier: {word}", file=sys.stderr)


def _measure(ctx, supplier, reference, ref, partition, phases) -> dict:
    job, init = ctx.config["job"], ctx.config["init"]
    t0 = time.perf_counter()
    device = platform.gate(ctx.cell["chips"], ctx.rehearse)
    phases["backend_s"] = time.perf_counter() - t0
    builds = platform.BuildCounter()

    from uda_tpu import native
    from uda_tpu.bridge import UdaBridge
    from uda_tpu.bridge.protocol import Cmd, form_cmd
    from uda_tpu.utils.metrics import metrics

    if not native.available():
        raise SetupError("the native library did not load")
    map_ids = partition.map_ids
    expect_bytes = partition.frame_bytes + 2
    init_cmd = form_cmd(Cmd.INIT, [
        str(len(map_ids)), job, "0", str(init["lpq_size"]),
        str(init["buffer_bytes"]), str(init["min_buffer_bytes"]),
        ctx.config["comparator"],
        str(init["codec"]), str(init["codec_block_bytes"]),
        str(init["shuffle_memory_bytes"])])
    fetch_cmds = [form_cmd(Cmd.FETCH, ["127.0.0.1", job, m, "0"])
                  for m in map_ids]
    slot = threading.local()      # each reduce slot's own output buffer

    def transport(index: int) -> tuple:
        """One task, timed: returns its record and the callable that
        holds its stream."""
        if not hasattr(slot, "out"):
            # touched once: a task must not pay first-touch page faults
            # for the harness's own buffer
            slot.out = np.zeros(expect_bytes, np.uint8)
        cb = ReducerCallable(supplier.port, slot.out)
        reducer = UdaBridge()
        signals = metrics.get("fallback.signals")
        t0 = time.perf_counter()
        reducer.start(True, [], cb)
        try:
            reducer.do_command(init_cmd)
            for cmd in fetch_cmds:
                reducer.do_command(cmd)
            reducer.do_command(form_cmd(Cmd.FINAL, []))
        finally:
            reducer.reduce_exit()             # joins the merge thread
        reducer.do_command(form_cmd(Cmd.EXIT, []))
        if cb.failure is not None or reducer.failed:
            raise RuntimeError(f"failure_in_uda: {cb.failure!r}")
        if metrics.get("fallback.signals") != signals:
            raise RuntimeError("the bridge signalled a fallback")
        if cb.size != expect_bytes:
            raise RuntimeError(f"{cb.size} bytes emitted, {expect_bytes} "
                               f"expected")
        return {"wall_s": cb.last_block_t - t0,
                "first_block_s": cb.first_block_t - t0}, cb

    def verify(cb: ReducerCallable) -> None:
        wrong = reference.compare(cb.out, ref)
        if wrong:
            raise RuntimeError(f"stream differs from the reference: {wrong}")

    def task(index: int, timed=contextlib.nullcontext) -> dict:
        with timed():
            record, cb = transport(index)
        verify(cb)                            # untimed, between tasks
        return record

    # set-up: the warm-up is one whole task of the cell's own shape. It
    # loads every program the window will use and is the first
    # correctness check
    t0 = time.perf_counter()
    _, warm_cb = transport(-1)
    phases["warm_up_task_s"] = time.perf_counter() - t0
    verify(warm_cb)
    del warm_cb
    if ctx.trace:
        metrics.enable_spans()
    trace = DeviceTrace(os.path.join(ctx.work_dir, "trace")) \
        if ctx.trace and not ctx.rehearse else None

    def unit(index: int) -> dict:
        if trace is not None and index == 0:
            with trace.session():
                return task(index, trace.mark)
        return task(index)

    counters0 = metrics.snapshot()
    builds0 = builds.builds
    setup_s = time.perf_counter() - ctx.t_start
    units = closed_loop(unit, ctx.seconds, ctx.traffic["concurrent_tasks"])
    built = builds.builds - builds0
    counters1 = metrics.snapshot()
    spans = list(metrics.spans)
    metrics.disable_spans()

    out = outcome(device, units, setup_s, "task_wall_s",
                  partition.payload_bytes, built, ctx.cell["chips"],
                  builds.cache, phases)
    obs = out["obs"]
    obs["counters"] = {k: counters1[k] - counters0.get(k, 0.0)
                       for k in counters1}
    obs["critical"] = critpath.per_task(spans)
    if "hbm_peak_MB" in obs["harness"]:
        from uda_tpu.utils.budget import device_bytes_estimate
        from uda_tpu.utils.config import Config

        obs["harness"]["hbm_model_ratio"] = device_bytes_estimate(
            partition.file_bytes, int(Config().get("uda.tpu.key.width"))) \
            / device["memory_peak_bytes"]
    if trace is not None:
        stages = [s for s in spans if s["name"] != critpath.ROOT]
        trace_reduce.finish(out, trace, chips=ctx.cell["chips"], units=1,
                            host_spans=stages, bucket_of=critpath.bucket_of,
                            priority=critpath.BUCKET_PRIORITY)
    return out
