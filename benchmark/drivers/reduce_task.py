"""Driver ``reduce_task``: the served reduce path, one task after
another.

A MOFSupplier-role ``UdaBridge`` in a child process (started before
this process touches JAX, alive for the whole run) serves one reduce
partition's map outputs over loopback TCP. Each task is a fresh
NetMerger-role ``UdaBridge`` in this process — the one that holds the
chip — taking reference-layout INIT/FETCH/FINAL through the embedder's
up-calls, every flag at its default. A task's wall runs from
``UdaBridge.start`` to the last ``data_from_uda`` block; its stream is
compared byte for byte with the plain reference between tasks, untimed.
"""

from __future__ import annotations

import contextlib
import importlib
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np

from benchmark.harness import platform
from benchmark.harness.loop import (DeviceTrace, SetupError, closed_loop,
                                    outcome)
from benchmark.trace import critpath
from benchmark.trace import reduce as trace_reduce


class ReducerCallable:
    """The NetMerger embedder: copies the framed stream out of each
    ``data_from_uda`` block (the DirectByteBuffer contract), stamps the
    first and the last block, and records the root cause the fallback
    contract reports instead of acting on it — a fallback is a failed
    task."""

    def __init__(self, port: int, out: np.ndarray):
        self.port = port
        self.out = out            # the slot's buffer, reused task after task
        self.size = 0
        self.first_block_t = self.last_block_t = None
        self.failure = None

    def get_conf_data(self, name, default):
        return {"uda.tpu.net.fetch": "true",
                "uda.tpu.net.port": str(self.port)}.get(name, "")

    def data_from_uda(self, data, length):
        end = self.size + length
        if end <= self.out.size:
            self.out[self.size:end] = np.frombuffer(data, np.uint8, length)
        self.size = end                       # an overrun shows as a size
        self.last_block_t = time.perf_counter()
        if self.first_block_t is None:
            self.first_block_t = self.last_block_t

    def failure_in_uda(self, error):
        self.failure = error


class Supplier:
    """The child process of ``supplier_role.py`` and its pipe protocol."""

    def __init__(self, repo: str, root: str):
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        script = os.path.join(os.path.dirname(__file__), "supplier_role.py")
        self.proc = subprocess.Popen(
            [sys.executable, script, repo, root], env=env, text=True,
            stdin=subprocess.PIPE, stdout=subprocess.PIPE)
        self.port = None

    def wait_ready(self) -> None:
        """Block until it listens (it starts while the map outputs are
        being written; it opens none before the first fetch)."""
        line = self.proc.stdout.readline()
        if not line:
            raise SetupError("the supplier process did not start")
        self.port = json.loads(line)["port"]

    def stop(self) -> dict:
        """Close its stdin, wait for it, return its last word."""
        try:
            out, _ = self.proc.communicate(timeout=60)
        except subprocess.TimeoutExpired:
            self.proc.kill()
            self.proc.wait()
            return {"failed": True, "failure": "supplier did not exit"}
        lines = out.strip().splitlines()
        if self.proc.returncode != 0 or not lines:
            return {"failed": True,
                    "failure": f"supplier exit code {self.proc.returncode}"}
        return json.loads(lines[-1])


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
        map_ids = gen.generate(mof_root, job, ctx.seed, shape["records"],
                               shape["maps"])
        phases["generate_s"] = time.perf_counter() - t0
        supplier.wait_ready()
        return _measure(ctx, supplier, reference, mof_root, map_ids,
                        shape["records"], phases)
    finally:
        word = supplier.stop()
        if word["failed"]:
            print(f"benchmark: supplier: {word}", file=sys.stderr)


def _measure(ctx, supplier, reference, mof_root, map_ids, records,
             phases) -> dict:
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
    expect_bytes = records * 102 + 2
    init_cmd = form_cmd(Cmd.INIT, [
        str(len(map_ids)), job, "0", str(init["lpq_size"]),
        str(init["buffer_bytes"]), str(init["min_buffer_bytes"]),
        ctx.config["comparator"],
        str(init["codec"]), str(init["codec_block_bytes"]),
        str(init["shuffle_memory_bytes"])])
    fetch_cmds = [form_cmd(Cmd.FETCH, ["127.0.0.1", job, m, "0"])
                  for m in map_ids]
    ref: dict = {}
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
        wrong = reference.compare(cb.out, ref["stream"])
        if wrong:
            raise RuntimeError(f"stream differs from the reference: {wrong}")

    def task(index: int, timed=contextlib.nullcontext) -> dict:
        with timed():
            record, cb = transport(index)
        verify(cb)                            # untimed, between tasks
        return record

    # set-up: the reference sorts beside the warm-up task (both untimed).
    # The warm-up is one whole task of the cell's own shape: it loads
    # every program the window will use and is the first correctness check
    def sort_reference() -> None:
        try:
            ref["stream"] = reference.sorted_stream(mof_root, job, map_ids)
        except Exception as e:  # noqa: BLE001 - re-raised below
            ref["error"] = e

    t0 = time.perf_counter()
    sorter = threading.Thread(target=sort_reference, name="bench-reference")
    sorter.start()
    try:
        _, warm_cb = transport(-1)
        phases["warm_up_task_s"] = time.perf_counter() - t0
    finally:
        sorter.join()
    phases["warm_up_and_reference_s"] = time.perf_counter() - t0
    if "error" in ref:
        raise SetupError(f"reference: {ref['error']!r}")
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

    out = outcome(device, units, setup_s, "task_wall_s", records * 100,
                  built, ctx.cell["chips"], builds.cache, phases)
    obs = out["obs"]
    obs["counters"] = {k: counters1[k] - counters0.get(k, 0.0)
                       for k in counters1}
    obs["critical"] = critpath.per_task(spans)
    if "hbm_peak_MB" in obs["harness"]:
        from uda_tpu.utils.budget import device_bytes_estimate
        from uda_tpu.utils.config import Config

        obs["harness"]["hbm_model_ratio"] = device_bytes_estimate(
            records * 100, int(Config().get("uda.tpu.key.width"))) \
            / device["memory_peak_bytes"]
    if trace is not None:
        stages = [s for s in spans if s["name"] != critpath.ROOT]
        trace_reduce.finish(out, trace, chips=ctx.cell["chips"], units=1,
                            host_spans=stages, bucket_of=critpath.bucket_of,
                            priority=critpath.BUCKET_PRIORITY)
    return out
