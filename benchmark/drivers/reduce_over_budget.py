"""Driver ``reduce_over_budget``: the served reduce path for a partition
the chip cannot hold whole, one task after another.

``reduce_task``'s shape — a MOFSupplier-role ``UdaBridge`` in a child
process serving the partition's map outputs over loopback TCP, each
task a fresh NetMerger-role ``UdaBridge`` in the process that holds the
chip, reference-layout INIT/FETCH/FINAL, every flag at its default —
at a size where neither the chip nor this harness may hold the
partition twice. So the harness holds ONE partition-sized buffer (the
slot's output buffer); the plain reference is computed in blocks
beside the warm-up task and kept as its size and a 256-bit digest
(``reference/host_sort_blocks.py``), and every task's stream — the
warm-up's and the timed ones' — is compared with those, whole, untimed,
between tasks.

Every task has a deadline (the traffic file's ``task_deadline_s``):
when one passes it the run says so and exits non-zero — a commit whose
over-budget route wedges fails, it does not hang.

A program that sizes no device groups (``utils/budget.py`` without
``group_capacity_rows``: every program before this cell) cannot run the
configuration — its over-budget route never touches the chip, and a
cell's traced run has to — so the driver says so and exits non-zero at
once, before anything is generated.

On the chip every flag is default. In the CPU rehearsal only, the
"HBM" budget would be the host's memory, which holds any rehearsal
partition whole: there the embedder answers ``uda.tpu.hbm.budget.mb``
with the traffic file's ``rehearsal.hbm_budget_mb``, so that the
rehearsal partition is over it and takes the route the cell measures.
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

from benchmark.drivers.reduce_slots import Deadline, _rss_mb, _trim_heap
from benchmark.drivers.reduce_task import ReducerCallable, Supplier
from benchmark.harness import platform
from benchmark.harness.loop import (DeviceTrace, SetupError, closed_loop,
                                    outcome)
from benchmark.trace import critpath
from benchmark.trace import reduce as trace_reduce


class RehearsalCallable(ReducerCallable):
    """The NetMerger embedder of a CPU rehearsal: ``ReducerCallable``
    plus the one answer that makes a rehearsal partition over-budget."""

    def __init__(self, port: int, out: np.ndarray, hbm_budget_mb: int):
        super().__init__(port, out)
        self.hbm_budget_mb = hbm_budget_mb

    def get_conf_data(self, name, default):
        if name == "uda.tpu.hbm.budget.mb":
            return str(self.hbm_budget_mb)
        return super().get_conf_data(name, default)


def run(ctx) -> dict:
    cfg, traffic = ctx.config, ctx.traffic
    shape = traffic["rehearsal"] if ctx.rehearse else traffic
    job = cfg["job"]

    try:
        from uda_tpu.utils.budget import group_capacity_rows  # noqa: F401
    except ImportError:
        raise SetupError(
            "this program sizes no device groups (no uda_tpu.utils.budget."
            "group_capacity_rows): a partition over the HBM budget never "
            "reaches the chip, so it cannot run this configuration") from None
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
        # what the machine still has once the map outputs are written:
        # on a machine that holds files in memory they are its largest
        # tenant beside the output buffer and the task's run files
        phases["rss_after_generate_MB"] = _rss_mb()
        supplier.wait_ready()
        return _measure(ctx, supplier, reference, mof_root, map_ids,
                        shape, phases)
    finally:
        word = supplier.stop()
        if word["failed"]:
            print(f"benchmark: supplier: {word}", file=sys.stderr)


def _measure(ctx, supplier, reference, mof_root, map_ids, shape,
             phases) -> dict:
    job, init = ctx.config["job"], ctx.config["init"]
    records = shape["records"]
    t0 = time.perf_counter()
    device = platform.gate(ctx.cell["chips"], ctx.rehearse)
    phases["backend_s"] = time.perf_counter() - t0
    phases["rss_after_backend_MB"] = _rss_mb()
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
    # the ONE partition-sized buffer of the harness, touched once: a
    # task must not pay first-touch page faults for the harness's own
    out = np.zeros(expect_bytes, np.uint8)
    ref: dict = {}
    deadline = Deadline(ctx.traffic["task_deadline_s"], supplier,
                        ctx.work_dir)
    threading.Thread(target=deadline.watch, name="bench-deadline",
                     daemon=True).start()

    def transport(index: int) -> dict:
        """One task, timed, its stream left in ``out``."""
        if ctx.rehearse:
            cb = RehearsalCallable(supplier.port, out,
                                   shape["hbm_budget_mb"])
        else:
            cb = ReducerCallable(supplier.port, out)
        reducer = UdaBridge()
        signals = metrics.get("fallback.signals")
        rejected = metrics.get("budget.rejected")
        with deadline.task(0, index):
            t0 = time.perf_counter()
            reducer.start(True, [], cb)
            try:
                reducer.do_command(init_cmd)
                for cmd in fetch_cmds:
                    reducer.do_command(cmd)
                reducer.do_command(form_cmd(Cmd.FINAL, []))
            finally:
                reducer.reduce_exit()         # joins the merge thread
            reducer.do_command(form_cmd(Cmd.EXIT, []))
        if cb.failure is not None or reducer.failed:
            raise RuntimeError(f"failure_in_uda: {cb.failure!r}")
        if metrics.get("fallback.signals") != signals:
            raise RuntimeError("the bridge signalled a fallback")
        if metrics.get("budget.rejected") != rejected:
            raise RuntimeError("admission rejected the partition")
        if cb.size != expect_bytes:
            raise RuntimeError(f"{cb.size} bytes emitted, {expect_bytes} "
                               f"expected")
        return {"wall_s": cb.last_block_t - t0,
                "first_block_s": cb.first_block_t - t0}

    def verify() -> None:
        wrong = reference.compare_digest(out, *ref["known"])
        if wrong:
            raise RuntimeError(f"stream differs from the reference: {wrong}")

    def task(index: int, timed=contextlib.nullcontext) -> dict:
        with timed():
            record = transport(index)
        verify()                              # untimed, between tasks
        return record

    # set-up: the reference is sorted in blocks beside the warm-up task
    # (both untimed). The warm-up is one whole task of the cell's own
    # shape: it loads every program the window will use and is the first
    # correctness check
    def sort_reference() -> None:
        try:
            ref["known"] = reference.sorted_digest(mof_root, job, map_ids)
        except Exception as e:  # noqa: BLE001 - re-raised below
            ref["error"] = e

    t0 = time.perf_counter()
    sorter = threading.Thread(target=sort_reference, name="bench-reference")
    sorter.start()
    try:
        transport(-1)
        phases["warm_up_task_s"] = time.perf_counter() - t0
    except Exception as e:  # noqa: BLE001 - set-up failed as a whole
        raise SetupError(f"warm-up task: {e!r}") from e
    finally:
        sorter.join()
    phases["warm_up_and_reference_s"] = time.perf_counter() - t0
    if "error" in ref:
        raise SetupError(f"reference: {ref['error']!r}")
    if ref["known"][0] != records * 102:
        raise SetupError(f"the reference holds {ref['known'][0]} bytes, "
                         f"the traffic {records * 102}")
    t0 = time.perf_counter()
    try:
        verify()
    except RuntimeError as e:
        raise SetupError(f"warm-up task: {e}") from e
    phases["verify_s"] = time.perf_counter() - t0
    phases["rss_after_warm_up_MB"] = _rss_mb()
    _trim_heap()
    phases["rss_after_trim_MB"] = _rss_mb()
    if ctx.trace:
        metrics.enable_spans()
    trace = DeviceTrace(os.path.join(ctx.work_dir, "trace")) \
        if ctx.trace and not ctx.rehearse else None

    def unit(index: int) -> dict:
        if trace is not None and index == 0:
            with trace.session():
                return task(index, trace.mark)
        return task(index)

    restart_peaks = getattr(metrics, "restart_gauge_peaks", None)
    if restart_peaks is not None:
        restart_peaks()
    counters0 = metrics.snapshot()
    builds0 = builds.builds
    setup_s = time.perf_counter() - ctx.t_start
    units = closed_loop(unit, ctx.seconds, ctx.traffic["concurrent_tasks"])
    built = builds.builds - builds0
    phases["rss_after_window_MB"] = _rss_mb()
    counters1 = metrics.snapshot()
    spans = list(metrics.spans)
    metrics.disable_spans()

    result = outcome(device, units, setup_s, "task_wall_s", records * 100,
                     built, ctx.cell["chips"], builds.cache, phases)
    obs = result["obs"]
    obs["counters"] = {k: counters1[k] - counters0.get(k, 0.0)
                       for k in counters1}
    peaks = getattr(metrics, "gauge_peaks_snapshot", None)
    obs["gauge_peaks"] = peaks() if peaks is not None else {}
    obs["critical"] = critpath.per_task(spans)
    if "hbm_peak_MB" in obs["harness"]:
        from uda_tpu.utils.budget import MemoryBudget
        from uda_tpu.utils.config import Config

        # the rows the program reserves for one group of the task in
        # the chip-wide ledger (what memory_stats can see of it: the
        # merge program's temporaries are booked beside them) over the
        # measured peak
        obs["harness"]["hbm_model_ratio"] = \
            MemoryBudget.from_config(Config()).group_reservation()[1] \
            / device["memory_peak_bytes"]
    if trace is not None:
        stages = [s for s in spans if s["name"] != critpath.ROOT]
        trace_reduce.finish(result, trace, chips=ctx.cell["chips"], units=1,
                            host_spans=stages, bucket_of=critpath.bucket_of,
                            priority=critpath.BUCKET_PRIORITY)
    return result
