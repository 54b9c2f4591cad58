"""Driver ``reduce_slots``: a node's reduce slots, each running one
reduce task after another of ONE job, all in the process that holds the
chip.

One MOFSupplier-role ``UdaBridge`` in a child process (``supplier_role``
as it is; started before this process touches JAX, alive for the whole
run) serves every partition of the job's map outputs over loopback TCP.
Slot ``s`` always runs reduce id ``s``: each task a fresh NetMerger-role
``UdaBridge`` taking reference-layout INIT/FETCH/FINAL, every flag at
its default, into the slot's own output buffer. A task's wall runs from
``UdaBridge.start`` to its last ``data_from_uda`` block WITH THE OTHER
SLOTS LIVE; its stream is compared with the plain reference of ITS OWN
partition between that slot's tasks, untimed — but beside the other
slots' timed tasks, whose host cores it shares. The warm-up round is
compared byte for byte; after it each reference is kept as its size and
a 256-bit digest, because four references of 1.07 GB beside four live
tasks do not fit the one-chip machine's 40 GiB (the first chip run of
this cell ended there).

Every task has a deadline (the traffic file's ``task_deadline_s``): when
one passes it the run says which and exits non-zero — a commit that
deadlocks under concurrency fails, it does not hang.
"""

from __future__ import annotations

import contextlib
import ctypes
import gc
import importlib
import os
import shutil
import subprocess
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmark.drivers.reduce_task import ReducerCallable, Supplier
from benchmark.harness import platform
from benchmark.harness.loop import (DeviceTrace, SetupError, closed_loop,
                                    outcome)
from benchmark.trace import critpath
from benchmark.trace import reduce as trace_reduce

DEADLINE_EXIT = 1


class Deadline:
    """The tasks in flight and when each started. ``watch`` runs on a
    thread of its own: a task older than ``seconds`` ends the PROCESS
    (its threads may be wedged past any clean unwinding) after the
    supplier child and the work directory are gone."""

    def __init__(self, seconds: float, supplier: Supplier, work_dir: str):
        self.seconds = seconds
        self.supplier = supplier
        self.work_dir = work_dir
        self._lock = threading.Lock()
        self._live: dict = {}             # (slot, index) -> start

    @contextlib.contextmanager
    def task(self, slot: int, index: int):
        key = (slot, index)
        with self._lock:
            self._live[key] = time.perf_counter()
        try:
            yield
        finally:
            with self._lock:
                del self._live[key]

    def watch(self) -> None:
        while True:
            time.sleep(0.5)
            now = time.perf_counter()
            with self._lock:
                late = [(k, now - t0) for k, t0 in self._live.items()
                        if now - t0 > self.seconds]
                live = sorted(self._live)
            if late:
                (slot, index), age = late[0]
                print(f"benchmark: task {index} of slot {slot} (reduce id "
                      f"{slot}) is {age:.0f} s old, past its "
                      f"{self.seconds:g} s deadline, with tasks {live} in "
                      f"flight: the slots deadlocked or starved each other; "
                      f"nothing was measured", file=sys.stderr, flush=True)
                self.supplier.proc.kill()
                shutil.rmtree(self.work_dir, ignore_errors=True)
                os._exit(DEADLINE_EXIT)


def _rss_mb() -> dict:
    """This process's resident memory and what the machine still has,
    in MB: the slots share one host's memory as they share its chip,
    and a one-chip machine has 40 GiB."""
    out = {}
    for path, key in (("/proc/self/status", "VmRSS"),
                      ("/proc/meminfo", "MemAvailable")):
        try:
            with open(path) as f:
                for line in f:
                    if line.startswith(key + ":"):
                        out[key] = int(line.split()[1]) / 1e3
        except OSError:
            pass
    return out


def _trim_heap() -> None:
    """Hand the allocator's free pages back to the machine, once, as
    set-up ends: the warm-up round leaves some 13 GB of freed memory in
    glibc's arenas (every stage, merge and loop thread grows its own),
    the window's four live tasks grow them again, and with both the
    first chip runs of this cell ended within 4 to 6 GB of the one-chip
    machine's 40 GiB. Untimed; a libc without ``malloc_trim`` is left
    as it is."""
    gc.collect()
    try:
        ctypes.CDLL(None).malloc_trim(0)
    except (OSError, AttributeError):
        pass


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
    if gen.PARTITIONS != traffic["partitions"]:
        raise SetupError(f"generator writes {gen.PARTITIONS} partitions, "
                         f"the traffic asks for {traffic['partitions']}")
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
    slots = ctx.traffic["concurrent_tasks"]
    if slots > ctx.traffic["partitions"]:
        raise SetupError(f"{slots} slots for {ctx.traffic['partitions']} "
                         f"partitions: slot s runs reduce id s")
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
    fetch_cmds = [[form_cmd(Cmd.FETCH, ["127.0.0.1", job, m, str(s)])
                   for m in map_ids] for s in range(slots)]
    refs: dict = {}
    # each slot's own output buffer, touched once: a task must not pay
    # first-touch page faults for the harness's own buffer
    outs = [np.zeros(expect_bytes, np.uint8) for _ in range(slots)]
    deadline = Deadline(ctx.traffic["task_deadline_s"], supplier,
                        ctx.work_dir)
    threading.Thread(target=deadline.watch, name="bench-deadline",
                     daemon=True).start()

    def transport(slot: int, index: int) -> tuple:
        """One task of reduce id ``slot``, timed: returns its record and
        the callable that holds its stream."""
        cb = ReducerCallable(supplier.port, outs[slot])
        reducer = UdaBridge()
        signals = metrics.get("fallback.signals")
        with deadline.task(slot, index):
            t0 = time.perf_counter()
            reducer.start(True, [], cb)
            try:
                reducer.do_command(form_cmd(Cmd.INIT, [
                    str(len(map_ids)), job, str(slot),
                    str(init["lpq_size"]), str(init["buffer_bytes"]),
                    str(init["min_buffer_bytes"]), ctx.config["comparator"],
                    str(init["codec"]), str(init["codec_block_bytes"]),
                    str(init["shuffle_memory_bytes"])]))
                for cmd in fetch_cmds[slot]:
                    reducer.do_command(cmd)
                reducer.do_command(form_cmd(Cmd.FINAL, []))
            finally:
                reducer.reduce_exit()         # joins the merge thread
            reducer.do_command(form_cmd(Cmd.EXIT, []))
        if cb.failure is not None or reducer.failed:
            raise RuntimeError(f"failure_in_uda: {cb.failure!r}")
        if metrics.get("fallback.signals") != signals:
            raise RuntimeError("a bridge of this process signalled a "
                               "fallback while the task ran")
        if cb.size != expect_bytes:
            raise RuntimeError(f"{cb.size} bytes emitted, {expect_bytes} "
                               f"expected")
        return {"wall_s": cb.last_block_t - t0, "slot": slot,
                "first_block_s": cb.first_block_t - t0}, cb

    def verify(slot: int, cb: ReducerCallable) -> None:
        wrong = reference.compare_digest(cb.out, *refs[slot])
        if wrong:
            raise RuntimeError(f"reduce id {slot}: stream differs from the "
                               f"reference of its partition: {wrong}")

    # set-up: one warm-up round of every slot at once (it loads every
    # program the window will use, and is the first correctness check
    # under concurrency), the references sorted beside it, two at a time
    # (each holds its partition twice while it sorts). A reference is
    # compared byte for byte with its slot's warm-up stream, then kept
    # as (size, digest): see the module's word on the host's memory
    def sort_reference(slot: int, warming) -> None:
        ref = reference.sorted_stream(mof_root, job, map_ids, slot)
        kept = (ref.size, reference.digest(ref))
        wrong = reference.compare(warming.result()[1].out, ref)
        if wrong:
            raise RuntimeError(f"reduce id {slot}: warm-up stream differs "
                               f"from the reference of its partition: "
                               f"{wrong}")
        refs[slot] = kept

    t0 = time.perf_counter()
    with ThreadPoolExecutor(2, "bench-reference") as sorters, \
            ThreadPoolExecutor(slots, "bench-warm-up") as warm:
        warming = [warm.submit(transport, s, -1 - s) for s in range(slots)]
        sorting = [sorters.submit(sort_reference, s, warming[s])
                   for s in range(slots)]
        try:
            for f in warming:
                f.result()
            phases["warm_up_round_s"] = time.perf_counter() - t0
            for f in sorting:
                f.result()
        except Exception as e:  # noqa: BLE001 - set-up failed as a whole
            raise SetupError(f"warm-up round or reference: {e!r}") from e
    del warming, sorting
    phases["warm_up_and_reference_s"] = time.perf_counter() - t0
    phases["rss_after_warm_up_MB"] = _rss_mb()
    _trim_heap()
    phases["rss_after_trim_MB"] = _rss_mb()
    if ctx.trace:
        metrics.enable_spans()
    trace = DeviceTrace(os.path.join(ctx.work_dir, "trace")) \
        if ctx.trace and not ctx.rehearse else None

    slot_of = threading.local()       # the loop's threads number themselves
    claim = iter(range(slots))
    claim_lock = threading.Lock()

    def unit(index: int) -> dict:
        if not hasattr(slot_of, "n"):
            with claim_lock:
                slot_of.n = next(claim)
        slot = slot_of.n
        timed = contextlib.nullcontext
        session = contextlib.nullcontext()
        if trace is not None and index == 0:
            # the profiler's one task runs beside the other slots' tasks
            timed, session = trace.mark, trace.session()
        with session:
            with timed():
                record, cb = transport(slot, index)
        verify(slot, cb)                      # untimed, between its tasks
        return record

    # high-water marks of the program's gauges restart with the window
    # (a program without them has none to restart or to read)
    restart_peaks = getattr(metrics, "restart_gauge_peaks", None)
    if restart_peaks is not None:
        restart_peaks()
    counters0 = metrics.snapshot()
    builds0 = builds.builds
    setup_s = time.perf_counter() - ctx.t_start
    units = closed_loop(unit, ctx.seconds, slots)
    built = builds.builds - builds0
    phases["rss_after_window_MB"] = _rss_mb()
    counters1 = metrics.snapshot()
    spans = list(metrics.spans)
    metrics.disable_spans()

    out = outcome(device, units, setup_s, "task_wall_s", records * 100,
                  built, ctx.cell["chips"], builds.cache, phases)
    obs = out["obs"]
    obs["counters"] = {k: counters1[k] - counters0.get(k, 0.0)
                       for k in counters1}
    peaks = getattr(metrics, "gauge_peaks_snapshot", None)
    obs["gauge_peaks"] = peaks() if peaks is not None else {}
    obs["critical"] = critpath.per_task(spans)
    if "hbm_peak_MB" in obs["harness"]:
        from uda_tpu.utils.budget import device_bytes_estimate
        from uda_tpu.utils.config import Config

        # the admission model of every live slot over the measured peak
        obs["harness"]["hbm_model_ratio"] = slots * device_bytes_estimate(
            records * 100, int(Config().get("uda.tpu.key.width"))) \
            / device["memory_peak_bytes"]
    if trace is not None:
        stages = [s for s in spans if s["name"] != critpath.ROOT]
        trace_reduce.finish(out, trace, chips=ctx.cell["chips"], units=1,
                            host_spans=stages, bucket_of=critpath.bucket_of,
                            priority=critpath.BUCKET_PRIORITY)
    return out
