"""Driver ``exchange_pods_step``: the multi-chip sort step across pods,
back to back.

``exchange_step``'s loop on a mesh with a pod structure: the
configuration's ``mesh`` (``dcn:2,ici:2``) names two axes, rows are
sharded over BOTH — ``PartitionSpec(("dcn", "ici"))``, pod-major — and
each step is one ``distributed_terasort(words, mesh, ("dcn", "ici"))``
call with every argument at its default, so the program picks its round
body from the mesh (``exchange_step`` takes the mesh's first axis
alone, which on such a mesh is a two-device exchange). The input —
``records_per_chip`` TeraSort records a chip, generated on the device
from the seed — stays resident; a step ends in ``block_until_ready`` of
the result and ``res.check()``. Between steps, untimed, the
configuration's own on-device verifier checks the result at full size;
set-up also runs one byte-exact comparison with ``np.lexsort`` at a
size the host holds. The window's counters and gauge marks go to the
readers as ``exchange_skew_step`` hands them.
"""

from __future__ import annotations

import contextlib
import importlib
import os
import time

import numpy as np

from benchmark.harness import platform
from benchmark.harness.loop import (DeviceTrace, SetupError, closed_loop,
                                    outcome)
from benchmark.trace import reduce as trace_reduce

HOST_SPAN_PRIORITY = ("step", "verify")


def run(ctx) -> dict:
    cfg, traffic = ctx.config, ctx.traffic
    shape = dict(cfg, **traffic)
    if ctx.rehearse:
        shape.update(traffic["rehearsal"])
    per_chip, small = (shape["records_per_chip"],
                       shape["byte_exact_records_per_chip"])
    chips = ctx.cell["chips"]
    t0 = time.perf_counter()
    device = platform.gate(chips, ctx.rehearse)
    phases = {"backend_s": time.perf_counter() - t0}
    builds = platform.BuildCounter()

    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    from uda_tpu.models import terasort
    from uda_tpu.parallel.distributed import uniform_splitters
    from uda_tpu.parallel.mesh import mesh_from_config
    from uda_tpu.utils import compile_cache
    from uda_tpu.utils.config import Config
    from uda_tpu.utils.metrics import metrics

    compile_cache.enable()
    gen = importlib.import_module(f"benchmark.gen.{traffic['generator']}")
    verifier = importlib.import_module(
        f"benchmark.reference.{cfg['reference']}")
    mesh = mesh_from_config(Config({"uda.tpu.mesh.shape": cfg["mesh"]}))
    axis = tuple(mesh.axis_names)
    rows = NamedSharding(mesh, PartitionSpec(axis))
    splitters = uniform_splitters(chips)
    spans: list = []                # the harness's own host spans

    def step(words) -> tuple:
        """One step, timed: (wall seconds, result)."""
        t0 = time.perf_counter()
        res = terasort.distributed_terasort(words, mesh, axis)
        jax.block_until_ready(res.words)
        res.check()
        wall = time.perf_counter() - t0
        spans.append({"name": "step", "ts": t0, "dur": wall})
        return wall, res

    # byte for byte against the host, once, at a size the host holds
    t0 = time.perf_counter()
    words = gen.records(ctx.seed + 1, chips * small, rows)
    _, res = step(words)
    wrong = verifier.byte_exact(np.asarray(words), np.asarray(res.words),
                                np.asarray(res.valid_counts).reshape(-1),
                                splitters)
    if wrong:
        raise SetupError(f"byte-exact check at {small} records a chip: "
                         f"{wrong}")
    del words, res
    phases["byte_exact_s"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    words = jax.block_until_ready(
        gen.records(ctx.seed, chips * per_chip, rows))
    phases["generate_s"] = time.perf_counter() - t0

    def verified_step(index: int, timed=contextlib.nullcontext) -> dict:
        with timed():
            wall, res = step(words)
        t0 = time.perf_counter()
        verdict = verifier.device_check(words, res.words, res.valid_counts,
                                        splitters, chips)
        spans.append({"name": "verify", "ts": t0,
                      "dur": time.perf_counter() - t0})
        if any(verdict.values()):
            raise RuntimeError(f"step output is wrong: {verdict}")
        return {"wall_s": wall}

    t0 = time.perf_counter()
    try:
        verified_step(-1)          # loads every program the window uses
    except RuntimeError as e:
        raise SetupError(f"warm-up step: {e}") from e
    phases["warm_up_step_s"] = time.perf_counter() - t0
    trace = DeviceTrace(os.path.join(ctx.work_dir, "trace")) \
        if ctx.trace and not ctx.rehearse else None
    traced = traffic["traced_steps"]
    del spans[:]
    metrics.restart_gauge_peaks()
    counters0 = metrics.snapshot()
    builds0 = builds.builds
    setup_s = time.perf_counter() - ctx.t_start
    if trace is not None:
        # the first few steps of the window run inside the trace, each
        # under a marker of its own: the verifier's device time between
        # them is not the step's
        deadline = time.perf_counter() + ctx.seconds
        with trace.session():
            units = [verified_step(i, trace.mark) for i in range(traced)]
        units += closed_loop(verified_step,
                             max(0.0, deadline - time.perf_counter()))
    else:
        units = closed_loop(verified_step, ctx.seconds)
    built = builds.builds - builds0
    counters1 = metrics.snapshot()

    out = outcome(device, units, setup_s, "step_wall_s",
                  chips * per_chip * 100, built, chips, builds.cache, phases)
    obs = out["obs"]
    obs["shapes"] = {"records_per_chip": per_chip}
    obs["counters"] = {k: counters1[k] - counters0.get(k, 0.0)
                       for k in counters1}
    obs["gauge_peaks"] = metrics.gauge_peaks_snapshot()
    if trace is not None:
        from benchmark.trace.reduce import peaks_for

        obs["peaks"] = peaks_for(device["kind"])
        trace_reduce.finish(out, trace, chips=chips, units=traced,
                            host_spans=spans, bucket_of=lambda name: name,
                            priority=HOST_SPAN_PRIORITY)
    return out
