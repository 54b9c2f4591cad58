"""Driver ``exchange_skew_step``: the multi-chip sort step on keys of
unknown distribution, back to back.

``exchange_step``'s shape with other keys and the job's other statement
about them: the input — ``records_per_chip`` records a chip with Zipf id
keys, generated on the device from the seed and already row-sharded —
stays resident; each step is one ``distributed_terasort(words, mesh,
axis, splitters="sampled")`` call, so the program samples its input and
chooses its splitters inside every timed step, ending in
``block_until_ready`` of the result and ``res.check()``. Between steps,
untimed, the benchmark's own on-device verifier holds the result to the
sort's contract at full size (it knows nothing of the splitters chosen).

Set-up runs two byte-exact comparisons with ``np.lexsort`` at a size the
host holds: the step as the window runs it, and the same input through
the same entry with the credit window cut to an eighth, so that the
fused attempt overflows and the windowed rounds — the route a hot key
takes at the deployment's own scale — run on the chip, untimed; their
result must also equal the fused one row for row.
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


def _valid_rows(res) -> list:
    """The result's shards on the host, valid rows only."""
    nvalid = np.asarray(res.valid_counts).reshape(-1)
    out = np.asarray(res.words).reshape(len(nvalid), -1, res.words.shape[-1])
    return [out[d, :nvalid[d]] for d in range(len(nvalid))]


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
    from uda_tpu.parallel.mesh import mesh_from_config
    from uda_tpu.utils import compile_cache
    from uda_tpu.utils.config import Config
    from uda_tpu.utils.metrics import metrics

    compile_cache.enable()
    gen = importlib.import_module(f"benchmark.gen.{traffic['generator']}")
    verifier = importlib.import_module(
        f"benchmark.reference.{cfg['reference']}")
    mesh = mesh_from_config(Config({"uda.tpu.mesh.shape": cfg["mesh"]}))
    axis = mesh.axis_names[0]
    rows = NamedSharding(mesh, PartitionSpec(axis))
    spans: list = []                # the harness's own host spans

    def step(words, **window) -> tuple:
        """One step, timed: (wall seconds, result)."""
        t0 = time.perf_counter()
        res = terasort.distributed_terasort(words, mesh, axis,
                                            splitters="sampled", **window)
        jax.block_until_ready(res.words)
        res.check()
        wall = time.perf_counter() - t0
        spans.append({"name": "step", "ts": t0, "dur": wall})
        return wall, res

    # byte for byte against the host, once, at a size the host holds
    t0 = time.perf_counter()
    words = gen.records(ctx.seed + 1, chips * small, rows)
    _, res = step(words)
    host_words = np.asarray(words)
    wrong = verifier.byte_exact(host_words, np.asarray(res.words),
                                np.asarray(res.valid_counts).reshape(-1))
    if wrong:
        raise SetupError(f"byte-exact check at {small} records a chip: "
                         f"{wrong}")
    fused = _valid_rows(res)
    phases["byte_exact_s"] = time.perf_counter() - t0

    # the overflow route, once, untimed: same input, same entry, a window
    # an eighth of the default — the fused attempt overflows, the rounds
    # run, and the result is the same rows
    t0 = time.perf_counter()
    check = traffic["overflow_check"]
    window = max(1, 2 * chips * small // (chips * chips)
                 // check["window_divisor"])
    before = metrics.snapshot()
    _, res = step(words, capacity=window)
    after = metrics.snapshot()
    rounds = after.get("exchange.rounds", 0) - before.get("exchange.rounds", 0)
    reruns = (after.get("exchange.fused.overflow_reruns", 0)
              - before.get("exchange.fused.overflow_reruns", 0))
    wrong = verifier.byte_exact(host_words, np.asarray(res.words),
                                np.asarray(res.valid_counts).reshape(-1))
    if not wrong and (reruns != 1 or rounds < check["least_rounds"]):
        wrong = (f"{reruns} overflow rerun(s), {rounds} round(s): the "
                 f"window of {window} rows did not send the step through "
                 f"at least {check['least_rounds']} rounds")
    if not wrong and not all(np.array_equal(a, b) for a, b in
                             zip(fused, _valid_rows(res))):
        wrong = "the rounds' shards differ from the fused step's"
    if wrong:
        raise SetupError(f"overflow route at {small} records a chip, "
                         f"window {window}: {wrong}")
    del words, host_words, res, fused
    phases["overflow_route_s"] = time.perf_counter() - t0
    phases["overflow_route_rounds"] = rounds

    t0 = time.perf_counter()
    words = jax.block_until_ready(
        gen.records(ctx.seed, chips * per_chip, rows))
    phases["generate_s"] = time.perf_counter() - t0

    def verified_step(index: int, timed=contextlib.nullcontext) -> dict:
        with timed():
            wall, res = step(words)
        t0 = time.perf_counter()
        verdict = verifier.device_check(words, res.words, res.valid_counts,
                                        chips)
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
