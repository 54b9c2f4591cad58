"""Test environment: run all JAX work on a virtual 8-device CPU mesh so
multi-chip sharding logic is exercised without a TPU pod (SURVEY §4's
"implication": the reference had no multi-node-without-a-cluster story;
we fix that here). Must run before jax is first imported."""

import os

# Force CPU whatever the ambient environment selects: unit tests
# exercise sharding on 8 virtual devices, not the one real chip. The
# jax.config update covers an interpreter that imported jax before this
# file ran (backends are created lazily, so it still wins as long as no
# array has touched a device yet).
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# exercise the cache wiring the entry points rely on (off on the CPU —
# see compile_cache.enable)
from uda_tpu.utils import compile_cache  # noqa: E402

compile_cache.enable()


# -- metrics hygiene + chaos telemetry ---------------------------------------
# Every test ends with a pristine global Metrics (reset() also restores
# span/histogram enablement to the env default, so a test that called
# enable_spans() cannot leak recording into the next test). The
# per-test snapshots accumulate into a session-level counter sum that
# pytest_sessionfinish dumps as a telemetry JSON when
# UDA_TPU_CHAOS_TELEMETRY names a path (scripts/run_chaos.sh does),
# giving chaos runs one comparable telemetry block.

import collections  # noqa: E402

import pytest  # noqa: E402

_SESSION_COUNTERS: dict = collections.defaultdict(float)


@pytest.fixture(autouse=True)
def _failpoint_phase_reset():
    """Each test sees the ambient chaos schedule (UDA_FAILPOINTS, the
    run_chaos.sh rungs) from phase 0: trigger counters and seeded
    probability draws restart per test via the documented
    disarm-then-rearm idiom. Without this, whether an `every:N` error
    hits a given test depends on how many failpoint evaluations every
    EARLIER test consumed — suite composition becomes schedule phase
    (the PR 9 "suite doubling shifted failpoint phase" class), and a
    chaos-rung failure does not even reproduce standalone. Tests that
    arm their own scoped() schedules are unaffected (the scope saves
    and restores around this)."""
    from uda_tpu.utils.failpoints import failpoints

    for site, spec in failpoints.active().items():
        failpoints.disarm(site)
        failpoints.arm(site, spec)
    yield


@pytest.fixture(autouse=True)
def _metrics_hygiene():
    yield
    from uda_tpu.utils.metrics import metrics
    from uda_tpu.utils.resledger import PAIRED_GAUGES, resledger

    # paired-gauge balance: every +N on the increment-must-meet-
    # decrement set (fetch.on_air, stage.inflight.bytes, ...) must have
    # met its -N by test end — metrics.reset() starts each test at
    # zero, so a nonzero here is THIS test's leak, reported at the
    # leaking test instead of silently polluting a later assertion
    unbalanced = {
        name: val
        for name, val in metrics.gauges_snapshot().items()
        if name in PAIRED_GAUGES and abs(val) > 1e-9
    }
    # runtime obligation books (armed runs only, e.g. the chaos rungs
    # under UDA_TPU_RESLEDGER=1): anything still open is a leak —
    # drain() reports each with its acquire stack, counts
    # resledger.leaks and appends to UDA_TPU_RESLEDGER_JSON, and the
    # pop guarantees the NEXT test starts with empty books
    leaked = resledger.drain("test.teardown")
    for name, value in metrics.snapshot().items():
        _SESSION_COUNTERS[name] += value
    metrics.reset()
    # flight-recorder hygiene: events/dump bookkeeping are per-test
    # (the ring is process-global and always on), and a test that
    # configured a dump directory must not leak it into later tests'
    # dumps
    from uda_tpu.utils.flightrec import flightrec
    flightrec.reset()
    flightrec._dump_dir = ""
    # profiler hygiene: a test that armed the global sampling profiler
    # must not keep its daemon thread (and the thread-span registry
    # writes it enables) running into later tests' timing assertions
    from uda_tpu.utils.profiler import profiler
    profiler.stop()
    profiler.reset()
    # observability-plane hygiene: a test that armed the rollup ring
    # (and with it the anomaly detectors, SLI book, or OpenMetrics
    # endpoint) must not keep its sampler thread, listeners, or HTTP
    # port alive into later tests
    from uda_tpu.utils.timeseries import disarm_observability_plane
    disarm_observability_plane()
    if unbalanced or leaked:
        parts = []
        if unbalanced:
            parts.append(f"paired gauges not back to zero: {unbalanced}")
        if leaked:
            opened = ", ".join(sorted({r["pair"] for r in leaked}))
            parts.append(f"{len(leaked)} leaked resledger obligation(s) "
                         f"({opened}) — acquire stacks in the log")
        pytest.fail("resource-balance teardown: " + "; ".join(parts))


def pytest_sessionfinish(session, exitstatus):
    path = os.environ.get("UDA_TPU_CHAOS_TELEMETRY")
    if not path:
        return
    import json

    with open(path, "w") as f:
        json.dump({"counters": dict(sorted(_SESSION_COUNTERS.items()))},
                  f, indent=1, sort_keys=True)
