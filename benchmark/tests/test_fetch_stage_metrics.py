"""PR 36's eight ``fetch and wire`` metrics: a chunk's fetch latency by
stage (supplier park, supplier serve, wire, dispatch-queue wait — chunk-
seconds), the crack and ``feed()``'s backpressure wait (thread-seconds)
and the one upcall thread's busy seconds, each a counter of the
program's hub read per task in the four reduce cells.

Written so that a later append does not fail it: nothing here asserts
where an entry sits in a list, or how long a list is."""

import os

import pytest

from benchmark.harness.manifest import Manifest
from benchmark.readers import counter

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
REDUCE_CELLS = ("reduce_wide", "reduce_fanin", "reduce_slots4",
                "reduce_over_budget")
COUNTER_OF = {
    "fetch_chunks": "fetch.chunks",
    "fetch_supplier_park_s": "fetch.chunk.park_seconds",
    "fetch_supplier_serve_s": "fetch.chunk.serve_seconds",
    "fetch_wire_s": "fetch.chunk.wire_seconds",
    "fetch_dispatch_wait_s": "fetch.chunk.dispatch_wait_seconds",
    "fetch_crack_s": "fetch_crack_time",
    "fetch_feed_wait_s": "fetch_feed_wait_time",
    "dispatch_busy_s": "net.dispatch.busy_seconds",
}


@pytest.fixture(scope="module")
def manifest():
    m = Manifest(ROOT)
    m.validate()
    return m


@pytest.mark.parametrize("name", sorted(COUNTER_OF))
def test_manifest_entry_file_and_cells(manifest, name):
    entry = manifest.metrics[name]
    assert (entry["better"], entry["source"], entry["layer"],
            entry["moves"]) == ("lower", "program_counter",
                                "fetch and wire", "task_wall_s")
    assert entry["unit"] == ("count" if name == "fetch_chunks" else "s")
    assert set(REDUCE_CELLS) <= set(entry["workloads"])
    for cell in REDUCE_CELLS:
        assert name in {m["name"]
                        for m in manifest.metrics_of(cell, "per_layer")}
    spec = manifest.layer_metric_file(name)
    assert (spec["reader"], spec["counter"], spec["per"]) == \
        ("counter", COUNTER_OF[name], "unit")
    # the layer's name, letter for letter, is the accepted metric's
    assert entry["layer"] == manifest.metrics["fetch_critical_s"]["layer"]


@pytest.mark.parametrize("name", sorted(COUNTER_OF))
def test_the_program_registers_the_counter(name):
    """A ``layer_metrics`` file may only name a counter the program
    declares: a dotted name in ``METRICS_REGISTRY``, or ``<timer>_time``
    of a timer the reduce path enters."""
    from uda_tpu.utils.metrics import METRICS_REGISTRY
    c = COUNTER_OF[name]
    if c.endswith("_time"):
        from uda_tpu.utils import critpath
        assert c[:-len("_time")] in critpath.SPAN_BUCKETS
    else:
        assert METRICS_REGISTRY[c][0] == "counter"


@pytest.mark.parametrize("name", sorted(COUNTER_OF))
def test_reader_returns_nothing_for_a_program_without_the_counter(
        manifest, name):
    spec = manifest.layer_metric_file(name)
    # the parent of PR 36: no such counter in the snapshot -> no value,
    # no raise; the line leaves the metric out
    assert counter.read(spec, {"counters": {"emit_gather_time": 1.0},
                               "units": [{}]}) is None
    assert counter.read(spec, {"counters": {spec["counter"]: 3.0},
                               "units": [{}, {}]}) == 1.5
    # a stage that took no time reads 0, not nothing
    assert counter.read(spec, {"counters": {spec["counter"]: 0.0},
                               "units": [{}]}) == 0.0
