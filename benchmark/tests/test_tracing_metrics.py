"""The per-layer metrics PR 24 added read what the program really
records."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.harness.manifest import Manifest
from benchmark.trace import critpath

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NEW = ("emit_readback_s", "emit_gather_s", "emit_frame_s", "emit_deliver_s",
       "task_open_s", "no_span_critical_s", "other_span_critical_s")
EMIT = NEW[:4]


@pytest.fixture(scope="module")
def report():
    """The stderr report of one traced rehearsal task: ``values`` are
    the per-layer readings, ``critical_of_first`` the first task's wall
    partition."""
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "reduce_fanin", "--seed", "2147483659",
         "--seconds", "1", "--trace", "1", "--rehearse-cpu"],
        capture_output=True, text=True, timeout=900, cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return next(json.loads(ln.split("benchmark: ", 1)[1])
                for ln in proc.stderr.splitlines()
                if ln.startswith('benchmark: {"cell"'))


def test_manifest_holds_the_new_metrics_for_both_reduce_cells():
    m = Manifest(ROOT)
    m.validate()
    assert [e["name"] for e in m.doc["per_layer"][-len(NEW):]] == list(NEW)
    for name in NEW:
        entry = m.metrics[name]
        assert entry["workloads"] == ["reduce_wide", "reduce_fanin"]
        assert (entry["unit"], entry["better"], entry["moves"]) == \
            ("s", "lower", "task_wall_s")


@pytest.mark.parametrize("name", NEW)
def test_new_metric_reads_what_the_program_records(name, report):
    m = Manifest(ROOT)
    spec = m.layer_metric_file(name)
    assert os.path.exists(os.path.join(m.bench, "readers",
                                       spec["reader"] + ".py"))
    if spec["reader"] == "counter":
        assert m.metrics[name]["source"] == "program_counter"
        assert spec["per"] == "unit"
    else:
        assert spec["reader"] == "critpath_bucket"
        assert m.metrics[name]["source"] == "program_span"
        partition = report["critical_of_first"][0]
        assert set(spec["buckets"]) <= set(partition)
        assert set(spec["buckets"]) <= set(critpath.BUCKET_PRIORITY
                                           + ("idle",))
    # the reader found the counter or the bucket after a real task
    assert report["values"][name] >= 0.0


def test_emit_timers_fall_into_the_frozen_tables_other(report):
    """The benchmark's bucket table does not know the new span names:
    they are charged to ``other``, so ``unattributed_critical_s`` (other
    + idle) keeps reading what it read, and the emit counters (busy, one
    thread) are critical time under ``other``."""
    for timer in ("emit_readback", "emit_gather", "emit_frame",
                  "emit_deliver"):
        assert critpath.bucket_of(timer) == "other"
    assert report["critical_of_first"][0]["other"] > 0.0
    assert sum(report["values"][n] for n in EMIT) > 0.0

