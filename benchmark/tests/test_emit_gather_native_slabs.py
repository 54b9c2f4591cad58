"""``emit_gather_native_slabs`` (PR 25) reads the program's engagement
counter: every slab of a task, when the native slab gather ran."""

import json
import os
import subprocess
import sys

from benchmark.harness.manifest import Manifest
from benchmark.readers import counter

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = "emit_gather_native_slabs"


def test_manifest_entry_and_reader():
    m = Manifest(ROOT)
    m.validate()
    entry = m.metrics[NAME]
    assert entry["workloads"] == ["reduce_wide", "reduce_fanin"]
    assert (entry["unit"], entry["better"], entry["source"], entry["layer"],
            entry["moves"]) == ("count", "higher", "program_counter", "emit",
                                "task_wall_s")
    spec = m.layer_metric_file(NAME)
    assert (spec["reader"], spec["counter"], spec["per"]) == \
        ("counter", "emit.gather.native_slabs", "unit")
    # a program without the counter (the parent of PR 25): nothing to
    # read, the line leaves the metric out
    assert counter.read(spec, {"counters": {"emit_gather_time": 1.0},
                               "units": [{}]}) is None
    assert counter.read(spec, {"counters": {spec["counter"]: 40.0},
                               "units": [{}, {}]}) == 20.0


def test_reads_every_slab_of_a_rehearsal_task():
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "reduce_fanin", "--seed", "2147483693",
         "--seconds", "1", "--trace", "1", "--rehearse-cpu"],
        capture_output=True, text=True, timeout=900, cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-3000:]
    report = next(json.loads(ln.split("benchmark: ", 1)[1])
                  for ln in proc.stderr.splitlines()
                  if ln.startswith('benchmark: {"cell"'))
    # 3,000 rehearsal records: one slab a task, gathered natively
    assert report["values"][NAME] == 1.0
    assert report["values"]["emit_gather_s"] > 0.0
