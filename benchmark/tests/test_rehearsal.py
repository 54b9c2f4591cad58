"""``run.py`` end to end on the CPU: every cell at its rehearsal size,
the contract's last line, and the refusal to measure without a TPU."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.harness.manifest import Manifest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELLS = sorted(Manifest(ROOT).cells)


def _run(*args, env=None):
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), *args],
        capture_output=True, text=True, timeout=900, cwd=ROOT,
        env=dict(os.environ, **(env or {})))


@pytest.mark.parametrize("trace", (0, 1))
@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_prints_the_contract_line(cell, trace):
    proc = _run("--workload", cell, "--seed", "7", "--seconds", "1",
                "--trace", str(trace), "--rehearse-cpu")
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    m = Manifest(ROOT)
    assert line["device"] == {"platform": "cpu", "kind": "cpu",
                              "count": m.cells[cell]["chips"]}
    assert line["metrics"] == {}          # a CPU run reports no metric
    withheld = next(json.loads(ln.split("rehearsal ", 1)[1])["withheld"]
                    for ln in proc.stderr.splitlines()
                    if ln.startswith("benchmark: rehearsal"))
    declared = {x["name"] for x in m.metrics_of(
        cell, "per_layer" if trace else "end_to_end")}
    if trace:
        # what no CPU run can read: the device trace and device memory
        on_cpu = {x["name"] for x in m.metrics_of(cell, "per_layer")
                  if x["source"] != "device_trace"
                  and x["name"] not in ("hbm_peak_MB", "hbm_model_ratio")}
        assert set(withheld) == on_cpu
        report = next(json.loads(ln.split("benchmark: ", 1)[1])
                      for ln in proc.stderr.splitlines()
                      if ln.startswith('benchmark: {"cell"'))
        assert report["values"]["compiles_in_window"] == 0
    else:
        assert set(withheld) == declared


def test_without_a_tpu_nothing_is_printed():
    proc = _run("--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                "--trace", "0", env={"JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 3
    assert proc.stdout.strip() == ""
    assert "needs" in proc.stderr


def test_unknown_cell_is_refused():
    proc = _run("--workload", "nope", "--seed", "1", "--seconds", "1")
    assert proc.returncode == 2 and proc.stdout.strip() == ""
