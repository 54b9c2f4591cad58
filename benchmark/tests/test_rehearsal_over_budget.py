"""The cell ``reduce_over_budget`` rehearsed on the CPU: the embedder
answers a 1 MB HBM budget there (the traffic file's
``rehearsal.hbm_budget_mb``), so the 3,600-record partition is over it
and goes the way the cell measures — three groups folded on the
"device", joined, emitted from the run files through the native
gather — and comes out as the blocked reference says. A program that
sizes no groups cannot run the configuration: the driver says so at
once."""

import json
import os
import subprocess
import sys
import types

import pytest

from benchmark.harness.loop import SetupError

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _rehearse(trace: int):
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "reduce_over_budget", "--seed", "3000000011",
         "--seconds", "1", "--trace", str(trace), "--rehearse-cpu"],
        capture_output=True, text=True, timeout=900, cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    report = next(json.loads(ln.split("benchmark: ", 1)[1])
                  for ln in proc.stderr.splitlines()
                  if ln.startswith('benchmark: {"cell"'))
    return line, report


def test_rehearsal_goes_through_three_groups():
    line, report = _rehearse(1)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1 and line["metrics"] == {}
    values = report["values"]
    assert values["merge_device_groups"] == 3
    assert values["admission_reroutes"] == 1
    assert values["stage_native_segments"] == 6
    assert values["emit_gather_native_slabs"] == 1
    assert values["compiles_in_window"] == 0
    for name in ("group_flush_s", "group_join_s", "run_spool_s"):
        assert values[name] >= 0, name
    assert values["run_spool_s"] > 0
    # the task booked one group's rows and its fold's temporaries
    assert 0 < values["hbm_ledger_peak_MB"] <= 1.05
    assert "emit_frame_s" not in values          # framed when spooled


def test_untraced_rehearsal_withholds_the_end_to_end_metrics():
    line, report = _rehearse(0)
    assert line["correct"] is True and line["metrics"] == {}
    assert set(report["values"]) == {"task_wall_s", "goodput_MBps",
                                     "setup_s"}


def test_a_program_without_groups_fails_at_once(monkeypatch, tmp_path):
    from benchmark.drivers import reduce_over_budget
    from uda_tpu.utils import budget

    monkeypatch.delattr(budget, "group_capacity_rows")
    ctx = types.SimpleNamespace(
        config={"job": "bench"}, traffic={"rehearsal": {}}, rehearse=True,
        root=ROOT, work_dir=str(tmp_path))
    with pytest.raises(SetupError, match="sizes no device groups"):
        reduce_over_budget.run(ctx)
    assert os.listdir(tmp_path) == []            # nothing was generated
