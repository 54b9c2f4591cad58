"""The manifest as PR 41 leaves it: nine cells, four of them four-chip
(``harness/manifest.py`` admits ``max(1, cells // 2)``); the deployment
``invindex_text`` with its cell ``reduce_invindex``, its four counter
metrics and a CPU rehearsal of its driver; and ``exchange_small``, the
last cell PR 23's benchmark left out, on ``terasort_exchange``. The
older ``test_manifest*.py`` files still count the cells of their day;
they are the accepted benchmark's and a PR that adds a cell may not
edit them."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.harness.manifest import Manifest
from benchmark.readers import counter

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL, SMALL = "reduce_invindex", "exchange_small"
NEW = {"overflow_fallbacks": "merge.overflow.fallbacks",
       "oversize_keys": "merge.overflow.keys",
       "overflow_resort_s": "overflow_resort_time",
       "overflow_rank_s": "overflow_rank_time"}
# the fallback's emit_batch never books these: the PR that keeps
# oversize keys on the forest appends the cell to them
SILENT = {"emit_readback_s", "emit_gather_s", "emit_gather_native_slabs"}


def test_the_manifest_validates_with_four_four_chip_cells_of_nine():
    m = Manifest(ROOT)
    m.validate()
    four = [c["name"] for c in m.cells.values() if c["chips"] == 4]
    assert four == ["exchange_ici4", "exchange_skew_ici4",
                    "exchange_dcn2_ici2", SMALL]
    assert len(m.cells) == 9 and len(four) == max(1, len(m.cells) // 2)
    assert list(m.cells)[-2:] == [CELL, SMALL]     # appended, nothing moved
    assert list(m.configs)[-1] == "invindex_text" and len(m.configs) == 7
    for name, config, traffic, chips in (
            (CELL, "invindex_text", "invindex_fanin1024", 1),
            (SMALL, "terasort_exchange", "resident_steps_small", 4)):
        cell = m.cells[name]
        assert (cell["config"], cell["traffic"], cell["chips"]) == (
            config, traffic, chips)
        assert len(cell["why"]) <= 200


def test_the_configuration_is_the_served_reduce_path_on_text_keys():
    m = Manifest(ROOT)
    cfg = m.config_file("invindex_text")
    base = m.config_file("terasort_reduce")
    entry = m.configs["invindex_text"]
    assert entry["source"] == cfg["source"] and len(cfg["source"]) <= 200
    assert set(entry["reduced"]) == set(cfg["reduced"]) == {"reduce_tasks",
                                                            "maps"}
    assert (cfg["driver"], cfg["reference"], cfg["chips"]) == (
        "reduce_text_task", "host_sort_text", 1)
    assert cfg["comparator"] == "org.apache.hadoop.io.Text"
    # INIT, the roles and the flags are terasort_reduce's, letter for letter
    assert cfg["init"] == base["init"] and cfg["roles"] == base["roles"]
    assert "uda.tpu.key.width stays 16" in cfg["flags"]
    assert cfg["flags"].replace("; uda.tpu.key.width stays 16", "") == \
        base["flags"]
    assert len(cfg["guarantees"]) == 5 and "stable" in cfg["guarantees"][1]
    traffic = m.traffic_file("invindex_fanin1024")
    fanin = m.traffic_file("fanin1024")
    assert (traffic["records"], traffic["maps"]) == (16_384_000, 1024)
    assert traffic["rehearsal"] == fanin["rehearsal"] == {"records": 3000,
                                                          "maps": 24}
    assert (traffic["concurrent_tasks"], traffic["loop"]) == (
        fanin["concurrent_tasks"], fanin["loop"])
    assert (traffic["driver"], traffic["generator"]) == ("reduce_text_task",
                                                         "invindex_mofs")


def test_the_small_traffic_is_resident_steps_at_two_to_the_twenty():
    m = Manifest(ROOT)
    small, flat = (m.traffic_file(t) for t in ("resident_steps_small",
                                               "resident_steps"))
    assert small.pop("records_per_chip") == 1 << 20
    assert small == flat
    assert m.config_file("terasort_exchange")["records_per_chip"] == 1 << 24


def test_the_cells_report_their_kind_s_metrics_and_the_four_new_ones():
    m = Manifest(ROOT)
    names = {x["name"] for x in m.metrics_of(CELL, "per_layer")}
    fanin = {x["name"] for x in m.metrics_of("reduce_fanin", "per_layer")}
    assert names - fanin == set(NEW)
    assert fanin - names == SILENT | {"emit_frame_s"}    # lists three cells
    assert {x["name"] for x in m.metrics_of(CELL, "end_to_end")} == {
        "task_wall_s", "goodput_MBps", "setup_s"}
    for name, series in NEW.items():
        entry, spec = m.metrics[name], m.layer_metric_file(name)
        assert entry["workloads"] == [CELL]
        assert (entry["layer"], entry["moves"], entry["source"]) == (
            "device merge", "task_wall_s", "program_counter")
        assert (spec["reader"], spec["counter"], spec["per"]) == (
            "counter", series, "unit")
    assert [x["name"] for x in m.doc["per_layer"]][-4:] == list(NEW)
    flat = {x["name"] for x in m.metrics_of("exchange_ici4", "per_layer")}
    assert {x["name"] for x in m.metrics_of(SMALL, "per_layer")} == flat
    assert {x["name"] for x in m.metrics_of(SMALL, "end_to_end")} == {
        "step_wall_s", "goodput_MBps", "setup_s"}


def test_a_program_without_the_counters_reports_none_of_the_four():
    # the parent: its fallback books no such series, so the window's
    # growth holds no such key and the line leaves the metrics out
    m = Manifest(ROOT)
    obs = {"units": [{"wall_s": 16.0}] * 3,
           "counters": {"merge.records": 3 * 16_384_000.0, "pack_time": 9.0}}
    for name in NEW:
        assert counter.read(m.layer_metric_file(name), obs) is None
    obs["counters"].update({"merge.overflow.fallbacks": 3.0,
                            "overflow_resort_time": 44.1})
    assert counter.read(m.layer_metric_file("overflow_fallbacks"), obs) == 1.0
    assert counter.read(m.layer_metric_file("overflow_resort_s"),
                        obs) == pytest.approx(14.7)


def _rehearse(cell: str) -> tuple:
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", cell, "--seed", "4100000007", "--seconds", "1",
         "--trace", "1", "--rehearse-cpu"],
        capture_output=True, text=True, timeout=900, cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["metrics"] == {}          # a CPU run reports no metric
    report = next(json.loads(ln.split("benchmark: ", 1)[1])
                  for ln in proc.stderr.splitlines()
                  if ln.startswith('benchmark: {"cell"'))
    return line, report["values"]


def test_rehearsal_of_the_text_driver_takes_the_fallback_every_task():
    line, values = _rehearse(CELL)
    assert line["device"] == {"platform": "cpu", "kind": "cpu", "count": 1}
    assert values["overflow_fallbacks"] == 1
    assert 1 <= values["oversize_keys"] <= 30      # of 3,000 records
    assert values["fetch_chunks"] == 24
    assert values["merge_device_runs"] == 0
    assert 0 < values["overflow_rank_s"] <= values["overflow_resort_s"]
    assert values["compiles_in_window"] == 0


def test_rehearsal_of_the_small_exchange_cell():
    line, values = _rehearse(SMALL)
    assert line["device"] == {"platform": "cpu", "kind": "cpu", "count": 4}
    assert values["compiles_in_window"] == 0
