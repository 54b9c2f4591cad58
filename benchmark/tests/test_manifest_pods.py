"""The manifest as PR 38 leaves it: seven cells, three of them four-chip
— all that ``harness/manifest.py`` admits (``max(1, cells // 2)``) until
there is an eighth cell — and the cell ``exchange_dcn2_ici2`` with its
configuration, its four counter metrics and a CPU rehearsal of its
driver. ``test_manifest.py`` and ``test_manifest_cells.py`` still name
the four-chip cells of their day; those files are the accepted
benchmark's and a PR that adds a cell may not edit them."""

import json
import os
import subprocess
import sys

from benchmark.harness.manifest import Manifest
from benchmark.readers import counter

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "exchange_dcn2_ici2"
NEW = {"exchange_dcn_bytes": "exchange.dcn.bytes",
       "exchange_dcn_messages": "exchange.dcn.messages",
       "exchange_ici_bytes": "exchange.ici.bytes",
       "exchange_wire_bytes": "exchange.wire.bytes"}


def test_the_manifest_validates_with_three_four_chip_cells_of_seven():
    m = Manifest(ROOT)
    m.validate()
    four = [c["name"] for c in m.cells.values() if c["chips"] == 4]
    assert four == ["exchange_ici4", "exchange_skew_ici4", CELL]
    assert len(m.cells) == 7 and len(four) == max(1, len(m.cells) // 2)
    cell = m.cells[CELL]
    assert (cell["config"], cell["traffic"]) == ("terasort_exchange_pods",
                                                 "resident_steps_pods")
    assert len(cell["why"]) <= 200
    assert list(m.cells)[-1] == CELL            # appended, nothing moved


def test_the_pods_configuration_is_the_exchange_configuration_on_two_pods():
    m = Manifest(ROOT)
    cfg = m.config_file("terasort_exchange_pods")
    base = m.config_file("terasort_exchange")
    entry = m.configs["terasort_exchange_pods"]
    assert entry["source"] == cfg["source"] and len(cfg["source"]) <= 200
    assert set(entry["reduced"]) == set(cfg["reduced"]) == {
        "pods", "chips_per_pod", "records_per_chip"}
    assert (cfg["mesh"], cfg["pods"], cfg["chips_per_pod"]) == (
        "dcn:2,ici:2", 2, 2)
    assert cfg["records_per_chip"] == 1 << 22
    # record, generator, splitters and guarantees are terasort_exchange's
    assert cfg["record"] == base["record"]
    assert cfg["guarantees"][:len(base["guarantees"])] == base["guarantees"]
    assert "coalesced" in cfg["guarantees"][-1]
    assert set(base["assumed"]) <= set(cfg["assumed"])
    assert (cfg["driver"], cfg["reference"]) == ("exchange_pods_step",
                                                 "exchange_verify_pods")
    traffic = m.traffic_file("resident_steps_pods")
    flat = m.traffic_file("resident_steps")
    assert {k: traffic[k] for k in ("generator", "traced_steps",
                                    "byte_exact_records_per_chip",
                                    "rehearsal")} == \
        {k: flat[k] for k in ("generator", "traced_steps",
                              "byte_exact_records_per_chip", "rehearsal")}


def test_the_cell_reports_the_exchange_cells_metrics_and_its_four():
    m = Manifest(ROOT)
    names = {x["name"] for x in m.metrics_of(CELL, "per_layer")}
    flat = {x["name"] for x in m.metrics_of("exchange_ici4", "per_layer")}
    assert flat <= names
    assert names - flat == set(NEW) | {"exchange_merged_runs",
                                       "sort_carried_passes",
                                       "exchange_overflow_reruns"}
    assert {x["name"] for x in m.metrics_of(CELL, "end_to_end")} == {
        "step_wall_s", "goodput_MBps", "setup_s"}
    for name, series in NEW.items():
        entry, spec = m.metrics[name], m.layer_metric_file(name)
        assert entry["workloads"] == [CELL]
        assert (entry["layer"], entry["moves"], entry["source"]) == (
            "exchange", "step_wall_s", "program_counter")
        assert (spec["reader"], spec["counter"], spec["per"]) == (
            "counter", series, "unit")
    assert [x["name"] for x in m.doc["per_layer"]][-4:] == list(NEW)


def test_a_program_that_books_no_fabric_reports_none_of_the_four():
    # the parent: its fused step writes none of these series, so the
    # window's growth holds no such key and the line leaves them out
    m = Manifest(ROOT)
    obs = {"units": [{"wall_s": 1.0}] * 3,
           "counters": {"exchange.merge.runs": 12.0}}
    for name in NEW:
        assert counter.read(m.layer_metric_file(name), obs) is None
    obs["counters"]["exchange.dcn.messages"] = 6.0
    assert counter.read(m.layer_metric_file("exchange_dcn_messages"),
                        obs) == 2.0


def test_rehearsal_of_the_pods_driver_books_two_transfers_a_step():
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", "4300000007", "--seconds", "1",
         "--trace", "1", "--rehearse-cpu"],
        capture_output=True, text=True, timeout=900, cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"] == {"platform": "cpu", "kind": "cpu", "count": 4}
    assert line["metrics"] == {}          # a CPU run reports no metric
    report = next(json.loads(ln.split("benchmark: ", 1)[1])
                  for ln in proc.stderr.splitlines()
                  if ln.startswith('benchmark: {"cell"'))
    values = report["values"]
    assert values["exchange_dcn_messages"] == 2       # pod pairs, not 8
    assert values["exchange_overflow_reruns"] == 0
    assert values["compiles_in_window"] == 0
    rows = 4 * 1024                     # the rehearsal's records, in all
    # about half the rows change pods; every row off its chip is booked
    assert 0.4 * rows * 104 < values["exchange_dcn_bytes"] < 0.6 * rows * 104
    assert values["exchange_ici_bytes"] > values["exchange_dcn_bytes"]
    # 18 x capacity rows of 27 words a chip, capacity 2n/p^2 = 512
    assert values["exchange_wire_bytes"] == 4 * 18 * 512 * 27 * 4
