"""The manifest and every data file parse and cross-reference; the
benchmark grows by new files and entries alone."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark.harness.manifest import (NAME, UNIT, Manifest,
                                        ManifestError)

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def test_manifest_cross_references():
    m = Manifest(ROOT)
    m.validate()
    assert m.doc["command"] == ["python3", "benchmark/run.py"]
    assert m.doc["paths"] == ["benchmark"]
    for cell in m.cells.values():
        assert len(cell["why"]) <= 200
        assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    four = [c["name"] for c in m.cells.values() if c["chips"] == 4]
    assert four == ["exchange_ici4"]


def test_names_and_units_use_permitted_characters():
    m = Manifest(ROOT)
    for entry in m.doc["end_to_end"] + m.doc["per_layer"]:
        assert NAME.match(entry["name"]) and UNIT.match(entry["unit"])
        allowed = {"name", "unit", "better", "source", "workloads",
                   "bound" if "bound" in entry else "layer",
                   "moves" if "moves" in entry else "bound"}
        assert set(entry) <= allowed, entry
    assert not NAME.match("goodput_MB/s") and UNIT.match("MB/s")
    bench = os.path.join(ROOT, "benchmark")
    for d, _, files in os.walk(bench):
        if "__pycache__" in d:
            continue
        for f in files:
            rel = os.path.relpath(os.path.join(d, f), bench)
            assert all(c.isalnum() or c in "_.-/" for c in rel), rel


def test_every_layer_metric_file_names_a_reader():
    m = Manifest(ROOT)
    on_disk = {f[:-5] for f in os.listdir(os.path.join(m.bench,
                                                       "layer_metrics"))}
    assert on_disk == {e["name"] for e in m.doc["per_layer"]}
    for name in on_disk:
        spec = m.layer_metric_file(name)
        assert os.path.exists(os.path.join(m.bench, "readers",
                                           spec["reader"] + ".py")), name


def test_a_breach_is_named(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    doc = json.loads((tmp_path / "BENCHMARK.json").read_text())
    doc["workloads"][0]["config"] = "no_such_config"
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))
    with pytest.raises(ManifestError, match="no_such_config"):
        Manifest(str(tmp_path)).validate()


def _digest(root) -> dict:
    out = {}
    for d, _, files in os.walk(root):
        if "__pycache__" in d:
            continue
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hash(fh.read())
    return out


def test_grows_by_new_files_and_entries_alone(tmp_path):
    """A cell with two concurrent tasks, a configuration and a
    counter-backed per-layer metric, added to a temporary copy as new
    files plus manifest entries: no file that existed is edited, the
    manifest still cross-references, and the new cell runs end to end
    and reads the new metric."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    bench = tmp_path / "benchmark"
    shutil.copytree(os.path.join(ROOT, "benchmark"), bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "uda_tpu"), tmp_path / "uda_tpu")
    before = _digest(bench)

    cfg = json.loads((bench / "configs" / "terasort_reduce.json").read_text())
    cfg.update(name="terasort_reduce_b", job="benchb",
               source=cfg["source"] + " (second deployment)")
    (bench / "configs" / "terasort_reduce_b.json").write_text(json.dumps(cfg))
    traffic = json.loads((bench / "traffic" / "wide64.json").read_text())
    traffic.update(concurrent_tasks=2, rehearsal={"records": 1500, "maps": 3})
    (bench / "traffic" / "pair2.json").write_text(json.dumps(traffic))
    (bench / "layer_metrics" / "merged_records.json").write_text(json.dumps({
        "name": "merged_records", "layer": "device merge", "unit": "count",
        "moves": "task_wall_s", "reader": "counter",
        "counter": "merge.records", "per": "unit"}))
    doc = json.loads((tmp_path / "BENCHMARK.json").read_text())
    doc["configs"].append({
        "name": "terasort_reduce_b", "source": cfg["source"],
        "file": "benchmark/configs/terasort_reduce_b.json",
        "reduced": ["reduce_tasks", "maps"], "why": "a second deployment"})
    doc["workloads"].append({
        "name": "reduce_pair", "config": "terasort_reduce_b",
        "traffic": "pair2", "chips": 1, "why": "two reduce slots"})
    doc["per_layer"].append({
        "name": "merged_records", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "device merge",
        "moves": "task_wall_s", "workloads": ["reduce_pair"]})
    for m in doc["end_to_end"] + doc["per_layer"]:
        if "workloads" in m and "reduce_wide" in m["workloads"]:
            m["workloads"].append("reduce_pair")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))

    after = _digest(bench)
    assert {k: v for k, v in after.items() if k in before} == before
    Manifest(str(tmp_path)).validate()
    proc = subprocess.run(
        [sys.executable, str(bench / "run.py"), "--workload", "reduce_pair",
         "--seed", "5", "--seconds", "1", "--trace", "1", "--rehearse-cpu"],
        capture_output=True, text=True, timeout=600, cwd=tmp_path)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["failed"] == 0 and line["attempted"] >= 2
    report = next(json.loads(ln.split("benchmark: ", 1)[1])
                  for ln in proc.stderr.splitlines()
                  if ln.startswith('benchmark: {"cell"'))
    assert report["values"]["merged_records"] == 1500
    assert "pack_critical_s" in report["values"]
