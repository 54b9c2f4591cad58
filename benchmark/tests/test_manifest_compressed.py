"""The manifest as PR 46 leaves it: ten cells, six on one chip and four
on four; the deployment ``invindex_text_compressed`` with its one cell
``reduce_invindex_compressed`` — ``reduce_invindex``'s partition with
map-output compression on —, its six counter metrics, and a CPU
rehearsal of the unedited ``reduce_text_task`` driver on it. The older
``test_manifest*.py`` files still count the cells of their day; they
are the accepted benchmark's and a PR that adds a cell may not edit
them."""

import json
import os
import subprocess
import sys

from benchmark.harness.manifest import Manifest
from benchmark.readers import counter

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL, CONTROL = "reduce_invindex_compressed", "reduce_invindex"
NEW = {"fetch_inflate_s": "fetch_inflate_time",
       "fetch_inflate_blocks": "decompress.blocks",
       "fetch_compressed_bytes": "decompress.wire_bytes",
       "fetch_inflated_bytes": "decompress.bytes",
       "fetch_inflate_carry_bytes": "decompress.carry_bytes",
       "fetch_inner_fetches": "decompress.fetches"}


def test_the_manifest_validates_with_ten_cells_four_of_them_four_chip():
    m = Manifest(ROOT)
    m.validate()
    chips = [c["chips"] for c in m.cells.values()]
    assert len(chips) == 10 and chips.count(4) == 4 and chips.count(1) == 6
    assert list(m.cells)[-1] == CELL              # appended, nothing moved
    assert list(m.configs)[-1] == "invindex_text_compressed"
    assert len(m.configs) == 8
    cell = m.cells[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "invindex_text_compressed", "invindex_compressed_fanin1024", 1)
    assert len(cell["why"]) <= 200 and CONTROL in cell["why"]
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


def test_the_configuration_is_invindex_text_with_compression_on():
    m = Manifest(ROOT)
    cfg = m.config_file("invindex_text_compressed")
    base = m.config_file("invindex_text")
    entry = m.configs["invindex_text_compressed"]
    assert entry["source"] == cfg["source"] and len(cfg["source"]) <= 200
    assert set(entry["reduced"]) == set(cfg["reduced"]) == {"reduce_tasks",
                                                            "maps"}
    assert (cfg["driver"], cfg["reference"]) == (
        "reduce_text_task", "host_sort_text_compressed")
    # fixed, letter for letter: the records, the keys, the comparator,
    # the roles, the flags, the cut
    for key in ("chips", "job", "key_distribution", "comparator", "flags",
                "roles", "reduce_tasks", "maps", "reduced"):
        assert cfg[key] == base[key], key
    for key in ("key", "value", "frame", "eof_marker"):
        assert cfg["record"][key] == base["record"][key], key
    # new: INIT parameters 7 and 8
    assert cfg["init"] == dict(
        base["init"], codec="org.apache.hadoop.io.compress.SnappyCodec",
        codec_block_bytes=262144)
    assert cfg["guarantees"][:5] == base["guarantees"]
    assert len(cfg["guarantees"]) == 7
    assert cfg["assumed"][-len(base["assumed"]):] == base["assumed"]
    assert "218,422" in cfg["compression"]["stream"]
    traffic = m.traffic_file("invindex_compressed_fanin1024")
    plain = m.traffic_file("invindex_fanin1024")
    for key in ("driver", "concurrent_tasks", "records", "maps"):
        assert traffic[key] == plain[key], key
    assert traffic["generator"] == "invindex_mofs_compressed"
    assert traffic["rehearsal"] == {"records": 150000, "maps": 6}


def test_the_cell_reports_what_its_control_reports_and_six_more():
    m = Manifest(ROOT)
    for group in ("end_to_end", "per_layer"):
        mine = [x["name"] for x in m.metrics_of(CELL, group)]
        control = [x["name"] for x in m.metrics_of(CONTROL, group)]
        assert [n for n in mine if n not in NEW] == control, group
    per_layer = [x["name"] for x in m.doc["per_layer"]]
    assert per_layer[-6:] == list(NEW)            # appended, nothing moved
    for name, source in NEW.items():
        entry, spec = m.metrics[name], m.layer_metric_file(name)
        assert entry["workloads"] == [CELL]
        assert (entry["layer"], entry["moves"], entry["source"]) == (
            "fetch and wire", "task_wall_s", "program_counter")
        assert (spec["reader"], spec["counter"], spec["per"]) == (
            "counter", source, "unit")
        # a program without the counter (every parent of this PR, but
        # for decompress.bytes) reports nothing, and does not raise
        assert counter.read(spec, {"counters": {}, "units": [1, 2]}) is None
        assert counter.read(spec, {"counters": {source: 6.0},
                                   "units": [1, 2]}) == 3.0


def test_rehearsal_of_the_cell_inflates_what_the_generator_wrote(tmp_path):
    from benchmark.gen import invindex_mofs_compressed as gen

    seed = 4600000031
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", str(seed), "--seconds", "1",
         "--trace", "1", "--rehearse-cpu"],
        capture_output=True, text=True, timeout=900, cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1 and line["metrics"] == {}
    report = next(json.loads(ln.split("benchmark: ", 1)[1])
                  for ln in proc.stderr.splitlines()
                  if ln.startswith('benchmark: {"cell"'))
    values = report["values"]
    part = gen.generate(str(tmp_path), "bench", seed, 150_000, 6)
    # every byte the tasks merged came off the wire compressed
    assert values["fetch_inflated_bytes"] == part.file_bytes
    assert values["fetch_compressed_bytes"] == part.wire_bytes
    assert values["fetch_inflate_blocks"] == part.blocks == 18
    assert values["fetch_inflate_s"] > 0.0
    # 25,000 records a map compress past the 209,715 B sub-buffer: two
    # inner fetches a segment, a block carried across
    assert values["fetch_inner_fetches"] == values["fetch_chunks"] == 12
    assert values["fetch_inflate_carry_bytes"] > 0
    assert values["fetch_crack_deferred_segments"] == 6
    # the route is reduce_invindex's: the forest, no fallback
    assert values["overflow_fallbacks"] == 0
    assert values["stage_native_segments"] == 6
    assert 300 < values["oversize_keys"] < 600
