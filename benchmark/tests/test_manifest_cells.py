"""The manifest as PR 34 leaves it: six cells, two of them four-chip —
what ``harness/manifest.py`` admits (``max(1, cells // 2)``).
``test_manifest.py::test_manifest_cross_references`` still names
``exchange_ici4`` as the only four-chip cell in its last line; that file
is the accepted benchmark's and a PR that adds a cell may not edit it."""

import os

from benchmark.harness.manifest import Manifest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def test_the_manifest_validates_with_its_second_four_chip_cell():
    m = Manifest(ROOT)
    m.validate()
    four = [c["name"] for c in m.cells.values() if c["chips"] == 4]
    assert four == ["exchange_ici4", "exchange_skew_ici4"]
    assert len(four) <= max(1, len(m.cells) // 2)
    cell = m.cells["exchange_skew_ici4"]
    assert (cell["config"], cell["traffic"]) == ("terasort_daytona_skew",
                                                 "resident_steps_zipf")
    assert len(cell["why"]) <= 200


def test_the_skew_cell_reports_the_exchange_cells_metrics_and_three_more():
    m = Manifest(ROOT)
    old = {x["name"] for x in m.metrics_of("exchange_ici4", "per_layer")}
    new = {x["name"] for x in m.metrics_of("exchange_skew_ici4", "per_layer")}
    assert new - old == {"exchange_overflow_reruns", "exchange_sample_keys",
                         "shard_max_permille"} and old <= new
    assert {x["name"] for x in m.metrics_of("exchange_skew_ici4",
                                            "end_to_end")} == \
        {"step_wall_s", "goodput_MBps", "setup_s"}
    cfg = m.config_file("terasort_daytona_skew")
    assert set(m.configs["terasort_daytona_skew"]["reduced"]) == \
        set(cfg["reduced"]) == {"chips", "records_per_chip"}
    assert cfg["sample_keys"] == 100_000 and len(cfg["source"]) <= 200
