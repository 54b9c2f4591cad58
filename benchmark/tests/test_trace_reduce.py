"""The reduction from a trace to numbers, on a small trace with known
answers, and the peaks table's refusal of an unknown device."""

import json
import os

import pytest

from benchmark.trace import critpath
from benchmark.trace import reduce as r

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "trace_small.json")


def _trace():
    with open(FIXTURE) as f:
        return json.load(f)


def test_known_busy_idle_and_per_op_numbers():
    doc = _trace()
    s = r.summarize(doc["trace"], devices=doc["devices"], units=doc["units"])
    want = doc["expected"]
    for key in ("window_s", "busy_s", "idle_share", "busy_per_unit_s",
                "collective_s", "collective_exposed_s"):
        assert s[key] == pytest.approx(want[key], rel=1e-9), key
    assert [n for n, _ in s["ops"][:3]] == want["top_ops"]
    assert s["ops"][0][1] == pytest.approx(want["top_op_s"], rel=1e-9)
    assert sum(hi - lo for lo, hi in s["gaps"]) / 1e9 == pytest.approx(
        want["device0_idle_s"], rel=1e-9)


def test_idle_gaps_are_charged_to_the_host_stage_active_in_them():
    doc = _trace()
    s = r.summarize(doc["trace"], devices=doc["devices"], units=doc["units"])
    gaps = r.attribute_gaps(s, doc["host_spans"], doc["perf_at_marker"],
                            critpath.bucket_of, critpath.BUCKET_PRIORITY)
    assert [g[0] for g in gaps] == [g[0] for g in doc["expected"]["gaps"]]
    for got, want in zip(gaps, doc["expected"]["gaps"]):
        assert got[1] == pytest.approx(want[1], rel=1e-9)
    assert sum(g[1] for g in gaps) == pytest.approx(
        doc["expected"]["device0_idle_s"], rel=1e-9)


def test_idle_time_under_no_span_is_unattributed():
    doc = _trace()
    s = r.summarize(doc["trace"], devices=1)
    gaps = r.attribute_gaps(s, [], doc["perf_at_marker"],
                            critpath.bucket_of, critpath.BUCKET_PRIORITY)
    assert gaps == [["unattributed",
                     pytest.approx(doc["expected"]["device0_idle_s"])]]


def test_a_trace_without_device_operations_is_refused():
    doc = _trace()
    host_only = {"planes": [p for p in doc["trace"]["planes"]
                            if not p["name"].startswith("/device:")]}
    with pytest.raises(r.TraceError, match="device planes"):
        r.summarize(host_only, devices=1)
    with pytest.raises(r.TraceError, match="annotation"):
        r.summarize({"planes": []}, devices=1)


def test_interval_arithmetic():
    assert r._union([(5, 7), (0, 2), (1, 3)]) == [[0, 3], [5, 7]]
    assert r._minus([[0, 10]], [[1, 2], [4, 6], [9, 12]]) == \
        [[0, 1], [2, 4], [6, 9]]
    assert r._minus([[0, 3], [5, 7]], []) == [[0, 3], [5, 7]]


def test_unknown_device_kind_is_an_error():
    assert r.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(r.TraceError, match="no peaks"):
        r.peaks_for("TPU v9 imaginary")
    with pytest.raises(r.TraceError):
        r.peaks_for("_source")


def test_critical_seconds_partition_the_wall():
    spans = [
        {"name": "reduce_task", "ts": 10.0, "dur": 10.0, "trace": 1, "id": 1},
        {"name": "fetch.segment", "ts": 10.0, "dur": 4.0, "trace": 1, "id": 2},
        {"name": "overlap_pack", "ts": 12.0, "dur": 4.0, "trace": 1, "id": 3},
        {"name": "merge.wait", "ts": 11.0, "dur": 7.0, "trace": 1, "id": 4},
        {"name": "emit", "ts": 18.5, "dur": 1.0, "trace": 1, "id": 5},
        {"name": "overlap_pack", "ts": 0.0, "dur": 30.0, "trace": 2, "id": 6},
    ]
    (c,) = critpath.per_task(spans)
    assert c["wall"] == 10.0
    assert c["fetch"] == pytest.approx(2.0)            # 10-12: fetch alone
    assert c["decompress_pack"] == pytest.approx(4.0)  # 12-16 outranks fetch
    assert c["wait"] == pytest.approx(2.0)             # 16-18: only waiting
    assert c["serve"] == pytest.approx(1.0)
    assert c["idle"] == pytest.approx(1.0)             # 18-18.5, 19.5-20
    assert sum(v for k, v in c.items() if k != "wall") == pytest.approx(10.0)


def test_collectives_and_their_exposed_part_over_two_marked_steps():
    """Two marked steps on two chips; between the markers a verifier op
    that must not count. Chip 0: a while (10..90) holding a sort kernel
    (10..40) and a synchronous all-to-all (40..60, exposed) and another
    kernel (60..90); chip 1: the all-to-all as an asynchronous pair
    (start 20 .. done 50) under a kernel that runs 10..35, so 35..50 of
    it is exposed."""
    ms = 1e6
    trace = {"planes": [
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [
            ["benchmark_window", 0.0, 100 * ms],
            ["benchmark_window", 200 * ms, 100 * ms]]}]},
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
            ["%while.1 = (u32[8]) while(...)", 10 * ms, 80 * ms],
            ["%sort_pass.1 = u32[8] custom-call(...)", 10 * ms, 30 * ms],
            ["%all_to_all.2 = u32[4,2]{1,0:T(8,128)} all-to-all(u32[8] %x), "
             "channel_id=1", 40 * ms, 20 * ms],
            ["%sort_pass.2 = u32[8] custom-call(u32[4,2] %all_to_all.2, "
             "u32[] %all-reduce.1)", 60 * ms, 30 * ms],
            ["%verify.1 = u32[] fusion(...)", 120 * ms, 50 * ms],
            ["%sort_pass.1 = u32[8] custom-call(...)", 210 * ms, 40 * ms]]}]},
        {"name": "/device:TPU:1", "lines": [
            {"name": "XLA Ops", "events": [
                ["%sort_pass.1 = u32[8] custom-call(...)", 10 * ms, 25 * ms],
                ["%all-to-all-done.2 = u32[8] all-to-all-done(...)",
                 45 * ms, 5 * ms]]},
            {"name": "Async XLA Ops", "events": [
                ["%all-to-all-start.2 = (u32[8]) all-to-all-start(...)",
                 20 * ms, 30 * ms]]}]},
    ]}
    s = r.summarize(trace, devices=2, units=2)
    assert s["window_s"] == pytest.approx(0.2)
    assert s["window_per_unit_s"] == pytest.approx(0.1)
    # busy: chip 0 = 80 + 40, chip 1 = 25 + 5 (the done op); mean 75 ms
    assert s["busy_s"] == pytest.approx(0.075)
    assert s["busy_per_unit_s"] == pytest.approx(0.0375)
    assert s["idle_share"] == pytest.approx(1 - 0.075 / 0.2)
    # collectives: chip 0 20 ms, chip 1 30 ms; mean 25 ms, per step 12.5
    assert s["collective_s"] == pytest.approx(0.0125)
    # exposed: chip 0 all 20 ms (the while around it is no work of its
    # own), chip 1 35..50 = 15 ms; mean 17.5 ms, per step 8.75
    assert s["collective_exposed_s"] == pytest.approx(0.00875)
    ops = dict(s["ops"])
    assert ops["sort_pass.1"] == pytest.approx((30 + 40 + 25) / 2 / 1e3)
    assert ops["while.1"] == pytest.approx(0.0)       # all in its children
    assert "verify.1" not in ops


def test_a_collective_is_known_by_its_opcode():
    a2a = ("%all_to_all.11 = u32[4,524288,26]{1,2,0:T(8,128)} "
           "all-to-all(%slice.105), channel_id=1")
    assert r.is_collective(a2a) and r.short_name(a2a) == "all_to_all.11"
    assert r.is_collective("%ar = (s32[]{:T(128)}, u32[]) all-reduce-start(%x)")
    assert not r.is_collective(
        "%fusion.3 = u32[8]{0:T(1024)S(1)} fusion(u32[] %all-reduce.11)")
    assert not r.is_collective(
        "%while.64 = (s32[]{:T(128)}, u32[32,8]{1,0:T(8,128)}) while(%t)")
