"""Observability layer: labeled counters/gauges/histograms, the span
tree, StatsReporter deltas/rates, the metrics-name lint, the Xprof
device-trace hook, and the end-to-end acceptance run (JSONL stream +
span-tree trace out of a real bridge-driven shuffle)."""

import importlib.util
import io
import json
import os
import threading

import pytest

from uda_tpu.utils.metrics import Metrics, device_trace, metrics
from uda_tpu.utils.stats import StatsReporter, telemetry_block


def test_counters_and_timer_spans():
    m = Metrics()
    m.record_spans = True
    m.add("fetched_bytes", 100)
    m.add("fetched_bytes", 50)
    with m.timer("merge"):
        pass
    snap = m.snapshot()
    assert snap["fetched_bytes"] == 150
    assert snap["merge_time"] >= 0
    assert [s["name"] for s in m.spans] == ["merge"]
    m.reset()
    assert m.snapshot() == {} and m.spans == []


def test_chrome_trace_export(tmp_path):
    m = Metrics()
    m.record_spans = True
    with m.timer("phase_a"):
        pass
    out = tmp_path / "trace.json"
    m.export_chrome_trace(str(out))
    events = json.loads(out.read_text())["traceEvents"]
    assert events and events[0]["name"] == "phase_a"
    assert events[0]["ph"] == "X" and events[0]["dur"] >= 0


# -- labeled counters / gauges / histograms ----------------------------------


def test_labeled_counters_accumulate_total_and_series():
    m = Metrics()
    m.add("fetch.bytes", 100, supplier="hostA")
    m.add("fetch.bytes", 50, supplier="hostB")
    m.add("fetch.bytes", 25, supplier="hostA")
    assert m.get("fetch.bytes") == 175  # unlabeled total always advances
    assert m.get("fetch.bytes", supplier="hostA") == 125
    assert m.get("fetch.bytes", supplier="hostB") == 50
    snap = m.snapshot()
    assert snap["fetch.bytes{supplier=hostA}"] == 125
    assert snap["fetch.bytes{supplier=hostB}"] == 50


def test_gauges_set_and_add():
    m = Metrics()
    m.gauge("arena.slots_in_use", 3)
    assert m.get_gauge("arena.slots_in_use") == 3
    m.gauge_add("fetch.on_air", 1)
    m.gauge_add("fetch.on_air", 1)
    m.gauge_add("fetch.on_air", -1)
    assert m.get_gauge("fetch.on_air") == 1
    m.gauge("fetch.on_air", 7, host="h1")
    assert m.get_gauge("fetch.on_air", host="h1") == 7
    assert m.gauges_snapshot()["fetch.on_air{host=h1}"] == 7


def test_histogram_percentiles():
    m = Metrics(stats=True)
    for v in range(1, 101):  # 1..100, uniform
        m.observe("fetch.latency_ms", float(v))
    s = m.histogram_summaries()["fetch.latency_ms"]
    assert s["count"] == 100 and s["sum"] == 5050
    assert s["min"] == 1 and s["max"] == 100
    # power-of-two buckets: estimates land within the containing bucket
    assert 32 <= s["p50"] <= 64
    assert 64 <= s["p95"] <= 100
    assert 64 <= s["p99"] <= 100
    assert s["p50"] <= s["p95"] <= s["p99"]


def test_histogram_labels_make_series():
    m = Metrics(stats=True)
    m.observe("fetch.latency_ms", 5.0, supplier="a")
    m.observe("fetch.latency_ms", 7.0, supplier="b")
    hs = m.histogram_summaries()
    assert hs["fetch.latency_ms"]["count"] == 2  # base series sees all
    assert hs["fetch.latency_ms{supplier=a}"]["count"] == 1


def test_disabled_stats_record_nothing():
    m = Metrics()  # default: histograms + spans off
    m.observe("fetch.latency_ms", 5.0)
    assert m.histogram_summaries() == {}
    with m.timer("merge"):
        pass
    assert m.spans == []  # no span append on the disabled path
    s = m.start_span("x")
    s.end()
    assert m.spans == [] and m.current_span() is None
    # counters stay live regardless
    m.add("fetch.bytes", 1)
    assert m.get("fetch.bytes") == 1


def test_enable_disable_spans_idempotent_and_reset_pristine():
    m = Metrics()
    m.enable_spans()
    m.enable_spans()  # idempotent
    assert m.record_spans
    with m.timer("merge"):
        pass
    m.add("fetch.bytes", 9, supplier="s")
    m.gauge("fetch.on_air", 2)
    m.enable_stats()
    m.observe("fetch.latency_ms", 1.0)
    m.reset()
    assert m.snapshot() == {} and m.spans == []
    assert m.gauges_snapshot() == {} and m.histogram_summaries() == {}
    assert not m.record_spans  # reset restores the pristine default
    m.disable_spans()
    m.disable_spans()  # idempotent
    assert not m.record_spans


# -- span tree ---------------------------------------------------------------


def test_span_tree_parent_child_across_threads(tmp_path):
    m = Metrics()
    m.enable_spans()
    with m.span("reduce_task", job="j1", reduce=0) as root:
        with m.timer("fetch"):
            fetch = m.current_span()
            assert fetch is not None and fetch.parent_id == root.span_id
            # explicit parent propagation onto a foreign thread (the
            # transport completion thread pattern)
            child = m.start_span("fetch.segment", parent=fetch,
                                 map="m_000001", supplier="hostA")

            def finish_on_other_thread():
                child.end(status="ok")

            t = threading.Thread(target=finish_on_other_thread)
            t.start()
            t.join()
        # adopting a span on a worker (use_span) parents nested timers
        def worker():
            with m.use_span(root):
                with m.timer("overlap_stage"):
                    pass

        t2 = threading.Thread(target=worker)
        t2.start()
        t2.join()
    by_name = {s["name"]: s for s in m.spans}
    assert by_name["reduce_task"]["parent"] is None
    assert by_name["fetch"]["parent"] == by_name["reduce_task"]["id"]
    seg = by_name["fetch.segment"]
    assert seg["parent"] == by_name["fetch"]["id"]
    assert seg["attrs"]["supplier"] == "hostA"
    assert seg["attrs"]["status"] == "ok"  # end-time attr merged
    assert by_name["overlap_stage"]["parent"] == by_name["reduce_task"]["id"]
    # one trace id spans the whole tree
    assert len({s["trace"] for s in m.spans}) == 1
    # chrome export carries the ids + attrs in args
    out = tmp_path / "t.json"
    m.export_chrome_trace(str(out))
    events = {e["name"]: e for e in
              json.loads(out.read_text())["traceEvents"]}
    assert events["fetch.segment"]["args"]["map"] == "m_000001"
    assert events["fetch.segment"]["args"]["parent_id"] == \
        events["fetch"]["args"]["span_id"]
    assert events["fetch.segment"]["args"]["trace_id"] == \
        events["reduce_task"]["args"]["trace_id"]


# -- StatsReporter -----------------------------------------------------------


class FakeClock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def test_stats_reporter_deltas_and_rates_with_fake_clock():
    m = Metrics()
    clock = FakeClock()
    out = io.StringIO()
    rep = StatsReporter(m, interval_s=1.0, out=out, clock=clock)
    m.add("fetch.bytes", 10_000_000)
    m.add("merge.records", 5000)
    clock.advance(2.0)
    rec1 = rep.report_once()
    assert rec1["interval_s"] == 2.0
    assert rec1["rates"]["fetch_mb_s"] == pytest.approx(5.0)
    assert rec1["rates"]["merge_records_s"] == pytest.approx(2500.0)
    assert rec1["rates"]["retry_per_s"] == 0.0
    # second interval: only the DELTA counts
    m.add("fetch.bytes", 1_000_000)
    m.add("fetch.retries", 4, supplier="s")
    clock.advance(4.0)
    rec2 = rep.report_once()
    assert rec2["rates"]["fetch_mb_s"] == pytest.approx(0.25)
    assert rec2["rates"]["retry_per_s"] == pytest.approx(1.0)
    # the JSONL stream has one parseable record per line
    lines = [json.loads(ln) for ln in out.getvalue().splitlines()]
    assert len(lines) == 2
    assert lines[1]["counters"]["fetch.retries{supplier=s}"] == 4


def test_stats_reporter_final_record_carries_parity_trio():
    m = Metrics()
    out = io.StringIO()
    rep = StatsReporter(m, out=out, clock=FakeClock())
    with m.timer("fetch"):
        pass
    rep.stop(final=True)
    final = json.loads(out.getvalue().splitlines()[-1])
    assert final["final"] is True
    for name in ("total_wait_mem_time", "total_fetch_time",
                 "total_merge_time"):
        assert name in final["counters"]
    assert final["counters"]["total_fetch_time"] == \
        final["counters"]["fetch_time"]
    rep.stop(final=False)  # idempotent


def test_telemetry_block_shape():
    m = Metrics(stats=True)
    m.add("emit.bytes", 10)
    m.observe("fetch.latency_ms", 2.0)
    blk = telemetry_block(m)
    assert blk["counters"]["emit.bytes"] == 10
    assert blk["counters"]["total_merge_time"] == 0.0  # trio always there
    assert blk["histograms"]["fetch.latency_ms"]["count"] == 1


def test_stats_progress_line_routes_through_uda_stats_logger():
    from uda_tpu.utils.logging import get_logger

    root_msgs, seen = [], []
    root = get_logger()
    stats_log = get_logger("uda.stats")
    old_sink = root.sink
    root.set_sink(lambda lvl, msg: (root_msgs.append(msg),
                                    seen.append(lvl)))
    try:
        stats_log.set_level(0)  # silence ONLY the stats stream
        rep = StatsReporter(Metrics(), out=io.StringIO(),
                            clock=FakeClock())
        rep.report_once()
        assert not root_msgs  # progress line silenced independently
        stats_log.set_level(4)
        rep.report_once()
        assert any("shuffle stats:" in m for m in root_msgs)
    finally:
        root.set_sink(old_sink)
        stats_log.clear_level()


# -- metrics-name lint (CI gate) ---------------------------------------------


def test_metrics_names_lint():
    here = os.path.dirname(os.path.abspath(__file__))
    script = os.path.join(here, os.pardir, "scripts",
                          "check_metrics_names.py")
    spec = importlib.util.spec_from_file_location("check_metrics_names",
                                                  script)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    violations = mod.check()
    assert violations == [], "\n".join(
        f"{f}:{ln}: {name}: {why}" for f, ln, name, why in violations)


# -- end-to-end acceptance: bridge shuffle with UDA_TPU_STATS=1 --------------


def test_observability_end_to_end(tmp_path, monkeypatch):
    """ISSUE 2 acceptance: a bridge-driven shuffle with UDA_TPU_STATS=1
    produces (a) a JSONL stream whose final record has the reference
    trio + per-supplier labeled fetch counters, (b) a Chrome trace whose
    fetch spans are children of the reduce-task root with supplier/map
    attrs, and (c) a GET_STATS pull that round-trips as JSON."""
    from tests.helpers import make_mof_tree, map_ids
    from tests.test_bridge import Harness
    from uda_tpu.bridge import Cmd, UdaBridge, form_cmd

    jsonl = tmp_path / "stats.jsonl"
    monkeypatch.setenv("UDA_TPU_STATS", "1")
    monkeypatch.setenv("UDA_TPU_STATS_JSONL", str(jsonl))
    job = "jobObs"
    make_mof_tree(str(tmp_path), job, 4, 1, 40, seed=71)
    harness = Harness(str(tmp_path))
    bridge = UdaBridge()
    bridge.start(True, ["-w", "4", "-s", "64"], harness)
    try:
        bridge.do_command(form_cmd(
            Cmd.INIT, [job, "0", "4", "uda.tpu.RawBytes"]))
        for i, mid in enumerate(map_ids(job, 4)):
            bridge.do_command(form_cmd(Cmd.FETCH,
                                       [f"host{i % 2}", job, mid, "0"]))
        bridge.do_command(form_cmd(Cmd.FINAL, []))
        assert harness.fetch_over.wait(timeout=30)
        # GET_STATS round-trips while the bridge is live
        stats = json.loads(bridge.do_command(form_cmd(Cmd.GET_STATS, [])))
        assert "counters" in stats
        bridge.do_command(form_cmd(Cmd.EXIT, []))  # final record + stop
        assert bridge._stats is None  # EXIT tore the reporter down
        assert not harness.failures, harness.failures
    finally:
        if bridge._stats is not None:  # only on assertion failure above
            bridge._stats.stop(final=False)

    # (a) JSONL stream, final record: parity trio + labeled series
    records = [json.loads(ln) for ln in
               jsonl.read_text().splitlines() if ln.strip()]
    finals = [r for r in records if r.get("final")]
    assert finals, "no final-flagged stats record"
    counters = finals[-1]["counters"]
    for name in ("total_wait_mem_time", "total_fetch_time",
                 "total_merge_time"):
        assert name in counters
    assert counters["total_fetch_time"] > 0
    labeled = sorted(k for k in counters
                     if k.startswith("fetch.bytes{supplier="))
    assert labeled == ["fetch.bytes{supplier=host0}",
                       "fetch.bytes{supplier=host1}"]
    assert counters["fetch.bytes"] == sum(counters[k] for k in labeled)

    # (b) span tree: fetch.segment spans -> fetch -> reduce_task root
    spans = {s["id"]: s for s in metrics.spans}
    roots = [s for s in spans.values() if s["name"] == "reduce_task"]
    assert len(roots) == 1 and roots[0]["parent"] is None
    segs = [s for s in spans.values() if s["name"] == "fetch.segment"]
    assert len(segs) == 4
    for s in segs:
        assert s["attrs"]["supplier"] and s["attrs"]["map"]
        # walk to the root through parent ids
        node, hops = s, 0
        while node["parent"] is not None and hops < 10:
            node = spans[node["parent"]]
            hops += 1
        assert node is roots[0]
    trace = tmp_path / "trace.json"
    metrics.export_chrome_trace(str(trace))
    events = json.loads(trace.read_text())["traceEvents"]
    assert any(e["name"] == "fetch.segment"
               and e["args"].get("supplier") for e in events)


# -- device trace hook -------------------------------------------------------


def test_device_trace_noop_without_config(monkeypatch):
    monkeypatch.delenv("UDA_TPU_XPROF", raising=False)
    ran = []
    with device_trace():
        ran.append(1)
    assert ran == [1]


def test_device_trace_captures_profile(tmp_path):
    # on the CPU test backend jax.profiler works; the hook must run the
    # block and leave a profile directory behind
    import jax
    import jax.numpy as jnp

    with device_trace(str(tmp_path)):
        jnp.arange(8).sum().block_until_ready()
    produced = list(tmp_path.rglob("*"))
    assert produced, "no profile artifacts written"


def test_device_trace_raises_when_trace_cannot_start(tmp_path):
    # a second concurrent trace raises inside start_trace; a trace that
    # was asked for and cannot start must fail the run, not degrade to
    # an untraced one that looks traced
    import jax

    jax.profiler.start_trace(str(tmp_path / "outer"))
    try:
        ran = []
        with pytest.raises(Exception):
            with device_trace(str(tmp_path / "inner")):
                ran.append(1)
        assert ran == []
    finally:
        jax.profiler.stop_trace()
