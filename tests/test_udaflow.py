"""udaflow (the CFG/dataflow analysis tier) + ResourceLedger coverage.

Four layers:

1. CFG unit tests: the edge shapes the dataflow verdicts depend on
   (try/finally routing, raise/except dispatch, loop back-edges, with
   headers) are pinned structurally;
2. per-rule fixtures: UDA101/UDA102/UDA103 each proven to FIRE on the
   known historical leak shapes (try_plan-style unguarded charge,
   helper-hop blocking-under-lock, AB/BA static lock nesting) and stay
   quiet on the guarded/balanced twins;
3. the static<->runtime inventory lockstep: the UDA101 pair registry
   (analysis/flow.DEFAULT_PAIRS) and the ResourceLedger's paired-gauge
   table (utils/resledger.PAIRED_GAUGES) must name the same
   disciplines, so a static finding and a runtime leak report agree;
4. ResourceLedger unit + integration tests, including the faults-marked
   mid-pipeline leak test: a fault aborts a real pipelined merger with
   ZERO leaked obligations, and a seeded stray lease is reported at the
   abort drain point exactly once, with its acquire stack.

Seeded-leak fixtures use PRIVATE ResourceLedger instances (the LockDep
pattern): the process-global ledger must report zero leaks on real
code, and a fixture leak must never pollute that invariant (or the
``resledger.leaks`` counter the chaos gate enforces).
"""

from __future__ import annotations

import ast
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from tests.helpers import make_mof_tree, map_ids
from uda_tpu.analysis.cfg import build_cfg
from uda_tpu.analysis.core import Engine, Finding
from uda_tpu.analysis.flow import (DEFAULT_PAIRS, ObligationPair,
                                   ResourceBalanceRule, StaticLockOrderRule,
                                   TransitiveBlockingRule)
from uda_tpu.analysis.rules import ALL_RULES
from uda_tpu.merger import LocalFetchClient, MergeManager
from uda_tpu.merger import overlap as overlap_mod
from uda_tpu.mofserver import DataEngine, DirIndexResolver
from uda_tpu.ops import merge as merge_ops
from uda_tpu.utils.config import Config
from uda_tpu.utils.errors import FallbackSignal
from uda_tpu.utils.failpoints import failpoints
from uda_tpu.utils.resledger import (PAIRED_GAUGES, ResourceLedger,
                                     resledger)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

KT = "uda.tpu.RawBytes"


def _cfg_of(src: str):
    tree = ast.parse(textwrap.dedent(src))
    return build_cfg(tree.body[0])


def lint(src: str, rules, rel: str = "uda_tpu/x.py") -> list[Finding]:
    eng = Engine(rules)
    out = eng.lint_source(textwrap.dedent(src), rel)
    out.extend(eng.finish())
    return out


def lint_tree(files: dict, rules) -> list[Finding]:
    eng = Engine(rules)
    out: list[Finding] = []
    for rel, src in files.items():
        out.extend(eng.lint_source(textwrap.dedent(src), rel))
    out.extend(eng.finish())
    return out


def rule_ids(findings) -> list[str]:
    return [f.rule for f in findings]


# -- CFG edge shapes ---------------------------------------------------------


class TestCFG:
    def test_straight_line_reaches_exit(self):
        cfg = _cfg_of("def f():\n    x = 1\n    y = 2\n")
        entry = cfg.node(cfg.entry)
        assert entry.kind == "stmt"
        nxt = cfg.node(entry.norm_succs[0])
        assert nxt.norm_succs == [cfg.exit_id]

    def test_call_gets_exception_edge_to_raise(self):
        cfg = _cfg_of("def f():\n    risky()\n")
        assert cfg.node(cfg.entry).exc_succs == [cfg.raise_id]

    def test_no_raise_callees_get_no_exception_edge(self):
        # metrics/log calls are modeled infallible (DEFAULT_NO_RAISE) —
        # without this, every counter bump between acquire and release
        # would manufacture a leak path
        cfg = _cfg_of("def f():\n    metrics.gauge_add('x', 1)\n")
        assert cfg.node(cfg.entry).exc_succs == []

    def test_raise_stmt_edge_shape(self):
        cfg = _cfg_of("def f():\n    raise ValueError('x')\n")
        entry = cfg.node(cfg.entry)
        assert entry.kind == "raise_stmt"
        assert entry.norm_succs == [] and entry.exc_succs == [cfg.raise_id]

    def test_finally_copied_per_continuation(self):
        # the finally body is wired once per way out of the try: the
        # normal path ends at EXIT, the exceptional path re-raises at
        # RAISE — never merged (a shared block would manufacture
        # normal-completion -> exceptional-exit paths)
        cfg = _cfg_of(
            "def f():\n"
            "    try:\n"
            "        risky()\n"
            "    finally:\n"
            "        cleanup()\n")
        copies = [n for n in cfg.nodes if n.line == 5]
        assert len(copies) == 2
        assert {c.norm_succs[0] for c in copies} == {cfg.exit_id,
                                                     cfg.raise_id}

    def test_return_through_finally(self):
        cfg = _cfg_of(
            "def f():\n"
            "    try:\n"
            "        return 1\n"
            "    finally:\n"
            "        cleanup()\n")
        ret = next(n for n in cfg.nodes if n.kind == "return")
        fin = cfg.node(ret.norm_succs[0])
        assert fin.line == 5  # the return routes through the finally
        assert fin.norm_succs == [cfg.exit_id]

    def test_narrow_except_keeps_propagate_edge(self):
        cfg = _cfg_of(
            "def f():\n"
            "    try:\n"
            "        risky()\n"
            "    except ValueError:\n"
            "        handle()\n")
        disp = next(n for n in cfg.nodes if n.kind == "except_dispatch")
        assert cfg.raise_id in disp.exc_succs  # may not match -> onward

    def test_broad_except_drops_propagate_edge(self):
        cfg = _cfg_of(
            "def f():\n"
            "    try:\n"
            "        risky()\n"
            "    except Exception:\n"
            "        handle()\n")
        disp = next(n for n in cfg.nodes if n.kind == "except_dispatch")
        assert disp.exc_succs == []

    def test_loop_break_and_back_edge(self):
        cfg = _cfg_of(
            "def f(xs):\n"
            "    for x in xs:\n"
            "        if x:\n"
            "            break\n"
            "        use(x)\n"
            "    tail()\n")
        loop = next(n for n in cfg.nodes if n.kind == "loop")
        brk = next(n for n in cfg.nodes if n.kind == "break")
        tail = next(n for n in cfg.nodes
                    if n.kind == "stmt" and n.line == 6)
        assert brk.norm_succs == [tail.index]
        use = next(n for n in cfg.nodes
                   if n.kind == "stmt" and n.line == 5)
        assert use.norm_succs == [loop.index]  # back edge

    def test_with_header_can_raise(self):
        cfg = _cfg_of("def f(lk):\n    with lk:\n        body()\n")
        w = next(n for n in cfg.nodes if n.kind == "with")
        assert cfg.raise_id in w.exc_succs  # __enter__ may raise


# -- UDA101: resource balance ------------------------------------------------


PAIRS = (
    ObligationPair("engine.admit", acquire=("_admit_bytes",),
                   release=("_unadmit",)),
    ObligationPair("pool.lease", acquire=("lease",), release=("release",),
                   recv=r".*(pool|bufs).*"),
    ObligationPair("gauge.fetch.on_air", kind="gauge",
                   gauge="fetch.on_air"),
    ObligationPair("ctx.failpoints.scoped", kind="context",
                   acquire=("scoped",), recv=r".*failpoints.*",
                   transfer=("enter_context",)),
)


class TestResourceBalanceRule:
    def rules(self):
        return [ResourceBalanceRule(pairs=PAIRS)]

    def test_tryplan_shape_unguarded_charge_fires(self):
        # PR 6's historical leak: charge, then a fallible call whose
        # exception path exits without the paired release
        src = """
        def plan(self, req):
            self._admit_bytes(8)
            out = self._build(req)
            self._unadmit(8)
            return out
        """
        out = lint(src, self.rules())
        assert rule_ids(out) == ["UDA101"]
        assert out[0].line == 3  # anchored on the acquire
        assert "exception path" in out[0].message

    def test_finally_guard_passes(self):
        src = """
        def plan(self, req):
            self._admit_bytes(8)
            try:
                return self._build(req)
            finally:
                self._unadmit(8)
        """
        assert lint(src, self.rules()) == []

    def test_exception_path_release_passes(self):
        # the overlap.py review-hardening shape: release on the
        # exception path, obligation rides the return value otherwise
        src = """
        def stage(self, n):
            buf = self._pool.lease(n, 4)
            try:
                fill(buf)
                return buf
            except BaseException:
                self._pool.release(buf)
                raise
        """
        assert lint(src, self.rules()) == []

    def test_early_constant_return_leaks_normal_path(self):
        src = """
        def serve(self):
            self._admit_bytes(8)
            if self.closed:
                return None
            self._unadmit(8)
        """
        out = lint(src, self.rules())
        assert rule_ids(out) == ["UDA101"]
        assert "normal path" in out[0].message

    def test_value_return_is_a_transfer(self):
        # the FdSlice idiom: the obligation rides the returned handle,
        # whoever holds it owes the release (the runtime ledger agrees)
        src = """
        def grab(self, n):
            buf = self._pool.lease(n, 4)
            return buf
        """
        assert lint(src, self.rules()) == []

    def test_receiver_filter_scopes_generic_names(self):
        src = """
        def f(self):
            self.sem.lease(4, 4)
        """
        assert lint(src, self.rules()) == []  # not a pool/bufs receiver

    def test_gauge_pair_unguarded_fires(self):
        src = """
        def f(self):
            metrics.gauge_add("fetch.on_air", 1)
            self._issue()
            metrics.gauge_add("fetch.on_air", -1)
        """
        out = lint(src, self.rules())
        assert rule_ids(out) == ["UDA101"]
        assert out[0].data == {"pair": "gauge.fetch.on_air"}

    def test_gauge_pair_finally_guard_passes(self):
        src = """
        def f(self):
            metrics.gauge_add("fetch.on_air", 1)
            try:
                self._issue()
            finally:
                metrics.gauge_add("fetch.on_air", -1)
        """
        assert lint(src, self.rules()) == []

    def test_context_pair_must_be_entered(self):
        out = lint("def f():\n    s = failpoints.scoped('a=error')\n"
                   "    use(s)\n", self.rules())
        assert rule_ids(out) == ["UDA101"]
        assert "not entered" in out[0].message

    def test_context_pair_with_guard_passes(self):
        src = """
        def f():
            with failpoints.scoped('a=error'):
                go()
        """
        assert lint(src, self.rules()) == []

    def test_context_pair_enter_context_passes(self):
        src = """
        def f(stack):
            stack.enter_context(failpoints.scoped('a=error'))
            go()
        """
        assert lint(src, self.rules()) == []

    def test_loop_reacquire_balanced_passes(self):
        src = """
        def f(self, xs):
            for x in xs:
                self._admit_bytes(8)
                try:
                    use(x)
                finally:
                    self._unadmit(8)
        """
        assert lint(src, self.rules()) == []

    def test_nested_def_analyzed_on_its_own_cfg(self):
        src = """
        def f(self):
            def later():
                self._admit_bytes(8)
            return later
        """
        out = lint(src, self.rules())
        # the ENCLOSING function does not inherit the nested acquire
        # (deferred code runs on its own CFG) — but the nested def's
        # own unreleased charge IS a finding, at its own line
        assert rule_ids(out) == ["UDA101"]
        assert out[0].line == 4

    def test_pair_impl_bodies_exempt(self):
        # the function NAMED like the pair's acquire IS its
        # implementation — charging its body would double count
        src = """
        def _admit_bytes(self, want):
            self._check(want)
            self.total += want
        """
        assert lint(src, self.rules()) == []

    def test_suppression_silences(self):
        src = """
        def f(self):
            self._admit_bytes(8)  # udalint: disable=UDA101
            self._build()
            self._unadmit(8)
        """
        assert lint(src, self.rules()) == []


# -- UDA102: transitive blocking ---------------------------------------------


class TestTransitiveBlockingRule:
    def rules(self):
        return [TransitiveBlockingRule()]

    def test_helper_hop_under_lock_fires(self):
        # the hop that defeats UDA007: the blocking call lives one
        # helper away from the `with lock:`
        src = """
        class C:
            def _settle(self):
                self._done.wait()
            def run(self):
                with self._lock:
                    self._settle()
        """
        out = lint(src, self.rules())
        assert rule_ids(out) == ["UDA102"]
        assert "_settle" in out[0].message and ".wait()" in out[0].message

    def test_two_hop_chain_fires_with_witness(self):
        src = """
        class C:
            def _inner(self):
                self._fut.result()
            def _outer(self):
                self._inner()
            def run(self):
                with self._mu:
                    self._outer()
        """
        out = lint(src, self.rules())
        assert rule_ids(out) == ["UDA102"]
        assert "_outer -> _inner -> Future.result()" in out[0].message

    def test_bounded_helper_passes(self):
        src = """
        class C:
            def _settle(self):
                self._done.wait(timeout=2.0)
            def run(self):
                with self._lock:
                    self._settle()
        """
        assert lint(src, self.rules()) == []

    def test_one_benign_homonym_acquits(self):
        # name-keyed resolution convicts a name only when EVERY def of
        # it blocks — a blocking twin in an unrelated module must not
        # poison callers of the benign one
        files = {
            "uda_tpu/a.py": """
            def flush(self):
                self._q.get()
            """,
            "uda_tpu/b.py": """
            def flush(self):
                self.buf.clear()
            def run(self):
                with self._lock:
                    self.flush()
            """,
        }
        assert lint_tree(files, self.rules()) == []

    def test_loop_callback_helper_hop_fires_in_net(self):
        src = """
        def _pump(self):
            self._fut.result()

        @loop_callback
        def on_readable(self, mask):
            self._pump()
        """
        out = lint(src, self.rules(), rel="uda_tpu/net/x.py")
        assert rule_ids(out) == ["UDA102"]
        assert "@loop_callback" in out[0].message

    def test_loop_callback_outside_net_ignored(self):
        src = """
        def _pump(self):
            self._fut.result()

        @loop_callback
        def on_readable(self, mask):
            self._pump()
        """
        assert lint(src, self.rules(), rel="uda_tpu/merger/x.py") == []

    def test_direct_blocking_left_to_uda007(self):
        src = """
        class C:
            def run(self):
                with self._lock:
                    self._done.wait()
        """
        assert lint(src, self.rules()) == []  # UDA007's finding, not ours

    def test_suppression_silences(self):
        src = """
        class C:
            def _settle(self):
                self._done.wait()
            def run(self):
                with self._lock:
                    self._settle()  # udalint: disable=UDA102
        """
        assert lint(src, self.rules()) == []


# -- UDA103: static lock order -----------------------------------------------


class TestStaticLockOrderRule:
    def rules(self):
        return [StaticLockOrderRule()]

    def test_ab_ba_nesting_fires(self):
        src = """
        class C:
            def __init__(self):
                self._alk = TrackedLock("alpha")
                self._blk = TrackedLock("beta")
            def one(self):
                with self._alk:
                    with self._blk:
                        pass
            def two(self):
                with self._blk:
                    with self._alk:
                        pass
        """
        out = lint(src, self.rules())
        assert rule_ids(out) == ["UDA103"]
        assert "alpha" in out[0].message and "beta" in out[0].message

    def test_consistent_order_passes(self):
        src = """
        class C:
            def __init__(self):
                self._alk = TrackedLock("alpha")
                self._blk = TrackedLock("beta")
            def one(self):
                with self._alk:
                    with self._blk:
                        pass
            def two(self):
                with self._alk:
                    with self._blk:
                        pass
        """
        assert lint(src, self.rules()) == []

    def test_cross_file_inversion_fires(self):
        # the whole point of the tree-wide sweep: the two halves of the
        # inversion live in different modules and no test interleaves
        # them — lexical nesting alone convicts
        files = {
            "uda_tpu/p.py": """
            class P:
                def __init__(self):
                    self._alk = TrackedLock("alpha")
                    self._blk = TrackedLock("beta")
                def go(self):
                    with self._alk:
                        with self._blk:
                            pass
            """,
            "uda_tpu/q.py": """
            class Q:
                def __init__(self):
                    self._xl = TrackedLock("beta")
                    self._yl = TrackedLock("alpha")
                def go(self):
                    with self._xl:
                        with self._yl:
                            pass
            """,
        }
        out = lint_tree(files, self.rules())
        assert rule_ids(out) == ["UDA103"]

    def test_condition_wraps_lock_class(self):
        src = """
        class C:
            def __init__(self):
                self._cv = TrackedCondition(TrackedLock("alpha"))
                self._blk = TrackedLock("beta")
            def one(self):
                with self._cv:
                    with self._blk:
                        pass
            def two(self):
                with self._blk:
                    with self._cv:
                        pass
        """
        out = lint(src, self.rules())
        assert rule_ids(out) == ["UDA103"]

    def test_same_class_nesting_is_not_an_edge(self):
        # lockdep's rule: class-level self-edges false-positive on
        # instance hierarchies
        src = """
        class C:
            def __init__(self):
                self._alk = TrackedLock("alpha")
                self._blk = TrackedLock("alpha")
            def go(self):
                with self._alk:
                    with self._blk:
                        pass
        """
        assert lint(src, self.rules()) == []

    def test_enclosing_def_boundary_stops_the_chain(self):
        # a `with` in an ENCLOSING def is not held when the nested def
        # runs later — no edge
        src = """
        class C:
            def __init__(self):
                self._alk = TrackedLock("alpha")
                self._blk = TrackedLock("beta")
            def one(self):
                with self._blk:
                    def later(self):
                        with self._alk:
                            pass
                    return later
            def two(self):
                with self._alk:
                    with self._blk:
                        pass
        """
        assert lint(src, self.rules()) == []


# -- static <-> runtime inventory lockstep -----------------------------------


def test_static_and_runtime_inventories_agree():
    """A UDA101 finding and a runtime leak report must name the same
    discipline: the static registry's gauge pairs ARE the ledger's
    paired-gauge table, id for id."""
    static_gauges = {p.gauge: p.pair_id for p in DEFAULT_PAIRS
                     if p.kind == "gauge"}
    assert static_gauges == PAIRED_GAUGES


def test_udaflow_rules_registered_in_engine():
    ids = {cls.rule_id for cls in ALL_RULES}
    assert {"UDA101", "UDA102", "UDA103"} <= ids


def test_udalint_json_output_is_machine_readable():
    """The --json contract the CI/chaos gates consume: one object,
    files + rules + findings[] with file/line/col/rule fields."""
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "udalint.py"),
         "--json", "uda_tpu/analysis"],
        capture_output=True, text=True, cwd=REPO, timeout=300)
    assert out.returncode == 0, out.stdout + out.stderr
    doc = json.loads(out.stdout)
    assert doc["findings"] == [] and doc["files"] >= 4
    assert "UDA101" in doc["rules"]


# -- ResourceLedger (runtime half) -------------------------------------------


class TestResourceLedger:
    def test_disabled_is_inert(self):
        led = ResourceLedger(enabled=False)
        led.acquire("pool.lease", key=1)
        assert led.outstanding() == []
        assert led.drain("x") == []

    def test_unit_acquire_settle(self):
        led = ResourceLedger(enabled=True)
        led.acquire("engine.fd", key="/a", owner=7)
        led.acquire("engine.fd", key="/a", owner=7)
        led.settle("engine.fd", key="/a", owner=7)
        out = led.outstanding()
        assert len(out) == 1 and out[0]["pair"] == "engine.fd"
        led.settle("engine.fd", key="/a", owner=7)
        assert led.outstanding() == []

    def test_amount_settle_retires_oldest_first(self):
        led = ResourceLedger(enabled=True)
        led.acquire("gauge.stage.inflight", key="g", amount=10)
        led.acquire("gauge.stage.inflight", key="g", amount=5)
        led.settle("gauge.stage.inflight", key="g", amount=12)
        out = led.outstanding()
        assert len(out) == 1 and out[0]["amount"] == 3

    def test_unmatched_settle_ignored(self):
        # arming the ledger mid-process must not turn pre-arming
        # acquires into phantom double-releases
        led = ResourceLedger(enabled=True)
        led.settle("pool.lease", key=9)
        assert led.outstanding() == []

    def test_drain_reports_once_with_stack(self):
        led = ResourceLedger(enabled=True)
        led.acquire("pool.lease", key=3, amount=64, detail="fixture")
        reports = led.drain("unit.test")
        assert len(reports) == 1
        r = reports[0]
        assert r["pair"] == "pool.lease" and r["point"] == "unit.test"
        assert "test_drain_reports_once_with_stack" in r["stack"]
        assert led.drain("unit.test") == []  # popped: reported ONCE
        assert len(led.leak_reports) == 1

    def test_drain_owner_scope(self):
        # one engine's drain point must not confiscate a live peer's
        # legitimately-open obligations (the killed-supplier shape)
        led = ResourceLedger(enabled=True)
        led.acquire("engine.fd", key="/a", owner=1)
        led.acquire("engine.fd", key="/a", owner=2)
        assert len(led.drain("stop", owner=1)) == 1
        assert len(led.outstanding()) == 1
        assert led.outstanding()[0]["owner"] == 2

    def test_drain_pair_filter(self):
        led = ResourceLedger(enabled=True)
        led.acquire("pool.lease", key=1)
        led.acquire("engine.fd", key="/a")
        assert len(led.drain("stop", pairs=("engine.fd",))) == 1
        assert led.outstanding()[0]["pair"] == "pool.lease"

    def test_note_gauge_balanced(self):
        led = ResourceLedger(enabled=True)
        led.note_gauge("stage.inflight.bytes", 100)
        led.note_gauge("stage.inflight.bytes", -100)
        assert led.outstanding() == []
        led.note_gauge("unpaired.gauge", 1)  # not in PAIRED_GAUGES
        assert led.outstanding() == []

    def test_settle_before_acquire_inversion_books_deficit(self):
        # the paired-gauge bumps ride OUTSIDE the state locks that
        # order the attempts, so a decrement can reach the books an
        # instant before its matching increment (watchdog-rescue
        # fail() racing _try_issue's +1); the shortfall must cancel
        # the late acquire instead of fabricating a phantom
        # obligation that false-leaks at the next drain
        led = ResourceLedger(enabled=True)
        led.note_gauge("fetch.on_air", -1)   # the settle wins the race
        led.note_gauge("fetch.on_air", 1)    # its increment lands late
        assert led.outstanding() == []
        assert led.drain("unit.test") == []
        # partial inversion: the deficit cancels only its own share
        led.note_gauge("stage.inflight.bytes", -40)
        led.note_gauge("stage.inflight.bytes", 100)
        open_now = led.outstanding()
        assert [r["amount"] for r in open_now] == [60]
        led.note_gauge("stage.inflight.bytes", -60)
        assert led.outstanding() == []

    def test_deficit_does_not_survive_a_drain(self):
        # a deficit is a transient in-flight inversion; at a quiescent
        # drain boundary it must not linger and swallow a LATER
        # legitimate acquire (which would hide a real leak)
        led = ResourceLedger(enabled=True)
        led.note_gauge("fetch.on_air", -1)
        led.drain("unit.test")               # quiescent boundary
        led.note_gauge("fetch.on_air", 1)    # fresh obligation
        assert len(led.outstanding()) == 1
        assert len(led.drain("unit.test")) == 1

    def test_json_report_appends(self, tmp_path, monkeypatch):
        path = str(tmp_path / "leaks.jsonl")
        monkeypatch.setenv("UDA_TPU_RESLEDGER_JSON", path)
        led = ResourceLedger(enabled=True, emit_json=True)
        led.acquire("pool.lease", key=4)
        led.drain("unit.json")
        with open(path) as f:
            recs = [json.loads(ln) for ln in f]
        assert len(recs) == 1 and recs[0]["point"] == "unit.json"

    def test_failpoints_scoped_is_ledgered(self, monkeypatch):
        led = ResourceLedger(enabled=True)
        import uda_tpu.utils.resledger as resledger_mod

        monkeypatch.setattr(resledger_mod, "resledger", led)
        with failpoints.scoped("data_engine.pread=delay:1:once"):
            assert len(led.outstanding()) == 1
            assert led.outstanding()[0]["pair"] == "ctx.failpoints.scoped"
        assert led.outstanding() == []


# -- the faults-marked mid-pipeline leak test --------------------------------


@pytest.mark.faults
def test_resledger_midpipeline_fault_and_seeded_leak(tmp_path, monkeypatch):
    """Two guarantees in one run. (1) A storage fault that aborts a
    REAL pipelined merger leaks zero obligations — the chaos rungs'
    zero-leaks gate in miniature. (2) A seeded stray pool lease (the
    lost-worker-buffer shape) is reported at the abort drain point
    exactly once, with the acquire stack pointing at this test."""
    priv = ResourceLedger(enabled=True)
    monkeypatch.setattr(merge_ops, "resledger", priv)
    monkeypatch.setattr(overlap_mod, "resledger", priv)

    make_mof_tree(str(tmp_path), "jobRL", 6, 1, 40, seed=11)
    engine = DataEngine(DirIndexResolver(str(tmp_path)))
    cfg = Config({"uda.tpu.stage.pool": 2,
                  "uda.tpu.fetch.retries": 0})
    mm = MergeManager(LocalFetchClient(engine), KT, cfg)
    try:
        with failpoints.scoped("data_engine.pread=error:prob:0.7:seed:5"):
            with pytest.raises(FallbackSignal):
                mm.run("jobRL", map_ids("jobRL", 6), 0, lambda b: None)
    finally:
        engine.stop()
    om = mm._active_overlap
    assert om is not None and om._aborted
    for t in om._threads:
        t.join(timeout=10)
        assert not t.is_alive()
    # (1) the fault-and-abort left the books EMPTY
    assert priv.leak_reports == []
    assert priv.outstanding() == []
    if om._buf_pool is None:
        pytest.skip("no host buffer pool on this engine config "
                    "(native rows merge unavailable)")
    # (2) seed the historical leak shape and re-drain
    stray = om._buf_pool.lease(64, 8)
    assert stray is not None
    om.abort()
    assert len(priv.leak_reports) == 1
    rep = priv.leak_reports[0]
    assert rep["pair"] == "pool.lease"
    assert rep["point"] == "merger.abort"
    assert "test_resledger_midpipeline_fault_and_seeded_leak" in rep["stack"]
    # reported exactly once: the drain popped it
    om.abort()
    assert len(priv.leak_reports) == 1


def test_rowbufferpool_lease_release_is_ledgered(monkeypatch):
    priv = ResourceLedger(enabled=True)
    monkeypatch.setattr(merge_ops, "resledger", priv)
    pool = merge_ops.RowBufferPool()
    buf = pool.lease(16, 4)
    out = priv.outstanding()
    assert len(out) == 1 and out[0]["pair"] == "pool.lease"
    assert out[0]["owner"] == id(pool)
    pool.release(buf)
    assert priv.outstanding() == []
    # reuse path settles under the same key (the base data pointer)
    again = pool.lease(16, 4)
    assert len(priv.outstanding()) == 1
    pool.release(again)
    assert priv.outstanding() == []


def test_fd_cache_pins_are_ledgered(tmp_path, monkeypatch):
    priv = ResourceLedger(enabled=True)
    import uda_tpu.mofserver.data_engine as de_mod

    monkeypatch.setattr(de_mod, "resledger", priv)
    path = tmp_path / "mof.bin"
    path.write_bytes(b"x" * 64)
    cache = de_mod._FdCache()
    cache.acquire(str(path))
    cache.acquire(str(path))
    assert len(priv.outstanding()) == 2
    cache.release(str(path))
    assert len(priv.outstanding()) == 1
    cache.release(str(path))
    assert priv.outstanding() == []
    cache.release(str(path))  # over-release: clamped, settle ignored
    assert priv.outstanding() == []
    cache.close_all()


def test_global_ledger_disabled_by_default():
    """UDA_TPU_RESLEDGER unset => every hook is one attribute check and
    the books stay empty (the zero-overhead-when-off contract)."""
    if resledger.enabled:
        pytest.skip("ledger armed in this environment")
    resledger.acquire("pool.lease", key=99)
    assert resledger.outstanding() == []
