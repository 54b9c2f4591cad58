"""The udalint rule suite: the invariants PRs 1-4 established, encoded.

====== ==============================================================
UDA001 config-key strings (``uda.tpu.*`` / ``mapred.*``) must be
       declared in the ``FLAGS`` registry (uda_tpu/utils/config.py)
UDA002 metrics names must resolve against ``METRICS_REGISTRY`` (the
       AST port of the old check_metrics_names regex, including
       f-string prefixes and aliased receivers)
UDA003 failpoint site names must be registered sites
       (uda_tpu/utils/failpoints.py ``KNOWN_SITES``)
UDA004 no raw ``sock.close()`` in uda_tpu/net/ outside wire.py —
       ``wire.close_hard`` (shutdown-then-close) is the only legal
       teardown (the PR 4 deadlock lesson)
UDA005 never branch on exception/admission reason *strings*: compare
       structured ``cause`` fields, not ``str(e)`` or ``.reason``
UDA006 ``except Exception`` must log, count, re-raise, or forward the
       exception — silent swallows are findings
UDA007 no unbounded blocking call (``.result()``, ``Queue.get()``,
       ``Condition.wait()`` without timeout, socket ``recv``) inside a
       ``with <lock>:`` body — the static half of deadlock prevention
       (the dynamic half is uda_tpu/utils/locks.py lockdep)
UDA008 no blocking call (``recv``/``sendall``/unbounded ``.result()``/
       unbounded ``Queue.get()``) inside an event-loop callback body
       in uda_tpu/net/ — registered callbacks are the functions marked
       ``@loop_callback`` (uda_tpu/net/evloop.py); the loop thread's
       own run loop is exempt (parking in select() is its job)
UDA009 span names passed to ``start_span``/``span`` must belong to the
       declared ``SPAN_REGISTRY`` (uda_tpu/utils/metrics.py) — the
       UDA002 contract for the trace plane: span names are
       cross-process identifiers (REQ frames carry them as trace
       context, trace_merge.py stitches on them), so a typo'd name is
       a broken trace, not just an ugly one
UDA101 resource balance over the per-function CFG: every registered
       acquire (uda_tpu/analysis/flow.py DEFAULT_PAIRS) must reach a
       release/transfer/with-guard on EVERY path, exception edges
       included (the udaflow dataflow tier, uda_tpu/analysis/cfg.py)
UDA102 transitive blocking-under-lock / blocking-in-loop-callback via
       the intra-package call graph (the helper hop UDA007/UDA008
       cannot see)
UDA103 static TrackedLock with-nesting order must be acyclic tree-wide
       (the compile-time complement of runtime lockdep)
====== ==============================================================

Every rule is constructor-injectable (registry/sites/flags overrides)
so the fixture tests can prove firing without depending on the live
tables.
"""

from __future__ import annotations

import ast
import re
from typing import Iterable, List, Optional, Set, Tuple

from uda_tpu.analysis.core import FileContext, Finding, Rule
from uda_tpu.analysis.flow import (ResourceBalanceRule, StaticLockOrderRule,
                                   TransitiveBlockingRule)
from uda_tpu.analysis.race import RaceLocksetRule, WireExhaustivenessRule

__all__ = ["ALL_RULES", "default_engine",
           "ConfigKeyRule", "MetricsNameRule", "FailpointSiteRule",
           "RawSocketCloseRule", "ReasonStringBranchRule",
           "SwallowedExceptionRule", "BlockingInLockRule",
           "EventLoopBlockingRule", "SpanNameRule",
           "ResourceBalanceRule",
           "TransitiveBlockingRule", "StaticLockOrderRule"]


def _dotted(node: ast.AST) -> Optional[str]:
    """``a.b.c`` for a Name/Attribute chain, else None."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _last_segment(node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Name):
        return node.id
    return None


def _call_has_timeout(call: ast.Call) -> bool:
    """True when the call passes any positional arg or a ``timeout=``
    keyword (the static signature of a bounded wait)."""
    if call.args:
        return True
    return any(kw.arg == "timeout" for kw in call.keywords)


# -- UDA001 ------------------------------------------------------------------

_KEY_RE = re.compile(r"(?:uda\.tpu|mapred)(?:\.[a-z0-9_]+)+")


class ConfigKeyRule(Rule):
    """Config-key string literals must be declared in ``FLAGS``."""

    rule_id = "UDA001"
    description = "uda.tpu.* / mapred.* key strings must be in FLAGS"
    hint = "declare the key in uda_tpu/utils/config.py FLAGS (or fix the typo)"
    node_types = (ast.Constant,)

    def __init__(self, flags: Optional[Set[str]] = None):
        if flags is None:
            from uda_tpu.utils.config import FLAGS
            flags = set(FLAGS)
        self.flags = flags

    def visit(self, node: ast.Constant,
              ctx: FileContext) -> Iterable[Finding]:
        v = node.value
        if not isinstance(v, str) or not _KEY_RE.fullmatch(v):
            return ()
        if v in self.flags or ctx.is_docstring(node):
            return ()
        return (self.finding(
            ctx, node,
            f"config key {v!r} is not declared in the FLAGS registry"),)


# -- UDA002 ------------------------------------------------------------------

_METRIC_METHODS = ("add", "gauge", "gauge_add", "observe", "series")


class MetricsNameRule(Rule):
    """Metric names at ``metrics.add/gauge/gauge_add/observe`` call
    sites must be static and resolve against ``METRICS_REGISTRY``
    (f-string families against ``REGISTRY_PREFIXES``). Receivers are
    resolved through per-file aliases (``from ... import metrics as m``,
    ``m = metrics``, ``self.metrics``), which the old regex missed."""

    rule_id = "UDA002"
    description = "metrics names must be registered in METRICS_REGISTRY"
    hint = ("register the name in uda_tpu/utils/metrics.py "
            "METRICS_REGISTRY (schema doc included)")
    node_types = (ast.Call, ast.ImportFrom, ast.Assign)

    def __init__(self, registry: Optional[Set[str]] = None,
                 prefixes: Optional[Tuple[str, ...]] = None,
                 name_re: Optional[str] = None):
        if registry is None or prefixes is None or name_re is None:
            from uda_tpu.utils.metrics import (METRICS_REGISTRY, NAME_RE,
                                               REGISTRY_PREFIXES)
            registry = set(METRICS_REGISTRY) if registry is None else registry
            prefixes = REGISTRY_PREFIXES if prefixes is None else prefixes
            name_re = NAME_RE if name_re is None else name_re
        self.registry = registry
        self.prefixes = tuple(prefixes)
        self.name_re = re.compile(name_re + r"\Z")
        self._aliases: Set[str] = set()

    def begin_file(self, ctx: FileContext) -> None:
        # "metrics" counts as the hub even without a visible import:
        # fixtures and generated code still get checked
        self._aliases = {"metrics"}

    def _is_metrics_receiver(self, recv: ast.AST) -> bool:
        seg = _last_segment(recv)
        return seg is not None and seg in self._aliases

    def visit(self, node: ast.AST, ctx: FileContext) -> Iterable[Finding]:
        if isinstance(node, ast.ImportFrom):
            if node.module and node.module.endswith("metrics"):
                for alias in node.names:
                    if alias.name == "metrics":
                        self._aliases.add(alias.asname or alias.name)
            return ()
        if isinstance(node, ast.Assign):
            if isinstance(node.value, ast.Name) \
                    and node.value.id in self._aliases:
                for tgt in node.targets:
                    seg = _last_segment(tgt)
                    if seg:
                        self._aliases.add(seg)
            return ()
        # ast.Call
        func = node.func
        if not (isinstance(func, ast.Attribute)
                and func.attr in _METRIC_METHODS
                and self._is_metrics_receiver(func.value)):
            return ()
        name_arg = node.args[0] if node.args else None
        if name_arg is None:
            for kw in node.keywords:
                if kw.arg == "name":
                    name_arg = kw.value
        if name_arg is None:
            return (self._bad(ctx, node, ast.unparse(node)[:60],
                              "metric name must be a string literal"),)
        if isinstance(name_arg, ast.Constant) \
                and isinstance(name_arg.value, str):
            name = name_arg.value
            if not self.name_re.match(name):
                return (self._bad(ctx, name_arg, name,
                                  "not dotted domain.metric namespace"),)
            if name not in self.registry:
                return (self._bad(ctx, name_arg, name,
                                  "not listed in METRICS_REGISTRY"),)
            return ()
        if isinstance(name_arg, ast.JoinedStr):
            prefix = ""
            for part in name_arg.values:
                if isinstance(part, ast.Constant) \
                        and isinstance(part.value, str):
                    prefix += part.value
                else:
                    break
            if not any(prefix.startswith(p) for p in self.prefixes):
                return (self._bad(
                    ctx, name_arg, ast.unparse(name_arg),
                    f"f-string prefix {prefix!r} not in "
                    f"REGISTRY_PREFIXES {self.prefixes}"),)
            return ()
        return (self._bad(ctx, name_arg, ast.unparse(name_arg)[:60],
                          "metric name must be a string literal"),)

    def _bad(self, ctx: FileContext, node: ast.AST, name: str,
             reason: str) -> Finding:
        return self.finding(ctx, node, f"metric {name!r}: {reason}",
                            data={"name": name, "reason": reason})


# -- UDA003 ------------------------------------------------------------------


class FailpointSiteRule(Rule):
    """``failpoint("<site>")`` must name a registered site — a typo'd
    site is a failpoint that can never fire (and a chaos schedule that
    silently tests nothing)."""

    rule_id = "UDA003"
    description = "failpoint() sites must be registered"
    hint = ("register the site in uda_tpu/utils/failpoints.py "
            "_SITE_ERRORS (and document it in the module docstring)")
    node_types = (ast.Call,)

    def __init__(self, sites: Optional[Set[str]] = None):
        if sites is None:
            from uda_tpu.utils.failpoints import KNOWN_SITES
            sites = set(KNOWN_SITES)
        self.sites = sites

    def visit(self, node: ast.Call, ctx: FileContext) -> Iterable[Finding]:
        if not (isinstance(node.func, ast.Name)
                and node.func.id == "failpoint"):
            return ()
        site_arg = node.args[0] if node.args else None
        if not (isinstance(site_arg, ast.Constant)
                and isinstance(site_arg.value, str)):
            return (self.finding(
                ctx, node, "failpoint site must be a string literal "
                           "(sites are a static, auditable inventory)"),)
        if site_arg.value in self.sites:
            return ()
        return (self.finding(
            ctx, site_arg,
            f"failpoint site {site_arg.value!r} is not a registered "
            f"site"),)


# -- UDA004 ------------------------------------------------------------------

_SOCK_RE = re.compile(r"_?(?:[a-z_]*sock(?:et)?|listener|ls)")


class RawSocketCloseRule(Rule):
    """In uda_tpu/net/ every socket teardown must go through
    ``wire.close_hard`` — ``close()`` alone neither wakes a blocked
    ``recv()`` nor sends FIN while a reader's syscall pins the fd (the
    deadlock that cost PR 4 its first version)."""

    rule_id = "UDA004"
    description = "net/ sockets close via wire.close_hard only"
    hint = "call wire.close_hard(sock) (shutdown-then-close)"
    node_types = (ast.Call,)

    def visit(self, node: ast.Call, ctx: FileContext) -> Iterable[Finding]:
        if not ctx.in_net or ctx.basename == "wire.py":
            return ()
        func = node.func
        if not (isinstance(func, ast.Attribute) and func.attr == "close"):
            return ()
        seg = _last_segment(func.value)
        if seg is None or not _SOCK_RE.fullmatch(seg):
            return ()
        return (self.finding(
            ctx, node,
            f"raw {seg}.close() in uda_tpu/net/ — close() neither wakes "
            f"a blocked recv() nor forces the FIN out"),)


# -- UDA005 ------------------------------------------------------------------

_CMP_OPS = (ast.Eq, ast.NotEq, ast.In, ast.NotIn)


def _is_str_of_exception(node: ast.AST) -> bool:
    """``str(e)`` where ``e`` is bound by an enclosing except handler."""
    if not (isinstance(node, ast.Call) and isinstance(node.func, ast.Name)
            and node.func.id == "str" and len(node.args) == 1
            and isinstance(node.args[0], ast.Name)):
        return False
    exc_name = node.args[0].id
    cur = getattr(node, "parent", None)
    while cur is not None:
        if isinstance(cur, ast.ExceptHandler) and cur.name == exc_name:
            return True
        cur = getattr(cur, "parent", None)
    return False


def _is_str_const(node: ast.AST) -> bool:
    return isinstance(node, ast.Constant) and isinstance(node.value, str)


class ReasonStringBranchRule(Rule):
    """Control flow must branch on structured ``cause`` fields, never on
    human-readable reason strings (``str(e)``, ``.reason``) — messages
    get reworded, causes are API (the PR 3 admission contract)."""

    rule_id = "UDA005"
    description = "branch on cause enums, not reason strings"
    hint = ("compare the structured `cause` field (e.g. adm.cause == "
            "'hbm') or the exception type, never its message text")
    node_types = (ast.Compare, ast.Call)

    def visit(self, node: ast.AST, ctx: FileContext) -> Iterable[Finding]:
        if isinstance(node, ast.Call):
            # str(e).startswith("...") and friends
            func = node.func
            if (isinstance(func, ast.Attribute)
                    and func.attr in ("startswith", "endswith")
                    and _is_str_of_exception(func.value)):
                return (self.finding(
                    ctx, node, "branching on the exception's message "
                               "text via str(e)." + func.attr),)
            return ()
        if len(node.ops) != 1 or not isinstance(node.ops[0], _CMP_OPS):
            return ()
        left, right = node.left, node.comparators[0]
        for a, b in ((left, right), (right, left)):
            if _is_str_of_exception(a) and _is_str_const(b):
                return (self.finding(
                    ctx, node, "comparing str(<exception>) against a "
                               "string literal"),)
            if (isinstance(a, ast.Attribute) and a.attr == "reason"
                    and _is_str_const(b)):
                return (self.finding(
                    ctx, node, "comparing a .reason string against a "
                               "literal"),)
        return ()


# -- UDA006 ------------------------------------------------------------------

_LOG_METHODS = {"debug", "info", "warn", "warning", "error", "exception",
                "fatal", "critical", "trace", "log"}
_BROAD = {"Exception", "BaseException"}


class SwallowedExceptionRule(Rule):
    """A broad ``except Exception`` handler must log, count
    (``metrics.*``), re-raise, or at least forward the bound exception
    somewhere — a handler that does none of these erases the error."""

    rule_id = "UDA006"
    description = "except Exception must log, count, or re-raise"
    hint = ("log it (log.warn/error), count it "
            "(metrics.add('errors.swallowed')), re-raise, or forward "
            "the exception object")
    node_types = (ast.ExceptHandler,)

    @staticmethod
    def _is_broad(handler: ast.ExceptHandler) -> bool:
        t = handler.type
        if t is None:
            return True
        if isinstance(t, ast.Name):
            return t.id in _BROAD
        if isinstance(t, ast.Tuple):
            return any(isinstance(e, ast.Name) and e.id in _BROAD
                       for e in t.elts)
        return False

    def visit(self, node: ast.ExceptHandler,
              ctx: FileContext) -> Iterable[Finding]:
        if not self._is_broad(node):
            return ()
        for stmt in node.body:
            for sub in ast.walk(stmt):
                if isinstance(sub, ast.Raise):
                    return ()
                if isinstance(sub, ast.Name) and node.name \
                        and sub.id == node.name:
                    return ()  # the exception object is being used
                if isinstance(sub, ast.Call):
                    f = sub.func
                    if isinstance(f, ast.Name) and f.id == "print":
                        return ()
                    if isinstance(f, ast.Attribute):
                        if f.attr in _LOG_METHODS:
                            return ()
                        if f.attr in _METRIC_METHODS \
                                and _last_segment(f.value) == "metrics":
                            return ()
        what = ("bare except" if node.type is None
                else ast.unparse(node.type))
        return (self.finding(
            ctx, node, f"`except {what}` silently swallows the error"),)


# -- UDA007 ------------------------------------------------------------------

_LOCK_RE = re.compile(r"_?(?:[a-z0-9_]*lock|cv|cond(?:ition)?|mu(?:tex)?)")
_QUEUE_RE = re.compile(r"_?(?:[a-z0-9_]*queue|(?:in|out|work)?q)")
_RECV = {"recv", "recv_into", "recvfrom", "recvmsg"}


class BlockingInLockRule(Rule):
    """No unbounded blocking call inside a ``with <lock>:`` body: a
    wait that can never time out while holding a lock is half a
    deadlock already (the other half is whoever needs that lock to
    produce the completion). Bounded waits — any positional arg or
    ``timeout=`` keyword — pass."""

    rule_id = "UDA007"
    description = "no unbounded blocking calls while holding a lock"
    hint = ("move the wait outside the lock, or bound it with a "
            "timeout= and handle the timeout")
    node_types = (ast.With,)

    @staticmethod
    def _lock_names(node: ast.With) -> List[str]:
        names = []
        for item in node.items:
            seg = _last_segment(item.context_expr)
            if seg is not None and _LOCK_RE.fullmatch(seg):
                names.append(seg)
        return names

    def visit(self, node: ast.With, ctx: FileContext) -> Iterable[Finding]:
        locks = self._lock_names(node)
        if not locks:
            return ()
        findings: List[Finding] = []
        # walk the body, but not into nested lock-withs (they get their
        # own dispatch) nor into nested function bodies (deferred code
        # does not run while this lock is held)
        stack: List[ast.AST] = list(node.body)
        while stack:
            cur = stack.pop()
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.Lambda)):
                continue
            if isinstance(cur, ast.With) and self._lock_names(cur):
                continue
            if isinstance(cur, ast.Call):
                bad = self._blocking(cur)
                if bad:
                    findings.append(self.finding(
                        ctx, cur,
                        f"unbounded {bad} inside `with {locks[0]}:`"))
            stack.extend(ast.iter_child_nodes(cur))
        return findings

    @staticmethod
    def _blocking(call: ast.Call) -> Optional[str]:
        func = call.func
        if not isinstance(func, ast.Attribute):
            return None
        attr = func.attr
        if attr == "result" and not _call_has_timeout(call):
            return "Future.result()"
        if attr in ("wait", "wait_for") and not _call_has_timeout(call):
            return f".{attr}()"
        if attr == "get" and not _call_has_timeout(call):
            seg = _last_segment(func.value)
            if seg is not None and _QUEUE_RE.fullmatch(seg):
                return f"{seg}.get()"
            return None
        if attr in _RECV:
            return f"socket .{attr}()"
        return None


# -- UDA008 ------------------------------------------------------------------


class EventLoopBlockingRule(Rule):
    """No blocking call inside an event-loop callback body in
    ``uda_tpu/net/``: one parked callback stalls EVERY connection the
    shared loop multiplexes (and, transitively, every fetch in the
    process) — the failure mode the event-loop refactor exists to make
    impossible. Registered callbacks are the functions marked with
    ``@loop_callback`` (the declarative contract from
    uda_tpu/net/evloop.py); the loop thread's own run loop is exempt —
    parking in ``select()`` is its job. Banned forms: blocking socket
    ``recv``/``sendall`` (use ``recv_into``/``send``/``sendmsg`` on
    the non-blocking fd), unbounded ``Future.result()``, unbounded
    queue ``get()``. Deferred code (nested defs, lambdas) is skipped —
    it does not run on the loop. Potentially-blocking completion
    upcalls belong on ``EventLoop.dispatch()``."""

    rule_id = "UDA008"
    description = "no blocking calls in event-loop callbacks in net/"
    hint = ("use the non-blocking form (recv_into/send/sendmsg, "
            "result(timeout=...), get(timeout=...)), or move the work "
            "to EventLoop.dispatch()")
    node_types = (ast.FunctionDef, ast.AsyncFunctionDef)

    def __init__(self, marker: str = "loop_callback"):
        self.marker = marker

    def _is_marked(self, node) -> bool:
        for dec in node.decorator_list:
            target = dec.func if isinstance(dec, ast.Call) else dec
            if _last_segment(target) == self.marker:
                return True
        return False

    def visit(self, node, ctx: FileContext) -> Iterable[Finding]:
        if not ctx.in_net or not self._is_marked(node):
            return ()
        findings: List[Finding] = []
        stack: List[ast.AST] = list(node.body)
        while stack:
            cur = stack.pop()
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.Lambda)):
                continue  # deferred code does not run on the loop
            if isinstance(cur, ast.Call):
                bad = self._blocking(cur)
                if bad:
                    findings.append(self.finding(
                        ctx, cur,
                        f"{bad} inside event-loop callback "
                        f"{node.name!r}"))
            stack.extend(ast.iter_child_nodes(cur))
        return findings

    @staticmethod
    def _blocking(call: ast.Call) -> Optional[str]:
        func = call.func
        if not isinstance(func, ast.Attribute):
            return None
        attr = func.attr
        if attr == "sendall":
            return "blocking .sendall()"
        if attr == "recv":
            return "blocking .recv()"
        if attr == "result" and not _call_has_timeout(call):
            return "unbounded Future.result()"
        if attr == "get" and not _call_has_timeout(call):
            seg = _last_segment(func.value)
            if seg is not None and _QUEUE_RE.fullmatch(seg):
                return f"unbounded {seg}.get()"
        return None


# -- UDA009 ------------------------------------------------------------------

_SPAN_METHODS = ("start_span", "span")


class SpanNameRule(Rule):
    """Span names at ``metrics.start_span``/``metrics.span`` call sites
    must be string literals registered in ``SPAN_REGISTRY`` — the
    UDA002 contract extended to the trace plane. Span names are
    cross-process identifiers (the wire carries their ids as trace
    context; scripts/trace_merge.py and every trace dashboard key on
    the inventory), so they are a static, auditable table like metrics
    names and failpoint sites. Receivers resolve through the same
    per-file alias tracking as UDA002 (``from ... import metrics as
    m``, ``m = metrics``); ``metrics.timer(name)`` spans are named by
    their timer counter and deliberately out of scope."""

    rule_id = "UDA009"
    description = "span names must be registered in SPAN_REGISTRY"
    hint = ("register the name in uda_tpu/utils/metrics.py "
            "SPAN_REGISTRY (description included) or fix the typo")
    node_types = (ast.Call, ast.ImportFrom, ast.Assign)

    def __init__(self, registry: Optional[Set[str]] = None):
        if registry is None:
            from uda_tpu.utils.metrics import SPAN_REGISTRY
            registry = set(SPAN_REGISTRY)
        self.registry = registry
        self._aliases: Set[str] = set()

    def begin_file(self, ctx: FileContext) -> None:
        self._aliases = {"metrics"}

    def visit(self, node: ast.AST, ctx: FileContext) -> Iterable[Finding]:
        if isinstance(node, ast.ImportFrom):
            if node.module and node.module.endswith("metrics"):
                for alias in node.names:
                    if alias.name == "metrics":
                        self._aliases.add(alias.asname or alias.name)
            return ()
        if isinstance(node, ast.Assign):
            if isinstance(node.value, ast.Name) \
                    and node.value.id in self._aliases:
                for tgt in node.targets:
                    seg = _last_segment(tgt)
                    if seg:
                        self._aliases.add(seg)
            return ()
        # ast.Call
        func = node.func
        if not (isinstance(func, ast.Attribute)
                and func.attr in _SPAN_METHODS
                and _last_segment(func.value) in self._aliases):
            return ()
        name_arg = node.args[0] if node.args else None
        if name_arg is None:
            for kw in node.keywords:
                if kw.arg == "name":
                    name_arg = kw.value
        if not (isinstance(name_arg, ast.Constant)
                and isinstance(name_arg.value, str)):
            return (self.finding(
                ctx, node,
                "span name must be a string literal (span names are a "
                "static, cross-process-auditable inventory)"),)
        if name_arg.value in self.registry:
            return ()
        return (self.finding(
            ctx, name_arg,
            f"span name {name_arg.value!r} is not declared in "
            f"SPAN_REGISTRY"),)


ALL_RULES = (ConfigKeyRule, MetricsNameRule, FailpointSiteRule,
             RawSocketCloseRule, ReasonStringBranchRule,
             SwallowedExceptionRule, BlockingInLockRule,
             EventLoopBlockingRule, SpanNameRule,
             # the udaflow dataflow tier (uda_tpu/analysis/flow.py)
             ResourceBalanceRule, TransitiveBlockingRule,
             StaticLockOrderRule,
             # the udarace lockset tier (uda_tpu/analysis/race.py):
             # UDA201/202/203 from the one collector + UDA204
             RaceLocksetRule, WireExhaustivenessRule)


def default_engine(root: Optional[str] = None):
    """The full-suite engine (lazy import keeps core importable without
    the live registries)."""
    from uda_tpu.analysis.core import Engine
    return Engine([cls() for cls in ALL_RULES], root=root)
