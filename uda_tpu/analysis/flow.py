"""udaflow: the dataflow rule tier (UDA101-UDA103).

udalint's first eight rules are per-statement; the bug class that kept
resurfacing in review (PR 6's ``try_plan`` admission-byte leak, the
PR 5 cancel-while-queued leak, PR 9's stranded ``stage.inflight.bytes``)
is a *path* property: a resource acquired on one path and never released
on an exception/early-exit path. This module makes balance a
machine-checked property over :mod:`uda_tpu.analysis.cfg` graphs:

====== ==============================================================
UDA101 resource-balance: an acquire (per the obligation-pair registry,
       :data:`DEFAULT_PAIRS`) from which some CFG path — exception
       edges included — reaches function exit without the paired
       release, a declared transfer, or a ``with`` guard
UDA102 transitive blocking: an unbounded blocking call reached through
       a *helper function* inside ``with <lock>:`` (the hop that
       defeats UDA007) or inside an ``@loop_callback`` body (the hop
       that defeats UDA008), via a lightweight intra-package call
       graph resolved by function name
UDA103 static lock order: ``with``-nesting pairs of TrackedLock/
       TrackedCondition *classes* collected tree-wide must form an
       acyclic order graph — the compile-time complement of the
       runtime lockdep validator (uda_tpu/utils/locks.py)
====== ==============================================================

The obligation model (UDA101)
-----------------------------

Obligations come from a declared acquire->release pair registry — the
same inventory the runtime :class:`~uda_tpu.utils.resledger
.ResourceLedger` arms. Three pair kinds:

- **method pairs**: ``acquire``/``release``/``transfer`` callee names
  (optionally receiver-filtered), e.g. DataEngine ``_admit_bytes`` /
  ``_unadmit`` with the charge transferable into an FdSlice;
- **gauge pairs**: ``metrics.gauge_add(<name>, +d)`` opens and
  ``gauge_add(<name>, -d)`` closes an obligation for the registered
  paired gauges (``fetch.on_air``, ``stage.inflight.bytes``, ...);
- **context pairs**: calls that return a context manager and are only
  balanced when entered (``failpoints.scoped``) — using one outside a
  ``with`` item (or ``enter_context``) is itself the finding.

A forward worklist ("may be open") analysis propagates the set of open
acquire sites; any site still open at a terminal is reported at its
acquire line. Settling events: the paired release, a declared transfer
call, a ``with`` guard (the acquire *is* a context expression), or a
``return`` of a non-constant value — the obligation may ride the
returned object to the caller (the FdSlice/BufferSlot/charge-int
hand-off idiom), so escaping values are the caller's problem, exactly
like the runtime ledger holds whoever ends up with the handle
responsible. What can NEVER settle silently is an exception edge: that
is the historical leak shape, and the rule exists for it.

All three rules keep the engine contract: constructor-injectable
registries for fixtures, findings on the line the developer must fix,
suppressions via ``# udalint: disable=...`` with a justification.
UDA102/UDA103 are tree-wide (they accumulate per-file state and report
from ``finalize()`` after the last file).
"""

from __future__ import annotations

import ast
import dataclasses
import re
from typing import Dict, Iterable, List, Optional, Set, Tuple

from uda_tpu.analysis.cfg import CFG, build_cfg
from uda_tpu.analysis.core import FileContext, Finding, Rule

__all__ = ["ObligationPair", "DEFAULT_PAIRS", "ResourceBalanceRule",
           "TransitiveBlockingRule", "StaticLockOrderRule"]


def _last_segment(node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Name):
        return node.id
    return None


def _call_has_timeout(call: ast.Call) -> bool:
    if call.args:
        return True
    return any(kw.arg == "timeout" for kw in call.keywords)


# -- the obligation-pair registry --------------------------------------------


@dataclasses.dataclass(frozen=True)
class ObligationPair:
    """One declared acquire->release discipline.

    ``kind``: "method" (call-name pair), "gauge" (paired gauge_add
    increments), or "context" (must be entered via ``with``).
    ``recv`` is an optional regex the receiver's last segment must
    match (keeps generic names like ``lease``/``acquire`` scoped to
    the objects that own the discipline). ``transfer`` names calls
    that take the obligation over (ownership hand-off, e.g. the pool
    submit that carries an admission charge to the worker's finally).
    """

    pair_id: str
    kind: str = "method"
    acquire: Tuple[str, ...] = ()
    release: Tuple[str, ...] = ()
    transfer: Tuple[str, ...] = ()
    recv: str = ""                 # regex on the receiver's last segment
    gauge: str = ""                # gauge name (kind == "gauge")
    description: str = ""

    def recv_ok(self, call: ast.Call) -> bool:
        if not self.recv:
            return True
        func = call.func
        if not isinstance(func, ast.Attribute):
            return False
        seg = _last_segment(func.value)
        return seg is not None and re.fullmatch(self.recv, seg) is not None


# The live registry: every runtime discipline the ResourceLedger arms
# (uda_tpu/utils/resledger.py) has its static mirror here — the two
# inventories are kept in lockstep deliberately (README table).
DEFAULT_PAIRS: Tuple[ObligationPair, ...] = (
    ObligationPair(
        "engine.admit", acquire=("_admit_bytes",), release=("_unadmit",),
        description="DataEngine read-budget admission bytes "
                    "(mofserver/data_engine.py)"),
    ObligationPair(
        "engine.fd", acquire=("acquire",), release=("release",),
        recv=r".*fds.*",
        description="DataEngine fd-cache references (_FdCache)"),
    ObligationPair(
        "pool.lease", acquire=("lease",), release=("release",),
        recv=r".*(pool|bufs).*",
        description="RowBufferPool host-buffer leases (ops/merge.py)"),
    ObligationPair(
        "gauge.fetch.on_air", kind="gauge", gauge="fetch.on_air",
        description="in-flight fetch attempts (merger/segment.py)"),
    ObligationPair(
        "gauge.stage.inflight", kind="gauge", gauge="stage.inflight.bytes",
        description="fed-but-unmerged staging bytes (merger/overlap.py)"),
    ObligationPair(
        "gauge.arena.slots", kind="gauge", gauge="arena.slots_in_use",
        description="staging-arena slot occupancy (merger/arena.py)"),
    ObligationPair(
        "gauge.reads.on_air", kind="gauge", gauge="supplier.reads.on_air",
        description="DataEngine reads queued or executing"),
    ObligationPair(
        "gauge.read.bytes", kind="gauge", gauge="supplier.read.bytes.on_air",
        description="admitted supplier read bytes"),
    ObligationPair(
        "gauge.io.batch", kind="gauge", gauge="io.batch.inflight",
        description="requests inside the batched read plane "
                    "(mofserver/data_engine.py submit_batch)"),
    ObligationPair(
        "gauge.tenant.read.bytes", kind="gauge",
        gauge="tenant.read.bytes.on_air",
        description="tenant-stamped supplier admission bytes (the "
                    "per-tenant partition level; the tenant.admit "
                    "attribution pair rides the same charge with "
                    "key=tenant — mofserver/data_engine.py)"),
    ObligationPair(
        "ctx.failpoints.scoped", kind="context", acquire=("scoped",),
        recv=r".*failpoints.*", transfer=("enter_context",),
        description="scoped failpoint arming must be entered "
                    "(utils/failpoints.py)"),
    ObligationPair(
        "store.fd", acquire=("acquire_fd",), release=("release_fd",),
        description="MOF-store backend handles (mofserver/store.py "
                    "MOFStore.acquire_fd/release_fd)"),
    ObligationPair(
        "gauge.store.migrate", kind="gauge",
        gauge="store.migrate.bytes.on_air",
        description="bytes mid-migration between store tiers "
                    "(mofserver/store.py StoreManager.migrate)"),
    ObligationPair(
        "gauge.push.on_air", kind="gauge", gauge="push.on_air",
        description="in-flight MSG_PUSH chunks awaiting ACK/NACK "
                    "(net/push.py PushScheduler)"),
    ObligationPair(
        "gauge.push.staged", kind="gauge", gauge="push.staged.bytes",
        description="pushed bytes staged reduce-side but not yet "
                    "adopted or discarded (net/push.py PushStaging)"),
    ObligationPair(
        "gauge.tasks.live", kind="gauge", gauge="reduce.tasks.live",
        description="reduce tasks on the chip-wide HBM ledger's books "
                    "(utils/budget.py HbmLedger.reserve / HbmHold."
                    "release)"),
    ObligationPair(
        "gauge.hbm.reserved", kind="gauge", gauge="budget.hbm.reserved",
        description="device bytes the live reduce tasks hold reserved "
                    "in the chip-wide HBM ledger (utils/budget.py)"),
)


# -- UDA101 ------------------------------------------------------------------


class _Events:
    """Per-CFG-node obligation effects."""

    __slots__ = ("acquires", "kills", "ret_value", "ret_names",
                 "ret_has_call")

    def __init__(self) -> None:
        # (pair id, bound variable name or None) opened here
        self.acquires: List[Tuple[str, Optional[str]]] = []
        self.kills: Set[str] = set()    # pair ids settled here
        # return-of-value escape data (see _ret_settles): names the
        # return expression references, and whether it contains a call
        # (a constructed object may carry a handle-less obligation)
        self.ret_value = False
        self.ret_names: Set[str] = set()
        self.ret_has_call = False


class ResourceBalanceRule(Rule):
    """UDA101: every acquire must be balanced on every CFG path.

    See the module docstring for the obligation model. Findings anchor
    on the acquire line (that is where the fix goes: a try/finally, a
    ``with``, or an exception-path release)."""

    rule_id = "UDA101"
    description = ("acquire/release balance on every CFG path "
                   "(exception edges included)")
    hint = ("guard the acquire with try/finally (or `with`), release "
            "on the exception path, or hand the obligation off "
            "explicitly and suppress with a justification")
    node_types = (ast.FunctionDef, ast.AsyncFunctionDef)

    def __init__(self, pairs: Optional[Iterable[ObligationPair]] = None):
        self.pairs = tuple(DEFAULT_PAIRS if pairs is None else pairs)
        self._by_kind = {
            "method": [p for p in self.pairs if p.kind == "method"],
            "gauge": [p for p in self.pairs if p.kind == "gauge"],
            "context": [p for p in self.pairs if p.kind == "context"],
        }
        # a function NAMED like a pair's acquire/release/transfer IS the
        # pair's implementation: its body performs the raw state moves
        # (the paired gauge bump inside _admit_bytes, the free-list push
        # inside release) that the registry models at its CALLERS —
        # charging the wrapper's own body would double count every pair
        self._impl_names: Set[str] = set()
        for p in self.pairs:
            self._impl_names.update(p.acquire)
            self._impl_names.update(p.release)
            self._impl_names.update(p.transfer)

    # -- event extraction ----------------------------------------------------

    @staticmethod
    def _gauge_delta_sign(call: ast.Call) -> Optional[int]:
        """+1 / -1 for the gauge_add delta argument's static sign,
        None when indeterminate (no delta argument)."""
        arg: Optional[ast.AST] = None
        if len(call.args) >= 2:
            arg = call.args[1]
        else:
            for kw in call.keywords:
                if kw.arg == "delta":
                    arg = kw.value
        if arg is None:
            return None
        if isinstance(arg, ast.UnaryOp) and isinstance(arg.op, ast.USub):
            return -1
        if isinstance(arg, ast.Constant) and isinstance(arg.value,
                                                        (int, float)):
            return -1 if arg.value < 0 else 1
        return 1  # bare name/expression: the idiom charges positively

    def _call_events(self, call: ast.Call, guarded: bool,
                     ev: _Events) -> None:
        seg = _last_segment(call.func)
        if seg is None:
            return
        if seg == "gauge_add":
            name_arg = call.args[0] if call.args else None
            if isinstance(name_arg, ast.Constant) \
                    and isinstance(name_arg.value, str):
                for pair in self._by_kind["gauge"]:
                    if pair.gauge != name_arg.value:
                        continue
                    sign = self._gauge_delta_sign(call)
                    if sign is not None and sign < 0:
                        ev.kills.add(pair.pair_id)
                    elif not guarded:
                        ev.acquires.append((pair.pair_id, None))
            return
        for pair in self._by_kind["method"] + self._by_kind["context"]:
            if seg in pair.release and pair.recv_ok(call):
                ev.kills.add(pair.pair_id)
            if seg in pair.transfer:
                ev.kills.add(pair.pair_id)
            if seg in pair.acquire and pair.recv_ok(call) and not guarded:
                ev.acquires.append((pair.pair_id, None))

    @staticmethod
    def _bound_target(node) -> Tuple[Optional[str], bool]:
        """(variable name the node's statement binds, escapes-to-
        attribute): ``x = <acquire>`` binds ``x``; ``self.x =
        <acquire>`` escapes the function scope immediately (the object
        owns the obligation now, like a returned handle)."""
        stmt = node.stmt
        if node.kind == "stmt" and isinstance(stmt, ast.Assign) \
                and len(stmt.targets) == 1:
            tgt = stmt.targets[0]
            if isinstance(tgt, ast.Name):
                return tgt.id, False
            if isinstance(tgt, ast.Attribute):
                return None, True
        return None, False

    def _node_events(self, node) -> _Events:
        """Extract obligation effects from one CFG node's expressions.
        Calls inside nested defs/lambdas are deferred code and do not
        count; a call that IS a ``with`` item's context expression is
        guarded (the with statement owns its balance); a call directly
        inside ``enter_context(...)`` likewise."""
        ev = _Events()
        guarded_calls: Set[int] = set()
        if node.kind == "with" and node.stmt is not None:
            for item in node.stmt.items:
                if isinstance(item.context_expr, ast.Call):
                    guarded_calls.add(id(item.context_expr))
        var, escapes = self._bound_target(node)
        for expr in node.exprs:
            if expr is None:
                continue
            stack = [expr]
            while stack:
                cur = stack.pop()
                if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef,
                                    ast.Lambda)):
                    continue
                if isinstance(cur, ast.Call):
                    guarded = id(cur) in guarded_calls or escapes
                    if not guarded:
                        seg = _last_segment(cur.func)
                        if seg in ("enter_context",):
                            for arg in cur.args:
                                if isinstance(arg, ast.Call):
                                    guarded_calls.add(id(arg))
                    before = len(ev.acquires)
                    self._call_events(cur, guarded, ev)
                    if var is not None:
                        # the handle the statement binds carries every
                        # obligation this call opened
                        ev.acquires[before:] = [
                            (pid, var) for pid, _ in ev.acquires[before:]]
                stack.extend(ast.iter_child_nodes(cur))
        if node.kind == "return" and node.stmt is not None:
            value = node.stmt.value
            if value is not None and not (
                    isinstance(value, ast.Constant)):
                ev.ret_value = True
                for sub in ast.walk(value):
                    if isinstance(sub, ast.Name):
                        ev.ret_names.add(sub.id)
                    elif isinstance(sub, ast.Call):
                        ev.ret_has_call = True
        return ev

    @staticmethod
    def _ret_settles(pair: ObligationPair, var: Optional[str],
                     ev: _Events) -> bool:
        """Does a ``return <non-constant>`` settle this open site? Only
        when the obligation can plausibly ride the returned value: the
        bound handle is referenced in the return expression, or the
        acquire bound no handle and the value is built by a call (the
        FdSlice idiom — the constructed object carries the charge).
        A paired-GAUGE increment can never ride a return value."""
        if pair.kind == "gauge":
            return False
        if var is not None:
            return var in ev.ret_names
        return ev.ret_has_call

    # -- the worklist --------------------------------------------------------

    def _analyze(self, cfg: CFG,
                 ctx: FileContext) -> List[Finding]:
        events = [self._node_events(n) for n in cfg.nodes]
        if not any(ev.acquires for ev in events):
            return []  # nothing acquired in this function
        # site = (pair_id, node_index, bound var or None); state = set
        # of open sites. TWO out-states per node: the normal edge
        # carries (IN - kills - ret_settled) | gens, the node's own
        # exception edge carries IN - kills only — an acquire that
        # raises did not acquire (and a release that raises is still
        # credited: release implementations settle before any failure
        # can surface). The return-of-value escape (_ret_settles)
        # applies to the normal edge only — a raising return never
        # produced the value.
        pair_by_id = {p.pair_id: p for p in self.pairs}
        Site = Tuple[str, int, Optional[str]]
        n_nodes = len(cfg.nodes)
        state_in: List[Set[Site]] = [set() for _ in range(n_nodes)]
        out_norm: List[Set[Site]] = [set() for _ in range(n_nodes)]
        out_exc: List[Set[Site]] = [set() for _ in range(n_nodes)]
        preds = cfg.preds()

        # standard forward may-analysis worklist: seed with every node
        # (gens self-seed), re-queue successors on any OUT change;
        # union join is monotone over finite site sets, so this
        # terminates at the least fixpoint
        work = list(range(n_nodes))
        queued = set(work)
        while work:
            idx = work.pop()
            queued.discard(idx)
            incoming: Set[Site] = set()
            for p, via_exc in preds[idx]:
                incoming |= out_exc[p] if via_exc else out_norm[p]
            state_in[idx] = incoming
            ev = events[idx]
            survived = ({s for s in incoming if s[0] not in ev.kills}
                        if ev.kills else set(incoming))
            norm = set(survived)
            if ev.ret_value:
                norm = {s for s in norm if not self._ret_settles(
                    pair_by_id[s[0]], s[2], ev)}
            norm.update((pid, idx, var) for pid, var in ev.acquires)
            if norm != out_norm[idx] or survived != out_exc[idx]:
                out_norm[idx] = norm
                out_exc[idx] = survived
                for s in cfg.nodes[idx].succs:
                    if s not in queued:
                        queued.add(s)
                        work.append(s)
        leaks_exit = state_in[cfg.exit_id]
        leaks_raise = state_in[cfg.raise_id]
        findings: List[Finding] = []
        reported: Set[Tuple[str, int]] = set()
        for site in sorted(leaks_exit | leaks_raise,
                           key=lambda s: (cfg.nodes[s[1]].line, s[0])):
            pid, node_idx, _var = site
            if (pid, node_idx) in reported:
                continue
            reported.add((pid, node_idx))
            node = cfg.nodes[node_idx]
            pair = pair_by_id[pid]
            if pair.kind == "context":
                msg = (f"{pid}: {pair.acquire[0]}() returns a context "
                       f"obligation but is not entered (`with ...:`) — "
                       f"the scope never closes")
            else:
                how = []
                if site in leaks_raise:
                    how.append("an exception path")
                if site in leaks_exit:
                    how.append("a normal path")
                msg = (f"{pid}: acquired here but "
                       f"{' and '.join(how)} reaches function exit "
                       f"without the paired release "
                       f"({'/'.join(pair.release) or 'with-guard'})")
            findings.append(Finding(
                ctx.rel, node.line,
                getattr(node.stmt, "col_offset", 0), self.rule_id, msg,
                self.hint, data={"pair": pid}))
        return findings

    def visit(self, node, ctx: FileContext) -> Iterable[Finding]:
        if node.name in self._impl_names:
            return ()  # the pair's own implementation (see __init__)
        try:
            cfg = build_cfg(node)
        except RecursionError:  # pathological nesting: skip, don't die
            return ()
        return self._analyze(cfg, ctx)


# -- UDA102 ------------------------------------------------------------------

_LOCK_RE = re.compile(r"_?(?:[a-z0-9_]*lock|cv|cond(?:ition)?|mu(?:tex)?)")
_QUEUE_RE = re.compile(r"_?(?:[a-z0-9_]*queue|(?:in|out|work)?q)")
_RECV = {"recv", "recv_into", "recvfrom", "recvmsg"}

# names that never resolve to a project def worth chasing (cheap noise
# filter; anything not defined in the linted tree is skipped anyway)
_SKIP_CALLEES = {"len", "int", "str", "float", "bool", "list", "dict",
                 "set", "tuple", "print", "isinstance", "getattr",
                 "setattr", "hasattr", "range", "min", "max", "sorted"}


def _direct_blocking(call: ast.Call) -> Optional[str]:
    """The shared unbounded-blocking-call detector (UDA007's notion,
    plus no-arg ``.join()`` and ``time.sleep``-style delays): what a
    function must contain to seed the transitive `blocks` set."""
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
    if attr == "join" and not call.args and not call.keywords:
        seg = _last_segment(func.value)
        if seg is not None and not isinstance(func.value, ast.Constant):
            return f"{seg}.join()"
        return None
    if attr == "sendall":
        return ".sendall()"
    if attr in _RECV:
        return f"socket .{attr}()"
    return None


@dataclasses.dataclass
class _DefInfo:
    file: str
    line: int
    blocking: Optional[str]          # direct blocking description
    calls: Set[str]                  # callee last-segments


@dataclasses.dataclass
class _GuardedCall:
    file: str
    line: int
    col: int
    callee: str
    guard: str                       # "with <lock>:" | "@loop_callback"
    owner: str                       # guarding function / lock name


class TransitiveBlockingRule(Rule):
    """UDA102: blocking through a helper hop. UDA007/UDA008 catch a
    blocking call written directly under a lock / in a loop callback;
    one helper function defeats them (``with lock: self._drain()``
    where ``_drain`` joins threads). This rule builds a lightweight
    intra-package call graph — functions keyed by NAME, calls resolved
    to project-defined names only — seeds it with the directly-blocking
    defs, propagates to a fixpoint, and reports guarded calls whose
    callee lands in the transitive `blocks` set. Name-keyed resolution
    over-approximates (two defs sharing a name share a verdict), which
    is the right direction for a linter: the finding names the witness
    chain so a false hit is a one-line justified suppression."""

    rule_id = "UDA102"
    description = ("no transitively-blocking helper calls under a lock "
                   "or in an event-loop callback")
    hint = ("bound the wait inside the helper (timeout=...), move the "
            "helper call outside the lock/callback, or suppress with "
            "the justification that this name's blocking twin is "
            "never the one called here")
    node_types = (ast.FunctionDef, ast.AsyncFunctionDef, ast.With)

    def __init__(self, marker: str = "loop_callback"):
        self.marker = marker
        self._defs: Dict[str, List[_DefInfo]] = {}
        self._guarded: List[_GuardedCall] = []

    def begin_file(self, ctx: FileContext) -> None:
        self._ctx = ctx

    # -- collection ----------------------------------------------------------

    @staticmethod
    def _lock_names(node: ast.With) -> List[str]:
        names = []
        for item in node.items:
            seg = _last_segment(item.context_expr)
            if seg is not None and _LOCK_RE.fullmatch(seg):
                names.append(seg)
        return names

    def _scan_calls(self, body, skip_lock_withs: bool):
        """(callee, line, col, direct_blocking) for every call in
        ``body``, excluding nested defs/lambdas (deferred) and — when
        asked — nested lock-with bodies (they get their own site)."""
        out = []
        stack = list(body)
        while stack:
            cur = stack.pop()
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.Lambda)):
                continue
            if skip_lock_withs and isinstance(cur, ast.With) \
                    and self._lock_names(cur):
                continue
            if isinstance(cur, ast.Call):
                seg = _last_segment(cur.func)
                if seg:
                    out.append((seg, cur.lineno, cur.col_offset,
                                _direct_blocking(cur)))
            stack.extend(ast.iter_child_nodes(cur))
        return out

    def _is_marked(self, node) -> bool:
        for dec in node.decorator_list:
            target = dec.func if isinstance(dec, ast.Call) else dec
            if _last_segment(target) == self.marker:
                return True
        return False

    def visit(self, node, ctx: FileContext) -> Iterable[Finding]:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            calls = self._scan_calls(node.body, skip_lock_withs=False)
            blocking = next((d for _, _, _, d in calls if d), None)
            self._defs.setdefault(node.name, []).append(_DefInfo(
                ctx.rel, node.lineno, blocking,
                {c for c, _, _, _ in calls}))
            if ctx.in_net and self._is_marked(node):
                for callee, line, col, direct in self._scan_calls(
                        node.body, skip_lock_withs=False):
                    if direct:
                        continue  # UDA008's finding, not ours
                    self._guarded.append(_GuardedCall(
                        ctx.rel, line, col, callee,
                        "@loop_callback", node.name))
            return ()
        # ast.With
        locks = self._lock_names(node)
        if not locks:
            return ()
        for callee, line, col, direct in self._scan_calls(
                node.body, skip_lock_withs=True):
            if direct:
                continue  # UDA007's finding, not ours
            self._guarded.append(_GuardedCall(
                ctx.rel, line, col, callee, f"with {locks[0]}:",
                locks[0]))
        return ()

    # -- the fixpoint + report -----------------------------------------------

    def _blocking_closure(self) -> Dict[str, str]:
        """name -> witness chain ("a -> b -> .result()") for every
        project-defined name that blocks. Resolution is by NAME, so a
        name with several defs is only convicted when EVERY def blocks
        (directly or via its calls) — a name whose blocking twin lives
        in an unrelated module must not poison every caller of the
        benign homonyms (the generic-name problem: release/close/run).
        Monotone: adding a convicted name only ever flips more defs, so
        the loop reaches a least fixpoint."""
        blocks: Dict[str, str] = {}
        changed = True
        while changed:
            changed = False
            for name, infos in self._defs.items():
                if name in blocks:
                    continue
                witness: Optional[str] = None
                for info in infos:
                    if info.blocking:
                        witness = witness or info.blocking
                        continue
                    hit = next((c for c in info.calls
                                if c != name and c in blocks), None)
                    if hit is None:
                        witness = None
                        break
                    witness = witness or f"{hit} -> {blocks[hit]}"
                if witness is not None:
                    blocks[name] = witness
                    changed = True
        return blocks

    def finalize(self) -> Iterable[Finding]:
        blocks = self._blocking_closure()
        findings = []
        for g in self._guarded:
            if g.callee in _SKIP_CALLEES or g.callee not in self._defs:
                continue
            tail = blocks.get(g.callee)
            if tail is None:
                continue
            chain = f"{g.callee} -> {tail}"
            findings.append(Finding(
                g.file, g.line, g.col, self.rule_id,
                f"call to {g.callee!r} inside `{g.guard}` blocks "
                f"transitively ({chain})",
                self.hint, data={"callee": g.callee, "guard": g.guard}))
        findings.sort(key=lambda f: (f.file, f.line))
        return findings


# -- UDA103 ------------------------------------------------------------------

_TRACKED = {"TrackedLock", "TrackedCondition"}


class StaticLockOrderRule(Rule):
    """UDA103: the ``with``-nesting order of TrackedLock *classes*,
    collected tree-wide, must be acyclic. The runtime lockdep validator
    only sees orders a test actually exercised; this is the
    compile-time sweep over every lexically-nested pair, so an AB/BA
    inversion is a build failure even when no test interleaves the two
    orders. Same-class nesting is not an edge (lockdep's rule: class-
    level self-edges false-positive on instance hierarchies)."""

    rule_id = "UDA103"
    description = ("static TrackedLock with-nesting order must be "
                   "acyclic tree-wide")
    hint = ("pick ONE global order for the two lock classes and "
            "restructure the inverted site (or drop one lock scope)")
    node_types = (ast.Assign, ast.With)

    def __init__(self) -> None:
        # (file, enclosing class name or "", attr/var name) -> class
        self._lock_vars: Dict[Tuple[str, str, str], str] = {}
        # attr/var name -> set of classes (global fallback)
        self._by_name: Dict[str, Set[str]] = {}
        # raw nesting observations, resolved at finalize
        self._nestings: List[Tuple[str, int, int, Tuple[Tuple[str, str],
                                                        ...]]] = []

    # -- helpers -------------------------------------------------------------

    @staticmethod
    def _enclosing_class(node: ast.AST) -> str:
        cur = getattr(node, "parent", None)
        while cur is not None:
            if isinstance(cur, ast.ClassDef):
                return cur.name
            cur = getattr(cur, "parent", None)
        return ""

    def _lock_class_of_ctor(self, call: ast.Call,
                            scope: Tuple[str, str]) -> Optional[str]:
        """The lock class a TrackedLock(...)/TrackedCondition(...)
        constructor creates, or None when indeterminate."""
        seg = _last_segment(call.func)
        if seg == "TrackedLock":
            if call.args and isinstance(call.args[0], ast.Constant) \
                    and isinstance(call.args[0].value, str):
                return call.args[0].value
            for kw in call.keywords:
                if kw.arg == "name" and isinstance(kw.value, ast.Constant):
                    return str(kw.value.value)
            return None
        if seg == "TrackedCondition":
            arg0 = call.args[0] if call.args else None
            if isinstance(arg0, ast.Call):
                return self._lock_class_of_ctor(arg0, scope)
            if arg0 is not None:
                ref = _last_segment(arg0)
                if ref is not None:
                    got = self._lock_vars.get((scope[0], scope[1], ref))
                    if got:
                        return got
            for kw in call.keywords:
                if kw.arg == "lock" and isinstance(kw.value, ast.Call):
                    return self._lock_class_of_ctor(kw.value, scope)
                if kw.arg == "name" and isinstance(kw.value, ast.Constant):
                    return str(kw.value.value)
            return "cond"  # TrackedCondition() default name
        return None

    def visit(self, node, ctx: FileContext) -> Iterable[Finding]:
        if isinstance(node, ast.Assign):
            if not isinstance(node.value, ast.Call):
                return ()
            seg = _last_segment(node.value.func)
            if seg not in _TRACKED:
                return ()
            scope = (ctx.rel, self._enclosing_class(node))
            cls = self._lock_class_of_ctor(node.value, scope)
            if cls is None:
                return ()
            for tgt in node.targets:
                name = _last_segment(tgt)
                if name:
                    self._lock_vars[(ctx.rel, scope[1], name)] = cls
                    self._by_name.setdefault(name, set()).add(cls)
            return ()
        # ast.With: record this with's lock refs + those of enclosing
        # withs (innermost last); resolution happens at finalize when
        # the variable table is complete
        refs = self._with_lock_refs(node)
        if not refs:
            return ()
        chain: List[Tuple[str, str]] = []
        cur = getattr(node, "parent", None)
        while cur is not None:
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.Lambda)):
                break  # a `with` in an enclosing def is not held here
            if isinstance(cur, ast.With):
                outer = self._with_lock_refs(cur)
                chain = outer + chain
            cur = getattr(cur, "parent", None)
        scope_cls = self._enclosing_class(node)
        self._nestings.append(
            (ctx.rel, node.lineno, node.col_offset,
             tuple((scope_cls, r) for r in chain + refs)))
        return ()

    @staticmethod
    def _with_lock_refs(node: ast.With) -> List[str]:
        refs = []
        for item in node.items:
            seg = _last_segment(item.context_expr)
            if seg is not None and not isinstance(item.context_expr,
                                                  ast.Call):
                refs.append(seg)
        return refs

    # -- the order graph -----------------------------------------------------

    def _resolve(self, file: str, scope_cls: str,
                 name: str) -> Optional[str]:
        got = self._lock_vars.get((file, scope_cls, name))
        if got:
            return got
        classes = self._by_name.get(name, set())
        if len(classes) == 1:
            return next(iter(classes))
        return None  # unknown or ambiguous: no edge

    def finalize(self) -> Iterable[Finding]:
        edges: Dict[Tuple[str, str], Tuple[str, int, int]] = {}
        for file, line, col, chain in sorted(self._nestings):
            resolved = [c for c in
                        (self._resolve(file, sc, r) for sc, r in chain)
                        if c is not None]
            for i in range(len(resolved) - 1):
                a, b = resolved[i], resolved[i + 1]
                if a != b and (a, b) not in edges:
                    edges[(a, b)] = (file, line, col)
        adj: Dict[str, List[str]] = {}
        for a, b in edges:
            adj.setdefault(a, []).append(b)

        def path(src: str, dst: str) -> Optional[List[str]]:
            stack, seen = [(src, [src])], {src}
            while stack:
                node, p = stack.pop()
                if node == dst:
                    return p
                for nxt in adj.get(node, ()):
                    if nxt not in seen:
                        seen.add(nxt)
                        stack.append((nxt, p + [nxt]))
            return None

        findings = []
        reported: Set[Tuple[str, ...]] = set()
        for (a, b), (file, line, col) in sorted(edges.items(),
                                                key=lambda kv: kv[1]):
            p = path(b, a)
            if p is None:
                continue
            key = tuple(sorted(set([a] + p)))
            if key in reported:
                continue
            reported.add(key)
            other = edges.get((p[0], p[1]))
            where = (f" (reverse order at {other[0]}:{other[1]})"
                     if other else "")
            findings.append(Finding(
                file, line, col, self.rule_id,
                f"static lock-order cycle: `with` nesting takes "
                f"{a!r} -> {b!r} here, but {b!r} already reaches "
                f"{a!r} via {' -> '.join(p)}{where}",
                self.hint, data={"cycle": [a] + p}))
        return findings
