"""Memory admission control: budgets, the device-bytes model, routing.

The reference validated every buffer budget at INIT and failed into the
vanilla path when the pool could not fit (handle_init_msg, reference
src/Merger/reducer.cc:56-133) and *blocked* on chunk-pool exhaustion
instead of dying (occupy_chunk, reference
src/MOFServer/IndexInfo.cc:276-292). This engine's equivalent exposure
is the device row matrix: the global sort holds ~27 uint32 words per
record device-resident (~108 B/record at the TeraSort shape, ≈1.08x the
shuffle bytes), so a >10 GB per-chip partition
OOMs a 16 GB v5e with no graceful route, and on CPU the same rows are
host RSS (the 9.3 GB xxlarge symptom).

:class:`MemoryBudget` is the front door: per-chip HBM and host-RSS
budgets (``uda.tpu.hbm.budget.mb`` / ``uda.tpu.host.budget.mb``,
defaults derived from the detected platform), an estimator that converts
the transport's on-disk partition estimate into row-matrix +
working-set bytes, and two admission points:

- :meth:`validate_init` — the INIT-time buffer-budget check (the
  reducer.cc:56-133 mirror): the fetch window + staging arena working
  set must fit the host budget; over-budget either shrinks the window
  (``uda.tpu.budget.enforce=reroute``, warn like the reference's
  buffer shrink) or raises (``=reject``, the fallback path);
- :meth:`route` — the merge-approach decision (consumed by
  ``MergeManager._run``'s auto policy): in-budget partitions keep the
  fast hybrid/in-memory path, partitions whose device estimate exceeds
  the HBM budget are rerouted to bounded-memory streaming, and
  partitions above the hard ceiling (``uda.tpu.budget.hard.mb``) are
  rejected *before any allocation* — the caller raises
  ``FallbackSignal``. Unknown estimates route to streaming (bounded
  memory is the only safe default for an unbounded input).

Every decision is logged and counted (``budget.admitted`` /
``budget.rerouted`` / ``budget.rejected``).

:class:`HbmLedger` (the module's :data:`hbm_ledger`) is the CHIP's side
of admission: a TPU host's one process holds the chip, so a node's
reduce slots are several reduce tasks in that process sharing one HBM.
``MemoryBudget`` sizes one task against the chip; the ledger books what
the LIVE tasks hold, so the next one is admitted against the budget
less their reservations (:meth:`MemoryBudget.admit_device`, called by
``MergeManager._run`` before a task stages its first run to the device,
at every merge approach that builds the device forest). A task that
does not fit beside the live ones WAITS for a release — the reference
blocked on pool exhaustion too (``occupy_chunk``) — and one that does
not fit the chip alone is sized into GROUPS: it reserves what the
largest group of rows the chip can hold needs (:func:`group_capacity_rows`),
waits its turn like any task, and merges its partition on the device a
group at a time (merger/overlap.py). A reservation has two parts: the
rows a task holds, which add up over the live tasks, and the
temporaries of its largest merge program, of which the chip needs only
the largest at a time — one program runs at a time. The ledger is told
no slot count; it observes.
"""

from __future__ import annotations

import dataclasses
import re
from collections import deque
from typing import Callable, Optional

from uda_tpu.utils.errors import MergeError, UdaError
from uda_tpu.utils.locks import TrackedCondition, TrackedLock
from uda_tpu.utils.logging import get_logger
from uda_tpu.utils.metrics import metrics

__all__ = ["MemoryBudget", "Admission", "HbmLedger", "HbmHold",
           "hbm_ledger", "device_bytes_estimate",
           "merge_temp_bytes_estimate", "group_capacity_rows",
           "stage_inflight_cap", "ROW_OVERHEAD_WORDS",
           "HBM_ROW_ALIGN_WORDS", "RUN_PAD_FACTOR", "FOREST_FACTOR",
           "MERGE_TEMP_ROW_BYTES", "RECORD_BYTES_DEFAULT",
           "WORKING_SET_FACTOR", "HBM_RESERVE_FRACTION",
           "PLATFORM_HBM_MB", "STAGE_INFLIGHT_FLOOR_MB"]

log = get_logger()

MB = 1 << 20

# floor for the auto-derived staging-pipeline in-flight byte budget
STAGE_INFLIGHT_FLOOR_MB = 256


def stage_inflight_cap(cfg, window: int, chunk_size: int,
                       budget: Optional["MemoryBudget"] = None) -> int:
    """In-flight byte budget for the staging pipeline (bytes fed to the
    overlap merger but not yet merged/spooled — uda_tpu.merger.overlap
    charges/releases them; the gauge is ``stage.inflight.bytes``).

    ``uda.tpu.stage.inflight.mb`` wins when set; the auto default is
    max(STAGE_INFLIGHT_FLOOR_MB, 2x the fetch window's wire bytes) —
    enough that staging never throttles a healthy fetch window, small
    enough that a stalled device consumer cannot pile the whole shuffle
    into host RSS. When a MemoryBudget has ALREADY been built (the auto
    merge-approach path), the cap additionally clamps to half its host
    budget; a budget is deliberately NOT constructed here — platform
    detection must not run for explicitly-configured approaches (the
    same laziness MergeManager.budget() preserves)."""
    mb = int(cfg.get("uda.tpu.stage.inflight.mb"))
    if mb > 0:
        return mb * MB
    cap = max(STAGE_INFLIGHT_FLOOR_MB * MB,
              2 * max(1, int(window)) * max(1, int(chunk_size)))
    if budget is not None:
        cap = min(cap, max(MB, budget.host_budget_bytes // 2))
    return cap

# -- the device-bytes model -------------------------------------------------
#
# Two engines hold a partition on the device, and the model takes the
# larger so that it covers both:
#
# - the RUN FOREST of the overlapped merge (merger/overlap.py, the route
#   the served reduce path runs). Per record one uint32 row of (key
#   words, content length, segment index, row index) = key_width/4 +
#   ROW_OVERHEAD_WORDS columns, which libtpu stores long-dimension-minor
#   with the columns rounded up to HBM_ROW_ALIGN_WORDS: 7 columns cost
#   32 B a record (measured, v5e, PR 22 — not the 28 B of the logical
#   matrix). Every run is padded to a power-of-two capacity: at worst
#   RUN_PAD_FACTOR x its rows. And the merger holds at most
#   FOREST_FACTOR x the staged rows at once: one executed copy of every
#   row (in the forest, or as the input of a merge that has not run
#   yet) and the outputs of merges dispatched but perhaps not executed,
#   which OverlappedMerger._await_device_room holds to FOREST_FACTOR - 1
#   times the staged bytes (one carry chain's outputs add up to 2x);
# - the SORT LADDER of the whole-run sort engines: ~27 words a record
#   at the TeraSort shape (~108 B, SORT_LADDER_RATIO x the shuffle
#   bytes: key and payload surrogate columns ride along), times
#   WORKING_SET_FACTOR for the transient (a pairwise step holds both
#   operands and the output; 2x bounds it).
#
# Against the device (v5e, memory_stats peak_bytes_in_use over
# back-to-back tasks): a 1.05 GB partition in 64 runs staged 537 MB of
# rows; before the dispatch bound a warm task peaked at 2,429 MB (4.5x
# the staged rows — the host outran the device) where this model said
# 2,268 MB, the unsafe side; a 131 MB partition in 1,024 runs peaked at
# 211 MB against 283 MB. PERF.md has what they read since.
#
# What memory_stats does NOT count is an executable's own temporaries,
# and the pairwise merge program (ops/pallas_merge.py) has large ones:
# it packs both runs into the lanes layout, uint32[32, rows] whatever
# the row's width, and the merge pass writes a second matrix of that
# shape — 2 x 32 x 4 = MERGE_TEMP_ROW_BYTES for every row of the
# OUTPUT's capacity, beside 32 B a row each of arguments and output.
# memory_analysis() of the compiled merge of two runs of 2^k rows of 7
# columns (v5e; compiled for the described chip and, at k = 18, 20, 23
# and 24, on the chip itself, which reported the same bytes; PR 31):
#   k = 17     0 B      k = 20    537,452,544 B (256.3 B a row)
#   k = 18   128.4 B    k = 22  2,148,520,448 B (256.1)
#      a row            k = 23  4,296,266,240 B (256.08)
#                       k = 24  8,591,983,616 B (256.06)
# One program runs at a time, so the chip needs the largest live
# task's temporaries, not their sum (HbmLedger books them so). The
# output's capacity follows from the forest's shape: every run is
# padded to a power of two and so is every merge's output, so the
# largest merge of a task writes the power of two at or above the sum
# of its runs' capacities (merge_temp_bytes_estimate).
#
# The model is told bytes, not records: it takes RECORD_BYTES_DEFAULT
# a record, TeraSort's, and a partition of smaller records holds that
# many more rows than it books — a 20-byte posting five times. So the
# booking is corrected as soon as somebody knows: staging tells the
# ledger the framed bytes a record it has seen
# (OverlappedMerger._observe_records -> MemoryBudget.rebook_device),
# and a hold sized for fewer rows GROWS to the model's figure for the
# observed size — grow-only, once or twice a task, never waiting (the
# rows are on their way whatever the books say, and two tasks that
# both waited to grow could wait for each other); the tasks that ask
# after it see the true figure. A task of 100-byte records or larger
# never grows. What this cannot do is re-route: a text partition the
# chip cannot hold whole was admitted whole (PERF.md §7).
ROW_OVERHEAD_WORDS = 3        # length, segment index, row index columns
HBM_ROW_ALIGN_WORDS = 8       # a row's columns as the device stores them
RUN_PAD_FACTOR = 2.0          # power-of-two run capacity, at worst
FOREST_FACTOR = 3.0           # executed rows + pending merge outputs
MERGE_TEMP_ROW_BYTES = 256    # merge temporaries a row of output capacity
SORT_LADDER_RATIO = 1.08      # device bytes / shuffle bytes, TeraSort shape
RECORD_BYTES_DEFAULT = 100    # TeraSort record (10 B key + 90 B value)
WORKING_SET_FACTOR = 2.0      # the sort ladder's transient

# Fraction of physical HBM the budget may claim by default (the rest is
# XLA scratch, compiled executables, and the exchange path's buffers).
HBM_RESERVE_FRACTION = 0.9

# Known per-chip HBM sizes by TPU device-kind substring, FIRST MATCH
# WINS (the published per-chip figures). Order matters: every v5e/lite
# spelling (libtpu reports e.g. "TPU v5 lite") must match before "v5p",
# and a BARE "v5"
# resolves to the small end — over-budgeting a 16 GB chip as 95 GB
# would silently re-open the exact OOM this layer exists to prevent.
PLATFORM_HBM_MB = (
    ("v5litepod", 16 * 1024),   # v5e: 16 GB/chip
    ("v5 lite", 16 * 1024),
    ("v5lite", 16 * 1024),
    ("v5e", 16 * 1024),
    ("v5p", 95 * 1024),         # v5p: 95 GB/chip
    ("v6e", 32 * 1024),
    ("v6", 32 * 1024),
    ("v4", 32 * 1024),
    ("v3", 16 * 1024),
    ("v2", 8 * 1024),
    ("v5", 16 * 1024),          # bare v5: assume the small end
)


def _host_available_mb() -> int:
    """Best-effort available host memory (MemAvailable, else MemTotal,
    else a conservative 4 GB)."""
    try:
        with open("/proc/meminfo") as f:
            text = f.read()
        for key in ("MemAvailable", "MemTotal"):
            m = re.search(rf"^{key}:\s+(\d+)\s*kB", text, re.M)
            if m:
                return int(m.group(1)) // 1024
    except OSError:
        pass
    return 4 * 1024


def _detect_hbm_mb() -> int:
    """Per-chip HBM of the ambient backend: what the device itself
    reports (``memory_stats()["bytes_limit"]``), else the
    PLATFORM_HBM_MB entry for its ``device_kind``. An accelerator that
    offers neither is an error — guessing a size re-opens the exact OOM
    this layer exists to prevent. On CPU backends the 'device' rows
    live in host RSS, so the HBM budget IS the host budget (the
    xxlarge-rung reality). jax import stays lazy: admission must not
    drag a backend up in processes that never touch the device."""
    import jax

    if jax.default_backend() == "cpu":
        return _host_available_mb()
    dev = jax.devices()[0]
    limit = (dev.memory_stats() or {}).get("bytes_limit")
    if limit:
        return int(limit) // MB
    kind = str(dev.device_kind).lower()
    for sub, mb in PLATFORM_HBM_MB:
        if sub in kind:
            return mb
    raise UdaError(
        f"cannot size the HBM budget: device {dev.device_kind!r} reports "
        f"no bytes_limit and matches no PLATFORM_HBM_MB entry (set "
        f"uda.tpu.hbm.budget.mb)")


def _row_bytes(key_width: int) -> int:
    """Device bytes of one composite-key row, as the chip stores it."""
    cols = max(4, key_width) // 4 + ROW_OVERHEAD_WORDS
    return 4 * -(-cols // HBM_ROW_ALIGN_WORDS) * HBM_ROW_ALIGN_WORDS


def _pow2_at_least(n: int) -> int:
    return 1 << max(0, int(n) - 1).bit_length()


def device_bytes_estimate(partition_bytes: int, key_width: int,
                          record_bytes: float = RECORD_BYTES_DEFAULT) -> int:
    """Device-resident bytes of ROWS the merge would hold for a
    partition of ``partition_bytes`` on-disk bytes: the larger of the
    run forest and the sort ladder (see the model above) — what
    ``memory_stats`` can see of a task. An upper bound by construction
    — admission errs toward the grouped path, and what the chip-wide
    ledger reserves for a task covers what the task can hold. The
    merge program's temporaries are booked beside it
    (:func:`merge_temp_bytes_estimate`)."""
    if partition_bytes <= 0:
        return 0
    records = max(1, int(partition_bytes // max(1, record_bytes)))
    forest = (records * _row_bytes(key_width) * RUN_PAD_FACTOR
              * FOREST_FACTOR)
    ladder = partition_bytes * SORT_LADDER_RATIO * WORKING_SET_FACTOR
    return int(max(forest, ladder))


def merge_temp_bytes_estimate(partition_bytes: int,
                              segments: Optional[int] = None,
                              record_bytes: float = RECORD_BYTES_DEFAULT
                              ) -> int:
    """Temporaries of the largest merge program a task of
    ``partition_bytes`` in ``segments`` equal runs dispatches (see the
    model above): ``MERGE_TEMP_ROW_BYTES`` for every row of its
    output's capacity, the power of two at or above the sum of the
    runs' power-of-two capacities. Without a segment count the runs
    are taken at their worst padding (``RUN_PAD_FACTOR``)."""
    if partition_bytes <= 0:
        return 0
    records = max(1, int(partition_bytes // max(1, record_bytes)))
    if segments and segments > 0:
        capacity = segments * _pow2_at_least(-(-records // segments))
    else:
        capacity = int(records * RUN_PAD_FACTOR)
    return MERGE_TEMP_ROW_BYTES * _pow2_at_least(capacity)


def group_capacity_rows(budget_bytes: int, key_width: int) -> int:
    """Rows of run capacity one device GROUP of an over-budget task may
    hold in ``budget_bytes``: the largest power of two M for which the
    group's forest (``FOREST_FACTOR`` x M rows, what the dispatch bound
    of merger/overlap.py keeps it to) and the temporaries of the merge
    that folds it into one run of M rows fit together. A power of two
    because the fold's output capacity is one: a group filled to M
    folds into exactly M, one row more and the fold would write 2M.
    0 when not even the smallest run fits."""
    per_row = FOREST_FACTOR * _row_bytes(key_width) + MERGE_TEMP_ROW_BYTES
    rows = int(budget_bytes // per_row)
    return 1 << (rows.bit_length() - 1) if rows > 0 else 0


# -- the chip-wide HBM ledger ----------------------------------------------


class HbmHold:
    """One live task's reservation. ``release()`` is idempotent and is
    the ONLY way bytes leave the ledger: the holder calls it on every
    exit (end, abort, exception) — :meth:`MemoryBudget.admit_device`
    hands it out as a context manager for exactly that."""

    __slots__ = ("_ledger", "nbytes", "temp_bytes")

    def __init__(self, ledger: "HbmLedger", nbytes: int,
                 temp_bytes: int = 0):
        self._ledger = ledger
        self.nbytes = nbytes              # rows: add up over the tasks
        self.temp_bytes = temp_bytes      # merge temporaries: the max

    def release(self) -> None:
        ledger = self._ledger
        if ledger is not None:
            ledger._release(self)

    def grow(self, nbytes: int, temp_bytes: int) -> bool:
        """Raise the reservation to ``nbytes`` of rows and
        ``temp_bytes`` of merge temporaries where it holds less: what
        the task turned out to need once its records were seen
        (:meth:`MemoryBudget.rebook_device`). Grow-only and never
        waits; False when nothing grew or the hold is released."""
        ledger = self._ledger
        return ledger is not None and ledger._grow(self, nbytes, temp_bytes)

    def __enter__(self) -> "HbmHold":
        return self

    def __exit__(self, *exc) -> None:
        self.release()


class HbmLedger:
    """What the live reduce tasks of this process hold reserved of the
    chip's HBM. One instance a process (:data:`hbm_ledger`): the
    process is what holds the chip.

    ``reserve(nbytes, budget_bytes, temp_bytes=...)`` admits the caller
    when what the chip would then hold booked — the live tasks' rows
    plus ``nbytes``, and the LARGEST of the live tasks' and the
    caller's merge temporaries (one program runs at a time) — fits
    ``budget_bytes`` (the CALLER's view of the chip's budget — a
    tenant's share is its own), else blocks until releases make room.
    Admission is first come, first served: a task waiting for a large
    reservation is not overtaken by later small ones, so it cannot
    starve. A caller that asks for more than the budget can ever hold
    is a bug of the caller (``admit_device`` sizes such a task into
    groups instead) and raises. ``stopped`` is polled while waiting (each poll
    is also the waiter's sign of life): a task that is being torn down
    leaves the queue with a ``MergeError``.

    Gauges ``reduce.tasks.live`` and ``budget.hbm.reserved`` follow the
    books (their high-water marks are kept, metrics.PEAK_GAUGES);
    ``budget.waited`` counts the tasks that had to wait, the
    ``hbm_admit`` timer their seconds."""

    POLL_S = 0.1

    def __init__(self) -> None:
        self._cv = TrackedCondition(TrackedLock("budget.hbm"))
        self._reserved = 0                # the live tasks' rows
        self._temps: list = []            # each live task's temporaries
        self._holders = 0
        self._queue: deque = deque()      # tickets, oldest first

    def _booked(self) -> int:
        """Under the lock: the live tasks' rows and the largest of
        their merge temporaries."""
        return self._reserved + max(self._temps, default=0)

    @property
    def reserved_bytes(self) -> int:
        with self._cv:
            return self._booked()

    @property
    def holders(self) -> int:
        """Live tasks holding a reservation (possibly of 0 bytes)."""
        with self._cv:
            return self._holders

    def reserve(self, nbytes: int, budget_bytes: int,
                stopped: Optional[Callable[[], bool]] = None,
                temp_bytes: int = 0) -> HbmHold:
        nbytes = max(0, int(nbytes))
        temp_bytes = max(0, int(temp_bytes))
        if nbytes + temp_bytes > budget_bytes:
            raise UdaError(f"HBM reservation of {nbytes} B of rows and "
                           f"{temp_bytes} B of merge temporaries can never "
                           f"fit the budget of {budget_bytes} B")
        ticket = object()
        waited = False
        with metrics.timer("hbm_admit"):
            with self._cv:
                self._queue.append(ticket)
                try:
                    while (self._queue[0] is not ticket
                           or self._reserved + nbytes
                           + max([temp_bytes, *self._temps])
                           > budget_bytes):
                        if stopped is not None and stopped():
                            raise MergeError(
                                "stopped while waiting for the chip's HBM "
                                f"ledger ({nbytes} B beside "
                                f"{self._reserved} B reserved by "
                                f"{self._holders} live task(s))")
                        if not waited:
                            waited = True
                            metrics.add("budget.waited")
                            log.info(
                                f"HBM ledger: waiting for {nbytes} B of "
                                f"rows and {temp_bytes} B of temporaries; "
                                f"{self._booked()} of {budget_bytes} B are "
                                f"booked by {self._holders} live task(s)")
                        self._cv.wait(timeout=self.POLL_S)
                    before = self._booked()
                    self._reserved += nbytes
                    self._temps.append(temp_bytes)
                    self._holders += 1
                    grew = self._booked() - before
                finally:
                    self._queue.remove(ticket)
                    self._cv.notify_all()     # the next ticket's turn
        # the books above are the truth; the gauges mirror them: each
        # change of what is booked is taken under the lock and added
        # here, so the gauge's sum is the books' whatever order the
        # adds land in. The +x rides the returned hold: every reserve()
        # is paired with exactly one _release() through HbmHold.release
        metrics.gauge_add("reduce.tasks.live", 1)  # udalint: disable=UDA101
        metrics.gauge_add(  # udalint: disable=UDA101
            "budget.hbm.reserved", grew)
        return HbmHold(self, nbytes, temp_bytes)

    def _grow(self, hold: HbmHold, nbytes: int, temp_bytes: int) -> bool:
        """Book a live task's larger need at once (HbmHold.grow): the
        rows are on their way to the chip whatever the books say, and
        a wait here could deadlock two tasks that both grow. What is
        booked may pass the budget; the tasks that ask after it wait
        for releases as they always do."""
        with self._cv:
            if hold._ledger is not self:
                return False        # released meanwhile (_release)
            nbytes = max(hold.nbytes, int(nbytes))
            temp_bytes = max(hold.temp_bytes, int(temp_bytes))
            if (nbytes, temp_bytes) == (hold.nbytes, hold.temp_bytes):
                return False
            before = self._booked()
            self._reserved += nbytes - hold.nbytes
            self._temps.remove(hold.temp_bytes)
            self._temps.append(temp_bytes)
            hold.nbytes, hold.temp_bytes = nbytes, temp_bytes
            grew = self._booked() - before
        metrics.add("budget.hbm.rebooked")
        # rides the hold like reserve()'s: its _release() takes it back
        metrics.gauge_add(  # udalint: disable=UDA101
            "budget.hbm.reserved", grew)
        return True

    def _release(self, hold: HbmHold) -> None:
        with self._cv:
            if hold._ledger is not self:
                return              # released already
            # under the lock: a grow() in flight lands before or not at all
            hold._ledger = None
            before = self._booked()
            self._reserved -= hold.nbytes
            self._temps.remove(hold.temp_bytes)
            self._holders -= 1
            shrank = before - self._booked()
            self._cv.notify_all()
        metrics.gauge_add("reduce.tasks.live", -1)
        metrics.gauge_add("budget.hbm.reserved", -shrank)


hbm_ledger = HbmLedger()


@dataclasses.dataclass(frozen=True)
class Admission:
    """One routing decision: which path the partition was admitted to
    and why — the logged/counted record of the budget layer."""

    decision: str                 # "in_memory" | "hybrid" | "streaming"
    #                             | "reject"
    reason: str                   # human-readable (logs only — never
    #                             branch on this string)
    estimate_bytes: Optional[int]   # transport estimate (None = unknown)
    device_bytes: Optional[int]     # modeled device working set
    hbm_budget_bytes: int
    host_budget_bytes: int
    # structured decision basis — what callers branch on: which budget
    # forced the decision ("hbm" | "host" | "hard" | "init", "ckpt" for
    # the checkpoint-steered streaming route, or "", the empty string
    # meaning no budget was binding)
    cause: str = ""
    rerouted: bool = False
    # rows of run capacity a device group may hold when the chip cannot
    # hold the task whole (admit_device); 0 = the task is not grouped
    group_rows: int = 0

    @property
    def rejected(self) -> bool:
        return self.decision == "reject"


class MemoryBudget:
    """Per-chip HBM + host-RSS budgets with lazy platform detection.

    Budgets resolve in this order: explicit config knob > platform
    default (detected HBM x HBM_RESERVE_FRACTION; available host memory
    x ``mapred.job.shuffle.input.buffer.percent``). Detection runs at
    most once per instance and only when a budget is actually read.
    """

    def __init__(self, hbm_budget_mb: int = 0, host_budget_mb: int = 0,
                 hard_ceiling_mb: int = 0, key_width: int = 16,
                 host_fraction: float = 0.7, enforce: str = "reroute",
                 tenant_share: float = 0.0):
        self._hbm_mb = int(hbm_budget_mb)
        self._host_mb = int(host_budget_mb)
        self.hard_ceiling_mb = int(hard_ceiling_mb)
        self.key_width = int(key_width)
        self.host_fraction = float(host_fraction)
        if enforce not in ("reroute", "reject"):
            raise UdaError(f"uda.tpu.budget.enforce must be 'reroute' or "
                           f"'reject', got {enforce!r}")
        self.enforce = enforce
        # the multi-tenant partition (uda.tpu.tenant.budget.share):
        # several reducers of different tenants sharing one host must
        # not each budget against the whole machine — every budget
        # read below is scaled to this job's slice. 0/1 = whole
        # machine (the single-job default). Applied to EXPLICIT knob
        # values too: the knob states the machine's capacity, the
        # share states this tenant's entitlement.
        if tenant_share < 0.0 or tenant_share > 1.0:
            raise UdaError(f"uda.tpu.tenant.budget.share must be in "
                           f"[0, 1], got {tenant_share!r}")
        self.tenant_share = float(tenant_share) or 1.0

    @classmethod
    def from_config(cls, cfg) -> "MemoryBudget":
        return cls(
            hbm_budget_mb=cfg.get("uda.tpu.hbm.budget.mb"),
            host_budget_mb=cfg.get("uda.tpu.host.budget.mb"),
            hard_ceiling_mb=cfg.get("uda.tpu.budget.hard.mb"),
            key_width=cfg.get("uda.tpu.key.width"),
            host_fraction=cfg.get(
                "mapred.job.shuffle.input.buffer.percent"),
            enforce=cfg.get("uda.tpu.budget.enforce"),
            tenant_share=cfg.get("uda.tpu.tenant.budget.share"))

    def _share(self, nbytes: int) -> int:
        # never below 1 MB: a pathological share must degrade to the
        # reroute/reject ladder, not to a zero budget that rejects the
        # arena itself with a confusing arithmetic message
        return max(MB, int(nbytes * self.tenant_share))

    @property
    def hbm_budget_bytes(self) -> int:
        if self._hbm_mb <= 0:
            self._hbm_mb = max(
                1, int(_detect_hbm_mb() * HBM_RESERVE_FRACTION))
        return self._share(self._hbm_mb * MB)

    @property
    def host_budget_bytes(self) -> int:
        if self._host_mb <= 0:
            self._host_mb = max(
                1, int(_host_available_mb() * self.host_fraction))
        return self._share(self._host_mb * MB)

    @property
    def hard_ceiling_bytes(self) -> int:
        """Estimate above which even the degraded paths are refused
        (0 = no ceiling): spool disk, emit wall-clock and the consumer
        side all scale with the partition, and past this point the
        embedder's vanilla path is the better failure mode."""
        return self.hard_ceiling_mb * MB

    def device_bytes(self, partition_bytes: int) -> int:
        return device_bytes_estimate(partition_bytes, self.key_width)

    def device_need(self, partition_bytes: int,
                    segments: Optional[int] = None,
                    record_bytes: float = RECORD_BYTES_DEFAULT) -> tuple:
        """``(rows, temporaries)`` bytes the chip must have free to
        hold the task whole: its rows and the temporaries of its
        largest merge program."""
        return (device_bytes_estimate(partition_bytes, self.key_width,
                                      record_bytes),
                merge_temp_bytes_estimate(partition_bytes, segments,
                                          record_bytes))

    def group_reservation(self) -> tuple:
        """``(group_rows, rows, temporaries)``: the largest device group
        this budget holds (:func:`group_capacity_rows`) and the bytes a
        task merged in such groups reserves — the group's forest and
        the temporaries of the merge that folds it."""
        group = group_capacity_rows(self.hbm_budget_bytes, self.key_width)
        return (group,
                int(FOREST_FACTOR * group * _row_bytes(self.key_width)),
                MERGE_TEMP_ROW_BYTES * group)

    # -- admission point 1: INIT buffer validation --------------------------

    def validate_init(self, cfg) -> Admission:
        """The reducer.cc:56-133 mirror: the fetch-window + staging-
        arena working set (window x chunk in-flight fetch bytes, arena
        slots, the emitter's double buffer) must fit the host budget.
        Over budget: ``enforce=reroute`` shrinks the window to fit and
        warns (the reference's buffer-shrink path); ``enforce=reject``
        raises ``UdaError`` (-> the fallback contract). A chunk that
        cannot fit even at window 1 always raises (the reference's
        "RDMA Buffer is too small" hard failure). Mutates ``cfg`` when
        it shrinks the window; returns the decision record."""
        chunk = max(1, cfg.get("mapred.rdma.buf.size")) * 1024
        window = max(1, cfg.get("mapred.rdma.wqe.per.conn"))
        slots = max(1, cfg.get("uda.tpu.arena.slots"))
        fixed = (slots + 2) * chunk           # arena + emitter pair
        budget = self.host_budget_bytes
        # the HBM side is not consulted at INIT (no partition known yet)
        # and must not force backend detection in host-only processes
        hbm = self._hbm_mb * MB if self._hbm_mb > 0 else 0
        need = window * chunk + fixed
        if need <= budget:
            adm = Admission("in_memory", "init-working-set-in-budget",
                            need, None, hbm, budget)
            self._record(adm, "budget.admitted")
            return adm
        max_window = (budget - fixed) // chunk
        if max_window < 1:
            adm = Admission(
                "reject",
                f"chunk {chunk} B + {slots}-slot arena cannot fit host "
                f"budget {budget} B at any window", need, None,
                hbm, budget, cause="init")
            self._record(adm, "budget.rejected")
            raise UdaError(
                f"Not enough memory for the fetch working set: "
                f"host budget {budget} B < one {chunk} B chunk plus the "
                f"{slots}-slot staging arena (reduce the buffer size or "
                f"raise uda.tpu.host.budget.mb)")
        if self.enforce == "reject":
            adm = Admission(
                "reject",
                f"window {window} x {chunk} B exceeds host budget "
                f"{budget} B (enforce=reject)", need, None,
                hbm, budget, cause="init")
            self._record(adm, "budget.rejected")
            raise UdaError(
                f"fetch window over budget: {window} x {chunk} B + "
                f"{fixed} B fixed > host budget {budget} B")
        cfg.set("mapred.rdma.wqe.per.conn", int(max_window))
        log.warn(f"shrinking fetch window {window} -> {int(max_window)} "
                 f"to fit host budget {budget} B "
                 f"(chunk {chunk} B, arena {slots} slots)")
        adm = Admission("in_memory",
                        f"over-host-budget: window shrunk to "
                        f"{int(max_window)}", need, None,
                        hbm, budget, cause="host", rerouted=True)
        self._record(adm, "budget.rerouted")
        return adm

    # -- admission point 2: merge-approach routing --------------------------

    def route(self, estimate_bytes: Optional[int],
              threshold_bytes: int,
              prefer_streaming: bool = False,
              segments: Optional[int] = None) -> Admission:
        """The budget-aware auto merge-approach decision.

        - unknown estimate -> streaming (bounded memory for unbounded
          input);
        - over the hard ceiling -> reject (caller raises
          ``FallbackSignal`` before any allocation);
        - device need (rows and merge temporaries, for a partition in
          ``segments`` runs) over the HBM budget -> streaming, merged
          on the device in groups (``admit_device`` sizes them);
          host-resident bytes over the host budget -> streaming;
        - small (within the measured hybrid crossover AND in budget) ->
          hybrid; in-budget above the crossover -> streaming (the
          measured-fastest large-scale path, which is also bounded).

        ``prefer_streaming`` (checkpointing armed, ``uda.tpu.ckpt.dir``)
        steers the in-budget-small case to streaming too: the hybrid
        LPQ/RPQ path has no durable run spool to snapshot, so
        crash-consistent resume needs the streaming path (cause
        ``"ckpt"``). Budget-forced decisions are unaffected.
        """
        hbm = self.hbm_budget_bytes
        host = self.host_budget_bytes
        if estimate_bytes is None:
            adm = Admission("streaming", "unknown-estimate", None, None,
                            hbm, host)
            self._record(adm, "budget.admitted")
            return adm
        dev, temps = self.device_need(estimate_bytes, segments)
        hard = self.hard_ceiling_bytes
        if hard and estimate_bytes > hard:
            adm = Admission(
                "reject", f"over-hard-ceiling: estimate "
                f"{estimate_bytes} B > {hard} B", estimate_bytes, dev,
                hbm, host, cause="hard")
            self._record(adm, "budget.rejected")
            return adm
        if dev + temps > hbm:
            adm = Admission(
                "streaming", f"over-hbm-budget: device working set "
                f"{dev} B + {temps} B of merge temporaries > {hbm} B",
                estimate_bytes, dev, hbm, host, cause="hbm", rerouted=True)
            self._record(adm, "budget.rerouted")
            return adm
        # hybrid/in-memory additionally hold the fetched bytes host-
        # resident through the LPQ spill; gate that on the host budget
        if estimate_bytes > host:
            adm = Admission(
                "streaming", f"over-host-budget: partition "
                f"{estimate_bytes} B > {host} B", estimate_bytes, dev,
                hbm, host, cause="host", rerouted=True)
            self._record(adm, "budget.rerouted")
            return adm
        if estimate_bytes <= threshold_bytes and prefer_streaming:
            adm = Admission(
                "streaming", "in-budget-small-ckpt: checkpoint/resume "
                "needs the run-spool (streaming) path", estimate_bytes,
                dev, hbm, host, cause="ckpt")
        elif estimate_bytes <= threshold_bytes:
            adm = Admission("hybrid", "in-budget-small", estimate_bytes,
                            dev, hbm, host)
        else:
            adm = Admission("streaming", "in-budget-large",
                            estimate_bytes, dev, hbm, host)
        self._record(adm, "budget.admitted")
        return adm

    # -- admission point 3: the chip-wide HBM ledger ------------------------

    def admit_device(self, estimate_bytes: Optional[int],
                     segments: Optional[int] = None,
                     stopped: Optional[Callable[[], bool]] = None,
                     counted: bool = False) -> tuple:
        """Put one task on the chip's books (:data:`hbm_ledger`) before
        it stages its first run to the device: reserve its device need
        — rows and the temporaries of its largest merge, for a
        partition in ``segments`` runs — against this budget's view of
        the chip, blocking while the live tasks leave no room (see
        :class:`HbmLedger`; a lone task never waits). Returns ``(hold,
        reroute)``: the hold to release on every exit, and None — or,
        when the chip cannot hold the task even alone, the
        :class:`Admission` (cause ``"hbm"``) that sends it down the
        streaming route to be merged on the device in GROUPS of
        ``reroute.group_rows`` rows of run capacity: the largest group
        the whole budget holds, which is what the task then reserves,
        waiting its turn like any other. ``counted`` says ``route``
        has sent this task there already and counted it. An unknown
        estimate reserves the whole budget: a task of unknown size has
        the chip to itself."""
        hbm = self.hbm_budget_bytes
        if estimate_bytes is None:
            return hbm_ledger.reserve(hbm, hbm, stopped), None
        dev, temps = self.device_need(estimate_bytes, segments)
        reroute = None
        if dev + temps > hbm:
            group, group_bytes, group_temps = self.group_reservation()
            reroute = Admission(
                "streaming", f"over-hbm-budget: device working set "
                f"{dev} B + {temps} B of merge temporaries > {hbm} B; "
                f"merged on the device in groups of {group} rows",
                estimate_bytes, dev, hbm, self.host_budget_bytes,
                cause="hbm", rerouted=True, group_rows=group)
            if not counted:
                self._record(reroute, "budget.rerouted")
            dev, temps = group_bytes, group_temps
        return hbm_ledger.reserve(dev, hbm, stopped, temps), reroute

    def rebook_device(self, hold: HbmHold, estimate_bytes: int,
                      segments: Optional[int], record_bytes: float) -> bool:
        """Staging has seen the task's records: ``record_bytes`` framed
        bytes each, where :meth:`admit_device` reckoned with
        ``RECORD_BYTES_DEFAULT``. Grow ``hold`` to the device need of
        that many more rows (the model's, for the observed record
        size); a task of larger records keeps what it has. Never
        waits, and never re-routes: a task admitted whole whose rows
        turn out not to fit the chip beside the live ones is beyond
        this (PERF.md §7). Counted in ``budget.hbm.rebooked``."""
        grew = hold.grow(*self.device_need(estimate_bytes, segments,
                                           record_bytes))
        if grew:
            log.info(f"HBM ledger: records of {record_bytes:.1f} B, not "
                     f"{RECORD_BYTES_DEFAULT}: the task's reservation "
                     f"grows to {hold.nbytes} B of rows and "
                     f"{hold.temp_bytes} B of merge temporaries")
        return grew

    # -- bookkeeping --------------------------------------------------------

    @staticmethod
    def _record(adm: Admission, counter: str) -> None:
        # literal names only: the metrics linter audits call sites
        if counter == "budget.admitted":
            metrics.add("budget.admitted")
        elif counter == "budget.rerouted":
            metrics.add("budget.rerouted")
        else:
            metrics.add("budget.rejected")
        line = (f"budget {adm.decision}: {adm.reason} "
                f"(estimate={adm.estimate_bytes}, "
                f"device={adm.device_bytes}, "
                f"hbm_budget={adm.hbm_budget_bytes}, "
                f"host_budget={adm.host_budget_bytes})")
        if counter == "budget.admitted":
            log.info(line)
        else:
            log.warn(line)
