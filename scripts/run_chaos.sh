#!/usr/bin/env bash
# Chaos tier: run the fault-marked tests under a randomized-but-seeded
# failpoint schedule (uda_tpu.utils.failpoints.chaos_spec). The seed is
# printed first — reproduce any failure exactly with:
#
#   CHAOS_SEED=<seed> scripts/run_chaos.sh
#
# The schedule is recoverable by construction (transport errors, delays,
# truncations — no undetectable corruption), so a failure here means the
# retry/backoff/penalty/carry machinery regressed, not that the dice
# came up wrong. Extra pytest args pass through ("$@").
#
# Telemetry: the run accumulates the session's fault/recovery counters
# (tests/conftest.py) and writes CHAOS_TELEMETRY.json — a "telemetry"
# block in the metrics snapshot's schema — wrapped with the seed and
# schedule so chaos rounds diff against each other.
set -euo pipefail
cd "$(dirname "$0")/.."

SEED="${CHAOS_SEED:-$RANDOM}"
SPEC="$(python -c "from uda_tpu.utils.failpoints import chaos_spec; print(chaos_spec(${SEED}))")"
OUT="${CHAOS_TELEMETRY_JSON:-CHAOS_TELEMETRY.json}"
COUNTERS="$(mktemp)"
# flight-recorder dump dirs, one per rung (utils/flightrec.py): every
# FallbackSignal/stall/resledger-leak inside a rung black-boxes its
# event stream here; the telemetry merge below archives the dumps per
# rung into CHAOS_TELEMETRY.json — and a rung that FAILS without
# leaving a dump is itself a failure (a fault path that dies without
# its post-mortem defeats the recorder's purpose).
FRROOT="$(mktemp -d)"
export FRROOT  # the telemetry merge below reads the dumps from it
for r in main pressure network exchange completion pipeline iobatch tenant resume anomaly elastic push lockdep; do
  mkdir -p "${FRROOT}/${r}"
done
trap 'rm -f "${COUNTERS}"; rm -rf "${FRROOT}"' EXIT
echo "chaos seed:          ${SEED}"
echo "failpoint schedule:  ${SPEC}"

rc=0
env JAX_PLATFORMS=cpu UDA_FAILPOINTS="${SPEC}" UDA_TPU_STATS=1 \
    UDA_TPU_FLIGHTREC_DIR="${FRROOT}/main" \
    UDA_TPU_CHAOS_TELEMETRY="${COUNTERS}" \
    python -m pytest tests/ -m faults -q -p no:cacheprovider \
    --continue-on-collection-errors "$@" || rc=$?

# Memory-pressure rung: the same faults tier again under a schedule
# biased toward supplier delays (reads hold their admission bytes
# longer), exercising the budget layer's graceful-reroute guarantees —
# tiny budgets + armed failpoints must degrade (streaming, bounded
# device, watchdog rescue), never crash or wedge. The pressure tests
# themselves pin tiny uda.tpu.*.budget knobs (tests/test_budget.py).
PSPEC="data_engine.pread=delay:$((SEED % 20 + 5)):prob:0.3:seed:${SEED},segment.fetch=delay:$((SEED % 8 + 1)):prob:0.15:seed:${SEED}"
PCOUNTERS="$(mktemp)"
trap 'rm -f "${COUNTERS}" "${PCOUNTERS}"; rm -rf "${FRROOT}"' EXIT
echo "pressure schedule:   ${PSPEC}"
prc=0
env JAX_PLATFORMS=cpu UDA_FAILPOINTS="${PSPEC}" UDA_TPU_STATS=1 \
    UDA_TPU_FLIGHTREC_DIR="${FRROOT}/pressure" \
    UDA_TPU_CHAOS_TELEMETRY="${PCOUNTERS}" \
    python -m pytest tests/ -m faults -q -p no:cacheprovider \
    -k "pressure or watchdog or budget" \
    --continue-on-collection-errors "$@" || prc=$?

# Network rung: the net-marked faults tier under a seeded network-chaos
# schedule (uda_tpu.utils.failpoints.net_chaos_spec) — torn frames (the
# sender closes: a disconnect mid-stream), slow accepts, slow dials.
# The wire layer's recovery contract (fail in-flight fetches ->
# Segment retry/penalty -> reconnect) must absorb all of it. Runs
# under the runtime lock-order validator (the former separate evloop
# rung folded in when the threaded core was deleted — the event loop
# IS the data plane now): the net lock classes (net.loop,
# net.conn.write, net.client.write) must produce zero order cycles
# mid-chaos.
NSPEC="$(python -c "from uda_tpu.utils.failpoints import net_chaos_spec; print(net_chaos_spec(${SEED}))")"
NCOUNTERS="$(mktemp)"
NCYCLES="$(mktemp)"
NLEAKS="$(mktemp)"
# runtime race detector (udarace's Eraser machine, utils/locks.py):
# armed on the rungs whose instrumented hot classes actually churn
# cross-thread — push scheduler/staging (push rung), the migration log
# (completion rung), the tenant books under the net plane (here). The
# race JSONLs live under FRROOT (the trap's rm -rf collects them) and
# fold into the telemetry merge below, where ANY real-code race fails
# the tier exactly like a lockdep cycle or a leaked obligation.
NRACES="${FRROOT}/races_network.jsonl"
trap 'rm -f "${COUNTERS}" "${PCOUNTERS}" "${NCOUNTERS}" "${NCYCLES}" "${NLEAKS}"; rm -rf "${FRROOT}"' EXIT
echo "network schedule:    ${NSPEC} (UDA_TPU_LOCKDEP=1, UDA_TPU_RESLEDGER=1, UDA_TPU_RACEDET=1)"
nrc=0
env JAX_PLATFORMS=cpu UDA_FAILPOINTS="${NSPEC}" UDA_TPU_STATS=1 \
    UDA_TPU_FLIGHTREC_DIR="${FRROOT}/network" \
    UDA_TPU_LOCKDEP=1 UDA_TPU_LOCKDEP_JSON="${NCYCLES}" \
    UDA_TPU_RESLEDGER=1 UDA_TPU_RESLEDGER_JSON="${NLEAKS}" \
    UDA_TPU_RACEDET=1 UDA_TPU_RACEDET_JSON="${NRACES}" \
    UDA_TPU_CHAOS_TELEMETRY="${NCOUNTERS}" \
    python -m pytest tests/ -m faults -q -p no:cacheprovider \
    -k "net" \
    --continue-on-collection-errors "$@" || nrc=$?

# Exchange rung: the exchange-marked faults tier (the hierarchical
# two-stage data plane: a stage-B fault must surface as TransportError,
# never a hang or silent loss — and, since ISSUE 15, the CODED stage-B
# path: a decode failure must complete the round byte-correct on the
# plain coalesced tile) under the lock-order validator. The
# exchange.round schedules are armed by the tests themselves
# (failpoints.scoped — the stage-B match needs precise phase, an
# ambient periodic spec would mis-fire on the planner loop); the rung
# layers a SEEDED ambient exchange.decode probability on top (it only
# ever fires on coded windows, where fallback is byte-correct by
# construction) and runs it all with lockdep watching the metrics/
# layout locks the device exchange shares with everything else.
ESPEC="exchange.decode=error:prob:0.$((SEED % 4 + 2)):seed:${SEED}"
ECOUNTERS="$(mktemp)"
ECYCLES="$(mktemp)"
trap 'rm -f "${COUNTERS}" "${PCOUNTERS}" "${NCOUNTERS}" "${NCYCLES}" "${NLEAKS}" "${ECOUNTERS}" "${ECYCLES}"; rm -rf "${FRROOT}"' EXIT
echo "exchange rung:       ${ESPEC} + scoped exchange.round/decode schedules (UDA_TPU_LOCKDEP=1)"
erc=0
env JAX_PLATFORMS=cpu UDA_FAILPOINTS="${ESPEC}" UDA_TPU_STATS=1 \
    UDA_TPU_FLIGHTREC_DIR="${FRROOT}/exchange" \
    UDA_TPU_LOCKDEP=1 UDA_TPU_LOCKDEP_JSON="${ECYCLES}" \
    UDA_TPU_CHAOS_TELEMETRY="${ECOUNTERS}" \
    python -m pytest tests/ -m faults -q -p no:cacheprovider \
    -k "exchange or coded" \
    --continue-on-collection-errors "$@" || erc=$?

# Completion rung: the survivable-shuffle guarantee (ISSUE 8) — a
# seeded supplier KILL (rs:4:6 coding, no restart) and a seeded
# supplier BOUNCE (warm restart + handoff) must both end in a FINISHED
# job with byte-correct merged output (coding.reconstructed.partitions
# > 0 for the kill, fetch.resumed > 0 for the bounce, zero
# FallbackSignals) — the tests assert all of it, so a job that merely
# "falls back cleanly" FAILS this rung. Runs under lockdep: the
# recovery paths (recovery ledger, stripe fan-out, speculation timers)
# must add no lock-order cycles.
CCOUNTERS="$(mktemp)"
CCYCLES="$(mktemp)"
CLEAKS="$(mktemp)"
CRACES="${FRROOT}/races_completion.jsonl"
trap 'rm -f "${COUNTERS}" "${PCOUNTERS}" "${NCOUNTERS}" "${NCYCLES}" "${NLEAKS}" "${ECOUNTERS}" "${ECYCLES}" "${CCOUNTERS}" "${CCYCLES}" "${CLEAKS}"; rm -rf "${FRROOT}"' EXIT
echo "completion rung:     seeded supplier kill + warm restart (seed ${SEED}, UDA_TPU_LOCKDEP=1, UDA_TPU_RESLEDGER=1, UDA_TPU_RACEDET=1)"
crc=0
env JAX_PLATFORMS=cpu UDA_TPU_STATS=1 UDA_TPU_CHAOS_SEED="${SEED}" \
    UDA_TPU_FLIGHTREC_DIR="${FRROOT}/completion" \
    UDA_TPU_LOCKDEP=1 UDA_TPU_LOCKDEP_JSON="${CCYCLES}" \
    UDA_TPU_RESLEDGER=1 UDA_TPU_RESLEDGER_JSON="${CLEAKS}" \
    UDA_TPU_RACEDET=1 UDA_TPU_RACEDET_JSON="${CRACES}" \
    UDA_TPU_CHAOS_TELEMETRY="${CCOUNTERS}" \
    python -m pytest tests/test_coding.py -m faults -q -p no:cacheprovider \
    --continue-on-collection-errors "$@" || crc=$?

# Pipeline rung: the staged fetch->decompress->pack->stage pipeline
# (ISSUE 9) under a schedule biased toward the pipeline's two injection
# sites — slow/failing preads feeding the stage pool and delayed block
# decompression inside it. The faults-marked pipeline tests assert the
# drain contract (abort stops every worker, the in-flight byte gauge
# returns to zero); the rung runs them with lockdep watching the new
# lock classes (stage.inflight, stage.bufpool) against everything the
# stage pool touches mid-fault.
PIPESPEC="data_engine.pread=delay:$((SEED % 15 + 5)):prob:0.25:seed:${SEED},decompress.block=delay:$((SEED % 5 + 1)):prob:0.15:seed:${SEED}"
PICOUNTERS="$(mktemp)"
PICYCLES="$(mktemp)"
PILEAKS="$(mktemp)"
trap 'rm -f "${COUNTERS}" "${PCOUNTERS}" "${NCOUNTERS}" "${NCYCLES}" "${NLEAKS}" "${ECOUNTERS}" "${ECYCLES}" "${CCOUNTERS}" "${CCYCLES}" "${CLEAKS}" "${PICOUNTERS}" "${PICYCLES}" "${PILEAKS}"; rm -rf "${FRROOT}"' EXIT
echo "pipeline schedule:   ${PIPESPEC} (UDA_TPU_LOCKDEP=1, UDA_TPU_RESLEDGER=1)"
pirc=0
env JAX_PLATFORMS=cpu UDA_FAILPOINTS="${PIPESPEC}" UDA_TPU_STATS=1 \
    UDA_TPU_FLIGHTREC_DIR="${FRROOT}/pipeline" \
    UDA_TPU_LOCKDEP=1 UDA_TPU_LOCKDEP_JSON="${PICYCLES}" \
    UDA_TPU_RESLEDGER=1 UDA_TPU_RESLEDGER_JSON="${PILEAKS}" \
    UDA_TPU_CHAOS_TELEMETRY="${PICOUNTERS}" \
    python -m pytest tests/ -m faults -q -p no:cacheprovider \
    -k "pipeline" \
    --continue-on-collection-errors "$@" || pirc=$?

# Batched host-I/O rung: batch-partial-failure (ISSUE 13) — a seeded
# data_engine.preadv schedule (error + delay, keyed per request range)
# against the batched serve plane. The faults-marked iobatch tests
# assert the isolation contract: an injected fault fails ONLY the
# targeted request, its coalesced batch-mates complete byte-correct,
# and at exit the ledger holds zero obligations (admission bytes, fd
# pins, the io.batch.inflight paired gauge) and lockdep zero cycles.
IOSPEC="data_engine.preadv=error:every:$((SEED % 5 + 3)),data_engine.pread=delay:$((SEED % 10 + 2)):prob:0.2:seed:${SEED}"
IOCOUNTERS="$(mktemp)"
IOCYCLES="$(mktemp)"
IOLEAKS="$(mktemp)"
trap 'rm -f "${COUNTERS}" "${PCOUNTERS}" "${NCOUNTERS}" "${NCYCLES}" "${NLEAKS}" "${ECOUNTERS}" "${ECYCLES}" "${CCOUNTERS}" "${CCYCLES}" "${CLEAKS}" "${PICOUNTERS}" "${PICYCLES}" "${PILEAKS}" "${IOCOUNTERS}" "${IOCYCLES}" "${IOLEAKS}"; rm -rf "${FRROOT}"' EXIT
echo "iobatch schedule:    ${IOSPEC} (UDA_TPU_LOCKDEP=1, UDA_TPU_RESLEDGER=1)"
iorc=0
env JAX_PLATFORMS=cpu UDA_FAILPOINTS="${IOSPEC}" UDA_TPU_STATS=1 \
    UDA_TPU_FLIGHTREC_DIR="${FRROOT}/iobatch" \
    UDA_TPU_LOCKDEP=1 UDA_TPU_LOCKDEP_JSON="${IOCYCLES}" \
    UDA_TPU_RESLEDGER=1 UDA_TPU_RESLEDGER_JSON="${IOLEAKS}" \
    UDA_TPU_CHAOS_TELEMETRY="${IOCOUNTERS}" \
    python -m pytest tests/ -m faults -q -p no:cacheprovider \
    -k "iobatch" \
    --continue-on-collection-errors "$@" || iorc=$?

# Multi-tenant rung: the abusive-tenant isolation contract (ISSUE 14)
# under ambient chaos. The faults-marked tenant test arms its OWN
# scoped schedule (tenant.validate errors matched to ONE tenant's key
# — the abusive job), so every abuser request draws a typed
# TenantError while the victim tenant's job must complete BYTE-CORRECT
# with zero fallbacks; this rung layers a seeded supplier-delay storm
# on top (reads hold admission bytes longer — per-tenant shares stay
# honest under pressure) and runs it all with lockdep + the resource
# ledger armed: the new lock classes (tenant.registry) and the
# per-tenant admission books (tenant.admit / the paired
# tenant.read.bytes.on_air gauge) must end with zero cycles and zero
# leaked obligations.
TSPEC="data_engine.pread=delay:$((SEED % 10 + 2)):prob:0.2:seed:${SEED}"
TENCOUNTERS="$(mktemp)"
TENCYCLES="$(mktemp)"
TENLEAKS="$(mktemp)"
trap 'rm -f "${COUNTERS}" "${PCOUNTERS}" "${NCOUNTERS}" "${NCYCLES}" "${NLEAKS}" "${ECOUNTERS}" "${ECYCLES}" "${CCOUNTERS}" "${CCYCLES}" "${CLEAKS}" "${PICOUNTERS}" "${PICYCLES}" "${PILEAKS}" "${IOCOUNTERS}" "${IOCYCLES}" "${IOLEAKS}" "${TENCOUNTERS}" "${TENCYCLES}" "${TENLEAKS}"; rm -rf "${FRROOT}"' EXIT
echo "tenant schedule:     ${TSPEC} + scoped tenant.validate abuse (UDA_TPU_LOCKDEP=1, UDA_TPU_RESLEDGER=1)"
tenrc=0
env JAX_PLATFORMS=cpu UDA_FAILPOINTS="${TSPEC}" UDA_TPU_STATS=1 \
    UDA_TPU_FLIGHTREC_DIR="${FRROOT}/tenant" \
    UDA_TPU_LOCKDEP=1 UDA_TPU_LOCKDEP_JSON="${TENCYCLES}" \
    UDA_TPU_RESLEDGER=1 UDA_TPU_RESLEDGER_JSON="${TENLEAKS}" \
    UDA_TPU_CHAOS_TELEMETRY="${TENCOUNTERS}" \
    python -m pytest tests/ -m faults -q -p no:cacheprovider \
    -k "tenant" \
    --continue-on-collection-errors "$@" || tenrc=$?

# Resume rung: the crash-consistent reduce guarantee (ISSUE 16) — a
# seeded kill -9 of the reduce process mid-merge (and once DURING a
# snapshot, via a ckpt.save truncate that tears the newest manifest),
# then a restart. The faults-marked checkpoint tests assert the whole
# contract: the resumed attempt's output is BYTE-IDENTICAL to an
# uninterrupted run, ckpt.resumed advances (a silent restart-from-
# scratch FAILS), ZERO manifest-recorded run files are refetched, and
# the torn manifest is skipped for the previous durable one. The kill
# point is derived from UDA_TPU_CHAOS_SEED (the child process arms its
# own deterministic faults); the rung layers only a seeded pread-delay
# storm on the parent so the kill lands at varied merge states without
# breaking determinism. Lockdep + the resource ledger watch the new
# checkpoint lock against everything the spool path touches.
RESSPEC="data_engine.pread=delay:$((SEED % 10 + 1)):prob:0.2:seed:${SEED}"
RESCOUNTERS="$(mktemp)"
RESCYCLES="$(mktemp)"
RESLEAKS="$(mktemp)"
trap 'rm -f "${COUNTERS}" "${PCOUNTERS}" "${NCOUNTERS}" "${NCYCLES}" "${NLEAKS}" "${ECOUNTERS}" "${ECYCLES}" "${CCOUNTERS}" "${CCYCLES}" "${CLEAKS}" "${PICOUNTERS}" "${PICYCLES}" "${PILEAKS}" "${IOCOUNTERS}" "${IOCYCLES}" "${IOLEAKS}" "${TENCOUNTERS}" "${TENCYCLES}" "${TENLEAKS}" "${RESCOUNTERS}" "${RESCYCLES}" "${RESLEAKS}"; rm -rf "${FRROOT}"' EXIT
echo "resume rung:         seeded kill -9 mid-merge + mid-snapshot (seed ${SEED}, ${RESSPEC}, UDA_TPU_LOCKDEP=1, UDA_TPU_RESLEDGER=1)"
resrc=0
env JAX_PLATFORMS=cpu UDA_FAILPOINTS="${RESSPEC}" UDA_TPU_STATS=1 \
    UDA_TPU_CHAOS_SEED="${SEED}" \
    UDA_TPU_FLIGHTREC_DIR="${FRROOT}/resume" \
    UDA_TPU_LOCKDEP=1 UDA_TPU_LOCKDEP_JSON="${RESCYCLES}" \
    UDA_TPU_RESLEDGER=1 UDA_TPU_RESLEDGER_JSON="${RESLEAKS}" \
    UDA_TPU_CHAOS_TELEMETRY="${RESCOUNTERS}" \
    python -m pytest tests/test_checkpoint.py -m faults -q \
    -p no:cacheprovider \
    --continue-on-collection-errors "$@" || resrc=$?

# Anomaly rung: the observability plane's proactive-capture guarantee
# (ISSUE 17) — a seeded slow-supplier storm (delays only: every fetch
# COMPLETES, nothing falls back) with the online detectors armed and
# proactive dumping ON. The faults-marked anomaly test asserts the
# whole contract: the p99-inflation detector fires on the live fetch
# path and leaves exactly ONE black-box dump (cause=anomaly) while
# fallback.signals is still zero — the recorder captures the minutes
# BEFORE a failure, not after. Enforced below like lockdep/resledger:
# an anomaly rung that ends with no cause=anomaly dump in its archive
# fails the tier even if pytest passed.
ASPEC="data_engine.pread=delay:$((SEED % 20 + 5)):prob:0.3:seed:${SEED}"
ACOUNTERS="$(mktemp)"
trap 'rm -f "${COUNTERS}" "${PCOUNTERS}" "${NCOUNTERS}" "${NCYCLES}" "${NLEAKS}" "${ECOUNTERS}" "${ECYCLES}" "${CCOUNTERS}" "${CCYCLES}" "${CLEAKS}" "${PICOUNTERS}" "${PICYCLES}" "${PILEAKS}" "${IOCOUNTERS}" "${IOCYCLES}" "${IOLEAKS}" "${TENCOUNTERS}" "${TENCYCLES}" "${TENLEAKS}" "${RESCOUNTERS}" "${RESCYCLES}" "${RESLEAKS}" "${ACOUNTERS}"; rm -rf "${FRROOT}"' EXIT
echo "anomaly schedule:    ${ASPEC} (UDA_TPU_ANOMALY_DUMP=1)"
anrc=0
env JAX_PLATFORMS=cpu UDA_FAILPOINTS="${ASPEC}" UDA_TPU_STATS=1 \
    UDA_TPU_ANOMALY_DUMP=1 \
    UDA_TPU_FLIGHTREC_DIR="${FRROOT}/anomaly" \
    UDA_TPU_CHAOS_TELEMETRY="${ACOUNTERS}" \
    python -m pytest tests/ -m faults -q -p no:cacheprovider \
    -k "anomaly" \
    --continue-on-collection-errors "$@" || anrc=$?

# Elastic rung: the disaggregated-store elasticity contract (ISSUE 18)
# — scripts/elastic_chaos.py drives ONE reduce job through a seeded
# blob-tier brown-out (store.get=error:prob:...:match:blob) while a
# second supplier JOINS mid-job and the primary DRAINS mid-job
# (retained MOFs migrate cutover-style to the blob tier). The driver
# enforces its own contract by exit code: merged output BYTE-IDENTICAL
# to a chaos-free reference, store.failover > 0 (every twinned blob
# kill re-routed to the surviving tier), the drain moved partitions,
# the join registered, and ZERO FallbackSignals. The faults-marked
# store tests (tests/test_store.py) run after it under the same
# armed validators: typed StoreError causes, penalty-boxed re-routing,
# batch-plane failover, spilled-locator revalidation.
ELJSON="$(mktemp)"
ELCOUNTERS="$(mktemp)"
ELCYCLES="$(mktemp)"
ELLEAKS="$(mktemp)"
trap 'rm -f "${COUNTERS}" "${PCOUNTERS}" "${NCOUNTERS}" "${NCYCLES}" "${NLEAKS}" "${ECOUNTERS}" "${ECYCLES}" "${CCOUNTERS}" "${CCYCLES}" "${CLEAKS}" "${PICOUNTERS}" "${PICYCLES}" "${PILEAKS}" "${IOCOUNTERS}" "${IOCYCLES}" "${IOLEAKS}" "${TENCOUNTERS}" "${TENCYCLES}" "${TENLEAKS}" "${RESCOUNTERS}" "${RESCYCLES}" "${RESLEAKS}" "${ACOUNTERS}" "${ELJSON}" "${ELCOUNTERS}" "${ELCYCLES}" "${ELLEAKS}"; rm -rf "${FRROOT}"' EXIT
echo "elastic rung:        seeded blob-kill + mid-job drain-and-join (seed ${SEED}, UDA_TPU_LOCKDEP=1, UDA_TPU_RESLEDGER=1)"
elrc=0
env JAX_PLATFORMS=cpu UDA_TPU_STATS=1 \
    UDA_TPU_FLIGHTREC_DIR="${FRROOT}/elastic" \
    UDA_TPU_LOCKDEP=1 UDA_TPU_LOCKDEP_JSON="${ELCYCLES}" \
    UDA_TPU_RESLEDGER=1 UDA_TPU_RESLEDGER_JSON="${ELLEAKS}" \
    python scripts/elastic_chaos.py --seed "${SEED}" \
    --out "${ELJSON}" || elrc=$?
env JAX_PLATFORMS=cpu UDA_TPU_STATS=1 \
    UDA_TPU_FLIGHTREC_DIR="${FRROOT}/elastic" \
    UDA_TPU_LOCKDEP=1 UDA_TPU_LOCKDEP_JSON="${ELCYCLES}" \
    UDA_TPU_RESLEDGER=1 UDA_TPU_RESLEDGER_JSON="${ELLEAKS}" \
    UDA_TPU_CHAOS_TELEMETRY="${ELCOUNTERS}" \
    python -m pytest tests/test_store.py -m faults -q \
    -p no:cacheprovider \
    --continue-on-collection-errors "$@" || elrc=$?

# Push rung: the push-shuffle pipeline contract (ISSUE 19) — the
# faults-marked push tests (a seeded supplier KILL racing in-flight
# pushes, torn MSG_PUSH frames, injected admission refusals) under a
# seeded ambient push-plane schedule: torn push frames and admission
# refusals by probability, plus a pread-delay storm that varies WHICH
# pushes are on the wire when the kill lands. Every shape must end
# byte-identical to the pull oracle with ZERO FallbackSignals — a
# refused, torn or orphaned push converts that partition to ordinary
# pull, it never loses a job — and lockdep + the resource ledger watch
# the new push leaf locks (push.sched, push.staging) and paired gauges
# (push.on_air, push.staged.bytes): a killed supplier or dropped
# connection must strand neither.
PUSHSPEC="net.push=truncate:prob:0.1:seed:${SEED},push.admit=error:prob:0.1:seed:$((SEED + 1)),data_engine.pread=delay:$((SEED % 8 + 1)):prob:0.2:seed:$((SEED + 2))"
PUSHCOUNTERS="$(mktemp)"
PUSHCYCLES="$(mktemp)"
PUSHLEAKS="$(mktemp)"
PUSHRACES="${FRROOT}/races_push.jsonl"
trap 'rm -f "${COUNTERS}" "${PCOUNTERS}" "${NCOUNTERS}" "${NCYCLES}" "${NLEAKS}" "${ECOUNTERS}" "${ECYCLES}" "${CCOUNTERS}" "${CCYCLES}" "${CLEAKS}" "${PICOUNTERS}" "${PICYCLES}" "${PILEAKS}" "${IOCOUNTERS}" "${IOCYCLES}" "${IOLEAKS}" "${TENCOUNTERS}" "${TENCYCLES}" "${TENLEAKS}" "${RESCOUNTERS}" "${RESCYCLES}" "${RESLEAKS}" "${ACOUNTERS}" "${ELJSON}" "${ELCOUNTERS}" "${ELCYCLES}" "${ELLEAKS}" "${PUSHCOUNTERS}" "${PUSHCYCLES}" "${PUSHLEAKS}"; rm -rf "${FRROOT}"' EXIT
echo "push schedule:       ${PUSHSPEC} (UDA_TPU_LOCKDEP=1, UDA_TPU_RESLEDGER=1, UDA_TPU_RACEDET=1)"
pushrc=0
env JAX_PLATFORMS=cpu UDA_FAILPOINTS="${PUSHSPEC}" UDA_TPU_STATS=1 \
    UDA_TPU_CHAOS_SEED="${SEED}" \
    UDA_TPU_FLIGHTREC_DIR="${FRROOT}/push" \
    UDA_TPU_LOCKDEP=1 UDA_TPU_LOCKDEP_JSON="${PUSHCYCLES}" \
    UDA_TPU_RESLEDGER=1 UDA_TPU_RESLEDGER_JSON="${PUSHLEAKS}" \
    UDA_TPU_RACEDET=1 UDA_TPU_RACEDET_JSON="${PUSHRACES}" \
    UDA_TPU_CHAOS_TELEMETRY="${PUSHCOUNTERS}" \
    python -m pytest tests/test_push.py -m faults -q \
    -p no:cacheprovider \
    --continue-on-collection-errors "$@" || pushrc=$?

# Lockdep rung: the whole faults tier again with the runtime lock-order
# validator armed (uda_tpu/utils/locks.py, UDA_TPU_LOCKDEP=1). Two
# guarantees, both checked: the seeded AB/BA inversion fixture
# (tests/test_udalint.py, on a private LockDep) must be DETECTED — its
# own assertion fails the tier otherwise — while the REAL code under
# chaos must produce ZERO cycles on the process-global validator: any
# uda_tpu lock-order inversion lands as a lockdep.cycles counter plus a
# cycle report (UDA_TPU_LOCKDEP_JSON) folded into the telemetry below.
LCOUNTERS="$(mktemp)"
LCYCLES="$(mktemp)"
trap 'rm -f "${COUNTERS}" "${PCOUNTERS}" "${NCOUNTERS}" "${NCYCLES}" "${NLEAKS}" "${ECOUNTERS}" "${ECYCLES}" "${CCOUNTERS}" "${CCYCLES}" "${CLEAKS}" "${PICOUNTERS}" "${PICYCLES}" "${PILEAKS}" "${IOCOUNTERS}" "${IOCYCLES}" "${IOLEAKS}" "${TENCOUNTERS}" "${TENCYCLES}" "${TENLEAKS}" "${RESCOUNTERS}" "${RESCYCLES}" "${RESLEAKS}" "${ACOUNTERS}" "${ELJSON}" "${ELCOUNTERS}" "${ELCYCLES}" "${ELLEAKS}" "${PUSHCOUNTERS}" "${PUSHCYCLES}" "${PUSHLEAKS}" "${LCOUNTERS}" "${LCYCLES}"; rm -rf "${FRROOT}"' EXIT
echo "lockdep schedule:    ${SPEC} (UDA_TPU_LOCKDEP=1)"
lrc=0
env JAX_PLATFORMS=cpu UDA_FAILPOINTS="${SPEC}" UDA_TPU_STATS=1 \
    UDA_TPU_FLIGHTREC_DIR="${FRROOT}/lockdep" \
    UDA_TPU_LOCKDEP=1 UDA_TPU_LOCKDEP_JSON="${LCYCLES}" \
    UDA_TPU_CHAOS_TELEMETRY="${LCOUNTERS}" \
    python -m pytest tests/ -m faults -q -p no:cacheprovider \
    --continue-on-collection-errors "$@" || lrc=$?

mrc=0
python - "${SEED}" "${SPEC}" "${COUNTERS}" "${OUT}" "${rc}" \
    "${PSPEC}" "${PCOUNTERS}" "${prc}" \
    "${NSPEC}" "${NCOUNTERS}" "${nrc}" "${NCYCLES}" \
    "${ECOUNTERS}" "${erc}" "${ECYCLES}" \
    "${CCOUNTERS}" "${crc}" "${CCYCLES}" \
    "${PIPESPEC}" "${PICOUNTERS}" "${pirc}" "${PICYCLES}" \
    "${LCOUNTERS}" "${lrc}" "${LCYCLES}" \
    "${NLEAKS}" "${CLEAKS}" "${PILEAKS}" \
    "${IOSPEC}" "${IOCOUNTERS}" "${iorc}" "${IOCYCLES}" \
    "${IOLEAKS}" \
    "${TSPEC}" "${TENCOUNTERS}" "${tenrc}" "${TENCYCLES}" \
    "${TENLEAKS}" \
    "${RESSPEC}" "${RESCOUNTERS}" "${resrc}" "${RESCYCLES}" \
    "${RESLEAKS}" \
    "${ASPEC}" "${ACOUNTERS}" "${anrc}" \
    "${ELJSON}" "${ELCOUNTERS}" "${elrc}" "${ELCYCLES}" \
    "${ELLEAKS}" \
    "${PUSHSPEC}" "${PUSHCOUNTERS}" "${pushrc}" "${PUSHCYCLES}" \
    "${PUSHLEAKS}" \
    "${NRACES}" "${CRACES}" "${PUSHRACES}" <<'EOF' || mrc=$?
import glob, json, os, sys
sys.path.insert(0, os.getcwd())
from uda_tpu.utils.critpath import buckets_from_counters
(seed, spec, counters_path, out, rc, pspec, pcounters, prc,
 nspec, ncounters, nrc, ncycles,
 ecounters, erc, ecycles,
 ccounters, crc_, ccycles,
 pipespec, picounters, pirc, picycles,
 lcounters, lrc, lcycles,
 nleaks_path, cleaks_path, pileaks_path,
 iospec, iocounters, iorc, iocycles, ioleaks_path,
 tenspec, tencounters, tenrc, tencycles, tenleaks_path,
 resspec, rescounters, resrc_, rescycles, resleaks_path,
 aspec, acounters, anrc,
 eljson, elcounters, elrc_, elcycles, elleaks_path,
 pushspec, pushcounters, pushrc_, pushcycles, pushleaks_path,
 nraces_path, craces_path, pushraces_path) = sys.argv[1:60]
frroot = os.environ.get("FRROOT", "")
def flightrec_block(rung, exit_code):
    """Archive the rung's black-box dumps (cause + structured extra +
    event count; the full event streams stay in the dump files) and
    flag the anti-pattern the recorder exists to prevent: a rung that
    FAILED without leaving a single post-mortem dump."""
    reports = []
    for path in sorted(glob.glob(
            os.path.join(frroot, rung, "flightrec_*.json"))):
        try:
            with open(path) as f:
                rep = json.load(f)
        except Exception:
            reports.append({"file": os.path.basename(path),
                            "cause": "unreadable"})
            continue
        reports.append({"file": os.path.basename(path),
                        "cause": rep.get("cause"),
                        "extra": rep.get("extra"),
                        "events": len(rep.get("events", []))})
    return {"dumps": len(reports), "reports": reports,
            "failed_without_dump": bool(int(exit_code)) and not reports}
def load(path):
    try:
        with open(path) as f:
            return json.load(f)
    except Exception:
        return {"counters": {}}
def load_cycles(path):
    reports = []
    try:
        with open(path) as f:
            reports = [json.loads(ln) for ln in f if ln.strip()]
    except Exception:
        pass
    return reports
def lockdep_block(schedule, exit_code, telem_path, cycles_path):
    telem = load(telem_path)
    reports = load_cycles(cycles_path)
    return {"schedule": schedule, "pytest_exit": int(exit_code),
            "cycles": int(telem.get("counters", {})
                          .get("lockdep.cycles", 0)),
            "cycle_reports": reports, "telemetry": telem,
            "time_accounting": timeacct_block(telem)}, reports
def timeacct_block(telem):
    """Where the rung's CPU seconds went, bucketed from the session's
    accumulated <timer>_time counters (busy seconds — a chaos rung has
    no single task wall; the per-task span partition rides the
    StatsReporter final records and flightrec dumps instead). Diffable
    across rounds like every other telemetry block."""
    return buckets_from_counters(telem.get("counters", {}))
def racedet_block(block, races_path):
    """Fold the rung's data-race reports (UDA_TPU_RACEDET_JSON lines
    from the runtime Eraser machine) into its telemetry block; returns
    the reports so the zero-races guarantee is ENFORCED below, like
    lockdep cycles and resledger leaks."""
    reports = load_cycles(races_path)
    block["racedet"] = {"armed": True, "races": len(reports),
                        "race_reports": reports}
    return reports
def resledger_block(block, leaks_path):
    """Fold the rung's leaked-obligation reports (UDA_TPU_RESLEDGER_
    JSON lines) into its telemetry block; returns the reports so the
    zero-leaks guarantee is ENFORCED below, like lockdep cycles."""
    reports = load_cycles(leaks_path)
    block["resledger"] = {"armed": True, "leaks": len(reports),
                          "leak_reports": reports}
    return reports
network, n_reports = lockdep_block(nspec, nrc, ncounters, ncycles)
n_leaks = resledger_block(network, nleaks_path)
n_races = racedet_block(network, nraces_path)
exchange, e_reports = lockdep_block(
    "seeded exchange.decode + scoped exchange.round (per-test)",
    erc, ecounters, ecycles)
# the coded-multicast guarantee, surfaced: injected decode failures,
# in-round fallbacks to the plain tile, and the multicast-model
# saved/coded byte split — the per-test asserts enforce byte-identity
# and the ledger-sum invariant; this block is the diffable record
ecc = exchange["telemetry"].get("counters", {})
exchange["coded"] = {
    "decode_failpoint_fires": ecc.get("failpoint.exchange.decode", 0),
    "decode_fallbacks": ecc.get("exchange.decode.fallbacks", 0),
    "coded_bytes": ecc.get("exchange.dcn.coded.bytes", 0),
    "saved_bytes": ecc.get("exchange.dcn.saved.bytes", 0),
}
completion, c_reports = lockdep_block(
    f"seeded supplier kill + warm restart (seed {seed})",
    crc_, ccounters, ccycles)
c_leaks = resledger_block(completion, cleaks_path)
c_races = racedet_block(completion, craces_path)
# the completion guarantee, surfaced in the telemetry: reconstructed
# partitions and resumed fetches with ZERO fallbacks (the per-test
# asserts enforce it; this block is the cross-round diffable record)
cc = completion["telemetry"].get("counters", {})
completion["survived"] = {
    "reconstructed_partitions": cc.get(
        "coding.reconstructed.partitions", 0),
    "resumed_fetches": cc.get("fetch.resumed", 0),
    "resumed_bytes": cc.get("fetch.resumed.bytes", 0),
    "speculation_won": cc.get("fetch.speculation.won", 0),
    "fallback_signals": cc.get("fallback.signals", 0),
}
pipeline, pi_reports = lockdep_block(pipespec, pirc, picounters,
                                     picycles)
pi_leaks = resledger_block(pipeline, pileaks_path)
iobatch, io_reports = lockdep_block(iospec, iorc, iocounters, iocycles)
io_leaks = resledger_block(iobatch, ioleaks_path)
tenant, ten_reports = lockdep_block(
    f"{tenspec} + scoped tenant.validate abuse", tenrc, tencounters,
    tencycles)
ten_leaks = resledger_block(tenant, tenleaks_path)
# the abusive-tenant isolation contract, surfaced: the abuser's
# injected refusals, the penalty boxings, and the VICTIM guarantee —
# zero fallbacks (its jobs completed, byte-correct per the test's own
# asserts) and zero admission bytes left on any tenant's books
tc = tenant["telemetry"].get("counters", {})
tenant["isolated"] = {
    "validate_failpoint_fires": tc.get("failpoint.tenant.validate", 0),
    "tenant_penalties": tc.get("tenant.penalties", 0),
    "sched_grants": tc.get("tenant.sched.grants", 0),
    "victim_fallback_signals": tc.get("fallback.signals", 0),
    "tenant_bytes_left": tenant["telemetry"].get(
        "gauges", {}).get("tenant.read.bytes.on_air", 0),
}
# the batch-partial-failure contract, surfaced: requests batched,
# coalesced runs/syscalls issued, injected per-request faults, and
# zero bytes/pins left in flight (the per-test asserts enforce it;
# this is the cross-round diffable record)
ioc = iobatch["telemetry"].get("counters", {})
iobatch["isolated"] = {
    "batch_requests": ioc.get("io.batch.requests", 0),
    "batch_reads": ioc.get("io.batch.reads", 0),
    "coalesce_runs": ioc.get("io.coalesce.runs", 0),
    "preadv_failpoint_fires": ioc.get("failpoint.data_engine.preadv",
                                      0),
    "inflight_left": iobatch["telemetry"].get(
        "gauges", {}).get("io.batch.inflight", 0),
}
# the drain contract, surfaced: staged runs consumed, backpressure
# blocks observed, and zero bytes left in flight after every
# faulted-and-aborted pipeline (the per-test asserts enforce the
# gauge; this is the cross-round diffable record)
pc = pipeline["telemetry"].get("counters", {})
pipeline["drained"] = {
    "pipeline_runs": pc.get("merge.pipeline.runs", 0),
    "backpressure_events": pc.get("stage.backpressure_events", 0),
    "staged_bytes": pc.get("stage.bytes", 0),
    "inflight_bytes_left": pipeline["telemetry"].get(
        "gauges", {}).get("stage.inflight.bytes", 0),
}
resume, res_reports = lockdep_block(
    f"{resspec} + seeded kill -9 mid-merge/mid-snapshot", resrc_,
    rescounters, rescycles)
res_leaks = resledger_block(resume, resleaks_path)
# the crash-consistent resume contract, surfaced: resumed-not-
# restarted counts, banked bytes, adopted run files and the
# invalidation ladder's verdicts (the per-test asserts enforce
# byte-identity and zero refetch; this is the diffable record). The
# parent pytest process hosts the RESUMED attempts, so its session
# counters carry the resume-side evidence; the killed child's counters
# die with it by design.
rsc = resume["telemetry"].get("counters", {})
resume["resumed"] = {
    "ckpt_resumed": rsc.get("ckpt.resumed", 0),
    "runs_adopted": rsc.get("ckpt.runs.adopted", 0),
    "resumed_fetches": rsc.get("fetch.resumed", 0),
    "resumed_bytes": rsc.get("fetch.resumed.bytes", 0),
    "snapshots": rsc.get("ckpt.snapshots", 0),
    "invalidated": rsc.get("ckpt.invalidated", 0),
    "save_errors": rsc.get("ckpt.save.errors", 0),
}
elastic, el_reports = lockdep_block(
    f"seeded blob-kill + mid-job drain-and-join (seed {seed})",
    elrc_, elcounters, elcycles)
el_leaks = resledger_block(elastic, elleaks_path)
# the elasticity contract, surfaced: the scenario driver's own JSON
# (byte-identity, failover count, drained partitions, the join — its
# exit code already enforces all of it) plus the store counters from
# the faults-marked test pass; the cross-round diffable record
try:
    with open(eljson) as f:
        el_scenario = json.load(f)
except Exception:
    el_scenario = {}
elc = elastic["telemetry"].get("counters", {})
elastic["scenario"] = el_scenario
elastic["survived"] = {
    "scenario_identical": el_scenario.get("identical"),
    "scenario_failover": el_scenario.get("store_failover", 0),
    "scenario_drained": el_scenario.get("drained_partitions", 0),
    "scenario_joins": el_scenario.get("elastic_joins", 0),
    "scenario_fallbacks": el_scenario.get("fallback_signals", 0),
    "test_failover": elc.get("store.failover", 0),
    "test_migrations": elc.get("store.migrations", 0),
    "test_revalidated": elc.get("store.revalidated", 0),
}
# a passing elastic rung whose scenario shows NO failover (the blob
# kills never re-routed) or ANY fallback means the machinery under
# test never engaged — fail the tier like the anomaly rung's
# no-proactive-dump check
elastic_dead = (not int(elrc_)
                and (not el_scenario.get("identical")
                     or not el_scenario.get("store_failover", 0)
                     or el_scenario.get("fallback_signals", 1)))
push, push_reports = lockdep_block(pushspec, pushrc_, pushcounters,
                                   pushcycles)
push_leaks = resledger_block(push, pushleaks_path)
push_races = racedet_block(push, pushraces_path)
# the push contract, surfaced: chunks pushed and acked, the typed
# refusals (each one a partition converting to pull, zero bytes
# lost), adopted prefixes, and the settlement guarantee — nothing
# left on the push window or in staging after every kill/tear (the
# per-test asserts enforce byte-identity against the pull oracle;
# this block is the cross-round diffable record)
pshc = push["telemetry"].get("counters", {})
pshg = push["telemetry"].get("gauges", {})
push["pushed"] = {
    "commits": pshc.get("push.commits", 0),
    "chunks": pshc.get("push.chunks", 0),
    "acks": pshc.get("push.acks", 0),
    "nacks": pshc.get("push.nacks", 0),
    "refused": pshc.get("push.refused", 0),
    "push_errors": pshc.get("push.errors", 0),
    "adopted": pshc.get("push.adopted", 0),
    "adopted_bytes": pshc.get("push.adopted.bytes", 0),
    "fallback_signals": pshc.get("fallback.signals", 0),
    "on_air_left": pshg.get("push.on_air", 0),
    "staged_bytes_left": pshg.get("push.staged.bytes", 0),
}
# a passing push rung that pushed NOTHING, fell back, or stranded its
# window/staging means the plane under test never engaged (or leaked)
# — fail the tier like the elastic/anomaly dead-rung checks
# absent counters/gauges read as 0 — a counter that never
# incremented is simply missing from the export, which is the
# HEALTHY case for fallback.signals and the settled gauges
push_dead = (not int(pushrc_)
             and (not pshc.get("push.chunks", 0)
                  or pshc.get("fallback.signals", 0)
                  or pshg.get("push.on_air", 0)
                  or pshg.get("push.staged.bytes", 0)))
anomaly_telem = load(acounters)
# the proactive-capture contract, surfaced: detector firings, the
# rate-limited black-box dumps, and the PROACTIVE guarantee — zero
# FallbackSignals in a rung whose detectors fired (the per-test
# asserts enforce the exactly-one ordering; this block is the
# cross-round diffable record)
acc = anomaly_telem.get("counters", {})
anomaly = {"schedule": aspec, "pytest_exit": int(anrc),
           "telemetry": anomaly_telem,
           "time_accounting": timeacct_block(anomaly_telem),
           "detected": {
               "fired": acc.get("anomaly.fired", 0),
               "dumps": acc.get("anomaly.dumps", 0),
               "p99_firings": acc.get(
                   "anomaly.p99{key=fetch.latency_ms}", 0),
               "fallback_signals": acc.get("fallback.signals", 0)}}
lockdep, l_reports = lockdep_block(spec, lrc, lcounters, lcycles)
nleak = (len(n_leaks) + len(c_leaks) + len(pi_leaks) + len(io_leaks)
         + len(ten_leaks) + len(res_leaks) + len(el_leaks)
         + len(push_leaks))
nrace = len(n_races) + len(c_races) + len(push_races)
# flight-recorder archive, one block per rung; a rung that failed
# without a single black-box dump flags failed_without_dump
fr = {"main": flightrec_block("main", rc),
      "pressure": flightrec_block("pressure", prc),
      "network": flightrec_block("network", nrc),
      "exchange": flightrec_block("exchange", erc),
      "completion": flightrec_block("completion", crc_),
      "pipeline": flightrec_block("pipeline", pirc),
      "iobatch": flightrec_block("iobatch", iorc),
      "tenant": flightrec_block("tenant", tenrc),
      "resume": flightrec_block("resume", resrc_),
      "anomaly": flightrec_block("anomaly", anrc),
      "elastic": flightrec_block("elastic", elrc_),
      "push": flightrec_block("push", pushrc_),
      "lockdep": flightrec_block("lockdep", lrc)}
network["flightrec"] = fr["network"]
exchange["flightrec"] = fr["exchange"]
completion["flightrec"] = fr["completion"]
pipeline["flightrec"] = fr["pipeline"]
iobatch["flightrec"] = fr["iobatch"]
tenant["flightrec"] = fr["tenant"]
resume["flightrec"] = fr["resume"]
anomaly["flightrec"] = fr["anomaly"]
elastic["flightrec"] = fr["elastic"]
push["flightrec"] = fr["push"]
lockdep["flightrec"] = fr["lockdep"]
# the anomaly rung's enforced guarantee (the flip side of
# failed_without_dump): a PASSING anomaly rung that left no proactive
# cause=anomaly dump means the detectors never fired under the storm —
# the capture machinery is dead and the rung must fail the tier
anomaly_proactive = [r for r in fr["anomaly"]["reports"]
                     if r.get("cause") == "anomaly"]
anomaly["detected"]["proactive_dumps"] = len(anomaly_proactive)
no_proactive = not int(anrc) and not anomaly_proactive
no_postmortem = sorted(r for r, b in fr.items()
                       if b["failed_without_dump"])
with open(out, "w") as f:
    main_telem = load(counters_path)
    pressure_telem = load(pcounters)
    json.dump({"chaos_seed": int(seed), "schedule": spec,
               "pytest_exit": int(rc), "telemetry": main_telem,
               "time_accounting": timeacct_block(main_telem),
               "flightrec": fr["main"],
               "pressure": {"schedule": pspec, "pytest_exit": int(prc),
                            "telemetry": pressure_telem,
                            "time_accounting":
                                timeacct_block(pressure_telem),
                            "flightrec": fr["pressure"]},
               "network": network,
               "exchange": exchange,
               "completion": completion,
               "pipeline": pipeline,
               "iobatch": iobatch,
               "tenant": tenant,
               "resume": resume,
               "anomaly": anomaly,
               "elastic": elastic,
               "push": push,
               "lockdep": lockdep,
               "resledger": {"armed_rungs": ["network", "completion",
                                             "pipeline", "iobatch",
                                             "tenant", "resume",
                                             "elastic", "push"],
                             "leaks": nleak},
               "racedet": {"armed_rungs": ["network", "completion",
                                           "push"],
                           "races": nrace},
               "flightrec_missing_postmortem": no_postmortem},
              f, indent=1, sort_keys=True)
    f.write("\n")
ncyc = (len(n_reports) + len(e_reports) + len(c_reports)
        + len(pi_reports) + len(io_reports) + len(ten_reports)
        + len(res_reports) + len(el_reports) + len(push_reports)
        + len(l_reports))
ndumps = sum(b["dumps"] for b in fr.values())
print(f"chaos telemetry:     {out} (lockdep cycles on real code: {ncyc}, "
      f"resledger leaks: {nleak}, racedet races: {nrace}, "
      f"flightrec dumps: {ndumps})")
if nrace:
    print(f"RACEDET: {nrace} data race(s) on real code under chaos — "
          f"a shared-modified field ended with an empty candidate "
          f"lockset (see the racedet blocks in {out})",
          file=sys.stderr)
if no_postmortem:
    print(f"FLIGHTREC: rung(s) failed with NO black-box dump: "
          f"{', '.join(no_postmortem)} — the post-mortem record is "
          f"part of the failure contract", file=sys.stderr)
if no_proactive:
    print("ANOMALY: the anomaly rung passed but left NO proactive "
          "cause=anomaly dump — the detectors never fired under the "
          "slow-supplier storm, which defeats the rung's purpose",
          file=sys.stderr)
if elastic_dead:
    print("ELASTIC: the elastic rung passed but its scenario record "
          "shows no engaged failover, a byte drift, or a fallback — "
          "the blob-kill/drain/join machinery never exercised, which "
          "defeats the rung's purpose", file=sys.stderr)
if push_dead:
    print("PUSH: the push rung passed but pushed no chunks, raised a "
          "FallbackSignal, or left the push window/staging gauges "
          "nonzero — the push plane never engaged or leaked, which "
          "defeats the rung's purpose", file=sys.stderr)
# the zero-cycles / zero-leaks / zero-races / dump-on-failure /
# proactive-capture guarantees are ENFORCED, not just printed: a
# detected inversion, a leaked obligation, a data race on real code, a
# failing rung with no post-mortem record, or an anomaly rung with no
# proactive capture all fail the tier — that is the entire point of
# lockdep, the ledger, the race detector and the flight recorder
sys.exit(3 if (ncyc or nleak or nrace or no_postmortem or no_proactive
               or elastic_dead or push_dead)
         else 0)
EOF
if [ "${prc}" -ne 0 ]; then rc="${prc}"; fi
if [ "${nrc}" -ne 0 ]; then rc="${nrc}"; fi
if [ "${erc}" -ne 0 ]; then rc="${erc}"; fi
if [ "${crc}" -ne 0 ]; then rc="${crc}"; fi
if [ "${pirc}" -ne 0 ]; then rc="${pirc}"; fi
if [ "${iorc}" -ne 0 ]; then rc="${iorc}"; fi
if [ "${tenrc}" -ne 0 ]; then rc="${tenrc}"; fi
if [ "${resrc}" -ne 0 ]; then rc="${resrc}"; fi
if [ "${anrc}" -ne 0 ]; then rc="${anrc}"; fi
if [ "${elrc}" -ne 0 ]; then rc="${elrc}"; fi
if [ "${pushrc}" -ne 0 ]; then rc="${pushrc}"; fi
if [ "${lrc}" -ne 0 ]; then rc="${lrc}"; fi
if [ "${mrc}" -ne 0 ]; then
  echo "LOCKDEP/RESLEDGER/RACEDET/FLIGHTREC: cycle reports, leaked" \
       "obligations, data races or a failing rung without its" \
       "black-box dump (see CHAOS_TELEMETRY.json)" >&2
  rc="${mrc}"
fi
exit "${rc}"
