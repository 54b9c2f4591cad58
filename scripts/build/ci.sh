#!/usr/bin/env bash
# CI driver: build + test + regression + artifact bundle in one gate.
#
# The uda_tpu analogue of the reference's nightly build+smoke system
# (reference scripts/build/: per-Hadoop-version builds, smoke runs,
# db/latest_hadoops bookkeeping) collapsed to what this framework
# needs: native libs -> unit/engine tests -> the workload-ladder
# regression -> one artifacts directory a nightly can archive.
#
# Usage: scripts/build/ci.sh [artifacts_dir]
# Exit code != 0 on any gate failure (the cases/uda.cases CI contract).

set -euo pipefail
cd "$(dirname "$0")/../.."

ART="${1:-ci_artifacts}"
mkdir -p "$ART"
echo "== uda_tpu CI $(date -u +%Y-%m-%dT%H:%M:%SZ) ==" | tee "$ART/ci.log"

echo "-- native build" | tee -a "$ART/ci.log"
make -C uda_tpu/native 2>&1 | tee -a "$ART/ci.log"
make -C uda_tpu/native libuda_tpu_bridge.so 2>&1 | tee -a "$ART/ci.log"
# Java gate. This image has NO Java compiler and cannot get one:
# javac/ecj exist nowhere on the filesystem, bazel's embedded Zulu 21
# JRE (~/.cache/bazel/.../embedded_tools/jdk) is a 13-module jlink
# image WITHOUT jdk.compiler, and the container has zero network
# egress (DNS fails), so vendoring a JDK is impossible here (probed
# 2026-07-30). The real compile gate below arms itself automatically
# on any host with a JDK; until then check_java.py gives the sources
# the strongest compiler-less gate (string-aware structural pass).
if command -v javac >/dev/null 2>&1; then
  echo "-- java build" | tee -a "$ART/ci.log"
  make -C java 2>&1 | tee -a "$ART/ci.log"
else
  echo "-- java build skipped (no JDK in image); structural check" \
    | tee -a "$ART/ci.log"
  python scripts/build/check_java.py 2>&1 | tee -a "$ART/ci.log"
fi

# Static analysis gate: the project invariants (metrics registry,
# config-key declaration, failpoint sites, shutdown-before-close,
# structured-cause branching, no silent swallows, no blocking under a
# lock) AND the udaflow dataflow tier (UDA101 resource balance on
# every CFG path, UDA102 transitive blocking, UDA103 static lock
# order) are machine-enforced BEFORE any test runs — a violation is a
# build failure, like the reference's scripts/build check_* gates.
# The machine-readable findings land in the artifacts (udalint.json)
# so downstream gates consume them structurally, never by grep.
echo "-- udalint static analysis (incl. UDA009 span names + udaflow UDA101-UDA103)" \
  | tee -a "$ART/ci.log"
# human-readable gate FIRST (findings must land in ci.log/console);
# the machine-readable artifact only runs on a clean tree, where the
# second pass hits the content-hash cache (--cache: the JSON pass
# re-parses nothing on an unchanged tree)
python scripts/udalint.py --cache uda_tpu scripts 2>&1 | tee -a "$ART/ci.log" | tail -1
python scripts/udalint.py --cache --json uda_tpu scripts > "$ART/udalint.json"

echo "-- unit + engine tests" | tee -a "$ART/ci.log"
python -m pytest tests/ -q 2>&1 | tee "$ART/pytest.log" | tail -2

# Network data plane: a real server + 2 concurrent reduce clients over
# 127.0.0.1, byte-compared against the in-process path (uda_tpu/net/),
# with span tracing on — the smoke's span JSONL feeds the trace-merge
# gate below, and the smoke itself now round-trips one MSG_STATS poll.
echo "-- net loopback smoke" | tee -a "$ART/ci.log"
env JAX_PLATFORMS=cpu UDA_TPU_STATS=1 \
  python scripts/net_smoke.py --spans "$ART/net_smoke_spans.jsonl" \
  2>&1 | tee -a "$ART/ci.log" | tail -1

# Trace-merge gate: the smoke's span file must stitch into one valid
# Perfetto-loadable Chrome trace (empty or unparsable span files fail;
# the cross-process link assertion rides tier-1's two-process-shaped
# e2e in tests/test_observability.py — the smoke is one process).
echo "-- trace merge (net smoke spans)" | tee -a "$ART/ci.log"
python scripts/trace_merge.py "$ART/net_smoke_spans.jsonl" \
  --out "$ART/net_smoke_trace.json" 2>&1 | tee -a "$ART/ci.log" | tail -1

# Net data-plane bench, quick mode: single-stream + p99 latency + the
# 256-connection fan-in on the event-loop core. Gates on correctness
# (zero fan-in errors/stalls); throughput is reported, not gated, so a
# noisy shared host cannot flake CI (full runs ride BENCH_NET_*.json).
echo "-- net data-plane bench (quick)" | tee -a "$ART/ci.log"
env JAX_PLATFORMS=cpu \
  python scripts/net_bench.py --quick --out "$ART/bench_net.json" \
  2>&1 | tee -a "$ART/ci.log" | tail -4

# Batched host-I/O serve A/B, quick mode: the batched+coalesced read
# plane (uda.tpu.read.batch=on) must be BYTE-IDENTICAL to the
# single-pread oracle (=off) on the hot-burst shape — identity is the
# gate (exit 3 on divergence); throughput/speedup are recorded as
# perfwatch trend data (full runs ride BENCH_IO_r*.json and gate the
# >= 1.3x acceptance there).
echo "-- batched host-I/O serve A/B (quick)" | tee -a "$ART/ci.log"
env JAX_PLATFORMS=cpu \
  python scripts/io_bench.py --quick --out "$ART/bench_io.json" \
  2>&1 | tee -a "$ART/ci.log" | tail -3

# Multi-tenant fairness bench, quick mode: T concurrent jobs through
# one daemon — the byte-identity gate (every job's concurrent fetch ==
# its solo run; exit 3 on divergence) plus the WDRR plumbing end to
# end; fairness/weighted ratios are recorded as perfwatch trend data
# (full runs ride BENCH_TENANT_r*.json and gate the >= 0.7 fairness +
# ~2:1 weighting bands there).
echo "-- multi-tenant fairness bench (quick)" | tee -a "$ART/ci.log"
env JAX_PLATFORMS=cpu \
  python scripts/tenant_bench.py --quick \
  --out "$ART/bench_tenant.json" 2>&1 | tee -a "$ART/ci.log" | tail -4

# Elastic disaggregated-store bench, quick mode: the spill ladder
# (10x-over-budget shuffle completes byte-identical with local
# retention bounded at the watermark) plus the mid-job supplier join
# (a degraded primary's stall collapses when the replica registers) —
# identity/bounded/registered are the gates (exit 3 on divergence);
# walls and the join speedup are perfwatch trend data (full runs ride
# BENCH_ELASTIC_r*.json and gate the >= 1.2x join speedup there).
echo "-- elastic store spill + mid-job join bench (quick)" | tee -a "$ART/ci.log"
env JAX_PLATFORMS=cpu \
  python scripts/bench_elastic.py --quick \
  --out "$ART/bench_elastic.json" 2>&1 | tee -a "$ART/ci.log" | tail -2

# Push-shuffle overlap bench, quick mode: supplier-initiated MSG_PUSH
# vs the fetch-wave pull baseline over the real loopback plane — the
# byte-identity gate (sha256 of the merged stream vs the pull oracle;
# exit 3 on divergence) plus push-plane engagement (chunks sent AND
# staged bytes adopted into the Segment ledger) and zero terminal
# FallbackSignals; walls/speedup are perfwatch trend data (full runs
# ride BENCH_PUSH_r*.json and gate the >= 1.1x overlap win there).
echo "-- push-shuffle overlap bench (quick)" | tee -a "$ART/ci.log"
env JAX_PLATFORMS=cpu \
  python scripts/bench_push.py --quick \
  --out "$ART/bench_push.json" 2>&1 | tee -a "$ART/ci.log" | tail -2

# Fleet observability gate: one tenanted, observability-armed daemon,
# 8 equal-weight tenant drivers, scripts/udafleet.py --once --json
# polled live against it — the CAP_OBS sections must round-trip and
# every tenant's fleet share of scheduled bytes must land within 2% of
# its weight-proportional entitlement (the WDRR fairness audit the SLI
# book exists to answer).
echo "-- fleet observability smoke (udafleet --once --json)" | tee -a "$ART/ci.log"
env JAX_PLATFORMS=cpu \
  python scripts/fleet_smoke.py 2>&1 | tee -a "$ART/ci.log" | tail -1

# Tuning-cache round trip: a quick io.read probe must persist
# a winner, and a SECOND probe run must serve from the cache without
# re-measuring (tune_probe prints "0 probe(s)" — the self-service
# routing contract; the full lifecycle matrix rides
# tests/test_tuncache.py in tier-1).
echo "-- tuning-cache probe round trip (quick)" | tee -a "$ART/ci.log"
env JAX_PLATFORMS=cpu \
  python scripts/tune_probe.py --cache "$ART/tune_cache.json" --quick 2>&1 \
  | tee -a "$ART/ci.log" | tail -2
env JAX_PLATFORMS=cpu \
  python scripts/tune_probe.py --cache "$ART/tune_cache.json" --quick 2>&1 \
  | tee -a "$ART/ci.log" | grep -q "0 probe(s) run" \
  || { echo "FAIL: second tune_probe run re-probed a fresh cache" \
       | tee -a "$ART/ci.log"; exit 1; }

# Hierarchical + CODED exchange gate, quick mode (2x4 virtual mesh):
# the two-stage pod exchange AND the coded multicast stage B must be
# byte-identical to the flat exchange and the host oracles, and the
# accounting invariants must hold — hierarchical per-round DCN
# messages <= the pod-pair bound and <= the flat device-pair count,
# DCN bytes no higher than flat, coded + saved == uncoded payload,
# uniform coded charge <= 0.67x hierarchical, zero coded overhead on
# the uncodable shapes (full 8/16/64 runs ride
# MULTICHIP_SCALE_r*.json and feed perfwatch).
echo "-- hierarchical + coded exchange bench (quick)" | tee -a "$ART/ci.log"
python scripts/exchange_bench.py --quick \
  --out "$ART/exchange_bench.json" 2>&1 | tee -a "$ART/ci.log" | tail -5

# perfwatch gate: the fresh quick point (throughput trends +
# correctness booleans) against the committed
# PERF_TRAJECTORY.json. The band is generous — shared CI
# hosts gate direction-of-change, not absolute MB/s; quick-mode
# throughputs are recorded as trend data and the hard gates are the
# correctness/identity metrics (per-entry tol 0). Exit 1 = a shipped
# perf regression, which is a build failure.
echo "-- perfwatch perf-regression gate" | tee -a "$ART/ci.log"
python scripts/perfwatch.py --check "$ART/bench_io.json" \
  --tolerance 0.6 2>&1 | tee -a "$ART/ci.log" | tail -3
python scripts/perfwatch.py --check "$ART/bench_tenant.json" \
  --tolerance 0.6 2>&1 | tee -a "$ART/ci.log" | tail -3
python scripts/perfwatch.py --check "$ART/exchange_bench.json" \
  --tolerance 0.6 2>&1 | tee -a "$ART/ci.log" | tail -3
python scripts/perfwatch.py --check "$ART/bench_elastic.json" \
  --tolerance 0.6 2>&1 | tee -a "$ART/ci.log" | tail -3
python scripts/perfwatch.py --check "$ART/bench_push.json" \
  --tolerance 0.6 2>&1 | tee -a "$ART/ci.log" | tail -3

echo "-- workload-ladder regression" | tee -a "$ART/ci.log"
python scripts/regression/run_regression.py \
  --size small --out "$ART/regression" 2>&1 | tee -a "$ART/ci.log" | tail -3

echo "-- multi-chip dryrun" | tee -a "$ART/ci.log"
env XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
  python -c "import __graft_entry__ as g; g.dryrun_multichip(8)" \
  2>&1 | tee -a "$ART/ci.log" | tail -1

# chip_smoke.py's CPU rehearsal: the plumbing of the on-chip smoke
# (bridge INIT/FETCH/FINAL over loopback vs the host reference, every
# selectable kernel interpreted, the four-device sort) at tiny sizes.
# Says nothing about the chip — that is `python chip_smoke.py` on one.
echo "-- chip_smoke rehearsal (CPU)" | tee -a "$ART/ci.log"
python chip_smoke.py --rehearse-cpu 2>&1 | tee -a "$ART/ci.log" | tail -1

echo "== CI PASS ==" | tee -a "$ART/ci.log"
