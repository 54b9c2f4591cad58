#!/usr/bin/env python
"""A/B/C bench + correctness gate: flat vs hierarchical vs CODED
exchange on CPU virtual multi-pod meshes.

For each mesh size (default ``dcn:2,ici:4`` / ``dcn:4,ici:4`` /
``dcn:8,ici:8`` — 8/16/64 virtual devices, each in a FRESH interpreter:
the device count locks at backend init) the child runs uniform, skewed
and pod-local workloads through ``shuffle_exchange`` THREE times on the
SAME 2-axis mesh — ``mode="flat"`` (one global all_to_all per round,
every cross-pod device pair its own DCN lane), ``mode="hierarchical"``
(pod-local all_to_all + ONE coalesced DCN tile per pod pair) and
``mode="coded"`` (the pair tile carries GF(2^8)-coded chunks every
member decodes locally — the Coded TeraSort multicast phase) — and
checks, per round:

- **byte-identity**: the hierarchical AND coded deliveries equal the
  flat delivery array-for-array, and all equal a pure-numpy host
  oracle of the window protocol; the per-destination record multiset
  equals the RecordBatch host oracle (``exchange_record_batches``);
- **accounting invariants**: hierarchical per-round DCN messages <=
  pods*(pods-1) (the pod-pair bound) and <= the flat per-round count;
  total hierarchical DCN bytes <= flat DCN bytes; the coded ledger sum
  ``coded + saved == uncoded payload``; on the uniform workload the
  coded DCN payload charge <= 0.67x hierarchical (the ~k-fold
  multicast cut, k = pod size); on the UNCODABLE workloads (skew,
  pod-local) zero coded overhead bytes — the plan routes every window
  to the plain tile. Byte figures are the planner's RECORD-payload
  ledger; the coded series charge the redundant-map multicast model —
  see the scope notes in uda_tpu/parallel/exchange.py + planner.py.

Wall clock is measured on the post-compile run (every mode executes
once to compile, then the timed pass). Output (default
``MULTICHIP_SCALE_r15.json``) carries per-size flat/hier/coded
accounting + timing; exit != 0 on any identity/invariant failure —
the ci.sh ``--quick`` gate (size 8 only).

Usage: scripts/exchange_bench.py [--quick] [--out PATH]
       [--sizes dcn:2,ici:4;dcn:4,ici:4;dcn:8,ici:8]
       [--per-size-timeout S]
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)

DEFAULT_SIZES = "dcn:2,ici:4;dcn:4,ici:4;dcn:8,ici:8"


def _parse_spec(spec: str):
    names, sizes = [], []
    for part in spec.split(","):
        name, _, size = part.partition(":")
        names.append(name.strip())
        sizes.append(int(size))
    if len(names) != 2:
        raise ValueError(f"mesh spec {spec!r} must be 'dcn:P,ici:C'")
    return tuple(names), tuple(sizes)


# ---------------------------------------------------------------------------
# child (runs in a fresh interpreter with the device count forced)

def _host_oracle_round(words, dest, capacity, r, p):
    """Pure-numpy model of the window protocol: the expected
    (recv_words, recv_counts) of round ``r`` on every device."""
    import numpy as np

    n, w = words.shape
    shard = n // p
    recv = np.zeros((p, p * capacity, w), words.dtype)
    counts = np.zeros((p, p), np.int64)
    for s in range(p):
        pos = {}
        for row in range(s * shard, (s + 1) * shard):
            t = int(dest[row])
            q = pos.get(t, 0)
            pos[t] = q + 1
            slot = q - r * capacity
            if 0 <= slot < capacity:
                recv[t, s * capacity + slot] = words[row]
                counts[t, s] += 1
    return recv, counts


def run_child(spec: str, rows_per_device: int, quick: bool) -> dict:
    names, sizes = _parse_spec(spec)
    ndev = sizes[0] * sizes[1]
    os.environ.setdefault(
        "XLA_FLAGS", f"--xla_force_host_platform_device_count={ndev}")
    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    from jax.sharding import Mesh

    from uda_tpu.parallel import plan_rounds, shuffle_exchange
    from uda_tpu.parallel.exchange import exchange_record_batches
    from uda_tpu.utils.ifile import RecordBatch, crack, write_records
    from uda_tpu.utils.metrics import metrics

    p_pods, c_chips = sizes
    mesh = Mesh(np.asarray(jax.devices()[:ndev]).reshape(sizes), names)
    axis = names
    rng = np.random.default_rng(7)
    n = ndev * rows_per_device
    wcols = 4
    rec_bytes = wcols * 4

    def workloads():
        uni = rng.integers(0, 2**32, size=(n, wcols), dtype=np.uint32)
        yield "uniform", uni, (uni[:, 1] % ndev).astype(np.int32), \
            max(2, rows_per_device // ndev + 2)
        skew = rng.integers(0, 2**32, size=(n, wcols), dtype=np.uint32)
        sdest = (skew[:, 1] % ndev).astype(np.int32)
        sdest[: (3 * n) // 4] = 0          # 75% of records hit device 0
        yield "skewed", skew, sdest, max(2, rows_per_device // 8)
        hot = rng.integers(0, 2**32, size=(n, wcols), dtype=np.uint32)
        # every record to ONE chip: every pod pair has a single
        # destination block — nothing to encode across, the plan must
        # decline every window (zero coded bytes)
        yield "skew_single_dest", hot, np.zeros(n, np.int32), \
            max(2, rows_per_device // 8)
        pod = rng.integers(0, 2**32, size=(n, wcols), dtype=np.uint32)
        pdest = np.zeros(n, np.int32)      # pod-local: no DCN traffic
        shard = n // ndev
        for s in range(ndev):
            base = (s // c_chips) * c_chips
            pdest[s * shard:(s + 1) * shard] = \
                base + pod[s * shard:(s + 1) * shard, 1] % c_chips
        yield "pod_local", pod, pdest, max(2, rows_per_device // ndev + 2)

    def run_mode(words, dest, capacity, mode):
        metrics.reset()
        t0 = time.perf_counter()
        results, layout = shuffle_exchange(words, dest, mesh, axis,
                                           capacity, mode=mode)
        compile_s = time.perf_counter() - t0
        host = [(np.asarray(rw), np.asarray(rc).reshape(-1))
                for rw, rc in results]
        snap = dict(metrics.counters)
        # timed pass: same layout/plan, post-compile
        t0 = time.perf_counter()
        results2, _ = shuffle_exchange(words, dest, mesh, axis,
                                       capacity, mode=mode)
        for rw, rc in results2:
            np.asarray(rw)                 # block until delivered
        wall = time.perf_counter() - t0
        plan = plan_rounds(layout.counts, capacity, layout.topology,
                           rec_bytes, layout.hierarchical,
                           coded=layout.coded)
        per_round_msgs = [w.dcn_messages for w in plan.windows]
        return {
            "rounds": len(host),
            "skipped": int(snap.get("exchange.rounds.skipped", 0)),
            "wall_s": round(wall, 4),
            "first_run_s": round(compile_s, 4),
            "ici_bytes": int(snap.get("exchange.ici.bytes", 0)),
            "dcn_bytes": int(snap.get("exchange.dcn.bytes", 0)),
            "dcn_messages": int(snap.get("exchange.dcn.messages", 0)),
            "dcn_messages_per_round_max":
                max(per_round_msgs, default=0),
            "dcn_coded_bytes":
                int(snap.get("exchange.dcn.coded.bytes", 0)),
            "dcn_saved_bytes":
                int(snap.get("exchange.dcn.saved.bytes", 0)),
            "decode_fallbacks":
                int(snap.get("exchange.decode.fallbacks", 0)),
            "coded_windows": sum(1 for w in plan.windows if w.coded),
        }, host

    def batch_of(rows):
        return crack(write_records([(r.tobytes(), b"") for r in rows]))

    cases = []
    ok = True
    for label, words, dest, capacity in workloads():
        flat_acct, flat_rounds = run_mode(words, dest, capacity, "flat")
        hier_acct, hier_rounds = run_mode(words, dest, capacity,
                                          "hierarchical")
        coded_acct, coded_rounds = run_mode(words, dest, capacity,
                                            "coded")
        checks = {"byte_identical": True, "oracle_identical": True,
                  "recordbatch_identical": True,
                  "coded_byte_identical": True}
        if len(flat_rounds) != len(hier_rounds):
            checks["byte_identical"] = False
        if len(flat_rounds) != len(coded_rounds):
            checks["coded_byte_identical"] = False
        for r, ((fw, fc), (cw, cc)) in enumerate(zip(flat_rounds,
                                                     coded_rounds)):
            if not (np.array_equal(fw, cw) and np.array_equal(fc, cc)):
                checks["coded_byte_identical"] = False
        for r, ((fw, fc), (hw, hc)) in enumerate(zip(flat_rounds,
                                                     hier_rounds)):
            if not (np.array_equal(fw, hw) and np.array_equal(fc, hc)):
                checks["byte_identical"] = False
            ow, oc = _host_oracle_round(words, dest, capacity, r, ndev)
            got_w = hw.reshape(ndev, ndev * capacity, wcols)
            got_c = hc.reshape(ndev, ndev)
            if not (np.array_equal(got_w, ow)
                    and np.array_equal(got_c, oc)):
                checks["oracle_identical"] = False
        # RecordBatch host oracle: per-destination record multiset
        shard = n // ndev
        by_dest = [[batch_of(words[s * shard:(s + 1) * shard]
                             [dest[s * shard:(s + 1) * shard] == t])
                    for t in range(ndev)] for s in range(ndev)]
        oracle = exchange_record_batches(by_dest)
        for t in range(ndev):
            want = sorted(k for k, _ in oracle[t].iter_records())
            got = []
            for (hw, hc) in hier_rounds:
                gw = hw.reshape(ndev, ndev, capacity, wcols)
                gc = hc.reshape(ndev, ndev)
                for s in range(ndev):
                    got.extend(gw[t, s, i].tobytes()
                               for i in range(gc[t, s]))
            if sorted(got) != want:
                checks["recordbatch_identical"] = False
        pair_bound = p_pods * (p_pods - 1)
        checks["dcn_messages_le_pod_pair_bound"] = \
            hier_acct["dcn_messages_per_round_max"] <= pair_bound
        checks["dcn_messages_le_flat"] = \
            hier_acct["dcn_messages"] <= flat_acct["dcn_messages"]
        checks["dcn_bytes_le_flat"] = \
            hier_acct["dcn_bytes"] <= flat_acct["dcn_bytes"]
        # the coded ledger-sum invariant: every window books either
        # its full payload (plain) or coded + saved == payload
        checks["coded_ledger_sum"] = (
            coded_acct["dcn_bytes"] + coded_acct["dcn_saved_bytes"]
            == hier_acct["dcn_bytes"])
        if label == "uniform" and hier_acct["dcn_bytes"]:
            # THE acceptance figure: the multicast charge cuts the
            # uniform cross-pod DCN payload to <= 0.67x hierarchical
            checks["coded_dcn_le_067x_hier"] = (
                coded_acct["dcn_bytes"]
                <= 0.67 * hier_acct["dcn_bytes"])
        elif label == "skewed":
            # partial skew: the break-even guard may still code the
            # balanced early windows (a genuine saving) but must NEVER
            # regress the ledger past the plain tile
            checks["skew_never_regresses"] = (
                coded_acct["dcn_bytes"] <= hier_acct["dcn_bytes"])
        else:
            # fully-uncodable shapes (single-destination skew,
            # pod-local): the plan must route every window to the
            # plain tile — zero coded overhead, byte-for-byte the
            # hierarchical ledger
            checks["uncodable_zero_coded_overhead"] = (
                coded_acct["dcn_coded_bytes"] == 0
                and coded_acct["dcn_bytes"] == hier_acct["dcn_bytes"])
        ok = ok and all(checks.values())
        cases.append({"workload": label, "capacity": int(capacity),
                      "flat": flat_acct, "hierarchical": hier_acct,
                      "coded": coded_acct,
                      "pod_pair_bound": pair_bound,
                      "device_pair_bound": ndev * (ndev - 1),
                      "checks": checks})
    return {"mesh": spec, "devices": ndev, "pods": p_pods,
            "pod_size": c_chips, "rows": n, "record_bytes": rec_bytes,
            "cases": cases, "ok": ok}


# ---------------------------------------------------------------------------
# parent

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true",
                    help="size 8 only, small rows (the ci.sh gate)")
    ap.add_argument("--out", default=os.path.join(
        REPO, "MULTICHIP_SCALE_r15.json"))
    ap.add_argument("--sizes", default=None,
                    help=f"';'-separated mesh specs "
                         f"(default {DEFAULT_SIZES})")
    ap.add_argument("--rows-per-device", type=int, default=None)
    ap.add_argument("--per-size-timeout", type=float, default=1800)
    ap.add_argument("--child", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args()

    if args.child:
        rows = args.rows_per_device or (32 if args.quick else 128)
        report = run_child(args.child, rows, args.quick)
        print("ACCT " + json.dumps(report))
        return 0 if report["ok"] else 1

    sizes = (args.sizes or
             ("dcn:2,ici:4" if args.quick else DEFAULT_SIZES)).split(";")
    rows = args.rows_per_device or (32 if args.quick else 128)
    runs = []
    ok = True
    for spec in sizes:
        _, dims = _parse_spec(spec)
        ndev = dims[0] * dims[1]
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   XLA_FLAGS=f"--xla_force_host_platform_device_count="
                             f"{ndev}")
        t0 = time.perf_counter()
        cmd = [sys.executable, os.path.abspath(__file__),
               "--child", spec, "--rows-per-device", str(rows)]
        if args.quick:
            cmd.append("--quick")
        try:
            proc = subprocess.run(cmd, capture_output=True, text=True,
                                  timeout=args.per_size_timeout, env=env,
                                  cwd=REPO)
            rc, stdout, stderr = proc.returncode, proc.stdout, proc.stderr
        except subprocess.TimeoutExpired as e:
            rc = -9
            stdout = (e.stdout or b"").decode("utf-8", "replace") \
                if isinstance(e.stdout, bytes) else (e.stdout or "")
            stderr = f"TIMEOUT after {e.timeout:.0f}s"
        dt = time.perf_counter() - t0
        acct = None
        for line in stdout.splitlines():
            if line.startswith("ACCT "):
                acct = json.loads(line[5:])
        good = rc == 0 and acct is not None and acct.get("ok", False)
        runs.append({"mesh": spec, "devices": ndev, "ok": good,
                     "wall_s": round(dt, 1), "report": acct,
                     "tail": [] if good else
                     (stderr or stdout).strip().splitlines()[-8:]})
        ok = ok and good
        print(f"[{spec}] {'ok' if good else 'FAIL'} in {dt:.0f}s")
        if acct:
            for case in acct["cases"]:
                f, h = case["flat"], case["hierarchical"]
                c = case.get("coded", {})
                print(f"  {case['workload']:>9}: DCN msgs/round "
                      f"{f['dcn_messages_per_round_max']} -> "
                      f"{h['dcn_messages_per_round_max']} "
                      f"(pod-pair bound {case['pod_pair_bound']}), "
                      f"DCN bytes {f['dcn_bytes']} -> {h['dcn_bytes']} "
                      f"-> coded {c.get('dcn_bytes', 0)} "
                      f"(saved {c.get('dcn_saved_bytes', 0)}), "
                      f"wall {f['wall_s']}s -> {h['wall_s']}s -> "
                      f"{c.get('wall_s', 0)}s, checks "
                      f"{'PASS' if all(case['checks'].values()) else case['checks']}")

    report = {"bench": "exchange_modes", "round": "r15",
              "quick": args.quick, "rows_per_device": rows,
              "runs": runs, "ok": ok}
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1)
        f.write("\n")
    print(f"wrote {args.out} ok={ok}")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
