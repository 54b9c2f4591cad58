"""Multichip scaling evidence: run the full dryrun at 8/16/32/64 virtual
devices (each in a FRESH interpreter — the device count locks at
backend init) and write the aggregated exchange-round/byte accounting
plus the v5p-64 ICI roofline extrapolation to MULTICHIP_SCALE_r{N}.json.

Usage: python scripts/multichip_scale.py [--out FILE] [--sizes 8,16,32,64]
       [--per-size-timeout S]   # 64 devices compiles for a while on 1 core
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

CHILD = r"""
import json, sys
sys.path.insert(0, {repo!r})
import __graft_entry__ as g
acct = g.dryrun_multichip({n})
print("ACCT " + json.dumps(acct))
"""

# v5p public specs for the roofline (cloud.google.com/tpu/docs/v5p):
# 4,800 Gbps inter-chip interconnect per chip = 600 GBYTES/s aggregate
# across links; the all-to-all egress-bound lower bound per chip is
# bytes_out / ICI_BW.
V5P_ICI_GBYTES_PER_S_PER_CHIP = 600.0
TERASORT_1TB_BYTES = 1e12
V5P64_CHIPS = 64


def roofline() -> dict:
    """Analytic lower bound for BASELINE config 5 (TeraSort-1TB on
    v5p-64): per-chip egress = (1 TB / 64) x (63/64) riding ICI."""
    per_chip_out = TERASORT_1TB_BYTES / V5P64_CHIPS * (
        (V5P64_CHIPS - 1) / V5P64_CHIPS)
    t_exchange = per_chip_out / (V5P_ICI_GBYTES_PER_S_PER_CHIP * 1e9)
    return {
        "target": "TeraSort-1TB on v5p-64 (BASELINE config 5)",
        "ici_gbytes_per_s_per_chip": V5P_ICI_GBYTES_PER_S_PER_CHIP,
        "ici_gbps_spec": 4800,
        "per_chip_egress_bytes": per_chip_out,
        "exchange_lower_bound_s": t_exchange,
        "note": "all-to-all egress bound only; local sort + HBM "
                "traffic add on top — see PARITY.md roofline section",
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=os.path.join(
        REPO, "MULTICHIP_SCALE_r05.json"))
    ap.add_argument("--sizes", default="8,16,32,64")
    ap.add_argument("--per-size-timeout", type=float, default=3600)
    args = ap.parse_args()
    sizes = [int(s) for s in args.sizes.split(",")]

    runs = []
    ok = True
    for n in sizes:
        t0 = time.perf_counter()
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   XLA_FLAGS=f"--xla_force_host_platform_device_count={n}")
        try:
            proc = subprocess.run(
                [sys.executable, "-c", CHILD.format(repo=REPO, n=n)],
                capture_output=True, text=True, timeout=args.per_size_timeout, env=env,
                cwd=REPO)
            rc, stdout, stderr = proc.returncode, proc.stdout, proc.stderr
        except subprocess.TimeoutExpired as e:
            # one hung size must not discard the completed runs
            rc = -9
            stdout = (e.stdout or b"").decode("utf-8", "replace") \
                if isinstance(e.stdout, bytes) else (e.stdout or "")
            stderr = f"TIMEOUT after {e.timeout:.0f}s"
        dt = time.perf_counter() - t0
        acct = None
        for line in stdout.splitlines():
            if line.startswith("ACCT "):
                acct = json.loads(line[5:])
        runs.append({"devices": n, "ok": rc == 0 and acct is not None,
                     "wall_s": round(dt, 1), "accounting": acct,
                     "tail": stdout.strip().splitlines()[-1:]
                     if rc == 0 else
                     (stderr or stdout).strip().splitlines()[-8:]})
        ok = ok and runs[-1]["ok"]
        print(f"[{n} devices] {'ok' if runs[-1]['ok'] else 'FAIL'} "
              f"in {dt:.0f}s")

    report = {"runs": runs, "roofline_v5p64": roofline(), "ok": ok}
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1)
    print(f"wrote {args.out} ok={ok}")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
