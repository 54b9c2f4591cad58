#!/usr/bin/env python3
"""Measure a cell as the driver does: sets of runs of ``run.py``, each
run with another seed and all in this one call, then for each
end-to-end metric the median and the spread (the distance between the
quartiles over the median) of every set.

    python benchmark/tools/spread.py --workload <cell> [--runs 6] [--sets 2]
        [--seed0 100] [--seconds <run_seconds>] [--trace-run] [--out <file>]

``--trace-run`` appends one ``--trace 1`` run. Prints one JSON object;
``--out`` also writes it to a file (under ``chiprun_out/`` to bring it
back from the chip). Never touches JAX itself: each run is a process
of its own and holds the chip alone.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def one_run(cell: str, seed: int, seconds: int, trace: int) -> dict:
    t0 = time.time()
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", cell, "--seed", str(seed), "--seconds", str(seconds),
         "--trace", str(trace)], capture_output=True, text=True, cwd=ROOT)
    lines = proc.stdout.strip().splitlines()
    run = {"seed": seed, "trace": trace, "rc": proc.returncode,
           "process_s": time.time() - t0,
           "line": json.loads(lines[-1]) if proc.returncode == 0 and lines
           else None,
           "notes": [ln for ln in proc.stderr.splitlines()
                     if ln.startswith("benchmark:")][-3:]}
    if run["line"] is None:
        run["stderr_end"] = proc.stderr[-3000:]
    return run


def spread(values: list) -> dict:
    q = statistics.quantiles(values, n=4, method="inclusive")
    med = statistics.median(values)
    return {"median": med, "q1": q[0], "q3": q[2],
            "spread": (q[2] - q[0]) / med if med else None, "n": len(values)}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--seed0", type=int, default=100)
    ap.add_argument("--seconds", type=int)
    ap.add_argument("--trace-run", action="store_true")
    ap.add_argument("--out")
    args = ap.parse_args()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        seconds = args.seconds or json.load(f)["run_seconds"]
    report = {"workload": args.workload, "seconds": seconds, "sets": []}
    seed = args.seed0
    for _ in range(args.sets):
        runs = []
        for _ in range(args.runs):
            runs.append(one_run(args.workload, seed, seconds, 0))
            print(f"spread: {json.dumps(runs[-1])[:600]}", file=sys.stderr)
            seed += 1
        good = [r["line"] for r in runs if r["line"] and r["line"]["correct"]]
        names = sorted({n for ln in good for n in ln["metrics"]})
        report["sets"].append({
            "runs": runs, "good": len(good),
            "metrics": {n: spread([ln["metrics"][n]["value"] for ln in good])
                        for n in names if len(good) >= 2}})
    if args.trace_run:
        report["traced"] = one_run(args.workload, seed, seconds, 1)
    text = json.dumps(report, indent=1)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            f.write(text)
    summary = {"workload": args.workload,
               "sets": [s["metrics"] for s in report["sets"]]}
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
