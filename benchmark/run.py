#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

A new process that sets up (generates the cell's inputs from the seed,
warms up every program the window will use), measures for ``--seconds``
and prints, as the last line of its standard output, one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` and, with
``--trace 1``, ``breakdown``. With ``--trace 0`` the metrics are the
cell's end-to-end metrics, taken with every tracer off; with
``--trace 1`` its per-layer metrics, each read by the reader its file
under ``layer_metrics/`` names. Which driver runs, at what sizes, is
data: ``BENCHMARK.json``, ``configs/``, ``traffic/``.

Without a TPU (or with fewer chips than the cell asks for) the run exits
non-zero and prints no result. ``--rehearse-cpu`` runs the same plumbing
on the CPU at the traffic file's rehearsal sizes with Pallas interpreted,
stamps ``"platform": "cpu"`` and withholds every metric: it checks the
harness, never the chip.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()   # set-up is everything before the window

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.harness.loop import SetupError  # noqa: E402
from benchmark.harness.manifest import Manifest, ManifestError  # noqa: E402


def read_layer_metrics(manifest: Manifest, cell: str, obs: dict) -> dict:
    """Every per-layer metric of the cell through its reader. A reader
    that finds nothing to read returns None and the metric is left out."""
    values = {}
    for m in manifest.metrics_of(cell, "per_layer"):
        spec = manifest.layer_metric_file(m["name"])
        reader = importlib.import_module(f"benchmark.readers.{spec['reader']}")
        value = reader.read(spec, obs)
        if value is not None:
            values[m["name"]] = value
    return values


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny CPU run of the plumbing; measures nothing")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "uda_tpu")):
        print("benchmark: no uda_tpu checkout beside the benchmark: there "
              "is no system to measure", file=sys.stderr)
        return 2
    try:
        manifest = Manifest(ROOT)
        cell = manifest.cell(args.workload)
        config = manifest.config_file(cell["config"])
        traffic = manifest.traffic_file(cell["traffic"])
    except ManifestError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    if args.rehearse_cpu:
        # before anything imports jax; one virtual device per chip
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={cell['chips']}")
    driver = importlib.import_module(f"benchmark.drivers.{config['driver']}")
    work_dir = tempfile.mkdtemp(prefix="uda_benchmark_")
    ctx = types.SimpleNamespace(
        root=ROOT, cell=cell, config=config,
        traffic=traffic, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), rehearse=args.rehearse_cpu,
        t_start=T_START, work_dir=work_dir)
    try:
        out = driver.run(ctx)
    except SetupError as e:
        print(f"benchmark: set-up failed: {e}", file=sys.stderr)
        return 1
    finally:
        shutil.rmtree(work_dir, ignore_errors=True)

    group = "per_layer" if args.trace else "end_to_end"
    if args.trace:
        values = read_layer_metrics(manifest, cell["name"], out["obs"])
    else:
        values = out["end_to_end"]
    wanted = manifest.metrics_of(cell["name"], group)
    metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
               for m in wanted if m["name"] in values}
    print("benchmark: " + json.dumps({
        "cell": cell["name"], "seed": args.seed, "trace": args.trace,
        "samples": out["attempted"] - out["failed"],
        "walls": out["walls"][:50], "setup_phases": out["setup_phases"],
        "cache": out["cache"],
        "critical_of_first": out["obs"].get("critical", [None])[:1],
        "errors": out["errors"][:5], "values": values}), file=sys.stderr)
    if args.rehearse_cpu:
        print("benchmark: rehearsal " + json.dumps(
            {"withheld": sorted(metrics)}), file=sys.stderr)
        metrics = {}
    elif not args.trace and set(metrics) != {m["name"] for m in wanted}:
        print(f"benchmark: no unit finished; metrics {sorted(metrics)}",
              file=sys.stderr)
        return 1
    line = {"correct": out["failed"] == 0 and out["attempted"] > 0,
            "attempted": out["attempted"], "failed": out["failed"],
            "metrics": metrics, "device": out["device"]}
    if args.trace and "breakdown" in out:
        line["breakdown"] = out["breakdown"]
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
