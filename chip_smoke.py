#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that uda_tpu still starts on the chip.

Drives the main path once, through the entry points an embedder calls,
and exits 0 only if all of it happened on the TPU:

- set-up: ``make -C uda_tpu/native`` from committed sources (the served
  path must not quietly run its pure-Python codec);
- Phase A, the served path on one chip: 64 per-map-sorted TeraSort map
  outputs (100-byte records, 10-byte uniform keys, from ``--seed``)
  forming one 10,500,000-record reduce partition (~1.05 GB, the >=1 GB
  rung of the reference's regression) are served by a MOFSupplier-role
  ``UdaBridge`` over loopback to a NetMerger-role ``UdaBridge`` that
  takes reference-layout INIT/FETCH/FINAL commands, every flag at its
  default. The framed ``data_from_uda`` stream is compared byte for
  byte with a plain host sort of the same records. Run twice — cold,
  then warm from the same persistent compile cache;
- Phase B, every device kernel the tree can select, compiled (never
  interpreted) and checked against ``np.lexsort``;
- Phase C, ``distributed_terasort`` with its defaults over four chips
  on the ``ici:4`` and ``dcn:2,ici:2`` meshes, when the machine has
  four, and the lanes engine by name; then, on ``ici:4``, the same
  entry with ``splitters="sampled"`` on Zipf id keys that all share
  their first word (records back, every one, in one total order). On
  ``dcn:2,ici:2`` the step is the hierarchical round body, whose staged
  buffers compile to 9,664 MB a chip at this size (2^22 records a chip)
  where the flat step on ``ici:4`` compiles to 3,557 MB
  (``memory_analysis()`` for a described v5e:2x2, PR 38): the benchmark
  cell ``exchange_dcn2_ici2`` times it.

The parent process never imports JAX: a chip belongs to one process at
a time, so the phases run as sequential children that share one compile
cache directory (``uda_tpu.utils.compile_cache`` decides where).

Without a TPU the script exits non-zero and names the platform JAX
found. ``--rehearse-cpu`` is the only way to run it on a CPU: tiny
sizes, Pallas interpreted, ``"platform": "cpu"`` stamped on everything
and no device observation printed — it checks the plumbing (tier-1
runs it), never the chip.

Output: on success one ``{"smoke": ...}`` line with every phase's
observations (smoke observations of ONE run — not benchmark metrics),
then as the last line ``{"ok": true, "device": {...}}``. On failure the
observations go to stderr and nothing is printed as a result. A run
that found the chip also writes them to ``chiprun_out/chip_smoke.json``.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
DEADLINE_S = 1150       # the contract allows 1200 s, compilation included
NO_ACCELERATOR = 3      # child exit code: JAX did not find the platform
JOB = "smoke"
JOB_TEXT = "smoketext"

# what each phase runs at: the real sizes, and the CPU rehearsal's. The
# two fixed configurations — a cut some limit forces is made HERE and
# reported as one
SIZES = {
    "chip": {"records": 10_500_000, "maps": 64, "sort_log2": 23,
             "engine_log2": 20, "per_chip_log2": 22,
             "text_records": 4_000, "text_maps": 8},
    "rehearsal": {"records": 2_000, "maps": 4, "sort_log2": 12,
                  "engine_log2": 11, "per_chip_log2": 10,
                  "text_records": 4_000, "text_maps": 8},
}
MESHES = ("ici:4", "dcn:2,ici:2")


class SmokeFailure(Exception):
    """A phase observed something other than a correct on-device run."""


# -- shared child helpers ----------------------------------------------------

def _platform_gate(rehearse: bool) -> dict:
    """Initialize the backend and refuse the wrong one. Returns the
    device stamp (the rehearsal's names the CPU and nothing else)."""
    import jax

    platform = jax.default_backend()
    want = "cpu" if rehearse else "tpu"
    if platform != want:
        print(f"chip_smoke: JAX platform is {platform!r}, need {want!r}"
              + ("" if rehearse else " (--rehearse-cpu runs the CPU "
                 "rehearsal)"), file=sys.stderr)
        sys.exit(NO_ACCELERATOR)
    if rehearse:
        return {"platform": "cpu"}
    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def _cache_counts() -> dict:
    """Count this process's persistent-compile-cache traffic: compile
    requests that consulted the cache, hits, and misses (a miss is a
    program that had to be compiled here)."""
    import jax

    names = {"/jax/compilation_cache/compile_requests_use_cache": "requests",
             "/jax/compilation_cache/cache_hits": "hits",
             "/jax/compilation_cache/cache_misses": "misses"}
    counts = dict.fromkeys(names.values(), 0)

    def on_event(event: str, **_kw) -> None:
        if event in names:
            counts[names[event]] += 1

    jax.monitoring.register_event_listener(on_event)
    return counts


def _attempt(verdicts: dict, name: str, fn) -> None:
    """Run one kernel or mesh check to a verdict: the boundary that must
    keep running so every check reports — ok, or the compiler's (or the
    comparison's) own message — before the phase decides."""
    t0 = time.perf_counter()
    try:
        fn()
        verdicts[name] = {"ok": True}
    except Exception as e:  # noqa: BLE001
        verdicts[name] = {"ok": False,
                          "error": f"{type(e).__name__}: {e}"[:800]}
    verdicts[name]["seconds"] = round(time.perf_counter() - t0, 2)
    print(f"chip_smoke: {name}: {verdicts[name]}", file=sys.stderr)


def _regression_module():
    """scripts/regression/run_regression.py, for its vectorized MOF
    generator (reused, not copied)."""
    import importlib.util

    path = os.path.join(HERE, "scripts", "regression", "run_regression.py")
    spec = importlib.util.spec_from_file_location("run_regression", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# -- Phase A: the served path ------------------------------------------------

def _ensure_mofs(root: str, sizes: dict, seed: int) -> list:
    """Write the map output files once per smoke (the warm child reuses
    the cold child's). ``records`` split over ``maps`` as evenly as
    whole records allow, larger maps first."""
    maps = sizes["maps"]
    map_ids = [f"attempt_{JOB}_m_{m:06d}_0" for m in range(maps)]
    done = os.path.join(root, ".done")
    if not os.path.exists(done):
        gen = _regression_module()._make_terasort_mofs
        base, extra = divmod(sizes["records"], maps)
        if extra:
            gen(root, JOB, extra, base + 1, seed=seed, first_map=0)
        gen(root, JOB, maps - extra, base, seed=seed, first_map=extra)
        open(done, "w").close()
    return map_ids


def _host_reference(root: str, map_ids: list):
    """The plain reference: the same records, stably sorted on the host
    under the bytewise comparator, in IFile framing (without the EOF
    marker). A TeraSort record frames as VInt(10) VInt(90) key value =
    102 bytes with one-byte VInts, so the files parse with a reshape —
    nothing of the engine's own codec is involved."""
    import numpy as np

    recs = []
    for mid in map_ids:
        raw = np.fromfile(os.path.join(root, JOB, mid, "file.out"), np.uint8)
        if raw[-2:].tobytes() != b"\xff\xff" or (raw.size - 2) % 102:
            raise SmokeFailure(f"map output {mid} is not 102-byte frames")
        recs.append(raw[:-2].reshape(-1, 102))
    recs = np.concatenate(recs)              # arrival order: (map, row)
    if not ((recs[:, 0] == 10).all() and (recs[:, 1] == 90).all()):
        raise SmokeFailure("map outputs are not 10/90-byte records")
    hi = np.ascontiguousarray(recs[:, 2:10]).view(">u8").ravel()
    lo = np.ascontiguousarray(recs[:, 10:12]).view(">u2").ravel()
    # np.lexsort: last key primary, stable — equal keys keep arrival order
    return recs[np.lexsort((lo, hi))].ravel()


class _SupplierCallable:
    """The MOFSupplier embedder: net knobs through the conf pull channel
    (as a jobconf would carry them), index resolution by up-call."""

    def __init__(self, root: str):
        self.root = root
        self.failure = None

    def get_conf_data(self, name, default):
        return {"uda.tpu.net.listen": "true", "uda.tpu.net.port": "0",
                "uda.tpu.net.bind": "127.0.0.1"}.get(name, "")

    def get_path_uda(self, job_id, map_id, reduce_id):
        from uda_tpu.mofserver import read_index_file

        d = os.path.join(self.root, job_id, map_id)
        return read_index_file(os.path.join(d, "file.out.index"),
                               os.path.join(d, "file.out"))[reduce_id]

    def failure_in_uda(self, error):
        self.failure = error


class _ReducerCallable:
    """The NetMerger embedder: collects the framed stream and records
    the root cause the fallback contract reports instead of acting on
    it — a fallback is a smoke failure."""

    def __init__(self, port: int):
        self.port = port
        self.out = bytearray()
        self.failure = None

    def get_conf_data(self, name, default):
        return {"uda.tpu.net.fetch": "true",
                "uda.tpu.net.port": str(self.port)}.get(name, "")

    def data_from_uda(self, data, length):
        self.out += data[:length]

    def failure_in_uda(self, error):
        self.failure = error


def _staged_bytes_per_record(cols: int) -> float:
    """What one record of a staged run costs in HBM: the allocator's
    growth for a ``uint32[n, cols]`` array, per row."""
    import jax
    import numpy as np

    dev, n = jax.devices()[0], 1 << 18
    before = dev.memory_stats()["bytes_in_use"]
    run = jax.block_until_ready(jax.device_put(np.zeros((n, cols),
                                                        np.uint32)))
    grown = dev.memory_stats()["bytes_in_use"] - before
    del run
    return grown / n


def phase_a(args, sizes: dict) -> dict:
    device = _platform_gate(args.rehearse_cpu)
    cache = _cache_counts()
    import jax
    import numpy as np

    from uda_tpu import native
    from uda_tpu.bridge import UdaBridge
    from uda_tpu.bridge.protocol import Cmd, form_cmd
    from uda_tpu.ops import merge as merge_ops
    from uda_tpu.utils.budget import device_bytes_estimate
    from uda_tpu.utils.config import Config
    from uda_tpu.utils.metrics import metrics

    if not native.available():
        raise SmokeFailure("the native library did not load")
    root = os.path.join(args.work_dir, "mofs")
    t0 = time.perf_counter()
    map_ids = _ensure_mofs(root, sizes, args.seed)
    setup_s = time.perf_counter() - t0
    records, maps = sizes["records"], sizes["maps"]

    sup_cb = _SupplierCallable(root)
    supplier = UdaBridge()
    supplier.start(False, [], sup_cb)
    supplier.do_command(form_cmd(Cmd.INIT, []))
    if supplier.failed or supplier.net_server() is None:
        raise SmokeFailure(f"supplier did not start: {sup_cb.failure!r}")

    def reduce_task(job: str, ids: list, key_class: str) -> tuple:
        """One NetMerger task against the supplier: ``(callable, merge
        manager, wall)``. A failure_in_uda is a smoke failure."""
        red_cb = _ReducerCallable(supplier.net_server().port)
        reducer = UdaBridge()
        t0 = time.perf_counter()
        reducer.start(True, [], red_cb)
        try:
            # reference-layout INIT (reducer.cc:56-133): num_maps, job,
            # reduce id, lpq size, buffer B, min buffer B, key class,
            # codec, codec block, shuffle memory B — the defaults' own
            # values
            reducer.do_command(form_cmd(Cmd.INIT, [
                str(len(ids)), job, "0", "0", str(1 << 20), str(16 << 10),
                key_class, "0", "0", str(1 << 30)]))
            mm = reducer._mm   # outlives reduce_exit(), for the counters
            for mid in ids:
                reducer.do_command(form_cmd(Cmd.FETCH,
                                            ["127.0.0.1", job, mid, "0"]))
            reducer.do_command(form_cmd(Cmd.FINAL, []))
        finally:
            reducer.reduce_exit()  # joins the merge thread
            wall_s = time.perf_counter() - t0
        failure = red_cb.failure or sup_cb.failure
        if failure is not None or reducer.failed or supplier.failed:
            cause = "".join(traceback.format_exception(failure)) if failure \
                else "bridge went inert without reporting a cause"
            raise SmokeFailure(f"failure_in_uda — the bridge asked for the "
                               f"vanilla fallback. Root cause:\n{cause}")
        return red_cb, mm, wall_s

    metrics.enable_spans()     # counts the merge.device_put spans below
    try:
        red_cb, mm, wall_s = reduce_task(JOB, map_ids, "uda.tpu.RawBytes")
        om = mm._active_overlap
        engine = merge_ops.resolve_run_engine("auto")
        obs = {
            "device": device, "records": records, "maps": maps,
            "partition_bytes": records * 100, "setup_s": round(setup_s, 3),
            "wall_s": round(wall_s, 3), "run_engine": engine,
            "interpret": om.interpret,
            "forest_merges": om.stats["device_merges"],
            "device_put_spans": sum(s["name"] == "merge.device_put"
                                    for s in metrics.spans),
            "merge_records": int(metrics.get("merge.records")),
            "budget_rerouted": int(metrics.get("budget.rerouted")),
            "fallback_signals": int(metrics.get("fallback.signals")),
            "fetch_retries": int(metrics.get("fetch.retries")),
            "emitted_bytes": len(red_cb.out), "compile_cache": cache,
        }
        want = {"merge_records": records, "budget_rerouted": 0,
                "fallback_signals": 0, "emitted_bytes": records * 102 + 2}
        if not args.rehearse_cpu:
            stats = jax.devices()[0].memory_stats()
            key_width = int(Config().get("uda.tpu.key.width"))
            obs["hbm"] = {
                "peak_bytes_in_use": stats["peak_bytes_in_use"],
                "bytes_limit": stats["bytes_limit"],
                "device_bytes_estimate": device_bytes_estimate(records * 100,
                                                               key_width),
                "staged_bytes_per_record": _staged_bytes_per_record(
                    key_width // 4 + merge_ops.ROW_EXTRA_COLS)}
            want.update(run_engine="pallas", interpret=False,
                        device_put_spans=maps)
            if om.stats["device_merges"] <= 0:
                raise SmokeFailure(f"no forest merge ran on the device: {obs}")
        wrong = {k: (obs[k], v) for k, v in want.items() if obs[k] != v}
        if wrong:
            raise SmokeFailure(f"observed != expected: {wrong}; all: {obs}")

        # correctness, outside every timed region: byte for byte
        got = np.frombuffer(red_cb.out, np.uint8)
        ref = _host_reference(root, map_ids)
        if got[-2:].tobytes() != b"\xff\xff":
            raise SmokeFailure("stream does not end in the IFile EOF marker")
        if not np.array_equal(got[:-2], ref):
            bad = int(np.flatnonzero(got[:-2] != ref)[0])
            raise SmokeFailure(f"stream differs from the host reference at "
                               f"byte {bad} (record {bad // 102})")
        obs["byte_identical"] = True
        obs["text_task"] = _text_task(args, sizes, root, reduce_task)
    finally:
        supplier.do_command(form_cmd(Cmd.EXIT, []))
    return obs


def _text_task(args, sizes: dict, root: str, reduce_task) -> dict:
    """A few thousand ``<word, posting>`` records under the comparator
    the benchmark cell ``reduce_invindex`` uses
    (``org.apache.hadoop.io.Text``), some words longer than the carried
    width: the task stays on the run forest — no overflow fallback —
    with any equal-prefix block of such words re-ordered at emit
    (``oversize_blocks``: few at this size, none for some seeds), and
    its stream is the benchmark's plain Text reference's, byte for byte."""
    import numpy as np

    from benchmark.gen import invindex_mofs
    from benchmark.reference import host_sort_text
    from uda_tpu.utils.metrics import metrics

    part = invindex_mofs.generate(root, JOB_TEXT, args.seed,
                                  sizes["text_records"], sizes["text_maps"])
    counted = ("merge.overflow.fallbacks", "merge.overflow.keys",
               "merge.oversize.blocks")
    before = [metrics.get(k) for k in counted]
    red_cb, _, wall_s = reduce_task(JOB_TEXT, part.map_ids,
                                    "org.apache.hadoop.io.Text")
    fallbacks, oversize, blocks = (int(metrics.get(k) - b)
                                   for k, b in zip(counted, before))
    obs = {"records": part.records, "maps": len(part.map_ids),
           "wall_s": round(wall_s, 3), "emitted_bytes": len(red_cb.out),
           "overflow_fallbacks": fallbacks, "oversize_keys": oversize,
           "oversize_blocks": blocks}
    if oversize < 1:
        raise SmokeFailure(f"the Text task met no key longer than the "
                           f"carried width: {obs}")
    if fallbacks:
        raise SmokeFailure(f"the Text task left the run forest: {obs}")
    wrong = host_sort_text.compare(
        np.frombuffer(red_cb.out, np.uint8),
        host_sort_text.sorted_stream(root, JOB_TEXT, part.map_ids))
    if wrong:
        raise SmokeFailure(f"Text task: stream differs from the host "
                           f"reference: {wrong}")
    obs["byte_identical"] = True
    return obs


# -- Phase B: the device sort engines and the merge kernel -------------------

def phase_b(args, sizes: dict) -> dict:
    device = _platform_gate(args.rehearse_cpu)
    import jax
    import numpy as np

    from uda_tpu.models import terasort
    from uda_tpu.ops import sort as sort_ops
    from uda_tpu.ops.pallas_merge import merge_sorted_pair
    from uda_tpu.utils import compile_cache

    compile_cache.enable()
    interpret = args.rehearse_cpu
    verdicts: dict = {}

    def lexsorted(rows, ncols):
        return rows[np.lexsort(tuple(rows[:, c]
                                     for c in range(ncols - 1, -1, -1)))]

    big = 1 << sizes["sort_log2"]
    auto_engine = sort_ops.resolve_sort_path("auto")

    def auto_sort():
        words = terasort.teragen(jax.random.key(args.seed), big)
        out = terasort.single_chip_sort(words, interpret=interpret)
        terasort.validate_sorted(out, words)
        if not np.array_equal(np.asarray(out),
                              lexsorted(np.asarray(words),
                                        terasort.KEY_WORDS)):
            raise SmokeFailure("output differs from np.lexsort")

    _attempt(verdicts, f"sort:auto={auto_engine}@2^{sizes['sort_log2']}",
             auto_sort)

    n = 1 << sizes["engine_log2"]
    words = np.array(terasort.teragen(jax.random.key(args.seed + 1), n))
    k = terasort.KEY_WORDS
    words[:n // 8, :k] = words[n // 8:n // 4, :k]     # ties: stability
    want = lexsorted(words, terasort.KEY_WORDS)
    for engine in sort_ops.SORT_PATHS:
        if engine == "carry" and not interpret:
            # XLA's 26-operand variadic sort: no kernel of this repo, and
            # its compile for a v5e had not ended after 10 minutes (here,
            # for a described chip; PERF.md section 6, PR 28) where the
            # whole smoke has 1200 s. The rehearsal runs it.
            continue

        def run(engine=engine):
            got = np.asarray(terasort.single_chip_sort(
                words, path=engine, interpret=interpret))
            if not np.array_equal(got, want):
                raise SmokeFailure("output differs from np.lexsort")

        _attempt(verdicts, f"sort:{engine}", run)

    # the served path's merge shape: every column a key
    cols = 7
    rng = np.random.default_rng(args.seed)
    a, b = (lexsorted(rng.integers(0, 1 << 32, (n // 2, cols),
                                   dtype=np.uint32), cols) for _ in "ab")
    merged = lexsorted(np.concatenate([a, b]), cols)

    def run_merge():
        got = np.asarray(merge_sorted_pair(a, b, num_keys=cols,
                                           interpret=interpret))
        if not np.array_equal(got, merged):
            raise SmokeFailure("merge differs from np.lexsort")

    _attempt(verdicts, "merge_sorted_pair", run_merge)

    obs = {"device": device, "interpret": interpret,
           "engine_rows": n, "verdicts": verdicts}
    failed = [k for k, v in verdicts.items() if not v["ok"]]
    if failed:
        raise SmokeFailure(f"kernels failed: {failed}; "
                           f"all: {json.dumps(obs)}")
    return obs


# -- Phase C: four chips -----------------------------------------------------

def phase_c(args, sizes: dict) -> dict:
    device = _platform_gate(args.rehearse_cpu)
    import jax
    import numpy as np

    ndev = len(jax.devices())
    if ndev < 4:
        return {"device": device, "multichip": f"skipped, {ndev} device"}
    from uda_tpu.models import terasort
    from uda_tpu.ops.sort import resolve_sort_path
    from uda_tpu.parallel.distributed import (distributed_sort_step,
                                              uniform_splitters)
    from uda_tpu.parallel.mesh import mesh_from_config
    from uda_tpu.utils import compile_cache
    from uda_tpu.utils.config import Config

    compile_cache.enable()
    p = 4
    n = p << sizes["per_chip_log2"]
    rng = np.random.default_rng(args.seed)
    words = rng.integers(0, 1 << 32, (n, terasort.RECORD_WORDS),
                         dtype=np.uint32)
    words[:, terasort.KEY_WORDS - 1] &= np.uint32(0xFFFF0000)
    splitters = uniform_splitters(p)
    # shard d must hold exactly range partition d of the input
    dest = np.searchsorted(splitters, words[:, 0], side="right")

    def run(spec: str, engine: str) -> None:
        mesh = mesh_from_config(Config({"uda.tpu.mesh.shape": spec}))
        names = tuple(mesh.axis_names)
        axis = names[0] if len(names) == 1 else names
        if engine == "auto":
            # the entry point a user calls, every argument at its default
            res = terasort.distributed_terasort(words, mesh, axis)
        else:
            res = distributed_sort_step(
                words, splitters, mesh, axis,
                capacity=max(1, (2 * n) // (p * p)),
                num_keys=terasort.KEY_WORDS, payload_path=engine)
        res.check()
        holders = {s.device for s in res.words.addressable_shards}
        if len(holders) != p:
            raise SmokeFailure(f"result sits on {len(holders)} devices, "
                               f"not {p}")
        nvalid = np.asarray(res.valid_counts).reshape(-1)
        out = np.asarray(res.words).reshape(p, -1, terasort.RECORD_WORDS)
        del res                # free the shards before validating
        if int(nvalid.sum()) != n:
            raise SmokeFailure(f"{int(nvalid.sum())} of {n} records "
                               f"came back")
        for d in range(p):
            terasort.validate_sorted(out[d, :nvalid[d]], words[dest == d])

    def run_sampled(spec: str) -> None:
        """Keys of unknown distribution: Zipf ids (s = 1 over 2^20) as
        10-byte big-endian numbers, so every first key word is 0 and
        the uniform splitters above would send every record to one
        chip. The step samples its own splitters; the shards in order
        must be one sorted sequence holding every record."""
        ids = np.floor(2 ** (20 * rng.random(n))).astype(np.uint32)
        skewed = words.copy()
        skewed[:, 0] = 0
        skewed[:, 1] = ids >> 16
        skewed[:, 2] = (ids & 0xFFFF) << 16
        mesh = mesh_from_config(Config({"uda.tpu.mesh.shape": spec}))
        res = terasort.distributed_terasort(skewed, mesh, mesh.axis_names[0],
                                            splitters="sampled")
        res.check()
        nvalid = np.asarray(res.valid_counts).reshape(-1)
        out = np.asarray(res.words).reshape(p, -1, terasort.RECORD_WORDS)
        del res
        if int(nvalid.sum()) != n or int(nvalid.max()) > 0.31 * n:
            raise SmokeFailure(f"shards hold {nvalid.tolist()} of {n} "
                               f"records")
        terasort.validate_sorted(
            np.concatenate([out[d, :nvalid[d]] for d in range(p)]), skewed)

    # the default engine first — a default that cannot start fails the
    # smoke — then the lanes engine by name where the default is another
    # (on the CPU; on a TPU the step's default IS lanes)
    default_engine = resolve_sort_path("auto")
    engines = ("auto",) if default_engine == "lanes" else ("auto", "lanes")
    runs: dict = {}
    for engine in engines:
        for spec in MESHES:
            _attempt(runs, f"{spec}/{engine}",
                     lambda spec=spec, engine=engine: run(spec, engine))
    _attempt(runs, f"{MESHES[0]}/sampled-zipf",
             lambda: run_sampled(MESHES[0]))
    obs = {"device": device, "records_per_chip": n // p,
           "default_engine": default_engine, "runs": runs}
    if not args.rehearse_cpu:
        obs["peak_bytes_in_use"] = [
            d.memory_stats()["peak_bytes_in_use"] for d in jax.devices()[:p]]
        if not all(obs["peak_bytes_in_use"]):
            raise SmokeFailure(f"a device held no data: {json.dumps(obs)}")
    if not all(r["ok"] for r in runs.values()):
        raise SmokeFailure(f"distributed runs failed: {json.dumps(obs)}")
    return obs


PHASES = {"a": phase_a, "b": phase_b, "c": phase_c}


def run_child(args) -> int:
    sizes = SIZES["rehearsal" if args.rehearse_cpu else "chip"]
    try:
        obs = PHASES[args.child](args, sizes)
    except SmokeFailure as e:
        print(f"chip_smoke phase {args.child} FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps(obs))
    return 0


# -- parent: never touches JAX -----------------------------------------------

def _spawn(name: str, phase: str, args, work_dir: str, deadline: float):
    """Run one phase child to its end (or the deadline). Returns
    (exit code, observations or None)."""
    cmd = [sys.executable, os.path.abspath(__file__), "--child", phase,
           "--seed", str(args.seed), "--work-dir", work_dir]
    if args.rehearse_cpu:
        cmd.append("--rehearse-cpu")
    print(f"chip_smoke: phase {name} ...", file=sys.stderr)
    try:
        proc = subprocess.run(cmd, stdout=subprocess.PIPE, text=True,
                              timeout=max(1.0, deadline - time.monotonic()))
    except subprocess.TimeoutExpired:
        print(f"chip_smoke: phase {name} hit the {DEADLINE_S} s deadline",
              file=sys.stderr)
        return 124, None
    lines = proc.stdout.strip().splitlines()
    if proc.returncode != 0 or not lines:
        return proc.returncode or 1, None
    return 0, json.loads(lines[-1])


def run_parent(args) -> int:
    start = time.monotonic()
    deadline = start + DEADLINE_S
    native_dir = os.path.join(HERE, "uda_tpu", "native")
    if not os.path.isdir(native_dir):
        print("chip_smoke: no uda_tpu checkout next to this script",
              file=sys.stderr)
        return 2
    t0 = time.monotonic()
    if subprocess.run(["make", "-C", native_dir],
                      stdout=sys.stderr).returncode != 0:
        print("chip_smoke: native build failed", file=sys.stderr)
        return 2
    from uda_tpu.utils import compile_cache   # imports no jax

    cache_dir = compile_cache.cache_dir()
    mode = "rehearsal" if args.rehearse_cpu else "chip"
    report = {
        "mode": mode, "seed": args.seed, "sizes": SIZES[mode],
        "native_build_s": round(time.monotonic() - t0, 3),
        "compile_cache_dir": cache_dir,
        "compile_cache_entries_at_start": (
            len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0),
    }
    work_dir = tempfile.mkdtemp(prefix="uda_chip_smoke_")
    failed = []
    no_chip = False
    try:
        for name, phase in (("a_cold", "a"), ("a_warm", "a"),
                            ("b", "b"), ("c", "c")):
            if name == "a_warm" and failed:
                continue       # nothing was cached worth re-reading
            rc, obs = _spawn(name, phase, args, work_dir, deadline)
            report[name] = obs if rc == 0 else {"exit_code": rc}
            if rc != 0:
                failed.append(name)
            if rc == NO_ACCELERATOR:
                no_chip = True
                break          # nothing else can say anything
    finally:
        shutil.rmtree(work_dir, ignore_errors=True)
    report["wall_s"] = round(time.monotonic() - start, 1)
    if not args.rehearse_cpu and not no_chip:
        # what a chip said is kept; a CPU has nothing to add to it
        out_dir = os.path.join(HERE, "chiprun_out")
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(out_dir, "chip_smoke.json"), "w") as f:
            json.dump({"smoke": report, "failed": failed}, f, indent=1)
    if failed:
        print(f"chip_smoke: FAILED phases {failed}\n"
              f"{json.dumps(report, indent=1)}", file=sys.stderr)
        return 1
    print(json.dumps({"smoke": report}))
    print(json.dumps({"ok": True, "device": report["a_cold"]["device"]}))
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=17,
                    help="all data is generated from it")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny CPU rehearsal of the plumbing (Pallas "
                         "interpreted); says nothing about the chip")
    ap.add_argument("--child", choices=sorted(PHASES),
                    help="run one phase in this process (the parent "
                         "starts these)")
    ap.add_argument("--work-dir", help="child: where the map outputs live")
    args = ap.parse_args(argv)
    if args.rehearse_cpu:
        # before any child imports jax; four virtual devices for Phase C
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=4").strip()
    if args.child:
        if not args.work_dir:
            ap.error("--child needs --work-dir")
        return run_child(args)
    return run_parent(args)


if __name__ == "__main__":
    sys.exit(main())
