"""Hierarchical (multi-pod) exchange: the two-stage ICI/DCN round body
vs the flat single-stage path — byte-identity, pod accounting, the host
round planner, and failure semantics.

Everything here runs on the conftest 8-virtual-device CPU mesh, shaped
(dcn=2, ici=4) and (dcn=4, ici=2); the 4x4 and 8x8 shapes ride the slow
subprocess rung at the bottom (the device count locks at backend init,
so bigger meshes need fresh interpreters — scripts/exchange_bench.py is
the shared driver)."""

import os
import subprocess
import sys

import numpy as np
import pytest

import jax
from jax.sharding import Mesh

from uda_tpu.parallel import (distributed_sort_step, make_mesh,
                              mesh_from_config, mesh_topology,
                              plan_rounds, shuffle_exchange,
                              uniform_splitters)
from uda_tpu.utils.config import Config
from uda_tpu.utils.errors import ConfigError, TransportError
from uda_tpu.utils.failpoints import failpoints
from uda_tpu.utils.metrics import metrics

AXIS = "shuffle"
AXIS2 = ("dcn", AXIS)


def _mesh2(p=2, c=4, ici=AXIS):
    devs = np.asarray(jax.devices()[:p * c])
    return Mesh(devs.reshape(p, c), ("dcn", ici))


def _random_words(n, w, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 2**32, size=(n, w), dtype=np.uint32)


def _assert_rounds_identical(a, b):
    assert len(a) == len(b)
    for r, ((aw, ac), (bw, bc)) in enumerate(zip(a, b)):
        np.testing.assert_array_equal(np.asarray(ac), np.asarray(bc),
                                      err_msg=f"counts, round {r}")
        np.testing.assert_array_equal(np.asarray(aw), np.asarray(bw),
                                      err_msg=f"words, round {r}")


# -- topology descriptor -----------------------------------------------------

def test_mesh_topology_classification():
    mesh1 = make_mesh(8, AXIS)
    t1 = mesh_topology(mesh1, AXIS)
    assert not t1.hierarchical and t1.num_pods == 1 and t1.pod_size == 8
    mesh2 = _mesh2(2, 4)
    t2 = mesh_topology(mesh2, AXIS2)
    assert t2.hierarchical
    assert (t2.dcn_axis, t2.ici_axis) == ("dcn", AXIS)
    assert (t2.num_pods, t2.pod_size, t2.num_devices) == (2, 4, 8)
    assert t2.pod_of(5) == 1 and t2.chip_of(5) == 1
    assert list(t2.pod_members(1)) == [4, 5, 6, 7]
    # egress rotation: symmetric per pair, within the pod, spread
    for g in range(2):
        for g2 in range(2):
            e = t2.egress_chip(g, g2)
            assert 0 <= e < 4
            assert e == t2.egress_chip(g2, g)
    # untagged 2-axis tuples carry no pod semantics -> one flat group
    mesh_u = Mesh(np.asarray(jax.devices()[:8]).reshape(2, 4),
                  ("rows", "cols"))
    tu = mesh_topology(mesh_u, ("rows", "cols"))
    assert not tu.hierarchical and tu.pod_size == 8


def test_mesh_from_config_dcn_ici_spec():
    cfg = Config({"uda.tpu.mesh.shape": "dcn:2,ici:4"})
    mesh = mesh_from_config(cfg)
    assert tuple(mesh.axis_names) == ("dcn", "ici")
    topo = mesh_topology(mesh, ("dcn", "ici"))
    assert topo.hierarchical and topo.num_pods == 2 and topo.pod_size == 4


def test_exchange_mode_dispatch_errors():
    mesh1 = make_mesh(8, AXIS)
    words = _random_words(64, 2, seed=1)
    dest = (words[:, 0] % 8).astype(np.int32)
    with pytest.raises(ConfigError, match="hierarchical"):
        shuffle_exchange(words, dest, mesh1, AXIS, capacity=8,
                         mode="hierarchical")
    with pytest.raises(ConfigError, match="unknown exchange mode"):
        shuffle_exchange(words, dest, mesh1, AXIS, capacity=8,
                         mode="bogus")


# -- byte-identity vs the flat exchange --------------------------------------

@pytest.mark.parametrize("shape", [(2, 4), (4, 2)])
def test_hierarchical_matches_flat_uniform(shape):
    p, c = shape
    mesh = _mesh2(p, c)
    words = _random_words(8 * 32, 3, seed=2)
    words[: 64, 0] = words[64:128, 0]       # duplicate keys ride along
    dest = (words[:, 1] % 8).astype(np.int32)
    hier, lay = shuffle_exchange(words, dest, mesh, AXIS2, capacity=9)
    assert lay.hierarchical
    flat, layf = shuffle_exchange(words, dest, mesh, AXIS2, capacity=9,
                                  mode="flat")
    assert not layf.hierarchical
    _assert_rounds_identical(hier, flat)


def test_hierarchical_matches_flat_skew_multiround():
    # extreme skew: every record to device 0 -> multi-round backlog;
    # the staged body must drain it identically to the flat windows
    mesh = _mesh2(2, 4)
    words = _random_words(8 * 16, 2, seed=3)
    dest = np.zeros(8 * 16, np.int32)
    hier, _ = shuffle_exchange(words, dest, mesh, AXIS2, capacity=4)
    flat, _ = shuffle_exchange(words, dest, mesh, AXIS2, capacity=4,
                               mode="flat")
    assert len(hier) == 4                    # 16 per bucket / capacity 4
    _assert_rounds_identical(hier, flat)


def test_hierarchical_empty_pod_edge():
    # every record lands in pod 0: pod 1 receives NOTHING (its tiles
    # are all-zero) and sends everything — the empty-ingress edge
    mesh = _mesh2(2, 4)
    words = _random_words(8 * 24, 2, seed=4)
    dest = (words[:, 0] % 4).astype(np.int32)    # devices 0..3 = pod 0
    metrics.reset()
    hier, _ = shuffle_exchange(words, dest, mesh, AXIS2, capacity=24)
    msgs = metrics.get("exchange.dcn.messages")
    assert msgs == 1.0                       # only pod1 -> pod0 traffic
    assert metrics.get("exchange.dcn.messages", pod=1) == 1.0
    assert metrics.get("exchange.dcn.messages", pod=0) == 0.0
    flat, _ = shuffle_exchange(words, dest, mesh, AXIS2, capacity=24,
                               mode="flat")
    _assert_rounds_identical(hier, flat)


def test_intra_pod_traffic_has_zero_dcn():
    mesh = _mesh2(2, 4)
    n = 8 * 16
    words = _random_words(n, 2, seed=5)
    dest = np.zeros(n, np.int32)
    shard = n // 8
    for s in range(8):
        base = (s // 4) * 4                  # stay inside my own pod
        dest[s * shard:(s + 1) * shard] = \
            base + words[s * shard:(s + 1) * shard, 1] % 4
    metrics.reset()
    hier, _ = shuffle_exchange(words, dest, mesh, AXIS2, capacity=16)
    assert metrics.get("exchange.dcn.messages") == 0.0
    assert metrics.get("exchange.dcn.bytes") == 0.0
    assert metrics.get("exchange.ici.bytes") > 0.0
    flat, _ = shuffle_exchange(words, dest, mesh, AXIS2, capacity=16,
                               mode="flat")
    _assert_rounds_identical(hier, flat)


def test_dcn_accounting_pod_pair_coalescing():
    # the tentpole claim at test scale: same DCN bytes, messages drop
    # from cross-pod DEVICE pairs to POD pairs
    mesh = _mesh2(2, 4)
    words = _random_words(8 * 32, 3, seed=6)
    dest = (words[:, 1] % 8).astype(np.int32)
    metrics.reset()
    shuffle_exchange(words, dest, mesh, AXIS2, capacity=32)
    hier = {k: metrics.get(k) for k in
            ("exchange.dcn.bytes", "exchange.dcn.messages",
             "exchange.ici.bytes")}
    metrics.reset()
    shuffle_exchange(words, dest, mesh, AXIS2, capacity=32, mode="flat")
    flat = {k: metrics.get(k) for k in
            ("exchange.dcn.bytes", "exchange.dcn.messages",
             "exchange.ici.bytes")}
    assert hier["exchange.dcn.bytes"] == flat["exchange.dcn.bytes"] > 0
    assert hier["exchange.dcn.messages"] <= 2 * 1     # p*(p-1) pod pairs
    assert flat["exchange.dcn.messages"] > hier["exchange.dcn.messages"]
    # the coalescing price: staging hops add ICI traffic, bounded by 2x
    # the DCN rows
    assert hier["exchange.ici.bytes"] <= (flat["exchange.ici.bytes"]
                                          + 2 * flat["exchange.dcn.bytes"])


# -- host round planner ------------------------------------------------------

def test_empty_exchange_skips_round():
    mesh = _mesh2(2, 4)
    metrics.reset()
    results, _ = shuffle_exchange(np.zeros((0, 3), np.uint32),
                                  np.zeros(0, np.int32), mesh, AXIS2,
                                  capacity=4)
    assert results == []
    assert metrics.get("exchange.rounds") == 0.0
    assert metrics.get("exchange.rounds.skipped") == 1.0


def test_plan_rounds_accounting():
    mesh = _mesh2(2, 4)
    topo = mesh_topology(mesh, AXIS2)
    counts = np.zeros((8, 8), np.int64)
    counts[0, 5] = 5          # pod 0 -> pod 1, needs 3 windows at cap 2
    counts[1, 6] = 1          # pod 0 -> pod 1 (same pod pair)
    counts[4, 4] = 2          # self-delivery: no wire traffic
    counts[2, 3] = 4          # intra-pod 0
    plan = plan_rounds(counts, 2, topo, record_bytes=8,
                       hierarchical=True)
    assert plan.planned == 3 and plan.skipped == 0
    w0 = plan.windows[0]
    # window 0: 2+1 cross rows in ONE pod-pair message, 2 intra rows
    assert w0.dcn_rows == 3 and w0.dcn_messages == 1
    assert w0.per_pod == ((0, 3, 1),)
    assert w0.moved_rows == 2 + 1 + 2 + 2
    flat_plan = plan_rounds(counts, 2, topo, record_bytes=8,
                            hierarchical=False)
    # flat: each cross-pod device pair is its own DCN message
    assert flat_plan.windows[0].dcn_messages == 2
    assert flat_plan.windows[0].dcn_rows == 3
    # identical per-window DCN rows either way (coalescing moves the
    # same bytes in fewer messages)
    for wh, wf in zip(plan.windows, flat_plan.windows):
        assert wh.dcn_rows == wf.dcn_rows
    # all-empty counts: one planned window, skipped
    empty = plan_rounds(np.zeros((8, 8), np.int64), 2, topo,
                        record_bytes=8, hierarchical=True)
    assert empty.planned == 1 and empty.skipped == 1
    assert empty.windows == ()


# -- distributed step dispatch ----------------------------------------------

def test_fused_step_hier_matches_flat_mesh():
    mesh1 = make_mesh(8, AXIS)
    mesh2 = _mesh2(2, 4)
    words = _random_words(1024, 4, seed=7)
    spl = uniform_splitters(8)
    r1 = distributed_sort_step(words, spl, mesh1, AXIS, capacity=256,
                               num_keys=2)
    r1.check()
    r2 = distributed_sort_step(words, spl, mesh2, AXIS2, capacity=256,
                               num_keys=2)
    r2.check()
    np.testing.assert_array_equal(np.asarray(r1.words),
                                  np.asarray(r2.words))
    np.testing.assert_array_equal(np.asarray(r1.valid_counts),
                                  np.asarray(r2.valid_counts))
    # forced-flat on the same 2-axis mesh: also identical
    r3 = distributed_sort_step(words, spl, mesh2, AXIS2, capacity=256,
                               num_keys=2, exchange_mode="flat")
    r3.check()
    np.testing.assert_array_equal(np.asarray(r2.words),
                                  np.asarray(r3.words))


def test_multiround_scatter_on_staged_body():
    # skew far past the credit window: the multiround accumulator path
    # must produce identical shards through the two-stage body
    mesh1 = make_mesh(8, AXIS)
    mesh2 = _mesh2(2, 4)
    words = _random_words(512, 3, seed=8)
    words[:, 0] = 0                          # all records to device 0
    spl = uniform_splitters(8)
    a = distributed_sort_step(words, spl, mesh2, AXIS2, capacity=16,
                              num_keys=1, multiround="always")
    b = distributed_sort_step(words, spl, mesh1, AXIS, capacity=16,
                              num_keys=1, multiround="always")
    a.check()
    b.check()
    np.testing.assert_array_equal(np.asarray(a.words),
                                  np.asarray(b.words))
    nv = np.asarray(a.valid_counts).reshape(-1)
    assert nv[0] == 512 and nv[1:].sum() == 0


def test_auto_mode_pod_size_one_stays_flat():
    # dcn:8,ici:1 has a DCN axis but no intra-pod fan-out: nothing to
    # coalesce, auto keeps the single-stage path (and still works)
    devs = np.asarray(jax.devices()[:8])
    mesh = Mesh(devs.reshape(8, 1), ("dcn", AXIS))
    topo = mesh_topology(mesh, AXIS2)
    assert topo.num_pods == 8 and topo.pod_size == 1
    assert not topo.hierarchical
    words = _random_words(64, 2, seed=10)
    dest = (words[:, 0] % 8).astype(np.int32)
    results, lay = shuffle_exchange(words, dest, mesh, AXIS2, capacity=8)
    assert not lay.hierarchical and len(results) >= 1


def test_recv_counts_match_counts_matrix():
    # the staged body's recv_counts must equal the windowed counts
    # matrix column — the planner and the device program agree on what
    # moved
    mesh = _mesh2(2, 4)
    words = _random_words(8 * 20, 2, seed=11)
    dest = (words[:, 1] % 8).astype(np.int32)
    cap = 7
    results, lay = shuffle_exchange(words, dest, mesh, AXIS2,
                                    capacity=cap)
    counts = np.asarray(lay.counts)
    for r, (_, rc) in enumerate(results):
        got = np.asarray(rc).reshape(8, 8)      # [dst, src]
        want = np.clip(counts - r * cap, 0, cap).T
        np.testing.assert_array_equal(got, want, err_msg=f"round {r}")


def test_hierarchical_capacity_one_many_rounds():
    mesh = _mesh2(4, 2)
    words = _random_words(8 * 6, 2, seed=12)
    dest = (words[:, 0] % 8).astype(np.int32)
    hier, _ = shuffle_exchange(words, dest, mesh, AXIS2, capacity=1)
    flat, _ = shuffle_exchange(words, dest, mesh, AXIS2, capacity=1,
                               mode="flat")
    assert len(hier) > 1
    _assert_rounds_identical(hier, flat)


def test_exchange_blobs_rides_hierarchical_mesh():
    # the opaque-bytes transport (bytes_exchange) runs on the same
    # shuffle_exchange: a hierarchical mesh must reassemble every blob
    # byte-exactly, same as the flat 1-axis mesh
    from uda_tpu.parallel import exchange_blobs

    mesh1 = make_mesh(8, AXIS)
    mesh2 = _mesh2(2, 4)
    rng = np.random.default_rng(13)
    blobs = [[(int(rng.integers(0, 8)),
               rng.bytes(int(rng.integers(0, 900))))
              for _ in range(3)] for _ in range(8)]
    out1 = exchange_blobs(blobs, mesh1, AXIS)
    out2 = exchange_blobs(blobs, mesh2, AXIS2)
    assert out1 == out2
    # spot-check contents against the send lists
    for s in range(8):
        for dst, payload in blobs[s]:
            assert payload in out2[dst][s]


def test_planner_flat_mesh_has_no_dcn_series():
    mesh = make_mesh(8, AXIS)
    topo = mesh_topology(mesh, AXIS)
    counts = np.zeros((8, 8), np.int64)
    counts[0, 1] = 3
    counts[2, 2] = 5                   # self rows: moved, not wired
    plan = plan_rounds(counts, 4, topo, record_bytes=8)
    assert plan.planned == 2 and plan.skipped == 0
    w0 = plan.windows[0]
    assert (w0.dcn_rows, w0.dcn_messages, w0.per_pod) == (0, 0, ())
    assert w0.ici_rows == 3 and w0.moved_rows == 7


def test_egress_rotation_is_balanced_on_square_meshes():
    # p == c: for any source pod, the egress map g' -> (g+g') % c is a
    # bijection — every chip relays exactly one peer-pod pair, no chip
    # is the pod's single DCN chokepoint
    from uda_tpu.parallel import MeshTopology

    topo = MeshTopology("dcn", "ici", 8, 8)
    for g in range(8):
        peers = [topo.egress_chip(g, g2) for g2 in range(8) if g2 != g]
        assert len(set(peers)) == len(peers)


# -- failure semantics -------------------------------------------------------

@pytest.mark.faults
def test_exchange_stage_b_failpoint_surfaces_transport_error():
    # a fault injected at the cross-pod (DCN) stage of a hierarchical
    # round must surface as TransportError, exactly like a whole-round
    # collective failure (the WC-error contract)
    mesh = _mesh2(2, 4)
    words = _random_words(8 * 16, 2, seed=9)
    dest = (words[:, 0] % 8).astype(np.int32)
    with failpoints.scoped("exchange.round=error:match:stageB"):
        with pytest.raises(TransportError) as ei:
            shuffle_exchange(words, dest, mesh, AXIS2, capacity=16)
        assert "exchange.round" in str(ei.value)
    # flat mode never reaches the stage-B rung: the armed match fires
    # nothing and the exchange completes
    with failpoints.scoped("exchange.round=error:match:stageB"):
        results, _ = shuffle_exchange(words, dest, mesh, AXIS2,
                                      capacity=16, mode="flat")
    assert len(results) == 1


@pytest.mark.faults
def test_exchange_mid_backlog_failpoint_round_key():
    # a fault keyed to a LATER window of a skewed multi-round exchange
    # fires only once the backlog reaches it — earlier rounds complete
    mesh = _mesh2(2, 4)
    words = _random_words(8 * 16, 2, seed=14)
    dest = np.zeros(8 * 16, np.int32)            # 4 rounds at capacity 4
    metrics.reset()
    with failpoints.scoped("exchange.round=error:match:round2"):
        with pytest.raises(TransportError):
            shuffle_exchange(words, dest, mesh, AXIS2, capacity=4)
    assert metrics.get("exchange.rounds") >= 2.0


# -- bigger shapes (fresh interpreters; the bench is the driver) -------------

@pytest.mark.slow
@pytest.mark.parametrize("spec", ["dcn:4,ici:4", "dcn:8,ici:8"])
def test_hier_byte_identity_subprocess_scale(spec, tmp_path):
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ndev = 1
    for part in spec.split(","):
        ndev *= int(part.split(":")[1])
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={ndev}")
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "scripts",
                                      "exchange_bench.py"),
         "--child", spec, "--rows-per-device", "16"],
        capture_output=True, text=True, timeout=1200, env=env, cwd=repo)
    assert proc.returncode == 0, proc.stderr[-2000:] or proc.stdout[-2000:]
    import json

    acct = None
    for line in proc.stdout.splitlines():
        if line.startswith("ACCT "):
            acct = json.loads(line[5:])
    assert acct is not None and acct["ok"]
    for case in acct["cases"]:
        assert all(case["checks"].values()), (spec, case)
        assert (case["hierarchical"]["dcn_messages_per_round_max"]
                <= case["pod_pair_bound"])
