"""The staged (hierarchical) round body moves rows in blocks (PR 39):
held, delivery and ``recv_counts`` element for element, to the body it
replaced — every row tagged and scattered by address into the staging
buffer, every arrived row scattered by tag into the delivery — kept here
as ``_scatter_staged_body``; and to the flat body on the same mesh. And
what the program's own text says: no row scatter left on the
hierarchical path, the coded body's two where they were.

Runs on the conftest 8-virtual-device CPU mesh, shaped (dcn, ici) =
(2, 2), (2, 4), (4, 2) and (3, 2) — the last with more peer-pod slots
than pods (m * c = 4 > p = 3), so a slot that no pod fills.
"""

from functools import partial

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from tests.test_exchange_pods import W as WIDTH, _step_jaxpr, _walk
from uda_tpu.parallel import shard_map
from uda_tpu.parallel.exchange import (_round_impl, hierarchical_round_body,
                                       prepare_layout, window_round_body)

AXES = ("dcn", "ici")
SHAPES = ((2, 2), (2, 4), (4, 2), (3, 2))
STAGES = ("exchange_stage_a", "exchange_stage_b", "exchange_stage_c",
          "exchange_assemble")


def _mesh(pods, chips):
    devs = np.asarray(jax.devices()[:pods * chips])
    return Mesh(devs.reshape(pods, chips), AXES)


# -- the reference: the staged body up to PR 38 ------------------------------

def _scatter_staged_body(w, d, q, lo, dcn_axis, ici_axis, capacity):
    """The plain reference of ``hierarchical_round_body``: how the round
    was written before its windows were placed as blocks. Every row
    rides with a tag word (``src_device * capacity + slot + 1``; 0 = an
    empty staging slot) and is scattered to its staging row — rows
    outside the window onto a trash row —, stage B's tiles are scattered
    into a slab with a trash block, and the delivery scatters row
    ``tag - 1``, ``recv_counts`` a ``bincount`` of the tags' source
    devices. It needs no order."""
    p = lax.psum(1, dcn_axis)
    c = lax.psum(1, ici_axis)
    g = lax.axis_index(dcn_axis)
    i = lax.axis_index(ici_axis)
    m = -(-p // c)
    nd, wcols = p * c, w.shape[1]
    wex = wcols + 1
    # stage A
    in_round = (q >= lo) & (q < lo + capacity)
    slot = q - lo
    tag = ((g * c + i) * capacity + slot + 1).astype(w.dtype)
    ext = jnp.concatenate([w, tag[:, None]], axis=1)
    dpod, dchip = d // c, d % c
    intra = dpod == g
    rows_a = capacity + m * c * capacity
    blk = jnp.where(intra, dchip, (g + dpod) % c)
    row = jnp.where(intra, slot, capacity + (dpod // c) * (c * capacity)
                    + dchip * capacity + slot)
    row = jnp.where(in_round, row, rows_a)
    send_a = jnp.zeros((c, rows_a + 1, wex), w.dtype)
    send_a = send_a.at[blk, row].set(ext, mode="drop")
    recv_a = lax.all_to_all(send_a[:, :rows_a], ici_axis, split_axis=0,
                            concat_axis=0, tiled=False)
    intra_rows = recv_a[:, :capacity].reshape(c * capacity, wex)
    cross = recv_a[:, capacity:].reshape(c, m, c, capacity, wex)
    # stage B
    peers = ((i - g) % c) + jnp.arange(m) * c
    tiles = jnp.swapaxes(cross, 0, 1).reshape(m, c * c * capacity, wex)
    send_b = jnp.zeros((p + 1, c * c * capacity, wex), w.dtype)
    send_b = send_b.at[jnp.where(peers < p, peers, p)].set(tiles,
                                                           mode="drop")
    recv_b = lax.all_to_all(send_b[:p], dcn_axis, split_axis=0,
                            concat_axis=0, tiled=False)
    # stage C
    compact = jnp.take(recv_b, jnp.minimum(peers, p - 1), axis=0)
    compact = jnp.where((peers < p)[:, None, None], compact, 0)
    compact = compact.reshape(m, c, c, capacity, wex)
    send_c = jnp.transpose(compact, (2, 0, 1, 3, 4)).reshape(
        c, m * c * capacity, wex)
    recv_c = lax.all_to_all(send_c, ici_axis, split_axis=0,
                            concat_axis=0, tiled=False)
    # assembly
    arrived = jnp.concatenate([
        intra_rows, recv_c.reshape(c * m * c * capacity, wex)])
    atag = arrived[:, wcols].astype(jnp.int32)
    valid = atag > 0
    idx = jnp.where(valid, atag - 1, nd * capacity)
    out = jnp.zeros((nd * capacity + 1, wcols), arrived.dtype)
    out = out.at[idx].set(arrived[:, :wcols], mode="drop")[:nd * capacity]
    peer_dev = jnp.where(valid, (atag - 1) // capacity, nd)
    recv_counts = jnp.bincount(peer_dev, length=nd + 1)[:nd].astype(
        jnp.int32)
    return out, recv_counts


# (local rows, capacity, window index, how a device's rows pick their
# destinations, whether lo is the Python 0 of the fused step — traced
# otherwise, as the round programs hand it)
_CASES = {
    "capacity_over_rows": (24, 40, 0, "uniform", False),
    "fused_step_static_lo": (24, 40, 0, "uniform", True),
    "bucket_over_window_0": (48, 8, 0, "skew", False),
    "bucket_over_window_1": (48, 8, 1, "skew", False),
    "bucket_over_window_2": (48, 8, 2, "skew", False),
    "capacity_one_static_lo": (16, 1, 0, "uniform", True),
    "capacity_one_window_2": (16, 1, 2, "uniform", False),
    "empty_destination": (32, 16, 0, "skip_last", False),
    "empty_pod": (32, 16, 0, "skip_last_pod", False),
    "empty_pod_window_1": (32, 4, 1, "skip_last_pod", False),
    "only_own_pod": (32, 16, 0, "own_pod", False),
    "all_to_one": (32, 12, 0, "one", False),
    "all_to_one_window_2": (32, 12, 2, "one", False),
    "window_past_every_bucket": (16, 4, 5, "uniform", False),
}


def _case_dest(kind, rng, n, pods, chips, device):
    nd = pods * chips
    if kind == "uniform":
        return rng.integers(0, nd, size=n)
    if kind == "skew":      # over half to one destination: > 3 windows
        hot = (device + chips + 1) % nd             # in another pod
        return np.where(rng.random(n) < 0.6, hot,
                        rng.integers(0, nd, size=n))
    if kind == "skip_last":                 # nobody sends to nd - 1
        return rng.integers(0, nd - 1, size=n)
    if kind == "skip_last_pod":             # nor to any chip of its pod
        return rng.integers(0, nd - chips, size=n)
    if kind == "own_pod":                   # nothing crosses pods
        return (device // chips) * chips + rng.integers(0, chips, size=n)
    return np.full(n, nd // 2)              # "one"


@pytest.mark.parametrize("case", sorted(_CASES))
@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: f"{s[0]}x{s[1]}")
def test_staged_body_delivers_what_the_scatter_built_one_does(shape, case):
    # flat WHOLE — the zeros past each peer's count included — and
    # recv_counts, on rows in _bucket_local's order (the precondition
    # the staged body now shares with window_round_body)
    pods, chips = shape
    nd = pods * chips
    n, capacity, window, kind, static_lo = _CASES[case]
    rng = np.random.default_rng(
        100 * SHAPES.index(shape) + sorted(_CASES).index(case))
    dest = np.concatenate([_case_dest(kind, rng, n, pods, chips, device)
                           for device in range(nd)]).astype(np.int32)
    words = rng.integers(1, 2**32, size=(nd * n, 3), dtype=np.uint32)
    mesh = _mesh(pods, chips)
    layout = prepare_layout(words, dest, mesh, AXES)
    assert layout.hierarchical
    if kind == "skew":
        assert layout.counts.max() > 2 * capacity
    if kind == "skip_last_pod":
        assert not layout.counts[:, nd - chips:].any()

    def run(body, *axes):
        @jax.jit
        @partial(shard_map, mesh=mesh,
                 in_specs=(P(AXES), P(AXES), P(AXES), P()),
                 out_specs=(P(AXES), P(AXES)))
        def go(w, d, q, lo):
            flat, counts = body(w, d, q, 0 if static_lo else lo[0], *axes,
                                capacity)
            return flat, counts.reshape(1, -1)

        flat, counts = go(layout.words, layout.dest, layout.pos,
                          jnp.asarray([window * capacity], jnp.int32))
        return np.asarray(flat), np.asarray(counts)

    got_flat, got_counts = run(hierarchical_round_body, *AXES)
    want_flat, want_counts = run(_scatter_staged_body, *AXES)
    np.testing.assert_array_equal(got_counts, want_counts)
    np.testing.assert_array_equal(got_flat, want_flat)
    assert got_flat.shape == (nd * nd * capacity, 3)
    # the flat body on the same mesh: callers cannot tell which ran
    flat_flat, flat_counts = run(window_round_body, AXES)
    np.testing.assert_array_equal(got_counts, flat_counts)
    np.testing.assert_array_equal(got_flat, flat_flat)
    # and the reference delivers what the counts say it should:
    # recv_counts[dst, src] = the part of bucket (src, dst) in the window
    np.testing.assert_array_equal(
        want_counts,
        np.clip(layout.counts.T - window * capacity, 0, capacity))


# -- what the program's own text says ----------------------------------------

def _record_sends(eqns):
    return [e for e in eqns if e.primitive.name == "all_to_all"
            and e.invars[0].aval.dtype == np.uint32]


def test_the_fused_step_on_the_pod_mesh_holds_no_row_scatter():
    # the step as the cell exchange_dcn2_ici2 lowers it: dcn:2,ici:2,
    # exchange_mode auto. `.at[].set` is the primitive "scatter"; the
    # step's bincounts ("scatter-add", P counters) are not row moves
    jaxpr = _step_jaxpr(_mesh(2, 2), "auto", 4 * 256, 128)
    eqns = list(_walk(jaxpr.jaxpr))
    assert not [e for e in eqns if e.primitive.name == "scatter"]
    sends = _record_sends(eqns)
    assert len(sends) == 3
    # rows travel as their W words: no tag column in any operand
    assert all(e.invars[0].aval.shape[-1] == WIDTH for e in sends)
    text = str(jaxpr.pretty_print(name_stack=True))
    for scope in STAGES:
        assert scope in text, scope


@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: f"{s[0]}x{s[1]}")
def test_the_round_program_holds_no_row_scatter(shape):
    # exchange_round's program (traced round index) on every mesh shape
    pods, chips = shape
    nd, n, capacity = pods * chips, 32, 8
    mesh = _mesh(pods, chips)
    args = (jax.ShapeDtypeStruct((nd * n, 3), np.uint32),
            jax.ShapeDtypeStruct((nd * n,), np.int32),
            jax.ShapeDtypeStruct((nd * n,), np.int32),
            jax.ShapeDtypeStruct((1,), np.int32))
    jaxpr = jax.make_jaxpr(
        lambda *a: _round_impl(*a, mesh, AXES, capacity, "hierarchical",
                               *AXES))(*args)
    eqns = list(_walk(jaxpr.jaxpr))
    assert not [e for e in eqns if e.primitive.name == "scatter"]
    assert len(_record_sends(eqns)) == 3


def test_the_coded_body_scatters_in_its_compaction_and_assembly_alone():
    # after compaction a coded block's rows are no longer at their
    # slots, so only a tag can place them: the coded body keeps its two
    # scatters and shares the block-placed stage A
    pods, chips = 2, 4
    nd, n, capacity, l_rows, width = pods * chips, 32, 8, 8, 3
    mesh = _mesh(pods, chips)
    m = -(-pods // chips)
    args = (jax.ShapeDtypeStruct((nd * n, width), np.uint32),
            jax.ShapeDtypeStruct((nd * n,), np.int32),
            jax.ShapeDtypeStruct((nd * n,), np.int32),
            jax.ShapeDtypeStruct((1,), np.int32))
    jaxpr = jax.make_jaxpr(
        lambda *a: _round_impl(*a, mesh, AXES, capacity, "coded", *AXES,
                               coded_l_rows=l_rows))(*args)
    scatters = [e for e in _walk(jaxpr.jaxpr)
                if e.primitive.name == "scatter"]
    assert sorted(e.invars[0].aval.shape for e in scatters) == sorted([
        (m, chips, l_rows + 1, width + 1),          # the compaction
        (nd * capacity + 1, width)])                # the assembly by tag
    # its tagged rows ride stage A's block placement: W + 1 words
    sends = _record_sends(list(_walk(jaxpr.jaxpr)))
    assert sends and all(e.invars[0].aval.shape[-1] == width + 1
                         for e in sends)
