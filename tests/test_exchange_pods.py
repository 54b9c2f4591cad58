"""The multi-chip sort across two pods of two chips, through the normal
entry with its defaults: ``distributed_terasort(words, mesh, ("dcn",
"ici"))`` on the mesh ``mesh_from_config`` builds from ``dcn:2,ici:2``
(benchmark cell ``exchange_dcn2_ici2``) against a reference that shares
no code with the program, and the fabric the fused step books against
the reference's own count from (source chip, destination chip) alone.
"""

import numpy as np
import pytest

import jax
from jax.sharding import NamedSharding, PartitionSpec

from uda_tpu.models import terasort
from uda_tpu.parallel import make_mesh, mesh_from_config
from uda_tpu.parallel import distributed
from uda_tpu.parallel.distributed import (distributed_sort_step,
                                          uniform_splitters)
from uda_tpu.parallel.exchange import (exchange_dispatch,
                                       resolve_exchange_mode)
from uda_tpu.utils.config import Config
from uda_tpu.utils.metrics import metrics

PODS, CHIPS_A_POD = 2, 2
P = PODS * CHIPS_A_POD
AXES = ("dcn", "ici")
W = terasort.RECORD_WORDS
RECORD_BYTES = 4 * W
BOOKED = ("exchange.dcn.bytes", "exchange.dcn.messages",
          "exchange.ici.bytes", "exchange.wire.bytes",
          "exchange.staged.block_copies")


def _pod_mesh():
    return mesh_from_config(Config({"uda.tpu.mesh.shape": "dcn:2,ici:2"}))


def _records(kind: str, n: int, seed: int) -> np.ndarray:
    """``uint32[n, 26]``, the third key word masked as TeraSort's is.
    ``duplicates``: every key four times over (payloads differ, so input
    order shows), and chip 1 holds no key of range 2 — an empty (source,
    destination) bucket."""
    rng = np.random.default_rng(seed)
    words = rng.integers(0, 2**32, size=(n, W), dtype=np.uint32)
    words[:, 2] &= 0xFFFF0000
    if kind == "duplicates":
        words[:, :3] = np.tile(words[: n // 4, :3], (4, 1))
        rng.shuffle(words[:, :3], axis=0)
        mine = slice(n // P, 2 * n // P)            # chip 1's rows
        in_range_2 = (words[mine, 0] >> 30) == 2
        elsewhere = rng.choice(np.array([0, 1, 3], np.uint32), n // P) << 30
        words[mine, 0] = np.where(in_range_2,
                                  words[mine, 0] & 0x3FFFFFFF | elsewhere,
                                  words[mine, 0])
    return words


# -- the reference: numpy alone ----------------------------------------------

def _reference_shards(words: np.ndarray) -> list:
    """Shard d of the pod-major device order = range d of the whole
    input in ``np.lexsort`` order (stable: equal keys in input order),
    cut at the uniform first-word splitters."""
    ordered = words[np.lexsort((words[:, 2], words[:, 1], words[:, 0]))]
    dest = (ordered[:, 0].astype(np.uint64) * P) >> 32
    return [ordered[dest == d] for d in range(P)]


def _reference_fabric(words: np.ndarray, hierarchical: bool,
                      capacity: int) -> dict:
    """What crosses which fabric, counted row by row from (source chip,
    destination chip): README "Multi-pod shuffle"'s definitions. A
    cross-pod row of the staged body hops source chip -> egress chip
    (stage A) and ingress chip -> destination chip (stage C) over ICI,
    the egress and ingress chip of pod pair (g, g') being chip
    ``(g + g') % chips_a_pod`` of each pod."""
    n = len(words)
    src = np.arange(n) // (n // P)
    dst = ((words[:, 0].astype(np.uint64) * P) >> 32).astype(np.int64)
    dcn_rows = ici_rows = 0
    pairs = set()
    for s, t in zip(src.tolist(), dst.tolist()):
        g, g2 = s // CHIPS_A_POD, t // CHIPS_A_POD
        if g == g2:
            ici_rows += s != t
            continue
        dcn_rows += 1
        if hierarchical:
            egress = (g + g2) % CHIPS_A_POD
            ici_rows += (s % CHIPS_A_POD != egress) + (egress !=
                                                        t % CHIPS_A_POD)
            pairs.add((g, g2))
        else:
            pairs.add((s, t))
    if hierarchical:
        # send_a: [c, cap + c * cap], send_b: [p, c * c * cap],
        # send_c: [c, c * cap] rows a chip (one peer-pod slot an egress
        # chip at p = c = 2), every row its 26 words (no tag word since
        # PR 39: the staged body places whole windows)
        rows = (CHIPS_A_POD * (1 + CHIPS_A_POD) + PODS * CHIPS_A_POD ** 2
                + CHIPS_A_POD ** 2) * capacity
        wire = P * rows * W * 4
    else:
        wire = P * P * capacity * W * 4
    return {"exchange.dcn.bytes": dcn_rows * RECORD_BYTES,
            "exchange.dcn.messages": len(pairs),
            "exchange.ici.bytes": ici_rows * RECORD_BYTES,
            "exchange.wire.bytes": wire,
            # P windows placed in send_a + P blocks delivered, a chip
            "exchange.staged.block_copies": 2 * P if hierarchical else 0}


def _booked() -> dict:
    return {k: metrics.get(k) for k in BOOKED}


def _assert_shards(res, words) -> None:
    nvalid = np.asarray(res.valid_counts).reshape(-1)
    out = np.asarray(res.words).reshape(P, -1, W)
    for d, want in enumerate(_reference_shards(words)):
        assert nvalid[d] == len(want), f"shard {d}"
        np.testing.assert_array_equal(out[d, :len(want)], want,
                                      err_msg=f"shard {d}")


# -- the step through its normal entry ---------------------------------------

@pytest.mark.parametrize("kind", ("uniform", "duplicates"))
def test_pod_sort_is_the_host_sort_and_books_its_fabric(kind):
    mesh = _pod_mesh()
    n = P * 256
    words = _records(kind, n, seed=38)
    if kind == "duplicates":
        dst = (words[n // P: 2 * n // P, 0].astype(np.uint64) * P) >> 32
        assert not (dst == 2).any()         # the empty bucket is there
    metrics.reset()
    res = terasort.distributed_terasort(words, mesh, AXES)
    res.check()
    assert metrics.get("exchange.fused.overflow_reruns") == 0
    _assert_shards(res, words)
    capacity = 2 * n // (P * P)
    assert _booked() == _reference_fabric(words, True, capacity)
    assert metrics.get("exchange.dcn.messages") == PODS * (PODS - 1)
    # each pod's share rides its own series, as the rounds label it
    assert (metrics.get("exchange.dcn.bytes", pod=0)
            + metrics.get("exchange.dcn.bytes", pod=1)
            == metrics.get("exchange.dcn.bytes"))


def test_the_flat_body_on_the_pod_mesh_books_device_pairs():
    # the alarm exchange_dcn_messages rings: the same rows cross pods,
    # as one transfer a cross-pod DEVICE pair
    mesh = _pod_mesh()
    n = P * 256
    words = _records("uniform", n, seed=39)
    capacity = 2 * n // (P * P)
    metrics.reset()
    res = distributed_sort_step(words, uniform_splitters(P), mesh, AXES,
                                capacity=capacity, num_keys=3,
                                exchange_mode="flat")
    res.check()
    _assert_shards(res, words)
    assert _booked() == _reference_fabric(words, False, capacity)
    assert metrics.get("exchange.dcn.messages") == 2 * PODS * CHIPS_A_POD


def test_a_flat_mesh_books_no_dcn():
    words = _records("uniform", P * 256, seed=40)
    metrics.reset()
    res = terasort.distributed_terasort(words, make_mesh(P, "ici"), "ici")
    res.check()
    _assert_shards(res, words)
    assert metrics.get("exchange.dcn.bytes") == 0
    assert metrics.get("exchange.dcn.messages") == 0
    assert not [k for k in metrics.snapshot()
                if "dcn" in k or "wire" in k or "staged" in k]


def test_an_overflowed_attempt_rerun_through_the_rounds_is_booked_once():
    mesh = _pod_mesh()
    n = P * 256
    words = _records("uniform", n, seed=41)
    metrics.reset()
    res = terasort.distributed_terasort(words, mesh, AXES, capacity=24)
    res.check()
    _assert_shards(res, words)
    assert metrics.get("exchange.fused.overflow_reruns") == 1
    want = _reference_fabric(words, True, 24)
    # the rounds book their own windows: the same rows, once; only a
    # kept fused step books its collectives' dense bytes
    assert metrics.get("exchange.dcn.bytes") == want["exchange.dcn.bytes"]
    assert metrics.get("exchange.ici.bytes") == want["exchange.ici.bytes"]
    assert metrics.get("exchange.wire.bytes") == 0
    assert metrics.get("exchange.staged.block_copies") == 0
    rounds = metrics.get("exchange.rounds")
    assert rounds >= 3
    # a pod pair's transfer a window it has rows in: the tail windows
    # of the largest buckets may have one pair left
    assert (PODS * (PODS - 1) * (rounds - 1)
            <= metrics.get("exchange.dcn.messages")
            <= PODS * (PODS - 1) * rounds)


@pytest.mark.parametrize("overflows", (False, True))
def test_booking_waits_for_the_totals_and_for_a_result_that_is_kept(
        overflows):
    mesh = _pod_mesh()
    n = P * 256
    words = _records("uniform", n, seed=42)
    capacity = 24 if overflows else 2 * n // (P * P)
    metrics.reset()
    res = distributed_sort_step(words, uniform_splitters(P), mesh, AXES,
                                capacity=capacity, num_keys=3,
                                multiround="never")
    jax.block_until_ready(res.words)
    assert not any(_booked().values())      # nothing read, nothing booked
    if overflows:
        assert res.overflow() > 0
        assert not any(_booked().values())  # rows were dropped: not kept
    else:
        res.check()
        res.check()                         # the second read books nothing
        assert _booked() == _reference_fabric(words, True, capacity)


@pytest.mark.parametrize("case", ("hierarchical", "flat_on_the_pod_mesh",
                                  "flat_mesh", "dropped_rows"))
def test_block_copies_are_booked_where_the_staged_body_delivered(case):
    # 2 * P a chip a step on the hierarchical body, 0 (the key is
    # there) when the flat body ran on the pod mesh, nothing at all on
    # a flat mesh, nothing for a step whose result is not kept
    key = "exchange.staged.block_copies"
    n, steps = P * 256, 2
    words = _records("uniform", n, seed=43)
    capacity = 24 if case == "dropped_rows" else 2 * n // (P * P)
    mesh, axis = ((make_mesh(P, "ici"), "ici") if case == "flat_mesh"
                  else (_pod_mesh(), AXES))
    mode = "flat" if case == "flat_on_the_pod_mesh" else "auto"
    metrics.reset()
    for _ in range(steps):
        res = distributed_sort_step(words, uniform_splitters(P), mesh, axis,
                                    capacity=capacity, num_keys=3,
                                    exchange_mode=mode, multiround="never")
        assert (res.overflow() > 0) == (case == "dropped_rows")
    want = {"hierarchical": steps * 2 * P, "flat_on_the_pod_mesh": 0}
    assert metrics.get(key) == want.get(case, 0)
    assert (key in metrics.snapshot()) == (case in want)


# -- what the program says of itself, against the traced program -------------

def _step_jaxpr(mesh, mode, n, capacity):
    topo, hier, _ = resolve_exchange_mode(mesh, AXES, mode)
    words = jax.ShapeDtypeStruct(
        (n, W), np.uint32, sharding=NamedSharding(mesh, PartitionSpec(AXES)))
    spl = jax.ShapeDtypeStruct((P - 1, 3), np.uint32)
    return jax.make_jaxpr(
        lambda w, s: distributed._sort_step(
            w, s, mesh, AXES, capacity, 3, "carry", pod_counts=True,
            **exchange_dispatch(topo, hier)))(words, spl)


def _walk(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _walk(sub)


@pytest.mark.parametrize("mode", ("auto", "flat"))
def test_wire_bytes_are_the_all_to_all_operands_of_the_traced_step(mode):
    n, capacity = P * 256, 128
    sends = [e for e in _walk(_step_jaxpr(_pod_mesh(), mode, n,
                                          capacity).jaxpr)
             if e.primitive.name == "all_to_all"
             and e.invars[0].aval.dtype == np.uint32]
    assert len(sends) == (3 if mode == "auto" else 1)
    per_chip = sum(e.invars[0].aval.size * 4 for e in sends)
    want = _reference_fabric(np.zeros((n, W), np.uint32), mode == "auto",
                             capacity)["exchange.wire.bytes"]
    assert P * per_chip == want


def test_the_staged_body_names_its_four_stages():
    text = str(_step_jaxpr(_pod_mesh(), "auto", P * 256, 128).pretty_print(
        name_stack=True))
    for scope in ("exchange_stage_a", "exchange_stage_b",
                  "exchange_stage_c", "exchange_assemble"):
        assert scope in text, scope
