"""The shuffle data plane: windowed all-to-all exchange over the mesh.

TPU-native replacement of the reference's RDMA transport (reference
src/DataNet/): instead of per-request one-sided RDMA-WRITEs into remote
registered buffers (RDMAServer.cc:537-631) with credit-based flow
control (RDMAComm.cc:707-752), the exchange is *globally scheduled*:

- every device buckets its records by destination partition;
- each round moves at most ``capacity`` records per (src, dst) pair
  through one ``lax.all_to_all`` over the named mesh axis — the round
  capacity is the credit window, bounding peak HBM exactly like the
  reference's 1000-chunk server pool bounded registered memory
  (NetlevComm.h:35);
- skewed destinations simply take more rounds (the chunked-rounds
  answer to the reference's backlog list, RDMAComm.h:132-152).

Records travel as fixed-stride uint32 row matrices (packed by
uda_tpu.ops.packing); within one jitted round everything is static
shapes, so XLA lowers the exchange to ICI collectives with no host in
the loop. A host-side variable-length RecordBatch exchange is provided
for the Hadoop byte-exact path and as the CPU reference.

Hierarchical (multi-pod) meshes: on a ``(dcn, ici)`` 2-axis mesh the
flat round would give every cross-pod *device* pair its own DCN lane —
O((p*c)^2) per-round DCN messages. The two-stage round body
(:func:`hierarchical_round_body`) instead runs the all_to_all only
over the ICI axis, staging every record's cross-pod hop onto the ONE
designated egress chip of its (pod, peer-pod) pair, moves one
coalesced tile per pod pair over the DCN axis — O(p^2) messages, the
reference's per-QP aggregation win (RDMAServer.cc chunked server
pool) — and delivers with a second pod-local all_to_all. Same window
semantics, same delivery contract, byte-identical output; the host
planner (parallel/planner.py) proves the per-round message reduction
and accounts the RECORD bytes each tier carries (identical to flat on
the DCN by construction — the same rows cross pods either way).
The staged body moves rows in BLOCKS, never by row address (PR 39):
every caller hands the round bodies rows in destination order, so a
(destination, window) is one contiguous slice, placed whole in each
staging buffer and delivered whole — 2*P block copies a chip where two
row scatters stood (counter ``exchange.staged.block_copies``) — and
rows travel as their W words, the senders' counts beside them.

Coded multicast stage B (``mode="coded"``, Coded TeraSort
arXiv:1702.04850): when the host plan says a window's pod pairs are
*codable* (cross rows spread over >= 2 destination chips and the
padded multicast chunk beats the payload — parallel/planner.py), the
egress chip compacts each destination chip's rows into an ``L``-row
block and GF(2^8)-encodes the ``pod_size`` blocks through a full-rank
Cauchy matrix (uda_tpu.coding.gfjax — the in-tree RS machinery's
square case), so the pair's ONE DCN tile carries coded chunks instead
of disjoint per-destination blocks; stage C broadcasts the arrived
chunks pod-locally (``lax.all_gather`` over ICI — the cheap fabric
pays for the expensive one, the Coded TeraSort trade) and every
member decodes its OWN block locally with the inverse row of its chip
index. The coded body ALONE tags its rows (``src_device * capacity +
slot + 1`` in a W+1-th word, handed to the shared block-placed stage
A): its compaction moves a block's rows off their slots, so only a tag
can place them again. The tags ride through encode/decode untouched,
and the post-decode scatter reproduces the exact flat (peer row-block,
slot) layout — byte-identity vs the flat oracle stays gated by
construction. Windows the plan declines (skew, single-destination
pairs, 1-pod meshes) ride the plain coalesced tile with zero coded
overhead, and a decode failure (failpoint site ``exchange.decode``)
falls back to the plain tile within the round.

Scope of the byte accounting: ``lax.all_to_all`` lowers to DENSE
static buffers, so the stage-B collective's wire footprint includes
the unpopulated tile slots of non-egress chips (a ~pod_size padding
factor over the populated rows; stage C likewise on ICI). A
sparse/ragged collective (``lax.ragged_all_to_all``, newer JAX) is
the lever that makes the wire footprint match the record accounting —
until then the hierarchical win this module claims, measures and
gates is the MESSAGE/coalescing structure (per-transfer setup cost,
the per-QP analogy) plus the per-tier record-byte ledger, not the
padded collective payload. The CODED ledger extends the same
discipline one step: ``exchange.dcn.coded.bytes`` charges what a
redundant-map Coded-TeraSort deployment would move — one L-row
multicast packet per pod pair serving every member at once, decode
side information being map-redundancy the deployment computes
locally. This virtual mesh has no map redundancy, so the coded tile
ships the full-rank chunk set (any member can decode every block) and
the side-information share of the tile rides the wire outside the
model charge — see the planner docstring, README and PARITY for the
full statement. ``shuffle_exchange``/``prepare_layout`` dispatch on
the mesh topology (flat 1-axis meshes keep the single-stage path).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from uda_tpu.parallel import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from uda_tpu.parallel.mesh import MeshTopology, mesh_topology
from uda_tpu.parallel.multihost import allgather, put_rows
from uda_tpu.utils.errors import (ConfigError, StorageError,
                                  TransportError)
from uda_tpu.utils.failpoints import failpoint
from uda_tpu.utils.ifile import RecordBatch
from uda_tpu.utils.metrics import metrics

__all__ = ["ShuffleLayout", "prepare_layout", "window_round_body",
           "hierarchical_round_body", "coded_round_body",
           "run_round_body", "round_wire_bytes", "staged_block_copies",
           "resolve_exchange_mode",
           "exchange_dispatch", "exchange_round",
           "execute_planned_window", "shuffle_exchange",
           "exchange_record_batches"]

EXCHANGE_MODES = ("auto", "flat", "hierarchical", "coded")


def resolve_exchange_mode(mesh: Mesh, axis, mode: str = "auto"):
    """Resolve the exchange dispatch for a (mesh, axis) pair.

    Returns ``(topology, hierarchical, coded)``. ``auto`` takes the
    two-stage path exactly when the mesh has a real pod structure (a
    DCN-tagged outer axis with >1 pod of >1 chip); ``flat`` forces the
    single-stage path on any mesh (the A/B baseline); ``hierarchical``
    demands a hierarchical mesh and refuses otherwise. ``coded`` ARMS
    the coded stage-B dispatch on hierarchical meshes — whether any
    window actually codes is the host plan's per-window decision — and
    deliberately degrades to the plain path elsewhere (a 1-pod mesh
    has no pod pairs to encode across: zero coded overhead, not an
    error)."""
    if mode not in EXCHANGE_MODES:
        raise ConfigError(f"unknown exchange mode {mode!r} "
                          f"(one of {EXCHANGE_MODES})")
    topo = mesh_topology(mesh, axis)
    if mode == "hierarchical" and not topo.hierarchical:
        raise ConfigError(
            f"exchange mode 'hierarchical' needs a (dcn, ici) mesh with "
            f">1 pod of >1 chip; got axes {axis!r} on mesh "
            f"{dict(mesh.shape)}")
    hier = topo.hierarchical if mode in ("auto", "coded") \
        else mode == "hierarchical"
    return topo, hier, (mode == "coded" and topo.hierarchical)


def exchange_dispatch(topology: Optional[MeshTopology],
                      hierarchical: bool) -> dict:
    """The static dispatch triple every jitted exchange entry point
    shares (``_round_impl``, ``distributed._sort_step``,
    ``distributed._round_scatter``) — ONE definition so the fused,
    multiround and plain-exchange paths can never disagree on which
    round body a mesh runs."""
    hier = bool(hierarchical) and topology is not None
    return {"exchange_mode": "hierarchical" if hier else "flat",
            "dcn_axis": topology.dcn_axis if hier else None,
            "ici_axis": topology.ici_axis if hier else None}


@dataclasses.dataclass
class ShuffleLayout:
    """Per-device bucketed layout, computed once per shuffle.

    All arrays are mesh-sharded along axis 0 (one row block per device):

    - ``words``: uint32[N, W] records, locally SORTED by destination
      (stable by arrival). The order is load-bearing, not descriptive:
      :func:`window_round_body` reads a destination's window as one
      contiguous slice of these rows;
    - ``dest``: int32[N] destination partition of each local record
      (ascending within a device's block, by the same order);
    - ``pos``: int32[N] position of the record within its (src, dst)
      bucket — ``pos // capacity`` is the round it travels in;
    - ``counts``: int32[P, P] full count matrix (row = src device,
      col = dst) gathered to every device for round planning;
    - ``topology``/``hierarchical``/``coded``: the resolved fabric
      dispatch — which round body :func:`exchange_round` runs
      (``coded`` arms the per-window coded stage-B decision in the
      host plan; the staged machinery is shared, so coded implies
      hierarchical).
    """

    words: jax.Array
    dest: jax.Array
    pos: jax.Array
    counts: np.ndarray
    mesh: Mesh
    axis: str
    topology: Optional[MeshTopology] = None
    hierarchical: bool = False
    coded: bool = False

    def dispatch(self) -> dict:
        """Static round-body dispatch kwargs (see
        :func:`exchange_dispatch`)."""
        return exchange_dispatch(self.topology, self.hierarchical)

    def record_bytes(self) -> int:
        """Wire stride of one record row — the byte unit of the
        planner's ICI/DCN accounting."""
        return (int(self.words.shape[1])
                * int(np.dtype(self.words.dtype).itemsize))


def _bucket_local(words, dest, axis):
    """Stable local bucket-by-destination; returns sorted rows, dest,
    in-bucket positions and per-dest counts. This order — rows sorted
    by destination, ``pos`` the index inside the bucket — is the
    precondition of :func:`window_round_body`, which slices it."""
    p = lax.psum(1, axis)
    order = jnp.argsort(dest, stable=True)
    sdest = jnp.take(dest, order)
    swords = jnp.take(words, order, axis=0)
    counts = jnp.bincount(sdest, length=p).astype(jnp.int32)
    starts = jnp.concatenate([jnp.zeros(1, jnp.int32),
                              jnp.cumsum(counts)[:-1].astype(jnp.int32)])
    pos = jnp.arange(sdest.shape[0], dtype=jnp.int32) - jnp.take(starts, sdest)
    return swords, sdest, pos, counts


def prepare_layout(words: jax.Array, dest: jax.Array, mesh: Mesh,
                   axis: str, mode: str = "auto") -> ShuffleLayout:
    """Bucket every device's records and gather the count matrix.
    ``mode`` resolves the fabric dispatch (see
    :func:`resolve_exchange_mode`)."""
    topo, hier, coded = resolve_exchange_mode(mesh, axis, mode)

    @partial(shard_map, mesh=mesh, in_specs=(P(axis), P(axis)),
             out_specs=(P(axis), P(axis), P(axis), P(axis)))
    def _prep(w, d):
        sw, sd, pos, counts = _bucket_local(w, d, axis)
        return sw, sd, pos, counts[None, :]

    words = put_rows(words, mesh, axis)
    dest = put_rows(dest, mesh, axis)
    if int(words.shape[0]) == 0:
        # an empty shuffle is its own bucketed layout with an all-zero
        # count matrix; shard_map refuses zero-row operands (XLA folds
        # the empty program's output sharding to replicated)
        p = topo.num_devices
        return ShuffleLayout(words, dest, dest, np.zeros((p, p), np.int32),
                             mesh, axis, topo, hier, coded)
    sw, sd, pos, counts = _prep(words, dest)
    # count-matrix readback: allgather works on multi-process meshes
    # where the sharded array is not host-addressable
    return ShuffleLayout(sw, sd, pos, allgather(counts), mesh, axis,
                         topo, hier, coded)


def _window_reader(w, d, lo, p: int, capacity: int):
    """What both round bodies read their send blocks through. On rows
    in destination order (``window_round_body``'s precondition),
    destination k's window ``[lo, lo + capacity)`` is one
    ``dynamic_slice`` of the rows, the rows past the bucket's end zeroed
    in the same pass. Returns ``(send_counts, window)``: the valid rows
    of each of the ``p`` windows and ``window(k)``, destination k's
    ``[capacity, W]`` block."""
    counts = jnp.bincount(d, length=p).astype(jnp.int32)
    starts = jnp.cumsum(counts) - counts
    send_counts = jnp.clip(counts - lo, 0, capacity)
    # a window may run past the last row (the last bucket's always can,
    # and capacity >= n whenever P <= 2), where dynamic_slice would
    # clamp its start and shift the rows: the slices read w padded with
    # zeros (fused into each slice by XLA, no padded copy exists)
    wpad = jnp.pad(w, ((0, capacity), (0, 0)))
    slot = jnp.arange(capacity, dtype=jnp.int32)[:, None]

    def window(k: int):
        rows = lax.dynamic_slice_in_dim(wpad, starts[k] + lo, capacity)
        return jnp.where(slot < send_counts[k], rows, 0)

    return send_counts, window


def window_round_body(w, d, q, lo, axis: str, capacity: int):
    """One windowed exchange round, for use INSIDE a shard_map body (the
    single definition of the round wire protocol — exchange_round and
    the multiround scatter in uda_tpu.parallel.distributed both build on
    it). ``lo`` (the window base, round * capacity) may be traced.

    PRECONDITION (every caller's ``_bucket_local`` order): the rows of
    ``w`` are sorted by destination ``d``, ascending, and ``q`` is each
    row's position inside its destination's bucket. In that order
    destination k's window ``[lo, lo + capacity)`` IS the contiguous
    rows ``starts[k] + lo ...`` of ``w``, so the send buffer is P
    window copies — one ``dynamic_slice`` a destination, the rows past
    the bucket's end zeroed in the same pass (``_window_reader``) — and
    ``q``, implied by the order, is not read. Why slices: a ``[n, W]``
    row matrix is stored long-dimension-minor on the chip, so scattering
    rows to ``(d, q - lo)`` is W lane scatters n long — a quarter of the
    fused step at 2^24 rows a chip (PERF.md §6, PR 33). That scatter is
    the reference tests/test_exchange.py holds this body to.

    Returns ``(flat, recv_counts)``: the local [P*capacity, W] delivery
    (row block i = peer i's contribution, zeros past its count) and
    per-peer valid counts [P].
    """
    p = lax.psum(1, axis)
    wcols = w.shape[1]
    send_counts, window = _window_reader(w, d, lo, p, capacity)
    # updates in place, not jnp.stack: the compiler's memory_analysis()
    # of the fused step is 134 MB a chip lower this way (v5e:2x2)
    send = jnp.zeros((p, capacity, wcols), w.dtype)
    for k in range(p):
        send = lax.dynamic_update_slice(send, window(k)[None], (k, 0, 0))
    recv = lax.all_to_all(send, axis, split_axis=0,
                          concat_axis=0, tiled=False)
    recv_counts = lax.all_to_all(send_counts[:, None], axis,
                                 split_axis=0, concat_axis=0,
                                 tiled=False).reshape(p)
    return recv.reshape(p * capacity, wcols), recv_counts


def _pod_coords(dcn_axis: str, ici_axis: str):
    """``(p, c, g, i, m)`` of the staged bodies: pods, chips a pod (both
    static), this chip's pod and chip index (traced) and ``m``, the
    peer-pod slots an egress chip holds (``ceil(p / c)``: the pairs
    ``(g, g')`` rotate over the chips by ``(g + g') % c``)."""
    p = lax.psum(1, dcn_axis)
    c = lax.psum(1, ici_axis)
    return (p, c, lax.axis_index(dcn_axis), lax.axis_index(ici_axis),
            -(-p // c))


def _stage_a(block, rows: int, wcols: int, dtype, coords, ici_axis: str):
    """Stage A of the staged bodies (pod-local all_to_all: an intra-pod
    block straight to its final chip, a cross-pod block onto its pod
    pair's egress chip), generic in what a block is: ``block(k)`` is the
    ``[rows, wcols]`` block this chip holds for destination device k —
    a window of record rows, of tagged rows (the coded body), or the
    window's one count. Every block is placed WHOLE in ``send_a[c, 1 +
    m*c, rows, wcols]``: destination ``(dpod, dchip)`` at ``[dchip, 0]``
    when ``dpod`` is this chip's pod, else at ``[(g + dpod) % c, 1 +
    (dpod // c)*c + dchip]`` — static but for the chip's own pod ``g``,
    so one ``dynamic_update_slice`` a destination. Peer-pod slots with
    ``dpod >= p`` stay zero. Returns ``(intra, cross)``: ``intra[s]``
    the block pod mate ``s`` sent this chip, ``cross`` shaped [src chip,
    peer-pod rank, dst chip, row, word].

    Here and down to the delivery a block keeps its two dimensions: the
    stages permute, select and slice whole blocks by their leading
    indices and never fold ``rows`` into another dimension (on the chip
    that is a re-tiling of the whole buffer, PERF.md §6, PR 39)."""
    p, c, g, _, m = coords
    send_a = jnp.zeros((c, 1 + m * c, rows, wcols), dtype)
    for k in range(p * c):
        dpod, dchip = divmod(k, c)
        intra = dpod == g
        at = (jnp.where(intra, dchip, (g + dpod) % c),
              jnp.where(intra, 0, 1 + (dpod // c) * c + dchip), 0, 0)
        send_a = lax.dynamic_update_slice(send_a, block(k)[None, None], at)
    recv_a = lax.all_to_all(send_a, ici_axis, split_axis=0,
                            concat_axis=0, tiled=False)
    return recv_a[:, 0], recv_a[:, 1:].reshape(c, m, c, rows, wcols)


def _stage_b(tiles, coords, dcn_axis: str):
    """Stage B: ONE coalesced tile a pod pair over the DCN axis.
    ``tiles[k]`` is what this chip's pod staged on it for the peer pod
    of rank k (stage A's ``cross`` with the rank in front, or the coded
    body's coded chunks). The chip is the egress chip of the peer pods
    g' with ``(g + g') % c == i``, rank ``g' // c``: its tile for every
    other pod is zeros. Returns ``recv_b[g']``, the tile pod g' sent
    here."""
    p, c, g, i, m = coords
    mine = (g + jnp.arange(m * c)) % c == i
    send_b = jnp.where(mine.reshape(-1, *[1] * (tiles.ndim - 1)),
                       jnp.repeat(tiles, c, axis=0), 0)[:p]
    return lax.all_to_all(send_b, dcn_axis, split_axis=0,
                          concat_axis=0, tiled=False)


def _ingress_tiles(recv_b, coords):
    """The arrived tiles this chip is the INGRESS chip of: by stage B's
    formula those of the pods g' = ``((i - g) mod c) + k*c`` (rank k),
    the only populated blocks of ``recv_b`` — compacted to the m ranks,
    a rank past the last pod zeros. One ``dynamic_slice`` a rank, not a
    ``take``: the chip's compiler unrolls a gather of slices this size
    into hundreds of pieces (PERF.md §6, PR 39)."""
    p, c, g, i, m = coords
    first = (i - g) % c
    return jnp.stack([
        jnp.where(first + k * c < p,
                  lax.dynamic_index_in_dim(
                      recv_b, jnp.minimum(first + k * c, p - 1), 0, False), 0)
        for k in range(m)])


def _route_blocks(block, rows: int, wcols: int, dtype, coords,
                  dcn_axis: str, ici_axis: str):
    """One kind of block — a window of rows, or its count — through the
    hierarchical body's three stages to its delivery: ``[P, rows,
    wcols]``, block k what source device k's ``block(me)`` held. The
    four parts carry a jax.named_scope each, so a profile attributes
    their copies and collectives by stage."""
    p, c, g, _, _ = coords
    with jax.named_scope("exchange_stage_a"):
        intra, cross = _stage_a(block, rows, wcols, dtype, coords, ici_axis)
    with jax.named_scope("exchange_stage_b"):
        recv_b = _stage_b(jnp.swapaxes(cross, 0, 1), coords, dcn_axis)
    with jax.named_scope("exchange_stage_c"):
        # the arrived tiles are [rank, src chip, dst chip, row, word]:
        # destination chip in front, and recv_c is [ingress chip, rank,
        # src chip, row, word]
        send_c = jnp.transpose(_ingress_tiles(recv_b, coords),
                               (2, 0, 1, 3, 4))
        recv_c = lax.all_to_all(send_c, ici_axis, split_axis=0,
                                concat_axis=0, tiled=False)
    with jax.named_scope("exchange_assemble"):
        # source device (g', s)'s block is intra[s] when g' is this
        # chip's pod, else recv_c[(g + g') % c, g' // c, s]: P block
        # copies, one traced block index each
        out = jnp.zeros((p * c, rows, wcols), dtype)
        for k in range(p * c):
            g2, s = divmod(k, c)
            far = lax.dynamic_slice(recv_c, ((g + g2) % c, g2 // c, s, 0, 0),
                                    (1, 1, 1, rows, wcols))
            out = lax.dynamic_update_slice(
                out, jnp.where(g2 == g, intra[s][None],
                               far.reshape(1, rows, wcols)), (k, 0, 0))
        return out


def _tag_assemble(arrived, wcols, nd, capacity: int):
    """The coded body's delivery: tag - 1 IS the output row of the flat
    ``[P*capacity, W]`` layout (0 marks an empty slot), recv_counts from
    the tags' source devices. The coded body alone needs it: after its
    compaction a block's rows are no longer at their slots, so only a
    tag can place them (the plain staged rows never leave theirs:
    ``_route_blocks``)."""
    atag = arrived[:, wcols].astype(jnp.int32)
    valid = atag > 0
    idx = jnp.where(valid, atag - 1, nd * capacity)
    out = jnp.zeros((nd * capacity + 1, wcols), arrived.dtype)
    out = out.at[idx].set(arrived[:, :wcols],
                          mode="drop")[:nd * capacity]
    peer_dev = jnp.where(valid, (atag - 1) // capacity, nd)
    recv_counts = jnp.bincount(peer_dev, length=nd + 1)[:nd].astype(
        jnp.int32)
    return out, recv_counts


def hierarchical_round_body(w, d, q, lo, dcn_axis: str, ici_axis: str,
                            capacity: int):
    """The two-stage (pod-local + coalesced DCN) round body, for use
    INSIDE a shard_map over BOTH mesh axes. Same window semantics, same
    precondition (rows in destination order) and same delivery contract
    as :func:`window_round_body` — callers cannot tell which body ran
    except through the fabric accounting:

    - **stage A (ICI all_to_all):** the P destination windows are read
      as ``window_round_body`` reads them and re-bucketed by destination
      POD; an intra-pod window goes straight to its final chip, a
      cross-pod window to the ONE designated egress chip of its (pod,
      peer-pod) pair (``MeshTopology.egress_chip`` = ``(g + g') % c``,
      rotating pairs across chips);
    - **stage B (DCN all_to_all):** each populated egress chip moves
      ONE coalesced tile per peer pod — O(p^2) DCN messages per round
      instead of the flat body's O((p*c)^2) device pairs;
    - **stage C (ICI all_to_all):** the ingress chip hands the arrived
      windows to their final chips.

    The body moves rows in BLOCKS, never by row address: a window is
    placed whole in each staging buffer, so it arrives whole, its rows
    at their slots and zeros past its count, at a place the sender's and
    the receiver's mesh coordinates alone decide — the ``[P*capacity,
    W]`` delivery is P block copies and byte for byte the flat body's.
    Rows travel as their W words; ``recv_counts`` is the senders'
    ``send_counts`` carried as int32 along the rows' own route (the same
    placement at one integer a block, through the same three axes), as
    the flat body sends its counts beside its rows. Why blocks: tagging
    every row and scattering it twice by address was 86 % of the step on
    the chip (PERF.md §6, PR 39). That body is the reference
    tests/test_exchange_staged.py holds this one to.
    """
    coords = _pod_coords(dcn_axis, ici_axis)
    nd, wcols = coords[0] * coords[1], w.shape[1]
    send_counts, window = _window_reader(w, d, lo, nd, capacity)
    flat = _route_blocks(window, capacity, wcols, w.dtype, coords,
                         dcn_axis, ici_axis)
    recv_counts = _route_blocks(lambda k: send_counts[k].reshape(1, 1),
                                1, 1, jnp.int32, coords, dcn_axis, ici_axis)
    return flat.reshape(nd * capacity, wcols), recv_counts.reshape(nd)


def coded_round_body(w, d, q, lo, dcn_axis: str, ici_axis: str,
                     capacity: int, l_rows: int):
    """The CODED two-stage round body: same staging as
    :func:`hierarchical_round_body`, but the pod-pair DCN tile carries
    GF(2^8)-coded chunks instead of disjoint per-destination blocks
    (the Coded TeraSort multicast phase, arXiv:1702.04850):

    - **stage A** is the hierarchical staging (``_stage_a``: whole
      windows, cross-pod ones onto the pair's egress chip) on rows that
      carry a tag word — this body alone tags, because of the next step;
    - **encode:** the egress chip COMPACTS each destination chip's
      rows to the front of an ``l_rows``-row block (``l_rows`` is the
      host plan's padded chunk length — the plan guarantees every
      block fits) and multiplies the ``c`` blocks through the full-
      rank Cauchy matrix (uda_tpu.coding.gfjax), one coded chunk per
      member chip;
    - **stage B** moves ONE ``[c, l_rows]`` coded tile per pod pair
      over the DCN axis — the same O(p^2) coalescing, with the tile
      now ``c*l_rows`` rows instead of ``c^2*capacity`` slots (the
      compaction also shrinks the dense collective buffer);
    - **stage C** is an ICI ``all_gather``: every member receives
      every arrived tile (the broadcast that stands in for the CDC
      side information — charged to the ICI ledger by the planner)
      and decodes its OWN destination block with the inverse-matrix
      row of its chip index (``gfjax.gf_decode_row``, traced row).

    Tags ride INSIDE the coded words (the GF action is exact), so the
    final tag-indexed scatter (``_tag_assemble``) reproduces the flat
    layout precisely — byte-identity by construction, the same contract
    as the plain staged body, whose rows never leave their slots and
    need no tag. The tag is computed and decoded in int32, capping
    ``P * capacity`` at 2^31 - 1, which the host planner
    (parallel/planner.py plan_rounds) rejects loudly. ``l_rows`` must
    be positive and cover the biggest per-(pair, destination-chip)
    in-window block; the host plan guarantees both before dispatching
    here.
    """
    from uda_tpu.coding.gfjax import (coded_matrices, gf_decode_row,
                                      gf_matmul_words)

    # -- stage A: the SHARED staging (_stage_a), on rows that carry
    # their tag: src_device * capacity + in-window slot + 1 (a window's
    # rows past its count are zeroed whole, tag and all: 0 = empty)
    coords = p, c, g, i, m = _pod_coords(dcn_axis, ici_axis)
    nd, wcols = p * c, w.shape[1]
    wex = wcols + 1
    tag = ((g * c + i) * capacity + (q - lo) + 1).astype(w.dtype)
    _, window = _window_reader(jnp.concatenate([w, tag[:, None]], axis=1),
                               d, lo, nd, capacity)
    intra, cross = _stage_a(window, capacity, wex, w.dtype, coords,
                            ici_axis)
    # [src chip, peer-pod rank, dst chip, slot, word] -> destination-
    # block view [peer slot, dst chip, (src chip, slot), word]
    blocks_full = jnp.transpose(cross, (1, 2, 0, 3, 4)).reshape(
        m, c, c * capacity, wex)

    # -- compaction: populated rows (tag > 0) to the chunk front; the
    # plan guarantees rank < l_rows for every populated row, so the
    # trash row at l_rows only ever receives empties
    populated = blocks_full[:, :, :, wcols] > 0
    rank = jnp.cumsum(populated.astype(jnp.int32), axis=2) - 1
    idx = jnp.where(populated, rank, l_rows)
    mi = jnp.arange(m)[:, None, None]
    ci = jnp.arange(c)[None, :, None]
    blocks = jnp.zeros((m, c, l_rows + 1, wex), w.dtype)
    blocks = blocks.at[mi, ci, idx].set(blocks_full,
                                        mode="drop")[:, :, :l_rows]

    # -- encode: coded chunk t = XOR_j A[t, j] * block[j] (per peer
    # slot; A static, built at trace time from the static pod size)
    enc, dec = coded_matrices(c)
    coded = gf_matmul_words(enc, jnp.swapaxes(blocks, 0, 1))
    tiles = jnp.swapaxes(coded, 0, 1).reshape(m, c * l_rows, wex)

    # -- stage B (shared): one coded tile per pod pair over the DCN
    # axis, the arrived ones compacted to the ranks I ingress for
    compact = _ingress_tiles(_stage_b(tiles, coords, dcn_axis), coords)

    # -- stage C: pod-local broadcast of the arrived coded tiles —
    # every member needs the full chunk set to decode its block
    gathered = lax.all_gather(compact, ici_axis, axis=0, tiled=False)
    chunks = jnp.transpose(
        gathered.reshape(c, m, c, l_rows, wex),
        (2, 0, 1, 3, 4))                # [chunk t, ingress, slot, ...]

    # -- local decode: my destination block only (inverse row = my
    # chip index, traced — gf_decode_row combines with traced coeffs)
    mine = gf_decode_row(dec, i, chunks)

    # -- final assembly: tag - 1 IS the output row (shared)
    arrived = jnp.concatenate([
        intra.reshape(c * capacity, wex),
        mine.reshape(c * m * l_rows, wex)])
    return _tag_assemble(arrived, wcols, nd, capacity)


def round_wire_bytes(topology: MeshTopology, hierarchical: bool,
                     capacity: int, wcols: int, itemsize: int = 4) -> int:
    """Dense bytes one round's record collectives carry, summed over
    the chips: the static shapes of the ``all_to_all`` operands as the
    round bodies build them — ``send`` ``[P, capacity, W]`` a chip on
    the flat body; ``send_a``, ``send_b`` and ``send_c`` on the
    hierarchical one, W words a row (the coded body's tagged rows are
    not counted here: the fused step never runs it). Each chip's block
    to itself is inside (it is in the operand). Over the record bytes
    of the planner's ledger this is the padding the module's scope note
    speaks of, as a number (counter ``exchange.wire.bytes``)."""
    nd = topology.num_devices
    if not hierarchical:
        return nd * nd * capacity * wcols * itemsize
    p, c = topology.num_pods, topology.pod_size
    m = -(-p // c)                      # peer-pod slots per egress chip
    rows = (c * (capacity + m * c * capacity)       # send_a
            + p * c * c * capacity                  # send_b
            + c * m * c * capacity)                 # send_c
    return nd * rows * wcols * itemsize


def staged_block_copies(topology: MeshTopology, hierarchical: bool) -> int:
    """Block copies a chip a round standing where the hierarchical
    body's two row scatters stood: P windows placed in ``send_a``, P
    blocks delivered (counter ``exchange.staged.block_copies``); 0 on
    the flat body."""
    return 2 * topology.num_devices if hierarchical else 0


def run_round_body(w, d, q, lo, capacity: int, axis,
                   exchange_mode="flat", dcn_axis=None, ici_axis=None,
                   coded_l_rows=None):
    """The flat-vs-hierarchical-vs-coded body dispatch, for use INSIDE
    a shard_map body — the single branch shared by ``_round_impl``,
    ``distributed._sort_step`` and ``distributed._round_scatter``
    (fed the static kwargs of :func:`exchange_dispatch`), completing
    the one-definition contract: a new mode or body signature changes
    exactly here. ``exchange_mode="coded"`` needs the host plan's
    static chunk length (``coded_l_rows``); a coded dispatch WITHOUT
    one runs the plain staged body — the plan is what turns coding on
    per window (the fused single-round step has no plan and lands
    there by design)."""
    if exchange_mode == "coded" and coded_l_rows:
        return coded_round_body(w, d, q, lo, dcn_axis, ici_axis,
                                capacity, int(coded_l_rows))
    if exchange_mode in ("hierarchical", "coded"):
        return hierarchical_round_body(w, d, q, lo, dcn_axis, ici_axis,
                                       capacity)
    return window_round_body(w, d, q, lo, axis, capacity)


@partial(jax.jit, static_argnames=("capacity", "axis", "mesh",
                                   "exchange_mode", "dcn_axis",
                                   "ici_axis", "coded_l_rows"))
def _round_impl(words, dest, pos, round_index, mesh, axis, capacity,
                exchange_mode="flat", dcn_axis=None, ici_axis=None,
                coded_l_rows=None):
    # round_index is TRACED: one compiled program serves every round
    # (and, coded, every coded window — the plan's single coded_l_rows)
    @partial(shard_map, mesh=mesh,
             in_specs=(P(axis), P(axis), P(axis), P()),
             out_specs=(P(axis), P(axis)))
    def _go(w, d, q, r):
        flat, recv_counts = run_round_body(
            w, d, q, r[0] * capacity, capacity, axis,
            exchange_mode, dcn_axis, ici_axis, coded_l_rows)
        return flat, recv_counts.reshape(1, -1)

    return _go(words, dest, pos, round_index)


def exchange_round(layout: ShuffleLayout, capacity: int,
                   round_index: int, coded_l_rows: Optional[int] = None):
    """One windowed exchange round (single-stage, the two-stage
    hierarchical body when the layout resolved a pod topology, or the
    coded stage-B body when ``coded_l_rows`` carries the host plan's
    chunk length for a coded window).

    Returns ``(recv_words, recv_counts)``: per device, ``capacity`` rows
    from each peer (``recv_words`` row-block i = peer i's contribution,
    of which ``recv_counts[i]`` rows are valid).
    """
    dispatch = layout.dispatch()
    if coded_l_rows:
        dispatch = dict(dispatch, exchange_mode="coded",
                        coded_l_rows=int(coded_l_rows))
    return _round_impl(layout.words, layout.dest, layout.pos,
                       jnp.asarray([round_index], jnp.int32),
                       layout.mesh, layout.axis, capacity, **dispatch)


def execute_planned_window(win, plan, coded_exec, plain_exec):
    """The ONE coded-window dispatch, shared by ``shuffle_exchange``
    and ``distributed.distributed_sort_multiround`` (the same
    one-definition contract as :func:`run_round_body`): fire the
    decode-failure rung (failpoint site ``exchange.decode``, keyed
    ``round<i>`` — it fires BEFORE the coded body runs, so the
    fallback re-dispatches an untouched window), run ``coded_exec``
    for plan-approved windows with in-round fallback to
    ``plain_exec`` on a decode failure (counted
    ``exchange.decode.fallbacks``), and book the ledger for the body
    that ACTUALLY ran."""
    from uda_tpu.parallel.planner import record_executed_window

    if plan.coded and win.coded:
        decode_ok = True
        try:
            failpoint("exchange.decode", key=f"round{win.index}")
        except StorageError:
            metrics.add("exchange.decode.fallbacks")
            decode_ok = False
        if decode_ok:
            # OUTSIDE the try by design: the multiround caller's
            # coded executor consumes a DONATED accumulator — an
            # error escaping the coded body itself must propagate,
            # never re-dispatch the already-deleted buffer on the
            # plain path (the fallback contract covers decode
            # failures, which fire before the body runs)
            out = coded_exec()
            record_executed_window(win, plan, coded=True)
            return out
    out = plain_exec()
    record_executed_window(win, plan, coded=False)
    return out


def shuffle_exchange(words, dest, mesh: Mesh, axis: str,
                     capacity: int,
                     max_rounds: Optional[int] = None,
                     mode: str = "auto"):
    """Full exchange: as many rounds as the largest (src, dst) bucket
    needs. Returns ``(per_round_results, layout)`` where each round entry
    is the (recv_words, recv_counts) pair of exchange_round.

    The round schedule is data-dependent but *host*-decided (one count
    matrix readback per shuffle, analogous to the reference's per-MOF
    fetch bookkeeping) so every device executes the same static
    program: the planner (parallel/planner.py) derives every window
    from the counts matrix, skips globally-empty ones
    (``exchange.rounds.skipped``) and records the per-axis fabric
    accounting (``exchange.ici.bytes`` / ``exchange.dcn.bytes`` /
    ``exchange.dcn.messages``) for each executed round. ``mode``
    picks flat vs two-stage hierarchical vs coded dispatch (see
    :func:`resolve_exchange_mode`); with ``mode="coded"`` the plan
    decides per window whether the coded stage-B body runs (skew and
    single-destination pairs stay on the plain tile at zero coded
    overhead), a decode failure (failpoint ``exchange.decode``) falls
    back to the plain tile within the round, and coded windows
    additionally book ``exchange.dcn.coded.bytes`` /
    ``exchange.dcn.saved.bytes``.
    """
    from uda_tpu.parallel.planner import (plan_layout_rounds,
                                          record_plan_skips)

    layout = prepare_layout(words, dest, mesh, axis, mode)
    plan = plan_layout_rounds(layout, capacity)
    if max_rounds is not None and plan.planned > max_rounds:
        biggest = int(layout.counts.max()) if layout.counts.size else 0
        raise TransportError(
            f"skew needs {plan.planned} rounds (bucket {biggest} > "
            f"capacity {capacity} x {max_rounds}); raise capacity or "
            f"max_rounds")
    results = []
    for win in plan.windows:
        # injection site for exchange-plane faults (a failed collective
        # surfaces as TransportError, like a reference WC error)
        failpoint("exchange.round", key=f"round{win.index}")
        if layout.hierarchical:
            # stage-resolved rung: a fault in the cross-pod DCN stage
            # (arm with match:stageB) must surface exactly like a
            # whole-round collective failure
            failpoint("exchange.round", key=f"round{win.index}.stageB")
        results.append(execute_planned_window(
            win, plan,
            lambda: exchange_round(layout, capacity, win.index,
                                   plan.coded_l_rows),
            lambda: exchange_round(layout, capacity, win.index)))
    record_plan_skips(plan)
    return results, layout


def exchange_record_batches(batches_by_dest: Sequence[Sequence[RecordBatch]]
                            ) -> list[RecordBatch]:
    """Host-side variable-length exchange: ``batches_by_dest[src][dst]``
    -> per-dst concatenated batch. The byte-exact path for Hadoop
    records (and the oracle the device exchange is tested against)."""
    ndst = max((len(row) for row in batches_by_dest), default=0)
    out = []
    for dst in range(ndst):
        out.append(RecordBatch.concat(
            [row[dst] for row in batches_by_dest if dst < len(row)]))
    return out
