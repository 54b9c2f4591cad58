"""Distributed shuffle+merge: the flagship multi-chip step.

The TPU-native equivalent of UDA's whole reason to exist: the all-to-all
segment exchange between M map outputs and R reducers (reference
partition addressing jobid/mapid/reduceid, src/DataNet/RDMAClient.cc:
575-586, src/MOFServer/MOFServlet.cc:28-96) fused with the reduce-side
merge (src/Merger/MergeManager.cc) into ONE jitted SPMD program:

    local lexicographic sort -> partition (whole-key compares) ->
    all_to_all (ICI) -> merge of the P received sorted runs ->
    globally sorted, device-sharded output

The reference's own shape (maps sort, the reduce side merges what
streams in): each chip SORTS its rows first. A range partition is
monotone in the key, so sorted rows are already in destination order —
no argsort of the destinations, no ``take`` — and a (destination,
window) is a contiguous run of rows, which the round body
(parallel/exchange.py ``window_round_body``) copies into the send
buffer as one slice a destination. What a chip receives is P sorted
runs, one a source: the lanes engine merges them (log2 P merge passes,
ops/pallas_sort.py ``merge_lanes_runs``) where a sort of the whole
receive buffer stood.

Global order: destinations are monotone in key-prefix, so after the
exchange device d holds exactly range-partition d and the concatenation
of per-device sorted shards is the total order — the same contract as
the reference's per-reducer partition files, but computed in one XLA
program with no host round-trips.

Range splitters are WHOLE keys, as Hadoop's TotalOrderPartitioner
compares them: uniform edges handed in by the caller (keys known to be
uniform, the sort benchmark's Indy category), or — ``splitters=None``,
keys of unknown distribution, its Daytona category — quantiles of a
sample the program takes of its own input before it partitions, on the
device (TeraSort's ``TeraInputFormat.writePartitionFile`` without the
job-start host pass).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from uda_tpu.parallel import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from uda_tpu.ops.sort import resolve_sort_path
from uda_tpu.parallel.multihost import put_global, put_rows, zeros_global
from uda_tpu.utils.errors import TransportError
from uda_tpu.utils.metrics import metrics

__all__ = ["uniform_splitters", "sample_splitters", "distributed_sort_step",
           "distributed_sort_multiround", "DistributedSortResult",
           "SAMPLE_KEYS"]

# Keys sampled a step, over all chips, when the program chooses its own
# splitters: Hadoop TeraSort's mapreduce.terasort.partitions.sample
# (TeraInputFormat.writePartitionFile), 100,000.
SAMPLE_KEYS = 100_000

# numpy scalar, NOT jnp: a module-level jnp constant would materialize
# a device array at import time, initializing the XLA backend and
# breaking any later jax.distributed.initialize (multi-host bring-up)
_INVALID = np.uint32(0xFFFFFFFF)


def _lanes_interpret(payload_path: str, mesh: Mesh) -> bool:
    """Pallas interpret-mode flag for the Pallas engines, resolved
    EAGERLY off the MESH's device platform (CPU meshes — tests, dryruns
    — have no Mosaic lowering, even when the host's default backend is
    a TPU). False for "carry" so it never splits its jit cache."""
    return (payload_path != "carry"
            and mesh.devices.flat[0].platform == "cpu")


def uniform_splitters(num_partitions: int) -> np.ndarray:
    """Range splitters on the first key word for uniformly distributed
    keys (TeraSort's keyspace): partition i covers
    [i*2^32/P, (i+1)*2^32/P)."""
    edges = (np.arange(1, num_partitions, dtype=np.uint64)
             * (1 << 32)) // num_partitions
    return edges.astype(np.uint32)


def sample_splitters(sample_keys: np.ndarray,
                     num_partitions: int) -> np.ndarray:
    """The quantile rule on the host: the ``num_partitions - 1`` keys
    that cut a sorted sample into equal parts (the
    TotalOrderPartitioner analogue). ``sample_keys`` is any sample of
    keys, first words ``[m]`` or whole keys ``[m, K]``; the splitters
    come back in the same form. The program's own sampling stage
    (``_sampled_splitters``) applies this rule on the device; this is
    the reference the tests hold it to."""
    sample = np.asarray(sample_keys, dtype=np.uint32)
    if sample.shape[0] == 0:
        edges = uniform_splitters(num_partitions)
        return edges if sample.ndim == 1 else _whole_keys(edges,
                                                          sample.shape[1])
    cols = sample[:, None] if sample.ndim == 1 else sample
    sample = sample[np.lexsort(cols.T[::-1])]
    idx = (np.arange(1, num_partitions) * len(sample)) // num_partitions
    return sample[np.minimum(idx, len(sample) - 1)]


def _whole_keys(splitters, num_keys: int) -> np.ndarray:
    """Splitters as whole keys ``uint32[P-1, num_keys]``: first-word
    edges ``[P-1]`` become ``(edge, 0, ..., 0)``, the least key with
    that first word, which partitions as the edge alone did."""
    spl = np.asarray(splitters, dtype=np.uint32)
    if spl.ndim == 2:
        if spl.shape[1] != num_keys:
            raise ValueError(f"splitters of {spl.shape[1]} words for keys "
                             f"of {num_keys}")
        return spl
    whole = np.zeros((spl.shape[0], num_keys), np.uint32)
    whole[:, 0] = spl
    return whole


def _partition(w, spl, num_keys: int):
    """Destination of every row of ``w``: the number of splitters (whole
    keys ``[P-1, num_keys]``, ascending) at or below the row's key in
    lexicographic order of the ``num_keys`` key words — THE partition,
    for splitters of either origin and for both routes. One elementwise
    pass a splitter over the key columns (P is the chip count: a
    search would gather, this fuses)."""
    dest = jnp.zeros(w.shape[0], jnp.int32)
    last = num_keys - 1
    for j in range(spl.shape[0]):
        at_or_above = w[:, last] >= spl[j, last]
        for c in reversed(range(last)):
            at_or_above = ((w[:, c] > spl[j, c])
                           | ((w[:, c] == spl[j, c]) & at_or_above))
        dest = dest + at_or_above.astype(jnp.int32)
    return dest


def _sample_size(n_local: int, p: int) -> int:
    """Rows a chip samples: its share of ``SAMPLE_KEYS``, or all it has."""
    return min(n_local, max(1, SAMPLE_KEYS // p))


def _sample_rows(n_local: int, p: int) -> np.ndarray:
    """The local rows a chip samples, evenly spaced — a function of
    (n, p) alone, so a step is a pure function of its input."""
    take = _sample_size(n_local, p)
    return ((np.arange(take, dtype=np.int64) * n_local + n_local // 2)
            // take).astype(np.int32)


def _count_sample(n: int, p: int) -> None:
    metrics.add("exchange.sample.keys", p * _sample_size(n // p, p))


def _replicated(x, mesh):
    """One copy of a value every chip computed alike."""
    return lax.with_sharding_constraint(x, NamedSharding(mesh, P()))


def _sampled_splitters(w, axis, num_keys: int, payload_path: str,
                       interpret: bool):
    """The sampling stage, INSIDE a shard_map body: each chip's
    systematic sample of its own rows' whole keys, gathered to every
    chip, sorted by the step's own engine (``_sort_valid_rows``: a
    3-operand ``lax.sort`` of 100,000 keys alone took a minute to
    compile for a v5e, PR 34), cut at the P-1 quantiles
    (``sample_splitters``' rule). Returns ``uint32[P-1, num_keys]``,
    the same on every chip (an ``all_gather``'s result is typed per-chip
    all the same: it leaves the body as one copy a chip, ``_replicated``
    keeps one)."""
    with jax.named_scope("exchange_sample"):
        p = lax.psum(1, axis)
        rows = _sample_rows(w.shape[0], p)
        mine = jnp.stack([jnp.take(w[:, c], rows) for c in range(num_keys)],
                         axis=1)
        sample = lax.all_gather(mine, axis).reshape(-1, num_keys)
        m = sample.shape[0]
        sample = _sort_valid_rows(sample, jnp.ones(m, jnp.bool_), num_keys,
                                  payload_path, interpret)
        idx = np.minimum((np.arange(1, p, dtype=np.int64) * m) // p, m - 1)
        return jnp.take(sample, idx.astype(np.int32), axis=0)


class DistributedSortResult:
    """Device-sharded sorted output of one distributed sort step."""

    def __init__(self, words: jax.Array, valid_counts: jax.Array,
                 send_overflow: jax.Array, totals=None, splitters=None,
                 input_rows: int = 0, book_fabric=None):
        self.words = words              # [P*cap_total rows, W] sharded
        self.valid_counts = valid_counts  # [P] valid rows per device
        self.send_overflow = send_overflow  # [P] records dropped (0 = ok)
        # the whole keys [P-1, K] the step partitioned by (replicated):
        # shard d holds the keys in [splitters[d-1], splitters[d])
        self.splitters = splitters
        # replicated int32 vector, (records dropped, largest shard's
        # valid rows) and, from a fused step on a pod mesh, every chip's
        # recv_counts behind them: readable on EVERY process of a
        # multi-host mesh (the per-device vectors are not addressable
        # cross-process), and ONE readback for all of it
        self._totals = totals
        self._input_rows = input_rows   # n, the gauge's denominator
        # what books the fused step's fabric from those recv_counts
        # (_book_fused_fabric with the step's statics bound)
        self._book_fabric = book_fabric
        self._read = False

    def overflow(self) -> int:
        """Records the step dropped (0 = every bucket fit its window).
        The first call is the readback of the replicated totals, and
        what hangs on them happens there, once: the
        ``exchange.shard.max_permille`` gauge and, for a fused step on
        a pod mesh whose result is KEPT (nothing dropped; a rerun
        through the rounds books its own windows), the step's fabric —
        ``_book_fused_fabric``. A result whose totals are never read
        books nothing."""
        if self._totals is None:
            return int(np.asarray(self.send_overflow).sum())
        if not self._read:
            self._totals, self._read = np.asarray(self._totals), True
            metrics.gauge("exchange.shard.max_permille",
                          1000.0 * int(self._totals[1])
                          / max(1, self._input_rows))
            if self._book_fabric is not None and self._totals[0] == 0:
                self._book_fabric(self._totals[2:])
        return int(self._totals[0])

    def check(self) -> None:
        total = self.overflow()
        if total != 0:
            detail = ""
            if self.send_overflow.is_fully_addressable:
                over = np.asarray(self.send_overflow)
                detail = f" on devices {np.nonzero(over)[0].tolist()}"
            raise TransportError(
                f"exchange capacity overflow{detail} ({total} records); "
                "raise capacity or use the multi-round path")


def _merges_runs(payload_path: str) -> bool:
    """Whether the engine combines sorted runs by merging them; the
    others sort them again (same answer: their sort is stable)."""
    return payload_path == "lanes"


def _book_fused_fabric(recv_counts, topology, hierarchical: bool,
                       capacity: int, wcols: int, itemsize: int) -> None:
    """Book one kept fused step's fabric as ONE planned window, through
    the rounds' own definitions: ``recv_counts`` — every chip's
    ``recv_counts`` as the step gathered them, destination-major — is
    the (source chip, destination chip) count matrix transposed, which
    ``plan_rounds`` turns into intra-pod rows, staging hops, cross-pod
    rows and pod-pair messages and ``record_window_metrics`` lands in
    ``exchange.ici.bytes`` / ``exchange.dcn.bytes`` /
    ``exchange.dcn.messages``. Beside them ``exchange.wire.bytes``: the
    dense bytes of the step's collectives (``round_wire_bytes``), and
    ``exchange.staged.block_copies``: the hierarchical body's block
    copies a chip (``staged_block_copies``; 0 on the flat body)."""
    from uda_tpu.parallel.exchange import (round_wire_bytes,
                                           staged_block_copies)
    from uda_tpu.parallel.planner import plan_rounds, record_window_metrics

    nd = topology.num_devices
    counts = np.asarray(recv_counts).reshape(nd, nd).T      # [src, dst]
    record_bytes = wcols * itemsize
    for win in plan_rounds(counts, capacity, topology, record_bytes,
                           hierarchical).windows:
        record_window_metrics(win, record_bytes)
    metrics.add("exchange.wire.bytes",
                round_wire_bytes(topology, hierarchical, capacity, wcols,
                                 itemsize))
    metrics.add("exchange.staged.block_copies",
                staged_block_copies(topology, hierarchical))


def _carried_passes(payload_path: str, n: int, p: int, capacity: int) -> int:
    """Merge passes of a chip's two sort stages in the fused step — the
    local sort of its ``n`` rows, the combine of the ``p`` runs of
    ``capacity`` rows it receives — whose kernel carried its merge-path
    split from tile to tile (ops/pallas_sort.py); 0 on the engine that
    runs no such pass."""
    from uda_tpu.ops import pallas_sort

    if payload_path == "carry":
        return 0
    return pallas_sort.sort_passes(n) + (
        pallas_sort.runs_passes(p) if _merges_runs(payload_path)
        else pallas_sort.sort_passes(p * capacity))


def _sort_valid_rows(flat, valid, num_keys, payload_path, interpret=False,
                     run_len=None):
    """Stable local sort of ``flat``'s rows by the first ``num_keys``
    columns, with ``valid``-masked rows forced past every real key (the
    fused step's local sort and its receive side, the sampling stage and
    the multi-round accumulator sort).

    ``run_len``: ``flat`` is already sorted runs of that many rows each —
    a run's valid rows first, ascending, equal keys in the order to
    keep, as the fused step's receive buffer is (one run a source chip).
    The answer is the same with or without it, equal keys by (run,
    row); an engine that can (``_merges_runs``) merges the runs instead
    of sorting the rows.

    payload_path="lanes": the Pallas bitonic pipeline
    (ops.pallas_sort.sort_lanes) — bounded compile (two Mosaic kernels
    regardless of n and width) AND streaming payload movement; the TPU
    default. "keys8": same pipeline on an 8-row keys-only view plus one
    global XLA payload gather (see _sort_valid_rows_lanes). The (masked
    keys, invalid flag) sort key rides as lanes rows, stability via the
    pipeline's arrival tie-break, so equal-key order is IDENTICAL to
    the lax.sort path below. "carry": all record columns ride the sort
    network (XLA variadic-sort compile time grows superlinearly in
    operand count — minutes on the TPU); the CPU default."""
    if payload_path != "carry":
        return _sort_valid_rows_lanes(
            flat, valid, num_keys, interpret, keys8=payload_path == "keys8",
            run_len=run_len if _merges_runs(payload_path) else None)
    keycols = tuple(jnp.where(valid, flat[:, i], _INVALID)
                    for i in range(num_keys))
    invalid_last = jnp.where(valid, 0, 1)
    payload = tuple(flat[:, i] for i in range(flat.shape[1]))
    sorted_ops = lax.sort((*keycols, invalid_last, *payload),
                          num_keys=num_keys + 1, is_stable=True)
    return jnp.stack(sorted_ops[num_keys + 1:], axis=1)


def _sort_valid_rows_lanes(flat, valid, num_keys, interpret, keys8=False,
                           run_len=None):
    """Lanes-path body of _sort_valid_rows: pack rows into the [32, n]
    lanes layout with sort key (masked key words, invalid flag), pad the
    lane count to a power of two with +inf-key lanes, run the Pallas
    pipeline, unpack the payload rows. With ``run_len`` the same matrix,
    unpadded — its runs are sorted by that very sort key, a run's
    invalid rows behind its valid ones — goes through the pipeline's
    merge passes alone (``merge_lanes_runs`` pads as it needs).

    Order parity with the lax.sort path: identical sort key, and the
    pipeline's arrival-index tie-break == their stable row order. The
    padding lanes share the invalid rows' (+inf, 1) key but have LARGER
    arrival indices than every real lane, so they sort strictly after
    all real rows and truncating back to n lanes drops exactly them."""
    from uda_tpu.ops import pallas_sort

    n, wcols = flat.shape
    first_pay = num_keys + 1             # payload starts past the flag row
    tb = pallas_sort.TB_ROW_DEFAULT
    npad, tile = (n, 1024) if run_len else pallas_sort.pad_pow2(n, 1024)
    keyrows = jnp.stack([jnp.where(valid, flat[:, i], _INVALID)
                         for i in range(num_keys)]
                        + [jnp.where(valid, jnp.uint32(0), jnp.uint32(1))])
    # padding lanes (n..npad) keep _INVALID in the flag row too: (keys
    # +inf, flag +inf) sorts strictly after real invalid lanes' (keys
    # +inf, flag 1), so no arrival-index comparison against padding
    # ever decides a real lane's position
    if keys8:
        # keys8 engine: the whole cascade runs on an 8-row keys-only
        # array (4x less VPU/HBM work per stage than the 32-row
        # pipeline) and the payload never stages into a lanes matrix at
        # all — it moves ONCE, a global XLA lane gather straight off
        # ``flat`` (minor-dim layout, no lane padding). Same sort key
        # and tie-break as the full-width pipeline, so equal-key order
        # is identical; record width is unconstrained (no 32-row limit).
        k8 = num_keys + 1                # masked keys + invalid flag
        if k8 > 7:
            raise ValueError(
                f"num_keys={num_keys} does not fit the 8-row keys view; "
                "use payload_path='lanes'")
        base = jnp.full((k8, npad), _INVALID, jnp.uint32)
        keyr = lax.dynamic_update_slice(base, keyrows, (0, 0))
        # the n real lanes sort strictly before the padding, so the
        # first n arrival indices all reference real rows of flat
        _, perm = pallas_sort.keys8_sort_perm(keyr, tile=tile,
                                              interpret=interpret)
        return jnp.take(flat.T, perm[:n], axis=1,
                        unique_indices=True, mode="clip").T
    if first_pay + wcols > tb:
        raise ValueError(
            f"record width {wcols} + {num_keys} keys does not fit the "
            f"{pallas_sort.ROWS}-row lanes layout; use payload_path="
            "'keys8'")
    mat = jnp.full((pallas_sort.ROWS, npad), _INVALID, jnp.uint32)
    mat = lax.dynamic_update_slice(mat, keyrows, (0, 0))
    mat = lax.dynamic_update_slice(mat, flat.T, (first_pay, 0))
    if run_len:
        out = pallas_sort.merge_lanes_runs(mat, run_len, num_keys + 1, tb,
                                           tile, interpret)
    else:
        out = pallas_sort.sort_lanes(mat, num_keys=num_keys + 1, tb_row=tb,
                                     tile=tile, interpret=interpret)
    return out[first_pay:first_pay + wcols, :n].T


@partial(jax.jit, static_argnames=("mesh", "axis", "capacity", "num_keys",
                                   "payload_path", "interpret",
                                   "exchange_mode", "dcn_axis",
                                   "ici_axis", "sample", "pod_counts"))
def _sort_step(words, splitters, mesh, axis, capacity, num_keys,
               payload_path="carry", interpret=False,
               exchange_mode="flat", dcn_axis=None, ici_axis=None,
               sample=False, pod_counts=False):
    """The fused step: sort, partition, exchange, combine. Each chip
    sorts its own rows (stable by input order), so they are in
    destination order with no permutation; the round body sends each
    destination its window of them, and a chip receives P sorted runs —
    block k of the receive buffer is source k's, ``recv_counts[k]`` rows
    and zeros after them — which the last stage combines by
    ``_sort_valid_rows(..., run_len=capacity)``: a merge of the runs on
    the lanes engine, a stable sort of them on the others. Equal keys
    come out by source chip, then source order: global input order.

    ``splitters``: whole keys ``uint32[P-1, num_keys]``, replicated;
    with ``sample`` they are ignored and the program takes its own from
    the unsorted ``words`` (``_sampled_splitters``) before anything else.
    Returns the sorted shards, their valid counts, the per-device
    overflow, the replicated ``(overflow, largest shard)`` pair and the
    splitters the step partitioned by. ``pod_counts`` (a mesh with a
    pod structure; static, so a flat mesh's program is the one it
    always was): every chip's ``recv_counts`` ride behind the pair,
    ``P x P`` integers destination-major — the count matrix the host
    books the step's fabric from (``_book_fused_fabric``)."""
    # check_vma is ON everywhere except interpret mode (which only the
    # Pallas engines on a CPU mesh ever set, _lanes_interpret): the
    # Pallas interpreter expands pallas_call into eval_jaxpr whose
    # grid-machinery dynamic_slice mixes replicated block indices with
    # varying operands — an emulator limitation, not a property of the
    # compiled kernel (minimal repro: scripts/repro_check_vma.py). The
    # compiled path traces clean: a merge pass is one pallas_call on
    # the varying data and three replicated scalars, and its output
    # carries the data's vma (ops/pallas_sort._uint32_struct).
    @partial(shard_map, mesh=mesh, in_specs=(P(axis), P()),
             out_specs=(P(axis),) * (5 if pod_counts else 4),
             check_vma=not interpret)
    def _go(w, spl):
        from uda_tpu.parallel.exchange import run_round_body

        p = lax.psum(1, axis)
        n, wcols = w.shape
        # 0. splitters from the input itself, when none were handed in
        spl = _sampled_splitters(w, axis, num_keys, payload_path,
                                 interpret) if sample else spl[0]
        # 1. local sort first (stable by input order)
        sw = _sort_valid_rows(w, jnp.ones(n, jnp.bool_), num_keys,
                              payload_path, interpret)
        # 2. partition: monotone in the whole key, so the sorted rows
        # are in destination order as they stand
        sd = _partition(sw, spl, num_keys)
        counts = jnp.bincount(sd, length=p).astype(jnp.int32)
        starts = jnp.concatenate([jnp.zeros(1, jnp.int32),
                                  jnp.cumsum(counts)[:-1].astype(jnp.int32)])
        pos = jnp.arange(n, dtype=jnp.int32) - jnp.take(starts, sd)
        # 3. single-round exchange at window base 0 (the shared round
        # bodies of parallel/exchange.py; overflow — rows past the
        # credit window — is reported, not silently lost)
        overflow = jnp.sum(jnp.maximum(counts - capacity, 0))
        flat, recv_counts = run_round_body(sw, sd, pos, 0, capacity,
                                           axis, exchange_mode,
                                           dcn_axis, ici_axis)
        # 4. combine the P sorted runs, one a source: invalid rows
        # forced past every real key
        slot = jnp.arange(capacity, dtype=jnp.int32)
        valid = (slot[None] < recv_counts[:, None]).reshape(p * capacity)
        out = _sort_valid_rows(flat, valid, num_keys, payload_path,
                               interpret, run_len=capacity)
        nvalid = jnp.sum(recv_counts)
        outs = out, nvalid[None], overflow[None], spl[None]
        return (*outs, recv_counts[None]) if pod_counts else outs

    out, nvalid, overflow, spl, *recv = _go(words, splitters[None])
    # replicated totals: host-readable on every process of a multi-host
    # mesh, where the per-device vectors are not addressable
    totals = jnp.stack([jnp.sum(overflow), jnp.max(nvalid)])
    if pod_counts:
        totals = _replicated(
            jnp.concatenate([totals, recv[0].reshape(-1)]), mesh)
    return out, nvalid, overflow, totals, _replicated(spl[0], mesh)


def distributed_sort_step(words, splitters, mesh: Mesh, axis: str,
                          capacity: int, num_keys: int,
                          payload_path: str = "auto",
                          multiround: str = "auto",
                          exchange_mode: str = "auto"
                          ) -> DistributedSortResult:
    """Run the fused step: each chip sorts its rows, partitions them
    by the splitters, exchanges one window a destination and combines
    the P sorted runs it receives (``_sort_step``; counter
    ``exchange.merge.runs``: the runs a chip merged, P on the lanes
    engine, 0 on an engine that sorts them again; counter
    ``sort.passes.carried``: ``_carried_passes``). On a mesh with a pod
    structure the step also books its fabric — ``exchange.ici.bytes``,
    ``exchange.dcn.bytes``, ``exchange.dcn.messages`` by the round
    planner's definitions, and ``exchange.wire.bytes`` — when its
    result is kept and its totals are read
    (``DistributedSortResult.overflow``; under ``multiround="auto"``
    that read is made here).

    ``words``: uint32[N, W] records (rows sharded over ``axis``; the
    first ``num_keys`` columns are the big-endian key words).
    ``splitters``: the P-1 range splitters, ascending — whole keys
    ``uint32[P-1, num_keys]``, or first-word edges ``[P-1]``
    (``uniform_splitters``), read as the whole keys ``(edge, 0, ..)``;
    destination = the number of splitters at or below the row's whole
    key. ``None``: keys of unknown distribution — the program samples
    ``SAMPLE_KEYS`` whole keys of its input on the device (each chip an
    even stride of its own rows), gathers and sorts them and partitions
    by their P-1 quantiles, inside the same fused program; a key never
    straddles two shards, so the largest shard is at most
    ``N * (1/P + h + e)``, ``h`` the most frequent key's share and ``e``
    the sampling error. The splitters used ride with the result.
    ``axis``: one mesh axis name, or a TUPLE of axis names for
    multi-pod meshes — e.g. ``("dcn", "shuffle")`` on a (pods, chips)
    mesh shards rows over both; results are byte-identical to the flat
    single-axis mesh of the same device order.
    ``exchange_mode``: fabric dispatch for multi-pod meshes —
    ``"auto"`` (default) runs the two-stage hierarchical round body
    (pod-local all_to_all, ONE coalesced DCN tile per pod pair, pod-
    local delivery — parallel/exchange.py) whenever the mesh
    has a DCN-tagged outer axis with >1 pod of >1 chip; ``"flat"``
    forces the single-stage body (the A/B baseline, where XLA routes
    one global all_to_all per axis); ``"hierarchical"`` demands a pod
    mesh. ``"coded"`` arms the coded multicast stage B on the WINDOWED
    path: the fused single-round attempt runs the plain staged body
    (coding is a per-window host-plan decision and the fused program
    has no plan), while the multiround path codes every window the
    plan approves — so ``multiround="always"`` is the fully-coded
    entry and the auto overflow re-run inherits it.
    ``capacity``: per-(src, dst) records per round — the credit window.
    ``payload_path``: the local sort's engine, one of
    ops.sort.SORT_PATHS or "auto" (ops.sort.resolve_sort_path:
    operand-carry on CPU, the Pallas "lanes" pipeline on TPU; see
    _sort_valid_rows for the trade-offs).
    ``multiround``: skew completion policy. "auto" (default) runs the
    fused single-round program and, if any (src, dst) bucket overflowed
    the credit window, re-runs the shuffle through the windowed
    multi-round exchange — the backlog-drain guarantee of the
    reference's credit flow (RDMAComm.cc:707-752: no-credit sends queue
    on the backlog and drain as credits return, so ANY skew eventually
    completes). "never" reports overflow in the result (caller handles
    it); "always" skips the fused attempt. The rounds partition by the
    SAME splitters as the fused attempt did (sampled once).
    """
    from uda_tpu.parallel.exchange import (exchange_dispatch,
                                           resolve_exchange_mode)

    payload_path = resolve_sort_path(payload_path)
    if multiround not in ("auto", "never", "always"):
        raise ValueError(f"unknown multiround policy {multiround!r}")
    topo, hier, _coded = resolve_exchange_mode(mesh, axis, exchange_mode)
    if multiround == "always":
        return distributed_sort_multiround(words, splitters, mesh, axis,
                                           capacity, num_keys, payload_path,
                                           exchange_mode)
    words = put_rows(words, mesh, axis)
    p = topo.num_devices
    sample = splitters is None
    splitters_dev = put_global(
        np.zeros((p - 1, num_keys), np.uint32) if sample
        else _whole_keys(splitters, num_keys), NamedSharding(mesh, P()))
    out, nvalid, overflow, totals, used = _sort_step(
        words, splitters_dev, mesh, axis, capacity, num_keys, payload_path,
        interpret=_lanes_interpret(payload_path, mesh), sample=sample,
        pod_counts=topo.hierarchical, **exchange_dispatch(topo, hier))
    if sample:
        _count_sample(int(words.shape[0]), p)
    # sorted runs a chip's receive side merged: one a source chip; 0,
    # not nothing, where the last stage sorted its buffer from scratch
    metrics.add("exchange.merge.runs",
                p if _merges_runs(payload_path) else 0)
    metrics.add("sort.passes.carried",
                _carried_passes(payload_path, int(words.shape[0]) // p,
                                p, capacity))
    metrics.add("exchange.fused.overflow_reruns", 0)
    res = DistributedSortResult(
        out, nvalid, overflow, totals, used, int(words.shape[0]),
        partial(_book_fused_fabric, topology=topo, hierarchical=hier,
                capacity=capacity, wcols=int(words.shape[1]),
                itemsize=words.dtype.itemsize)
        if topo.hierarchical else None)
    if multiround == "auto" and res.overflow() != 0:
        metrics.add("exchange.fused.overflow_reruns")
        return distributed_sort_multiround(words, used, mesh, axis,
                                           capacity, num_keys, payload_path,
                                           exchange_mode)
    return res


@partial(jax.jit, static_argnames=("mesh", "axis", "capacity",
                                   "exchange_mode", "dcn_axis",
                                   "ici_axis", "coded_l_rows"),
         donate_argnames=("acc",))
def _round_scatter(words, dest, pos, acc, colbase, r, mesh, axis, capacity,
                   exchange_mode="flat", dcn_axis=None, ici_axis=None,
                   coded_l_rows=None):
    """One windowed exchange round scattered into the accumulator.

    The accumulator (donated: updated in place across rounds) holds each
    device's final shard grouped by (src peer, in-bucket arrival):
    the row from peer s with in-bucket position q lands at
    ``colbase[s] + q``. Rows outside this round's window or past a
    peer's bucket count scatter to the drop sentinel. ``r`` is TRACED,
    so ONE compiled program serves every round. On hierarchical meshes
    the round runs the staged two-stage body — identical delivery
    contract, so the scatter below is dispatch-blind.
    """

    from uda_tpu.parallel.exchange import run_round_body

    @partial(shard_map, mesh=mesh,
             in_specs=(P(axis), P(axis), P(axis), P(axis), P(axis), P()),
             out_specs=P(axis))
    def _go(w, d, q, acc, cb, rr):
        p = lax.psum(1, axis)
        lo = rr[0] * capacity
        flat, recv_counts = run_round_body(w, d, q, lo, capacity, axis,
                                           exchange_mode, dcn_axis,
                                           ici_axis, coded_l_rows)
        row = jnp.arange(p * capacity, dtype=jnp.int32)
        peer = row // capacity
        slot = row % capacity
        valid = slot < jnp.take(recv_counts, peer)
        idx = jnp.where(valid, jnp.take(cb[0], peer) + lo + slot,
                        acc.shape[0])
        return acc.at[idx].set(flat, mode="drop")

    return _go(words, dest, pos, acc, colbase, r[None])


@partial(jax.jit, static_argnames=("mesh", "axis", "num_keys",
                                   "payload_path", "interpret"))
def _sort_shard(acc, nvalid, mesh, axis, num_keys, payload_path,
                interpret=False):
    """Local stable sort of the accumulated shard. The accumulator is
    already in (src peer, arrival) order, so a stable sort by (keys,
    valid flag) reproduces exactly the fused single-round program's
    equal-key order."""

    # same interpret-mode-only checker exception as _sort_step
    @partial(shard_map, mesh=mesh, in_specs=(P(axis), P(axis)),
             out_specs=P(axis),
             check_vma=not interpret)
    def _go(a, nv):
        row = jnp.arange(a.shape[0], dtype=jnp.int32)
        return _sort_valid_rows(a, row < nv[0], num_keys, payload_path,
                                interpret)

    return _go(acc, nvalid)


@partial(jax.jit, static_argnames=("mesh", "axis", "num_keys",
                                   "payload_path", "interpret"))
def _sample_step(words, mesh, axis, num_keys, payload_path, interpret):
    """The sampling stage alone, for a sort that skips the fused attempt
    (multiround="always"): replicated ``uint32[P-1, num_keys]``."""
    spl = shard_map(
        lambda w: _sampled_splitters(w, axis, num_keys, payload_path,
                                     interpret)[None],
        mesh=mesh, in_specs=P(axis), out_specs=P(axis),
        check_vma=not interpret)(words)
    return _replicated(spl[0], mesh)


def distributed_sort_multiround(words, splitters, mesh: Mesh, axis: str,
                                capacity: int, num_keys: int,
                                payload_path: str = "auto",
                                exchange_mode: str = "auto"
                                ) -> DistributedSortResult:
    """Skew-proof distributed sort: windowed multi-round exchange
    scattered into a shard-sized accumulator, then one local sort
    (scatter-then-sort, where the fused step sorts first and merges:
    the rounds deliver a bucket piecewise, in arrival order).

    The round schedule comes from the gathered count matrix (one host
    readback per shuffle, planned by parallel/planner.py — globally-
    empty windows are skipped and the per-axis ICI/DCN accounting is
    recorded per executed round), so every (src, dst) bucket — however
    skewed — drains completely: the TPU-native equivalent of the
    reference's credit backlog (reference src/DataNet/RDMAComm.cc:
    707-752, drained in RDMAClient.cc:64-92). Peak memory per device is
    O(largest destination shard + P x capacity): each round's delivery
    is compacted into the accumulator immediately (donated buffer), so
    nothing scales with the round count. ``splitters`` as in
    ``distributed_sort_step``; the partition is the same function.
    """
    from uda_tpu.parallel.exchange import (execute_planned_window,
                                           prepare_layout)
    from uda_tpu.parallel.planner import (plan_layout_rounds,
                                          record_plan_skips)

    payload_path = resolve_sort_path(payload_path)
    p = int(np.prod(list(mesh.shape.values())))
    spec = NamedSharding(mesh, P(axis))
    words = put_rows(words, mesh, axis)
    if splitters is None:
        splitters_dev = _sample_step(
            words, mesh, axis, num_keys, payload_path,
            _lanes_interpret(payload_path, mesh))
        _count_sample(int(words.shape[0]), p)
    else:
        splitters_dev = put_global(_whole_keys(splitters, num_keys),
                                   NamedSharding(mesh, P()))

    @partial(shard_map, mesh=mesh, in_specs=(P(axis), P()),
             out_specs=P(axis))
    def _dests(w, spl):
        return _partition(w, spl[0], num_keys)

    dest = _dests(words, splitters_dev[None])
    layout = prepare_layout(words, dest, mesh, axis, exchange_mode)
    counts = layout.counts                      # [src, dst]
    plan = plan_layout_rounds(layout, capacity)
    # destination-side layout: shard sized to the largest destination,
    # rows grouped by (src, in-bucket arrival)
    colbase = np.zeros((p, p), np.int32)        # [dst, src] exclusive cumsum
    colbase[:, 1:] = np.cumsum(counts.T[:, :-1], axis=1)
    per_dst = counts.sum(axis=0).astype(np.int64)
    shard_rows = max(int(per_dst.max()), 1)
    acc = zeros_global((p * shard_rows, int(words.shape[1])), np.uint32,
                       spec)
    colbase_dev = put_global(colbase, spec)
    dispatch = layout.dispatch()
    for win in plan.windows:
        # the shared coded-window dispatch (decode-failure rung +
        # in-round fallback + coded-vs-plain ledger; the exchange.
        # decode failpoint fires BEFORE the scatter runs, so the
        # fallback re-dispatches the untouched donated accumulator)
        acc = execute_planned_window(
            win, plan,
            lambda: _round_scatter(
                layout.words, layout.dest, layout.pos, acc,
                colbase_dev, jnp.int32(win.index), mesh, axis,
                capacity, **dict(dispatch, exchange_mode="coded",
                                 coded_l_rows=plan.coded_l_rows)),
            lambda: _round_scatter(layout.words, layout.dest,
                                   layout.pos, acc, colbase_dev,
                                   jnp.int32(win.index), mesh, axis,
                                   capacity, **dispatch))
    record_plan_skips(plan)
    # the rounds deliver piecewise into the accumulator: sorted whole
    metrics.add("exchange.merge.runs", 0)
    nvalid = put_global(per_dst.astype(np.int32), spec)
    out = _sort_shard(acc, nvalid, mesh, axis, num_keys, payload_path,
                      interpret=_lanes_interpret(payload_path, mesh))
    overflow = put_global(np.zeros(p, np.int32), spec)
    return DistributedSortResult(
        out, nvalid, overflow, np.array([0, per_dst.max()], np.int32),
        splitters_dev, int(words.shape[0]))
