"""Multi-chip data plane (the DataNet/ layer of SURVEY §1, rebuilt as
mesh collectives): mesh helpers, windowed all-to-all exchange, fused
distributed sort step."""

# first, so ``from uda_tpu.parallel import shard_map`` works from inside
# the submodules below during package init
from jax import shard_map

from uda_tpu.parallel.bytes_exchange import (ExchangeFetchClient,
                                             exchange_blobs)
from uda_tpu.parallel.distributed import (DistributedSortResult,
                                          distributed_sort_step,
                                          sample_splitters,
                                          uniform_splitters)
from uda_tpu.parallel.exchange import (ShuffleLayout, exchange_record_batches,
                                       exchange_round, prepare_layout,
                                       resolve_exchange_mode,
                                       shuffle_exchange)
from uda_tpu.parallel.mesh import (SHUFFLE_AXIS, MeshTopology, make_mesh,
                                   mesh_from_config, mesh_topology,
                                   shard_spec)
from uda_tpu.parallel.planner import RoundPlan, WindowPlan, plan_rounds

__all__ = ["DistributedSortResult", "distributed_sort_step",
           "sample_splitters", "uniform_splitters", "ShuffleLayout",
           "exchange_record_batches", "exchange_round", "prepare_layout",
           "resolve_exchange_mode", "shuffle_exchange", "exchange_blobs",
           "ExchangeFetchClient", "SHUFFLE_AXIS", "MeshTopology",
           "make_mesh", "mesh_from_config", "mesh_topology", "shard_spec",
           "RoundPlan", "WindowPlan", "plan_rounds", "shard_map"]
