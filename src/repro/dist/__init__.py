"""repro.dist — the distribution layer (DESIGN.md §5).

Four small modules, one concern each:

* :mod:`~repro.dist.sharding`    — param-path -> PartitionSpec rules,
  plus pytree-level param/batch/cache sharding helpers for a mesh.
* :mod:`~repro.dist.collectives` — int8-compressed allreduce (with and
  without error feedback) and the exact top-k shard merge.
* :mod:`~repro.dist.fault`       — heartbeat + straggler monitors
  emitting :class:`FaultEvent` records for the launch driver.
* :mod:`~repro.dist.elastic`     — mesh replanning after host loss.
"""
from .collectives import (
    compressed_psum,
    merge_topk,
    psum_with_error_feedback,
)
from .elastic import replan_mesh
from .fault import FaultEvent, HeartbeatMonitor, StragglerMitigator
from .sharding import (
    batch_sharding,
    cache_sharding,
    data_axes,
    param_sharding,
    param_spec,
)

__all__ = [
    "FaultEvent",
    "HeartbeatMonitor",
    "StragglerMitigator",
    "batch_sharding",
    "cache_sharding",
    "compressed_psum",
    "data_axes",
    "merge_topk",
    "merge_topk_unique",
    "param_sharding",
    "param_spec",
    "psum_with_error_feedback",
    "replan_mesh",
]
