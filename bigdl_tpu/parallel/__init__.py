"""bigdl_tpu.parallel — sharding strategies over the device mesh.

The reference's only strategy is sync data-parallel SGD over the Spark block
manager (SURVEY.md §2.5); TP/SP/PP here are net-new TPU capabilities (§7):
- layout: MeshLayout (named data/fsdp/tp axes) + the canonical per-role
  PartitionSpec table and module-annotation assigner (docs/parallelism.md)
- sharding: DataParallel / ShardedDataParallel (ZeRO) / TensorParallel /
  LayoutSharding specs
- ring_attention: sequence/context parallelism (shard_map + ppermute ring)
- ulysses_attention: all-to-all sequence parallelism
- pipeline: GPipe-style microbatched stage parallelism
- expert: capacity-routed MoE over the `expert` axis (GSPMD + shard_map),
  and the dropless `GatedMoE` (shared experts, a held share of the experts)
- elastic: coordinated host-loss recovery (detect -> negotiate ->
  re-form -> resume; docs/robustness.md "Elasticity")
"""

from .layout import (MeshLayout, UnannotatedParameterError, MeshReformError,
                     assign_specs, assign_shardings)
from .sharding import (ShardingStrategy, DataParallel, ShardedDataParallel,
                       TensorParallel, LayoutSharding)
from .ring_attention import ring_attention, ulysses_attention
from .pipeline import (pipeline_apply, pipeline_apply_scheduled,
                       stack_stage_params, GPipeSequential,
                       partition_pipeline, PipelinePartitionError,
                       pipe_microbatches, pipe_schedule,
                       pipe_virtual_stages, bubble_fraction)
from .schedule import ScheduleTable, build_schedule
from .expert import (GatedMoE, MoEFFN, expert_parallel_ffn,
                     group_limited_top_k, top_k_routing,
                     load_balancing_loss)
from .elastic import PeerLostError, ElasticNegotiationError

__all__ = ["ShardingStrategy", "DataParallel", "ShardedDataParallel",
           "TensorParallel", "LayoutSharding", "MeshLayout",
           "UnannotatedParameterError", "MeshReformError", "assign_specs",
           "assign_shardings", "ring_attention", "ulysses_attention",
           "pipeline_apply", "pipeline_apply_scheduled",
           "stack_stage_params", "GPipeSequential",
           "partition_pipeline", "PipelinePartitionError",
           "pipe_microbatches", "pipe_schedule", "pipe_virtual_stages",
           "bubble_fraction", "ScheduleTable", "build_schedule", "MoEFFN",
           "expert_parallel_ffn", "top_k_routing", "load_balancing_loss",
           "GatedMoE", "group_limited_top_k",
           "PeerLostError", "ElasticNegotiationError"]
