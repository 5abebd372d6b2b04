"""Distributed/parallel execution over TPU meshes.

This package is the TPU-native replacement for the reference's entire
communication stack (SURVEY.md §2.3): src/kvstore/comm.h (local reduce),
comm_tree.h + gpu_topology.h (NVLink tree allreduce), kvstore_nccl.h, and the
ps-lite parameter server. On TPU none of those mechanisms survive: a
jax.sharding.Mesh names the hardware axes, parameters/batches carry
NamedShardings, and XLA inserts ICI/DCN collectives (psum/all-gather/
reduce-scatter) chosen for the physical torus — the topology solver the
reference hand-rolls (Kernighan-Lin over the PCIe matrix) is the XLA
compiler's job here.

Also hosts what the reference does NOT have (SURVEY.md §5.7): sequence/
context parallelism via ring attention, and tensor-parallel layer shardings.
"""
from .mesh import make_mesh, local_mesh_axis_sizes
from .functional import functionalize
from .train import TrainStep, shard_batch
from .ring_attention import ring_attention, ring_attention_sharded
from .flash_attention import flash_attention, flash_attention_bh
from .paged_attention import paged_attention, paged_attention_multiquery
from .pipeline import pipeline_apply, pipeline_sharded
from .moe import moe_apply, moe_sharded, init_moe_params
from .partition import match_partition_rules
from .tensor_parallel import (column_parallel_spec, row_parallel_spec,
                              transformer_param_specs,
                              transformer_partition_rules)
from .compression import (quantized_allreduce, quantized_psum,
                          quantize_pack, quantize_pack_pallas,
                          two_bit_pack, two_bit_unpack)

__all__ = ["make_mesh", "local_mesh_axis_sizes", "functionalize", "TrainStep",
           "match_partition_rules",
           "shard_batch", "ring_attention", "ring_attention_sharded",
           "flash_attention", "flash_attention_bh",
           "paged_attention", "paged_attention_multiquery",
           "pipeline_apply", "pipeline_sharded",
           "moe_apply", "moe_sharded", "init_moe_params",
           "column_parallel_spec", "row_parallel_spec",
           "transformer_param_specs", "transformer_partition_rules",
           "quantized_allreduce",
           "quantized_psum", "quantize_pack", "quantize_pack_pallas",
           "two_bit_pack", "two_bit_unpack"]
