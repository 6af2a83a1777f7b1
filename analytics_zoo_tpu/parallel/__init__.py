from .mesh import AXES, make_mesh
from .pipeline import (pipeline_forward, sequential_reference,
                       stack_stage_params, stage_param_sharding)
from .ring_attention import ring_attention, ring_attention_sharded
from .ulysses import ulysses_attention, ulysses_attention_sharded
from .sharding import (DEFAULT_RULES, FSDP_RULES, make_param_sharding_fn,
                       shard_params)

__all__ = ["AXES", "make_mesh", "ring_attention",
           "ring_attention_sharded", "ulysses_attention",
           "ulysses_attention_sharded",
           "DEFAULT_RULES", "FSDP_RULES", "make_param_sharding_fn",
           "shard_params", "pipeline_forward", "sequential_reference",
           "stack_stage_params", "stage_param_sharding"]
