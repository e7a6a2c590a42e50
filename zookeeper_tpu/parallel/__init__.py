"""Distribution subsystem: device meshes, shardings, partitioners.

The TPU-native replacement for the reference GPU baseline's
``tf.distribute.MirroredStrategy`` + NCCL (SURVEY.md §2.5): a
``Partitioner`` component owns the ``jax.sharding.Mesh`` and the placement
of state and data; the training step itself stays a pure function and XLA
inserts all collectives (gradient all-reduce over ICI for data parallelism,
all-gathers for tensor-parallel params) from sharding annotations alone —
no hand-written communication layer, by design.
"""

from zookeeper_tpu.parallel.partitioner import (
    DataParallelPartitioner,
    FsdpPartitioner,
    MeshPartitioner,
    Partitioner,
    SingleDevicePartitioner,
)
from zookeeper_tpu.parallel.rules import (
    PartitionRule,
    auto_fsdp_rules,
    conv_model_tp_rules,
    match_partition_rules,
    transformer_tp_rules,
)
from zookeeper_tpu.parallel.sequence import SequenceParallelPartitioner
from zookeeper_tpu.parallel.distributed import (
    DistributedRuntime,
    enable_compile_cache,
    initialize_distributed,
)
from zookeeper_tpu.parallel.sharding import (
    activation_sharding_scope,
    constrain_batch_sharded,
)

__all__ = [
    "activation_sharding_scope",
    "constrain_batch_sharded",
    "DataParallelPartitioner",
    "DistributedRuntime",
    "FsdpPartitioner",
    "auto_fsdp_rules",
    "MeshPartitioner",
    "Partitioner",
    "PartitionRule",
    "SequenceParallelPartitioner",
    "SingleDevicePartitioner",
    "conv_model_tp_rules",
    "enable_compile_cache",
    "initialize_distributed",
    "match_partition_rules",
    "transformer_tp_rules",
]
