"""parallel — device-mesh scaling for the batched validation hot path.

The reference's parallelism is peers/threads/STM (SURVEY.md §2 "Parallelism
strategies"); its crypto hot path is strictly sequential.  Here the device
dimension is first-class: a window of independent proofs (the "sequence" of
headers being validated) is sharded over a jax.sharding.Mesh axis and each
chip runs the same branch-free ladder on its shard; the shards meet only in
the fold's `pmin` over ICI.  No NCCL/MPI analog: collectives are XLA's.
"""
from .mesh import log_compile_time, make_mesh
from .sharded_verify import ShardedJaxBackend

__all__ = ["ShardedJaxBackend", "log_compile_time", "make_mesh"]
