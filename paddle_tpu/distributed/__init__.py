"""paddle.distributed parity over JAX device meshes (SURVEY.md §2.6, §5.8).

The reference's stack — TCPStore rendezvous, ProcessGroupNCCL, 161
collective ops, fleet topology/strategies — maps here to: the JAX runtime's
pod formation, ONE global `jax.sharding.Mesh` with named axes
(dp/sharding/pp/mp/sp/ep), eager collectives as jitted shard_map
mini-programs, and parallelism expressed as shardings compiled by GSPMD
(ParallelTrainStep).
"""
from . import fleet  # noqa: F401
from .collective import (Group, P2POp, ReduceOp, Work, all_gather,
                         all_gather_object, all_reduce, alltoall,
                         alltoall_single, barrier, batch_isend_irecv,
                         broadcast, get_group, irecv, isend, new_group,
                         recv, reduce, reduce_scatter, scatter, send,
                         stream)
from .env import ParallelEnv, get_rank, get_world_size
from .mesh import (Mesh, PartitionSpec, get_mesh, init_mesh, mesh_axis_size,
                   named_sharding, set_mesh)
from .parallel import DataParallel, init_parallel_env, is_initialized, \
    shard_batch
from .parallel_step import ParallelTrainStep, param_sharding, shard_params
from .moe import (GShardGate, MoELayer, NaiveGate, SwitchGate,
                  TokenChoiceMoE, last_moe_dispatch)
from .recompute import recompute, recompute_sequential
from .sequence_parallel import (ring_attention, shard_sequence,
                                ulysses_attention)
from .checkpoint import load_state_dict, save_state_dict, verify_checkpoint
from .resilience import (FaultInjected, FaultInjector, LossSpike,
                         LossSpikeDetector, NanInfStorm,
                         RetryPolicy, StepTimeout, StepWatchdog,
                         restore_train_state, save_train_state,
                         train_state_layout, with_retries)
from .checkpoint import (describe_layout, gc_checkpoints,
                         latest_checkpoint, layout_changes,
                         list_checkpoints, read_layout,
                         reshard_state_dict)
from .supervisor import (REQUEUE_EXIT_CODE, SupervisorGaveUp,
                         SupervisorResult, TrainSupervisor)
from .store import TCPStore
from .strategy import DistributedStrategy
from .topology import (CommunicateTopology, HybridCommunicateGroup,
                       get_hybrid_communicate_group,
                       set_hybrid_communicate_group)

from . import auto_parallel  # noqa: E402
from . import communication  # noqa: E402
from . import io  # noqa: E402
from . import launch  # noqa: E402
from . import passes  # noqa: E402
from . import rpc  # noqa: E402
from . import sharding  # noqa: E402
from .compat import (CountFilterEntry, InMemoryDataset,  # noqa: E402
                     ParallelMode, ProbabilityEntry, QueueDataset,
                     ShowClickEntry, broadcast_object_list,
                     destroy_process_group, get_backend,
                     gloo_barrier, gloo_init_parallel_env, gloo_release,
                     is_available, scatter_object_list, split, wait)
from .localsgd import LocalSGDStep  # noqa: E402
from .quantized import quantized_all_reduce  # noqa: E402
from .spawn import spawn  # noqa: E402
from .metric import DistributedAuc, global_auc  # noqa: E402
from .auto_parallel import (ProcessMesh, shard_tensor,  # noqa: E402
                            shard_op, Engine)

__all__ = [
    "auto_parallel", "ProcessMesh", "shard_tensor", "shard_op", "Engine",
    "rpc", "spawn", "DistributedAuc", "global_auc", "LocalSGDStep",
    "quantized_all_reduce",
    "communication", "io", "launch", "passes", "sharding",
    "ParallelMode", "broadcast_object_list", "scatter_object_list",
    "destroy_process_group", "get_backend", "is_available", "wait",
    "gloo_init_parallel_env", "gloo_barrier", "gloo_release", "split",
    "InMemoryDataset", "QueueDataset", "CountFilterEntry",
    "ProbabilityEntry", "ShowClickEntry",
    "init_parallel_env", "is_initialized", "get_rank", "get_world_size",
    "ParallelEnv", "DataParallel", "shard_batch",
    "Mesh", "PartitionSpec", "init_mesh", "get_mesh", "set_mesh",
    "mesh_axis_size", "named_sharding",
    "ReduceOp", "Group", "new_group", "get_group", "all_reduce",
    "all_gather", "all_gather_object", "broadcast", "reduce", "scatter",
    "reduce_scatter", "alltoall", "alltoall_single", "barrier", "send",
    "recv", "isend", "irecv", "batch_isend_irecv", "P2POp", "Work",
    "stream",
    "DistributedStrategy", "CommunicateTopology", "HybridCommunicateGroup",
    "get_hybrid_communicate_group", "set_hybrid_communicate_group",
    "ParallelTrainStep", "param_sharding", "shard_params", "fleet",
    "MoELayer", "SwitchGate", "GShardGate", "NaiveGate", "TokenChoiceMoE",
    "last_moe_dispatch",
    "recompute", "recompute_sequential",
    "save_state_dict", "load_state_dict", "verify_checkpoint", "TCPStore",
    "list_checkpoints", "latest_checkpoint", "gc_checkpoints",
    "describe_layout", "read_layout", "layout_changes",
    "reshard_state_dict", "train_state_layout",
    "RetryPolicy", "with_retries", "StepWatchdog", "StepTimeout",
    "NanInfStorm", "LossSpike", "LossSpikeDetector",
    "FaultInjector", "FaultInjected",
    "save_train_state", "restore_train_state",
    "TrainSupervisor", "SupervisorResult", "SupervisorGaveUp",
    "REQUEUE_EXIT_CODE",
    "ring_attention", "ulysses_attention", "shard_sequence",
]
