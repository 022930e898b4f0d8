from mpi4jax_tpu.parallel.comm import (
    Comm,
    MeshComm,
    SelfComm,
    default_comm,
    get_default_comm,
    set_default_comm,
)
from mpi4jax_tpu.parallel import distributed
from mpi4jax_tpu.parallel.halo import halo_exchange_2d, halo_slabs_2d
from mpi4jax_tpu.parallel.longseq import (
    zigzag_indices,
    zigzag_shard,
    zigzag_unshard,
    local_attention,
    ring_attention,
    ulysses_attention,
)
from mpi4jax_tpu.parallel import moe
from mpi4jax_tpu.parallel.moe import (
    expert_combine,
    expert_dispatch,
    topk_moe,
    topk_route,
)
from mpi4jax_tpu.parallel.proc import ProcComm, ProcGridComm, grid_comm

__all__ = [
    "distributed",
    "moe",
    "Comm",
    "MeshComm",
    "SelfComm",
    "ProcComm",
    "ProcGridComm",
    "grid_comm",
    "halo_exchange_2d",
    "halo_slabs_2d",
    "local_attention",
    "ring_attention",
    "zigzag_indices",
    "zigzag_shard",
    "zigzag_unshard",
    "ulysses_attention",
    "expert_dispatch",
    "expert_combine",
    "default_comm",
    "get_default_comm",
    "set_default_comm",
]
