"""mpi4jax_tpu — TPU-native, jit-compatible MPI-style communication for JAX.

A ground-up redesign of the capabilities of mpi4jax (reference public API:
mpi4jax/__init__.py:9-38 — twelve token-threaded communication primitives
plus a capability probe) built TPU-first instead of wrapping CPU/CUDA MPI
in Cython:

* **mesh backend** (:class:`MeshComm`): ops called inside ``jax.shard_map``
  lower to XLA ICI collectives (``psum`` / ``ppermute`` / ``all_gather`` /
  ``all_to_all``) — jitted code never leaves HBM (the reference's GPU
  backend instead stages device→host→MPI→host→device,
  mpi_xla_bridge_gpu.pyx:211-251; that round trip does not exist here).
* **self backend** (:class:`SelfComm`): the single-process world, ops are
  local identities (the reference's behaviour with one MPI process).
* **proc backend** (:class:`ProcComm`): true multi-process MPMD over the
  native C++ DCN bridge (replaces mpi_xla_bridge_cpu.pyx).

Ordering is guaranteed by threading a :class:`Token` through every op,
preserving the reference's token discipline (docs/sharp-bits.rst:6-34)
via data dependence instead of side-effect annotations.
"""

import time as _time

import jax as _jax

_import_began_ns = _time.perf_counter_ns()  # `build/import`: the package's own, not jax's

from mpi4jax_tpu.utils.jax_compat import check_jax_version as _check_jax_version

_check_jax_version()

from mpi4jax_tpu.ops import (
    ANY_SOURCE,
    ANY_TAG,
    BAND,
    BOR,
    BXOR,
    LAND,
    LOR,
    LXOR,
    MAX,
    MIN,
    PROD,
    SUM,
    BucketedGradSync,
    Op,
    Request,
    Status,
    Token,
    allgather,
    allreduce,
    alltoall,
    alltoall_multi,
    annotate_step,
    as_token,
    barrier,
    assert_requests_drained,
    bcast,
    create_token,
    current_step,
    end_step,
    gather,
    iallreduce,
    ireduce_scatter,
    irecv,
    isend,
    recv,
    reduce,
    reduce_scatter,
    scan,
    scatter,
    send,
    sendrecv,
    sendrecv_multi,
    step_scope,
    test,
    token_array,
    wait,
    waitall,
)
from mpi4jax_tpu.native.runtime import WorldResized
from mpi4jax_tpu.parallel import (
    Comm,
    MeshComm,
    ProcComm,
    SelfComm,
    default_comm,
    get_default_comm,
    set_default_comm,
)
from mpi4jax_tpu.utils import spans as _spans

# what a process pays to have the library, jax's own import apart,
# beside what its programs' builds cost: a span of the process's
# recorder of builds
_spans.builds.record(
    _spans.IMPORT, (_time.perf_counter_ns() - _import_began_ns) / 1e9,
    module=__name__)

def __getattr__(name):
    # lazy: version resolution may shell out to git (checkout installs);
    # don't pay that — or import anything — at package-import time
    if name == "__version__":
        from mpi4jax_tpu._version import get_version

        version = get_version()
        globals()["__version__"] = version
        return version
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def has_tpu_support():
    """True if a TPU device backs the default JAX platform.

    Capability probe in the spirit of the reference's
    ``has_cuda_support()`` (mpi4jax/_src/utils.py:102-108).
    """
    try:
        return any(d.platform == "tpu" for d in _jax.devices())
    except RuntimeError:
        return False


def has_cuda_support():
    """True if a CUDA device backs the default JAX platform.

    Reference analog: ``mpi4jax.has_cuda_support()``
    (mpi4jax/_src/utils.py:102-108) — there it reports whether the CUDA
    XLA extension was *built*; here the staged (``io_callback``) native
    tier is platform-generic, so the question is simply whether CUDA
    devices are live: the same HBM↔host staging that serves TPU serves
    them (tests/proc/test_staged_backend.py::test_staged_ops_cuda).
    """
    try:
        if not any(d.platform == "gpu" for d in _jax.devices()):
            return False
        # 'gpu' covers ROCm too — require the backend to really be CUDA
        from jax.extend import backend as _jxb

        version = getattr(_jxb.get_backend(), "platform_version", "")
        return "cuda" in version.lower()
    except RuntimeError:
        return False


__all__ = [
    "ANY_SOURCE",
    "ANY_TAG",
    "BAND",
    "BOR",
    "BXOR",
    "BucketedGradSync",
    "Comm",
    "LAND",
    "LOR",
    "LXOR",
    "MAX",
    "MIN",
    "MeshComm",
    "Op",
    "PROD",
    "ProcComm",
    "Request",
    "SUM",
    "SelfComm",
    "Status",
    "Token",
    "WorldResized",
    "allgather",
    "allreduce",
    "alltoall",
    "alltoall_multi",
    "annotate_step",
    "assert_requests_drained",
    "as_token",
    "barrier",
    "bcast",
    "create_token",
    "current_step",
    "default_comm",
    "end_step",
    "gather",
    "get_default_comm",
    "has_cuda_support",
    "has_tpu_support",
    "iallreduce",
    "ireduce_scatter",
    "irecv",
    "isend",
    "recv",
    "reduce",
    "reduce_scatter",
    "scan",
    "scatter",
    "send",
    "sendrecv",
    "sendrecv_multi",
    "set_default_comm",
    "step_scope",
    "test",
    "token_array",
    "wait",
    "waitall",
]
