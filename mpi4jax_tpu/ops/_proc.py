"""Multi-process (MPMD) backend: op implementations over the native DCN
bridge via XLA typed FFI.

This is the tier that preserves the reference's exact process model —
one OS process per rank, true per-rank control flow, rank-dependent
shapes — with the Cython/libmpi data plane replaced by the C++ socket
bridge (native/src/dcn.cc).  Each function here mirrors one CPU
custom-call encoder of the reference
(mpi4jax/_src/collective_ops/*.py "xla_encode_cpu" rules): static config
travels as FFI attributes, the array and an ordering stamp as operands,
and ``has_side_effect=True`` pins the call into the executable.

The sendrecv autodiff contract (transpose = swapped source/dest,
sendrecv.py:366-385) lives on a dedicated primitive below; allreduce
reuses the shared primitive in ops/allreduce.py whose impl dispatches
here for proc comms.
"""

from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax.extend.core import Primitive
from jax.interpreters import ad, batching, mlir

from mpi4jax_tpu.ops._core import ANY_SOURCE, ANY_TAG

_OP_CODES = {
    "sum": 0,
    "prod": 1,
    "min": 2,
    "max": 3,
    "land": 4,
    "lor": 5,
    "lxor": 6,
    "band": 7,
    "bor": 8,
    "bxor": 9,
}


def _op_code(op):
    """Wire code for a built-in reduction op.  User-defined ops never
    reach here: proc_allreduce/reduce/scan route them through the
    gather-wire + on-device fold path (:func:`_user_fold`) before any
    native op code is needed."""
    if getattr(op, "is_user", False):
        raise AssertionError(
            f"user-defined op {op.name!r} reached the native op table — "
            "it should have been routed through the _user_fold path"
        )
    return _OP_CODES[op.name]


def _user_fold(gathered, op, upto=None):
    """User-op fold on the proc tier: the operands ride the native
    allgather/gather wire and the combine — jax-traceable by the
    :meth:`Op.create` contract — lowers to on-device code through the
    shared rank-ordered fold (same kernel as the mesh tier; reference
    parity: mpi4jax/_src/utils.py:77-96, allreduce.py:36-66)."""
    from mpi4jax_tpu.ops.reductions import rank_ordered_fold

    return rank_ordered_fold(gathered, op, upto=upto)


def _handle(comm):
    from mpi4jax_tpu.native import runtime

    runtime.ensure_initialized()
    # fail fast once the bridge is faulted (a peer died, an op timed
    # out, or an abort broadcast arrived): dispatching another op onto
    # the dead transport would hang or abort, and the recorded fault
    # message is strictly more useful than either
    runtime.check_health()
    return np.int32(runtime.comm_handle(comm))


def proc_topology(comm):
    """(host_id, local_rank, local_size, leader_rank, n_hosts) map for
    a communicator, backend-agnostic.

    Proc comms read the native bridge's bootstrap topology (host
    fingerprints — the map the hierarchical collectives are built on);
    other backends read the rendezvous registry
    (ops/_rendezvous.py), which defaults to the trivial single-host
    map.  Benchmarks use this to label records with the local/leader
    world sizes."""
    if getattr(comm, "backend", None) == "proc":
        from mpi4jax_tpu.native import runtime

        runtime.ensure_initialized()
        topo = runtime.topology()
        if topo is not None:
            return topo
    from mpi4jax_tpu.ops import _rendezvous

    size = int(getattr(comm, "size", 1))
    rank = int(comm.rank()) if hasattr(comm, "rank") else 0
    tmap = _rendezvous.topology_map(
        getattr(comm, "context", 0), size=size
    )
    host, local, leader = tmap.get(rank, (0, rank, 0))
    return {
        "host_id": host,
        "local_rank": local,
        "local_size": sum(1 for h, _l, _r in tmap.values() if h == host),
        "leader_rank": leader,
        "n_hosts": len({h for h, _l, _r in tmap.values()}),
    }


def _staged():
    """True when arrays live on an accelerator: route ops through
    ``io_callback`` (device->host staging handled by JAX) instead of the
    CPU FFI custom call — the analog of the reference's GPU
    COPY_TO_HOST path (mpi_xla_bridge_gpu.pyx:211-251; there the bridge
    cudaMemcpys manually, here the runtime stages for us).

    ``MPI4JAX_TPU_FORCE_STAGED=1`` forces this path on CPU, for testing
    the staging tier without an accelerator.
    """
    import os

    from mpi4jax_tpu.utils.config import truthy

    if truthy(os.environ.get("MPI4JAX_TPU_FORCE_STAGED"), default=False):
        return True
    return jax.default_backend() != "cpu"


def _call(name, results, *operands, **attrs):
    from mpi4jax_tpu.native.runtime import _ffi_module

    fn = _ffi_module().ffi_call(name, results, has_side_effect=True)
    return fn(*operands, **attrs)


def _io(py_fn, results, *operands):
    from jax.experimental import io_callback

    # ordered=False: ordering is already guaranteed by data dependence —
    # every op threads the stamp through its callback — which is this
    # library's ordering model everywhere else (ops/_core.py docstring).
    # An ordered effect would add a second, global order on top of it
    # and serialise callbacks of independent communicators.
    return io_callback(py_fn, results, *operands, ordered=False)


def _staged_data(comm, out_sds, host_fn, x, stamp, name="op"):
    """Shared staged-tier shape for data-in/data-out ops: stages ``x``
    to host, runs ``host_fn(runtime, handle, np_x) -> np_out``, threads
    the stamp through for ordering.  The callback is bracketed with
    Python-level telemetry begin/end events (T4J_TELEMETRY=trace,
    docs/observability.md): under jit this is the execution-time span
    that encloses the native segment events — the trace-time bracket in
    ops/_core.py cannot see runtime from inside a compiled program."""
    from mpi4jax_tpu.native import runtime
    from mpi4jax_tpu.telemetry import recorder as _telrec

    h = int(_handle(comm))

    def cb(x_, stamp_):
        a = np.asarray(x_)
        with _telrec.py_op(f"staged_{name}", a.nbytes):
            return host_fn(runtime, h, a), stamp_

    return _io(cb, (out_sds, _STAMP), x, stamp)


def _sds(x):
    return jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x))


_STAMP = jax.ShapeDtypeStruct((), np.float32)
_STATUS = jax.ShapeDtypeStruct((2,), np.int32)


def proc_allreduce(x, stamp, op, comm):
    if getattr(op, "is_user", False):
        # Op.Create on the multi-process backend (VERDICT r3 missing #1):
        # operands cross the wire via the native allgather, the fold runs
        # on-device in rank order (commute=False safe)
        g, stamp = proc_allgather(x, stamp, comm)
        return _user_fold(g, op), stamp
    if _staged():
        code = _op_code(op)
        return _staged_data(
            comm, _sds(x),
            lambda rt, h, a: rt.host_allreduce(h, a, code), x, stamp,
            name="allreduce",
        )
    return _call(
        "t4j_allreduce",
        (_sds(x), _STAMP),
        x,
        stamp,
        comm=_handle(comm),
        op=np.int32(_op_code(op)),
    )


def proc_reduce(x, stamp, op, comm, root):
    if getattr(op, "is_user", False):
        # MPMD branch is a Python if: proc ranks are static ints
        g, stamp = proc_gather(x, stamp, comm, root)
        if int(comm.rank()) != int(root):
            return x, stamp  # off-root passthrough (wrapper contract)
        return _user_fold(g, op), stamp
    if _staged():
        code = _op_code(op)
        return _staged_data(
            comm, _sds(x),
            lambda rt, h, a: rt.host_reduce(h, a, code, root), x, stamp,
            name="reduce",
        )
    return _call(
        "t4j_reduce",
        (_sds(x), _STAMP),
        x,
        stamp,
        comm=_handle(comm),
        op=np.int32(_op_code(op)),
        root=np.int32(root),
    )


def proc_reduce_scatter(x, stamp, op, comm):
    """MPI_Reduce_scatter_block on the native bridge: ``x`` has shape
    ``(comm.size, *rest)``, the result is the reduction of row ``rank``
    with shape ``rest``.  Large payloads ride the segmented ring
    reduce-scatter directly — O((n-1)/n * payload) per link — instead
    of the alltoall + on-device fold detour.  Builtin ops only: callers
    route user-defined ops through the alltoall + rank-ordered-fold
    path (ops/collectives.py), which is the jax-traceable contract
    user combines require."""
    code = _op_code(op)
    out = jax.ShapeDtypeStruct(jnp.shape(x)[1:], jnp.result_type(x))
    if _staged():
        return _staged_data(
            comm, out,
            lambda rt, h, a: rt.host_reduce_scatter(h, a, code), x, stamp,
            name="reduce_scatter",
        )
    return _call(
        "t4j_reduce_scatter",
        (out, _STAMP),
        x,
        stamp,
        comm=_handle(comm),
        op=np.int32(code),
    )


def proc_scan(x, stamp, op, comm):
    if getattr(op, "is_user", False):
        g, stamp = proc_allgather(x, stamp, comm)
        return _user_fold(g, op, upto=int(comm.rank())), stamp
    if _staged():
        code = _op_code(op)
        return _staged_data(
            comm, _sds(x),
            lambda rt, h, a: rt.host_scan(h, a, code), x, stamp,
            name="scan",
        )
    return _call(
        "t4j_scan",
        (_sds(x), _STAMP),
        x,
        stamp,
        comm=_handle(comm),
        op=np.int32(_op_code(op)),
    )


def proc_barrier(stamp, comm):
    if _staged():
        from mpi4jax_tpu.native import runtime

        h = int(_handle(comm))

        def cb(stamp_):
            runtime.host_barrier(h)
            return stamp_

        return _io(cb, _STAMP, stamp)
    (out,) = _call("t4j_barrier", (_STAMP,), stamp, comm=_handle(comm))
    return out


def proc_bcast(x, stamp, comm, root):
    if _staged():
        return _staged_data(
            comm, _sds(x),
            lambda rt, h, a: rt.host_bcast(h, a, root), x, stamp,
            name="bcast",
        )
    return _call(
        "t4j_bcast",
        (_sds(x), _STAMP),
        x,
        stamp,
        comm=_handle(comm),
        root=np.int32(root),
    )


def proc_allgather(x, stamp, comm):
    out = jax.ShapeDtypeStruct((comm.size, *jnp.shape(x)), jnp.result_type(x))
    if _staged():
        return _staged_data(
            comm, out, lambda rt, h, a: rt.host_allgather(h, a), x, stamp,
            name="allgather",
        )
    return _call(
        "t4j_allgather", (out, _STAMP), x, stamp, comm=_handle(comm)
    )


def proc_gather(x, stamp, comm, root):
    out = jax.ShapeDtypeStruct((comm.size, *jnp.shape(x)), jnp.result_type(x))
    if _staged():
        return _staged_data(
            comm, out,
            lambda rt, h, a: rt.host_gather(h, a, root), x, stamp,
            name="gather",
        )
    return _call(
        "t4j_gather",
        (out, _STAMP),
        x,
        stamp,
        comm=_handle(comm),
        root=np.int32(root),
    )


def proc_scatter(x, stamp, comm, root):
    # MPMD shapes: the root passes (nproc, *rest) and receives (rest);
    # other ranks pass a (rest)-shaped template (scatter.py:52-58)
    shape = jnp.shape(x)[1:] if comm.rank() == root else jnp.shape(x)
    out = jax.ShapeDtypeStruct(shape, jnp.result_type(x))
    if _staged():
        return _staged_data(
            comm, out,
            lambda rt, h, a: rt.host_scatter(h, a, root), x, stamp,
            name="scatter",
        )
    return _call(
        "t4j_scatter",
        (out, _STAMP),
        x,
        stamp,
        comm=_handle(comm),
        root=np.int32(root),
    )


def proc_alltoall(x, stamp, comm):
    if _staged():
        return _staged_data(
            comm, _sds(x), lambda rt, h, a: rt.host_alltoall(h, a), x, stamp,
            name="alltoall",
        )
    return _call("t4j_alltoall", (_sds(x), _STAMP), x, stamp, comm=_handle(comm))


def proc_send(x, stamp, comm, dest, tag):
    if _staged():
        from mpi4jax_tpu.native import runtime

        h = int(_handle(comm))

        def cb(x_, stamp_):
            runtime.host_send(h, np.asarray(x_), dest, tag)
            return stamp_

        return _io(cb, _STAMP, x, stamp)
    (out,) = _call(
        "t4j_send",
        (_STAMP,),
        x,
        stamp,
        comm=_handle(comm),
        dest=np.int32(dest),
        tag=np.int32(tag),
    )
    return out


def proc_recv(template, stamp, comm, source, tag):
    """Returns (data, stamp, status[2])."""
    if _staged():
        from mpi4jax_tpu.native import runtime

        h = int(_handle(comm))
        shape = jnp.shape(template)
        dtype = jnp.result_type(template)

        def cb(stamp_):
            out, src, tg = runtime.host_recv(h, shape, dtype, source, tag)
            return out, stamp_, np.array([src, tg], np.int32)

        return _io(cb, (_sds(template), _STAMP, _STATUS), stamp)
    return _call(
        "t4j_recv",
        (_sds(template), _STAMP, _STATUS),
        stamp,
        comm=_handle(comm),
        source=np.int32(source),
        tag=np.int32(tag),
    )


# -- sendrecv primitive (AD: transpose swaps source and dest) -------------

sendrecv_p = Primitive("mpi4jax_tpu_proc_sendrecv")
sendrecv_p.multiple_results = True


def _sendrecv_impl(sendbuf, recvbuf, stamp, *, comm, source, dest, sendtag,
                   recvtag, _must_transpose):
    if _must_transpose:
        # only pure forward mode can leak a flipped marker to execution;
        # reverse mode transposes it back (the reference's scheme:
        # sendrecv.py:128-133 error, :320-361 jvp marker flip)
        raise RuntimeError(
            "forward-mode differentiation through sendrecv is not "
            "supported on the multi-process backend; use reverse mode"
        )
    if _staged():
        from mpi4jax_tpu.native import runtime

        h = int(_handle(comm))

        def cb(sendbuf_, recvbuf_, stamp_):
            out, src, tg = runtime.host_sendrecv(
                h, np.asarray(sendbuf_), np.asarray(recvbuf_), source, dest,
                sendtag, recvtag,
            )
            return out, stamp_, np.array([src, tg], np.int32)

        return _io(
            cb, (_sds(recvbuf), _STAMP, _STATUS), sendbuf, recvbuf, stamp
        )
    return _call(
        "t4j_sendrecv",
        (_sds(recvbuf), _STAMP, _STATUS),
        sendbuf,
        recvbuf,
        stamp,
        comm=_handle(comm),
        source=np.int32(source),
        dest=np.int32(dest),
        sendtag=np.int32(sendtag),
        recvtag=np.int32(recvtag),
    )


def _sendrecv_abstract(sendbuf, recvbuf, stamp, **kw):
    return (
        recvbuf,
        stamp,
        jax.core.ShapedArray((2,), np.int32),
    )


def _zero_like(x):
    if hasattr(ad.Zero, "from_primal_value"):
        return ad.Zero.from_primal_value(x)
    return ad.Zero.from_value(x)


def _sendrecv_jvp(primals, tangents, *, comm, source, dest, sendtag, recvtag,
                  _must_transpose):
    # the reference's rule (sendrecv.py:320-361): tangent exchange binds
    # with the _must_transpose marker flipped — executable only after a
    # transpose flips it back (reverse mode); pure forward mode then
    # errors at execution, exactly as the reference's lowering does
    sendbuf, recvbuf, stamp = primals
    st, rt, _ = tangents
    st = jnp.zeros_like(sendbuf) if type(st) is ad.Zero else st
    rt = jnp.zeros_like(recvbuf) if type(rt) is ad.Zero else rt
    val, stamp_out, status = sendrecv_p.bind(
        sendbuf, recvbuf, stamp, comm=comm, source=source, dest=dest,
        sendtag=sendtag, recvtag=recvtag, _must_transpose=_must_transpose,
    )
    jvp, jstamp, jstatus = sendrecv_p.bind(
        st, rt, stamp_out, comm=comm, source=source, dest=dest,
        sendtag=sendtag, recvtag=recvtag,
        _must_transpose=not _must_transpose,
    )
    return (
        (val, stamp_out, status),
        (jvp, _zero_like(jstamp), _zero_like(jstatus)),
    )


def _sendrecv_batch(args, dims, *, comm, source, dest, sendtag, recvtag,
                    _must_transpose):
    # one exchange of the whole batch (the reference's batch rule,
    # sendrecv.py:291-319)
    sendbuf, recvbuf, stamp = args
    bd_s, bd_r, bd_t = dims
    if bd_t is not None:
        raise NotImplementedError("batched tokens are not supported")
    if bd_s is None and bd_r is None:
        raise ValueError("sendrecv batch rule called without batched data")

    def tile(unbatched, axis, n):
        """Insert a batch dim of size n at ``axis`` (send/recv buffers
        may have different base shapes)."""
        shape = list(unbatched.shape)
        shape.insert(axis, n)
        return jnp.broadcast_to(jnp.expand_dims(unbatched, axis), shape)

    if bd_s is None:
        sendbuf = tile(sendbuf, bd_r, recvbuf.shape[bd_r])
        bd_s = bd_r
    if bd_r is None:
        recvbuf = tile(recvbuf, bd_s, sendbuf.shape[bd_s])
        bd_r = bd_s
    if bd_s != bd_r:
        sendbuf = jnp.moveaxis(sendbuf, bd_s, bd_r)
    out = sendrecv_p.bind(
        sendbuf, recvbuf, stamp, comm=comm, source=source, dest=dest,
        sendtag=sendtag, recvtag=recvtag, _must_transpose=_must_transpose,
    )
    return out, (bd_r, None, None)


def _sendrecv_transpose(cts, sendbuf, recvbuf, stamp, *, comm, source, dest,
                        sendtag, recvtag, _must_transpose):
    # gradients travel the reverse network direction (sendrecv.py:366-385)
    out_ct, _, _ = cts
    if type(out_ct) is ad.Zero:
        out_ct = jnp.zeros(recvbuf.aval.shape, recvbuf.aval.dtype)
    fresh = jnp.zeros((), np.float32)
    res, _, _ = sendrecv_p.bind(
        out_ct,
        out_ct,
        fresh,
        comm=comm,
        source=dest,
        dest=source,
        sendtag=sendtag,
        recvtag=recvtag,
        _must_transpose=not _must_transpose,
    )
    send_ct = res if ad.is_undefined_primal(sendbuf) else None
    recv_ct = (
        ad.Zero(recvbuf.aval) if ad.is_undefined_primal(recvbuf) else None
    )
    stamp_ct = (
        ad.Zero(stamp.aval) if ad.is_undefined_primal(stamp) else None
    )
    return send_ct, recv_ct, stamp_ct


sendrecv_p.def_impl(_sendrecv_impl)
sendrecv_p.def_abstract_eval(_sendrecv_abstract)
ad.primitive_jvps[sendrecv_p] = _sendrecv_jvp
ad.primitive_transposes[sendrecv_p] = _sendrecv_transpose
batching.primitive_batchers[sendrecv_p] = _sendrecv_batch
mlir.register_lowering(
    sendrecv_p, mlir.lower_fun(_sendrecv_impl, multiple_results=True)
)


def proc_sendrecv(sendbuf, recvbuf, stamp, comm, source, dest, sendtag,
                  recvtag):
    return sendrecv_p.bind(
        sendbuf,
        recvbuf,
        stamp,
        comm=comm,
        source=int(source),
        dest=int(dest),
        sendtag=int(sendtag),
        recvtag=int(recvtag),
        _must_transpose=False,
    )


# -- fused multi-part sendrecv (small-message coalescing) ------------------
#
# One wire frame for a run of small same-peer messages
# (docs/performance.md "small-message coalescing"): operands are the
# send parts (+ stamp), the recv parts come back as results, and the
# native layer gathers/scatters iovec-style — no packing copies on
# either side.  AD mirrors the single sendrecv primitive: the
# transpose swaps source and dest AND the send/recv part lists, so
# gradients travel the reverse network direction part-for-part.

sendrecv_fused_p = Primitive("mpi4jax_tpu_proc_sendrecv_fused")
sendrecv_fused_p.multiple_results = True


def _srf_split(args, n_send, n_recv):
    return (
        args[:n_send],
        args[n_send:n_send + n_recv],
        args[n_send + n_recv],
    )


def _srf_impl(*args, comm, source, dest, sendtag, recvtag, n_send,
              n_recv, _must_transpose):
    if _must_transpose:
        raise RuntimeError(
            "forward-mode differentiation through sendrecv_multi is not "
            "supported on the multi-process backend; use reverse mode"
        )
    sendbufs, recvbufs, stamp = _srf_split(args, n_send, n_recv)
    if _staged():
        from mpi4jax_tpu.native import runtime

        h = int(_handle(comm))
        templates = [
            jax.ShapeDtypeStruct(jnp.shape(r), jnp.result_type(r))
            for r in recvbufs
        ]

        def cb(*host_args):
            sends = [np.asarray(a) for a in host_args[:-1]]
            # templates pass through as ShapeDtypeStructs — the host
            # wrapper allocates the result buffers itself
            outs, src, tg = runtime.host_sendrecv_fused(
                h, sends, templates, source, dest, sendtag, recvtag,
            )
            return (*outs, host_args[-1], np.array([src, tg], np.int32))

        return _io(
            cb, (*[_sds(r) for r in recvbufs], _STAMP, _STATUS),
            *sendbufs, stamp,
        )
    return _call(
        "t4j_sendrecv_fused",
        (*[_sds(r) for r in recvbufs], _STAMP, _STATUS),
        *sendbufs,
        stamp,
        comm=_handle(comm),
        source=np.int32(source),
        dest=np.int32(dest),
        sendtag=np.int32(sendtag),
        recvtag=np.int32(recvtag),
        n_send=np.int32(n_send),
    )


def _srf_abstract(*args, n_send, n_recv, **kw):
    recvs = args[n_send:n_send + n_recv]
    stamp = args[n_send + n_recv]
    return (*recvs, stamp, jax.core.ShapedArray((2,), np.int32))


def _srf_jvp(primals, tangents, *, comm, source, dest, sendtag, recvtag,
             n_send, n_recv, _must_transpose):
    # the single-sendrecv scheme (sendrecv.py:320-361 in the
    # reference): the tangent exchange binds with the marker flipped —
    # executable only after a transpose flips it back
    sends, recvs, stamp = _srf_split(primals, n_send, n_recv)
    tsends = [
        jnp.zeros_like(p) if type(t) is ad.Zero else t
        for p, t in zip(sends, tangents[:n_send])
    ]
    trecvs = [
        jnp.zeros_like(p) if type(t) is ad.Zero else t
        for p, t in zip(recvs, tangents[n_send:n_send + n_recv])
    ]
    out = sendrecv_fused_p.bind(
        *sends, *recvs, stamp, comm=comm, source=source, dest=dest,
        sendtag=sendtag, recvtag=recvtag, n_send=n_send, n_recv=n_recv,
        _must_transpose=_must_transpose,
    )
    stamp_out = out[n_recv]
    jout = sendrecv_fused_p.bind(
        *tsends, *trecvs, stamp_out, comm=comm, source=source, dest=dest,
        sendtag=sendtag, recvtag=recvtag, n_send=n_send, n_recv=n_recv,
        _must_transpose=not _must_transpose,
    )
    return (
        out,
        (*jout[:n_recv], _zero_like(jout[n_recv]), _zero_like(jout[n_recv + 1])),
    )


def _srf_transpose(cts, *args, comm, source, dest, sendtag, recvtag,
                   n_send, n_recv, _must_transpose):
    # gradients travel the reverse network direction: the transposed
    # exchange SENDS the recv parts' cotangents back to `source` and
    # RECEIVES the send parts' cotangents from `dest`, part for part
    sends, recvs, stamp = _srf_split(args, n_send, n_recv)
    out_cts = [
        jnp.zeros(r.aval.shape, r.aval.dtype) if type(c) is ad.Zero else c
        for r, c in zip(recvs, cts[:n_recv])
    ]
    send_templates = [
        jnp.zeros(s.aval.shape, s.aval.dtype)
        if ad.is_undefined_primal(s) else jnp.zeros_like(s)
        for s in sends
    ]
    fresh = jnp.zeros((), np.float32)
    res = sendrecv_fused_p.bind(
        *out_cts, *send_templates, fresh, comm=comm, source=dest,
        dest=source, sendtag=sendtag, recvtag=recvtag, n_send=n_recv,
        n_recv=n_send, _must_transpose=not _must_transpose,
    )
    send_cts = [
        res[i] if ad.is_undefined_primal(s) else None
        for i, s in enumerate(sends)
    ]
    recv_cts = [
        ad.Zero(r.aval) if ad.is_undefined_primal(r) else None
        for r in recvs
    ]
    stamp_ct = (
        ad.Zero(stamp.aval) if ad.is_undefined_primal(stamp) else None
    )
    return (*send_cts, *recv_cts, stamp_ct)


sendrecv_fused_p.def_impl(_srf_impl)
sendrecv_fused_p.def_abstract_eval(_srf_abstract)
ad.primitive_jvps[sendrecv_fused_p] = _srf_jvp
ad.primitive_transposes[sendrecv_fused_p] = _srf_transpose
mlir.register_lowering(
    sendrecv_fused_p, mlir.lower_fun(_srf_impl, multiple_results=True)
)


def proc_sendrecv_fused(sendbufs, recvbufs, stamp, comm, source, dest,
                        sendtag, recvtag):
    """Returns ``(*recv_parts, stamp, status[2])``.  ``source`` /
    ``dest`` may be -1 (no send / no recv side) only when the matching
    part list is empty."""
    return sendrecv_fused_p.bind(
        *sendbufs,
        *recvbufs,
        stamp,
        comm=comm,
        source=int(source),
        dest=int(dest),
        sendtag=int(sendtag),
        recvtag=int(recvtag),
        n_send=len(sendbufs),
        n_recv=len(recvbufs),
        _must_transpose=False,
    )


def proc_alltoall_fused(parts, stamp, comm):
    """Fused multi-part alltoall: each peer receives ONE wire frame
    carrying its slice of every part (bit-identical to per-part
    alltoall; docs/performance.md "small-message coalescing").
    Returns ``(outs, stamp)``."""
    if _staged():
        from mpi4jax_tpu.native import runtime
        from mpi4jax_tpu.telemetry import recorder as _telrec

        h = int(_handle(comm))
        total = sum(int(np.prod(jnp.shape(p), dtype=np.int64))
                    for p in parts)

        def cb(*host_args):
            arrs = [np.asarray(a) for a in host_args[:-1]]
            with _telrec.py_op("staged_alltoall_fused", total):
                outs = runtime.host_alltoall_fused(h, arrs)
            return (*outs, host_args[-1])

        out = _io(cb, (*[_sds(p) for p in parts], _STAMP), *parts, stamp)
        return list(out[:-1]), out[-1]
    out = _call(
        "t4j_alltoall_fused",
        (*[_sds(p) for p in parts], _STAMP),
        *parts,
        stamp,
        comm=_handle(comm),
    )
    return list(out[:-1]), out[-1]
