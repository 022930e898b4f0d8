"""Collective ops: allgather, alltoall, barrier, bcast, gather, reduce,
scan, scatter.

API surface mirrors the reference one-to-one
(mpi4jax/__init__.py:26-38); each docstring cites the matching reference
op.  On the mesh backend every op is a composition of XLA ICI collectives
(``all_gather`` / ``all_to_all`` / ``psum`` / ``ppermute``) inside the
enclosing ``shard_map`` — data never leaves HBM.  Autodiff falls out of
the underlying collectives' JAX rules, a superset of the reference (which
defines AD only for allreduce and sendrecv).

On the multi-process backend the native bridge picks the data plane
per call: same-host comms ride the shm arena, cross-host ones the
tree/segmented-ring TCP algorithms, and multi-host topologies with
several ranks per host the hierarchical shm-leaf + leader-ring plane
(selection knobs ``T4J_HIER`` / ``T4J_LEADER_RING_MIN_BYTES``;
docs/performance.md).  ``ops._proc.proc_topology`` exposes the
(host_id, local_rank, leader_rank) map the selection is built on.

SPMD note (the MPMD↔SPMD gap, SURVEY §7): the reference's rooted ops have
*rank-dependent output shapes* — e.g. gather returns ``(nproc, *shape)``
on root and the input unchanged elsewhere
(mpi4jax/_src/collective_ops/gather.py:74-87).  A single SPMD program must
have uniform shapes, so here rooted ops return the root's result on
*every* member: ``gather ≡ allgather``, ``reduce ≡ allreduce`` value-wise.
Off-root values are well-defined (not garbage); programs written against
the reference's root-only guarantees remain correct.  The multi-process
backend preserves exact MPMD shapes.
"""

from functools import partial

import numpy as np

import jax.numpy as jnp
from jax import lax

from mpi4jax_tpu.ops import reductions
from mpi4jax_tpu.ops._core import (
    as_token,
    fence_in,
    fence_out,
    promote_vma,
    publishes_token,
)
from mpi4jax_tpu.ops.allreduce import allreduce
from mpi4jax_tpu.utils.validation import check_comm, check_op, check_root

__all__ = [
    "allgather",
    "alltoall",
    "alltoall_multi",
    "barrier",
    "bcast",
    "gather",
    "reduce",
    "reduce_scatter",
    "scan",
    "scatter",
]


def _prologue(x, comm, token):
    comm = check_comm(comm)
    token = as_token(token)
    x = jnp.asarray(x) if x is not None else None
    return x, comm, token


def _unsupported(name, comm):
    return NotImplementedError(
        f"{name} not implemented for backend {comm.backend!r}"
    )


@publishes_token
def allgather(x, *, comm=None, token=None):
    """Gather ``x`` from every rank onto every rank.

    Output shape is ``(comm.size, *x.shape)`` on all ranks (reference:
    mpi4jax/_src/collective_ops/allgather.py:35-74, out shape at
    :167-174).
    """
    x, comm, token = _prologue(x, comm, token)
    if comm.backend == "self":
        y = x[None]
        token, (y,) = fence_out(token, y)
        return y, token
    if comm.backend == "mesh":
        token, (x,) = fence_in(token, x)
        x = promote_vma(x, comm.axes)
        y = lax.all_gather(
            x, comm.axes, axis=0, tiled=False, axis_index_groups=comm.groups
        )
        token, (y,) = fence_out(token, y)
        return y, token
    if comm.backend == "proc":
        from mpi4jax_tpu.ops import _proc

        y, stamp = _proc.proc_allgather(x, token.stamp, comm)
        return y, token.with_stamp(stamp)
    raise _unsupported("allgather", comm)


@publishes_token
def alltoall(x, *, comm=None, token=None):
    """All-to-all block exchange.

    ``x`` must have leading dimension ``comm.size`` (checked eagerly, as
    in the reference — mpi4jax/_src/collective_ops/alltoall.py:62-64);
    output row ``j`` is rank ``j``'s row ``rank``.
    """
    x, comm, token = _prologue(x, comm, token)
    if x.ndim == 0 or x.shape[0] != comm.size:
        raise ValueError(
            # wording matches the reference's check (alltoall.py:62-64
            # there; its own test suite asserts on the phrase)
            f"alltoall input must have shape (nproc, ...) with nproc == "
            f"comm.size={comm.size}, got shape {x.shape}"
        )
    if comm.backend == "self":
        token, (x,) = fence_out(token, x)
        return x, token
    if comm.backend == "mesh":
        token, (x,) = fence_in(token, x)
        x = promote_vma(x, comm.axes)
        y = lax.all_to_all(
            x, comm.axes, split_axis=0, concat_axis=0, tiled=True,
            axis_index_groups=comm.groups,
        )
        token, (y,) = fence_out(token, y)
        return y, token
    if comm.backend == "proc":
        from mpi4jax_tpu.ops import _proc

        y, stamp = _proc.proc_alltoall(x, token.stamp, comm)
        return y, token.with_stamp(stamp)
    raise _unsupported("alltoall", comm)


@publishes_token
def alltoall_multi(parts, *, comm=None, token=None, coalesce=None):
    """Several independent alltoalls at once — the coalescing entry
    point for per-expert dispatch (docs/performance.md "small-message
    coalescing"; ``parallel.moe.topk_moe`` with multiple experts per
    rank is the canonical caller).

    Semantically identical to one :func:`alltoall` per part
    (bit-identical outputs), but on the multi-process backend a small
    run travels as ONE fused frame per peer — carrying that peer's
    slice of every part — instead of ``len(parts)`` frames per peer.
    Fusion applies when the combined per-peer payload is at or below
    ``T4J_COALESCE_BYTES``; ``coalesce=True``/``False`` forces a side,
    ``T4J_COALESCE_BYTES=0`` restores the exact per-part wire
    behaviour.  Returns ``(outs, token)``.
    """
    comm = check_comm(comm)
    token = as_token(token)
    parts = [jnp.asarray(p) for p in parts]
    for p in parts:
        if p.ndim == 0 or p.shape[0] != comm.size:
            raise ValueError(
                f"alltoall input must have shape (nproc, ...) with "
                f"nproc == comm.size={comm.size}, got shape {p.shape}"
            )
    if not parts:
        return [], token
    if comm.backend == "proc" and len(parts) > 1:
        if isinstance(coalesce, bool):
            fuse = coalesce
        else:
            from mpi4jax_tpu import tuning

            per_peer = sum(
                int(p.size) * p.dtype.itemsize // comm.size
                for p in parts
            )
            fuse = tuning.coalesce_eligible(per_peer, len(parts))
        if fuse:
            from mpi4jax_tpu.ops import _proc

            outs, stamp = _proc.proc_alltoall_fused(
                parts, token.stamp, comm
            )
            return outs, token.with_stamp(stamp)
    outs = []
    for p in parts:
        y, token = alltoall(p, comm=comm, token=token)
        outs.append(y)
    return outs, token


@publishes_token
def barrier(*, comm=None, token=None):
    """Synchronisation barrier; returns only a token (reference:
    mpi4jax/_src/collective_ops/barrier.py:32-53).

    On the mesh backend this is a zero-payload ``psum`` chained into the
    token, forcing a cross-device rendezvous at this point in the program
    order.
    """
    comm = check_comm(comm)
    token = as_token(token)
    if comm.backend == "self":
        return token
    if comm.backend == "mesh":
        z = jnp.zeros((), jnp.int32)
        token, (z,) = fence_in(token, z)
        s = reductions.group_psum(z, comm.axes, comm.groups)
        token, _ = fence_out(token, s)
        return token
    if comm.backend == "proc":
        from mpi4jax_tpu.ops import _proc

        stamp = _proc.proc_barrier(token.stamp, comm)
        return token.with_stamp(stamp)
    raise _unsupported("barrier", comm)


def _bcast_psum(xv, root, comm):
    """Masked all-reduce: non-root contributions zeroed, one psum
    delivers the root's value everywhere."""
    rank = comm.rank()
    masked = jnp.where(rank == root, xv, jnp.zeros_like(xv))
    return reductions.group_psum(masked, comm.axes, comm.groups)


@publishes_token
def bcast(x, root, *, comm=None, token=None):
    """Broadcast ``x`` from ``root`` to every rank (reference:
    mpi4jax/_src/collective_ops/bcast.py:36-72).

    On a mesh it is a masked ``psum`` (:func:`_bcast_psum`): the one
    schedule, faster than a binomial ``ppermute`` tree at 8 B and at
    4 MiB on a 2x2 of v5e chips (docs/performance.md "bcast schedule
    measurement").
    """
    x, comm, token = _prologue(x, comm, token)
    root = check_root(root, comm)
    if comm.backend == "self":
        token, (x,) = fence_out(token, x)
        return x, token
    if comm.backend == "mesh":
        token, (x,) = fence_in(token, x)
        as_int = x.dtype == jnp.bool_
        xv = x.astype(jnp.int8) if as_int else x
        xv = promote_vma(xv, comm.axes)
        y = _bcast_psum(xv, root, comm)
        if as_int:
            y = y.astype(jnp.bool_)
        token, (y,) = fence_out(token, y)
        return y, token
    if comm.backend == "proc":
        from mpi4jax_tpu.ops import _proc

        y, stamp = _proc.proc_bcast(x, token.stamp, comm, root)
        return y, token.with_stamp(stamp)
    raise _unsupported("bcast", comm)


@publishes_token
def gather(x, root, *, comm=None, token=None):
    """Gather ``x`` from every rank to ``root`` (reference:
    mpi4jax/_src/collective_ops/gather.py:36-87).

    Mesh backend: output is ``(comm.size, *x.shape)`` on every rank (SPMD
    uniform-shape note in the module docstring).
    """
    comm_r = check_comm(comm)
    root = check_root(root, comm_r)
    if comm_r.backend == "proc":
        from mpi4jax_tpu.ops import _proc

        x, comm_r, token = _prologue(x, comm_r, token)
        y, stamp = _proc.proc_gather(x, token.stamp, comm_r, root)
        token = token.with_stamp(stamp)
        if comm_r.rank() != root:
            # MPMD rank-dependent shape: unmodified input off-root
            return x, token
        return y, token
    del root  # value identical on every member under SPMD
    return allgather(x, comm=comm, token=token)


@publishes_token
def reduce(x, op, root, *, comm=None, token=None):
    """Reduce ``x`` with ``op`` to ``root`` (reference:
    mpi4jax/_src/collective_ops/reduce.py:37-71).

    Mesh backend: result is delivered on every rank (≡ allreduce).
    """
    op = check_op(op)
    comm_r = check_comm(comm)
    root = check_root(root, comm_r)
    if comm_r.backend == "proc":
        from mpi4jax_tpu.ops import _proc

        x, comm_r, token = _prologue(x, comm_r, token)
        y, stamp = _proc.proc_reduce(x, token.stamp, op, comm_r, root)
        return y, token.with_stamp(stamp)
    del root
    return allreduce(x, op, comm=comm, token=token)


@publishes_token
def reduce_scatter(x, op=reductions.SUM, *, comm=None, token=None):
    """Reduce ``x`` with ``op`` across ranks and scatter the result by
    row blocks (``MPI_Reduce_scatter_block``): rank ``r`` receives the
    reduction over all ranks of row ``r``.

    An **extension op** — not one of the reference's twelve (mpi4jax has
    no reduce_scatter; its MPI parent is standard) — included because it
    is the native TPU collective: with ``op=SUM`` it lowers to one
    ``lax.psum_scatter``, the ring reduce-scatter the ICI torus is
    optimised for, at O(payload) wire cost where ``allreduce`` of the
    same data costs ~2x.  Gradient sharding (ZeRO-style optimizer
    partitioning) is the canonical use — see
    ``models/train.py:make_global_zero_train_step``.

    ``x`` must have shape ``(comm.size, *rest)`` on every rank; the
    result has shape ``rest``.  Identity: ``reduce_scatter(x)`` on rank
    ``r`` equals ``allreduce(x)[r]``.  Differentiable for ``op=SUM``
    (the composition transposes to an ``all_gather``).  On the mesh
    backend, non-SUM and user-defined ops ride an ``all_to_all`` +
    rank-ordered local fold (correct for ``commute=False`` operators);
    on the proc backend every builtin op is a single native
    ``reduce_scatter`` over the DCN bridge — the segmented ring at
    large payloads, ``O((n-1)/n * payload)`` per link, and on
    multi-host topologies with several ranks per host the hierarchical
    shm-leaf + leader-ring plane, which cuts cross-host traffic by the
    local world size (docs/performance.md "TCP-tier algorithm
    selection" / "hierarchical collectives") — and only user-defined
    ops take the ``all_to_all`` + fold detour.
    """
    x, comm, token = _prologue(x, comm, token)
    op = check_op(op)
    if x.ndim == 0 or x.shape[0] != comm.size:
        raise ValueError(
            f"reduce_scatter input must have shape (nproc, ...) with "
            f"nproc == comm.size={comm.size}, got shape {x.shape}"
        )
    as_int = x.dtype == jnp.bool_
    # axis 0 is source-rank order after the exchange, so the shared
    # rank-ordered fold gives the commute=False contract
    fold_rows = partial(reductions.rank_ordered_fold, op=op)

    if comm.backend == "self":
        y = x[0]
        token, (y,) = fence_out(token, y)
        return y, token
    if comm.backend == "mesh":
        token, (x,) = fence_in(token, x)
        xv = promote_vma(x, comm.axes)
        if op.name == "sum" and not op.is_user:
            # bool rides the int8 psum_scatter like scatter does; the
            # final nonzero→True cast matches the general path's fold
            y = _scatter_sum(xv.astype(jnp.int8) if as_int else xv, comm)
            if as_int:
                y = y.astype(jnp.bool_)
        else:
            xv = xv.astype(jnp.int8) if as_int else xv
            rows = lax.all_to_all(
                xv, comm.axes, split_axis=0, concat_axis=0, tiled=True,
                axis_index_groups=comm.groups,
            )
            y = fold_rows(rows)
            if as_int:
                y = y.astype(jnp.bool_)
        token, (y,) = fence_out(token, y)
        return y, token
    if comm.backend == "proc":
        from mpi4jax_tpu.ops import _proc

        xv = x.astype(jnp.int8) if as_int else x
        if not op.is_user:
            # native segmented ring reduce-scatter (dcn.cc): the
            # scattered-gradient collective ZeRO wants, at
            # O((n-1)/n * payload) per link — the alltoall + fold
            # detour ships the same bytes but pays the fold on every
            # rank and a full staging pass
            y, stamp = _proc.proc_reduce_scatter(xv, token.stamp, op, comm)
        else:
            rows, stamp = _proc.proc_alltoall(xv, token.stamp, comm)
            y = fold_rows(rows)
        if as_int:
            y = y.astype(jnp.bool_)
        return y, token.with_stamp(stamp)
    raise _unsupported("reduce_scatter", comm)


@publishes_token
def scan(x, op, *, comm=None, token=None):
    """Inclusive prefix reduction over ranks (MPI_Scan; reference:
    mpi4jax/_src/collective_ops/scan.py:36-61).

    XLA has no native prefix collective (SURVEY §7 hard part 4); this is a
    Hillis–Steele ladder of ``ceil(log2(size))`` masked ``ppermute`` steps
    over ICI.
    """
    x, comm, token = _prologue(x, comm, token)
    op = check_op(op)
    if comm.backend == "self":
        token, (x,) = fence_out(token, x)
        return x, token
    if comm.backend == "mesh":
        size = comm.size
        token, (x,) = fence_in(token, x)
        rank = comm.rank()
        as_int = x.dtype == jnp.bool_
        acc = x.astype(jnp.int8) if as_int else x
        acc = promote_vma(acc, comm.axes)
        dist = 1
        while dist < size:
            perm = comm.expand_perm(
                [(r, r + dist) for r in range(size - dist)]
            )
            shifted = lax.ppermute(acc, comm.axes, perm)
            # lower-rank prefix on the left: correct for non-commutative
            # (user-defined, commute=False) operators
            combined = op.combine(shifted.astype(acc.dtype), acc)
            acc = jnp.where(rank >= dist, combined.astype(acc.dtype), acc)
            dist *= 2
        if as_int:
            acc = acc.astype(jnp.bool_)
        token, (acc,) = fence_out(token, acc)
        return acc, token
    if comm.backend == "proc":
        from mpi4jax_tpu.ops import _proc

        y, stamp = _proc.proc_scan(x, token.stamp, op, comm)
        return y, token.with_stamp(stamp)
    raise _unsupported("scan", comm)


@publishes_token
def scatter(x, root, *, comm=None, token=None):
    """Scatter rows of ``x`` from ``root`` (reference:
    mpi4jax/_src/collective_ops/scatter.py:36-92).

    ``x`` must have shape ``(comm.size, *rest)`` (the reference checks
    this on root, scatter.py:77-81; under SPMD every member passes the
    same template and only the root's values matter).  Returns the row at
    index ``rank``.
    """
    x, comm, token = _prologue(x, comm, token)
    root = check_root(root, comm)
    if comm.backend == "proc":
        from mpi4jax_tpu.ops import _proc

        if comm.rank() == root and (x.ndim == 0 or x.shape[0] != comm.size):
            raise ValueError(
                # reference wording (scatter.py:77-81 there)
                f"Scatter input must have shape (nproc, ...) with nproc "
                f"== comm.size={comm.size} on root, got shape {x.shape}"
            )
        y, stamp = _proc.proc_scatter(x, token.stamp, comm, root)
        return y, token.with_stamp(stamp)
    if x.ndim == 0 or x.shape[0] != comm.size:
        raise ValueError(
            # wording matches the reference's check (scatter.py:77-81
            # there; its own test suite asserts on the phrase)
            f"Scatter input must have shape (nproc, ...) with nproc == "
            f"comm.size={comm.size}, got shape {x.shape}"
        )
    if comm.backend == "self":
        y = x[0]
        token, (y,) = fence_out(token, y)
        return y, token
    if comm.backend == "mesh":
        token, (x,) = fence_in(token, x)
        rank = comm.rank()
        as_int = x.dtype == jnp.bool_
        xv = x.astype(jnp.int8) if as_int else x
        xv = promote_vma(xv, comm.axes)
        masked = jnp.where(rank == root, xv, jnp.zeros_like(xv))
        # reduce-scatter of the masked buffer: rank r receives
        # sum_over_ranks(row r) = root's row r.  O(payload) on the wire
        # (ring reduce-scatter), vs O(size*payload) for a full psum.
        y = _scatter_sum(masked, comm)
        if as_int:
            y = y.astype(jnp.bool_)
        token, (y,) = fence_out(token, y)
        return y, token
    raise _unsupported("scatter", comm)


def _scatter_sum(masked, comm):
    """``psum_scatter`` row ``rank`` of the summed buffer to each rank."""
    if comm.groups is None:
        return lax.psum_scatter(
            masked, comm.axes, scatter_dimension=0, tiled=False
        )
    return lax.psum_scatter(
        masked,
        comm.axes,
        scatter_dimension=0,
        axis_index_groups=comm.groups,
        tiled=False,
    )
