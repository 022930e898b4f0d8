"""Halo (ghost-cell) exchange for 2-D domain decomposition.

The reference builds halo exchange by hand from token-ordered
send/recv/sendrecv in a deadlock-free clockwise order
(examples/shallow_water.py:173-271) — four blocking MPI calls per field
per step.  TPU-native equivalent (SURVEY §2.4 "Spatial / domain
decomposition"): each direction is one ``sendrecv`` over a mesh-axis
sub-communicator, which lowers to a single ``lax.ppermute`` — a
nearest-neighbour ICI transfer, the physically native communication
pattern on a TPU torus.

Order: the x exchange moves full columns (including y-halo cells), then
the y exchange moves full rows (including the just-filled x halos), so
corner cells are correct after two rounds — same transitive-corner trick
as the reference's clockwise ordering.

Two forms of one exchange: :func:`halo_exchange_2d` (and its batched
sibling) writes the received slabs into the block's ghosts;
:func:`halo_slabs_2d` stops before that and returns them.  Both slice
(``pack``) and send (``wire``) through the same code.
"""

import numpy as np

import jax
import jax.numpy as jnp

from mpi4jax_tpu.ops._core import as_token, publishes_token
from mpi4jax_tpu.ops.p2p import sendrecv, sendrecv_multi

__all__ = ["halo_exchange_2d", "halo_exchange_2d_batch", "halo_slabs_2d"]

# The exchange's phases, as jax.named_scope segments inside the op's own
# ``mpi4jax_tpu.<op>`` scope (ops/_core.py publishes_token): a lowered
# instruction's op_name reads ``.../mpi4jax_tpu.halo_exchange_2d/pack/...``,
# which is how a device profile splits the op's time into slicing slabs,
# the permute and writing ghosts (halo_slabs_2d has no unpack: its
# caller writes them).  Metadata only; none may start with
# SCOPE_PREFIX (the analyzer takes the innermost such segment as the op).
PACK, WIRE, UNPACK = "pack", "wire", "unpack"


def _axis_shift(arr_slice, template, comm, axis, disp, periodic, token):
    """One directional exchange along ``axis`` (disp = ±1).

    Returns ``(halo, token)``; ``halo is None`` signals a global no-op
    (non-periodic shift on a size-1 axis) — every device keeps its
    existing ghost values, so the caller can skip the ghost write
    entirely instead of re-writing identical values.
    """
    sub = comm.sub(axis)
    pairs = sub.shift_perm(axis, disp, periodic=periodic)
    if not pairs:
        return None, token
    return sendrecv(
        arr_slice,
        template,
        source=pairs,
        dest=pairs,
        comm=sub,
        token=token,
    )


@publishes_token
def halo_exchange_2d(arr, comm, *, periodic=(False, True), token=None, width=1):
    """Exchange ``width``-cell halos of a local block over a ("y", "x")
    MeshComm.

    ``arr`` is the device-local block of shape ``(ny_local + 2*width,
    nx_local + 2*width)`` (interior plus a ``width``-deep ghost ring).
    Returns ``(arr, token)`` with ghost cells holding the neighbours'
    adjacent interior cells.  ``periodic`` is (y, x); non-periodic edge
    devices keep their existing ghost values (apply wall conditions
    separately).

    Works for any decomposition including 1×1 (periodic wrap becomes a
    self-permute, so single-chip runs use the identical program).
    Ghost slabs are written with dynamic-update-slices: on a v5e a
    write of two columns of a 7204 x 14404 block takes 29 us, a whole
    vector register's lanes a row for 58 KB (``PERF.md``, PR 29's
    trace).  A caller whose next kernel reads and writes the whole
    block anyway takes the slabs from :func:`halo_slabs_2d` and places
    them there.
    """
    arrs, token = _exchange(
        [arr], comm, periodic=periodic, token=token, width=width,
        stack=False,
    )
    return arrs[0], token


@publishes_token
def halo_exchange_2d_batch(arrs, comm, *, periodic=(False, True), token=None,
                           width=1):
    """Exchange the halos of several same-shaped blocks at once.

    Same contract as :func:`halo_exchange_2d`, but the per-direction
    slabs of all arrays travel in a single stacked ``sendrecv`` — one
    ``ppermute`` per direction for the whole field group instead of one
    per field.  Fewer, larger ICI transfers win on real multi-chip
    meshes; on a single chip permutes are elided and the stacking copies
    cost, so the per-field function is preferred there.

    Returns ``(list_of_arrs, token)``.
    """
    return _exchange(
        list(arrs), comm, periodic=periodic, token=token, width=width,
        stack=True,
    )


def _shifts(width, periodic):
    """The exchange's four shifts in order, as ``(axis, disp, per, sent,
    received)``: whether the axis wraps, the region a block sends and
    the ghost region its neighbour's lands in (west, east, south,
    north).  x first, full height; then y, full width, so that corners
    fill transitively."""
    w = width
    per_y, per_x = periodic
    return (
        ("x", +1, per_x, np.s_[:, -2 * w : -w], np.s_[:, :w]),
        ("x", -1, per_x, np.s_[:, w : 2 * w], np.s_[:, -w:]),
        ("y", +1, per_y, np.s_[-2 * w : -w, :], np.s_[:w, :]),
        ("y", -1, per_y, np.s_[w : 2 * w, :], np.s_[-w:, :]),
    )


def _shift(slabs, templates, comm, axis, disp, per, token, *, stack):
    """One direction's slabs of every array over the wire.  Returns the
    received slabs (``None`` each on a global no-op shift) and the
    token.  ``stack=True`` sends all arrays' slabs in one permute;
    ``stack=False`` sends them one by one."""
    if comm.backend == "proc":
        # multi-process tier: the whole field group's slabs for this
        # direction go through one sendrecv_multi — below
        # T4J_COALESCE_BYTES they travel as ONE fused wire frame
        # instead of one frame per field (docs/performance.md
        # "small-message coalescing"); above it, per-part frames
        # (the exact pre-coalescing behaviour).  No stacking copy
        # either way.
        sub = comm.sub(axis)
        pairs = sub.shift_perm(axis, disp, periodic=per)
        if not pairs:
            return [None] * len(slabs), token
        with jax.named_scope(WIRE):
            outs, token = sendrecv_multi(
                slabs, templates, source=pairs, dest=pairs, comm=sub,
                token=token,
            )
        return list(outs), token
    if stack:
        with jax.named_scope(PACK):
            slab, template = jnp.stack(slabs), jnp.stack(templates)
        with jax.named_scope(WIRE):
            halo, token = _axis_shift(
                slab, template, comm, axis, disp, per, token
            )
        if halo is None:
            return [None] * len(slabs), token
        with jax.named_scope(UNPACK):
            return list(halo), token
    out = []
    for slab, template in zip(slabs, templates):
        with jax.named_scope(WIRE):
            halo, token = _axis_shift(
                slab, template, comm, axis, disp, per, token
            )
        out.append(halo)
    return out, token


def _pack(arrs, sent, received):
    with jax.named_scope(PACK):
        return [a[sent] for a in arrs], [a[received] for a in arrs]


def _exchange(arrs, comm, *, periodic, token, width, stack):
    """Shared four-direction exchange body: each shift's slabs are
    written into the ghosts before the next shift packs its own, so the
    y slabs carry the x ghosts just received."""
    token = as_token(token)
    for axis, disp, per, sent, received in _shifts(width, periodic):
        halo, token = _shift(
            *_pack(arrs, sent, received), comm, axis, disp, per, token,
            stack=stack,
        )
        # halo[i] is None on a global no-op shift: ghosts already hold
        # the right values, skip the (identical) write
        with jax.named_scope(UNPACK):
            arrs = [
                a if halo[i] is None else a.at[received].set(halo[i])
                for i, a in enumerate(arrs)
            ]
    return arrs, token


@publishes_token
def halo_slabs_2d(arr, comm, *, periodic=(False, True), token=None, width=1):
    """:func:`halo_exchange_2d` without its last phase: the four slabs
    a block receives, for a caller that places them itself (a kernel
    that reads and writes the block's every tile anyway:
    ``models/sw_kernels.py wide_step``).

    Returns ``((west, east, south, north), token)``: what
    :func:`halo_exchange_2d` writes to ``arr[:, :width]``, ``arr[:,
    -width:]``, ``arr[:width, :]`` and ``arr[-width:, :]``, in that
    order.  Written so, in that order, they give its result bit for
    bit.  A slab is ``None`` where the shift is a no-op on the whole
    axis (no wrap on an axis of one device): those ghosts stay as they
    are.  The x slabs are full height.  The y slabs are full width with
    the x ghosts fresh: no ghost is written between the shifts here, so
    the ``width`` x ``width`` ends of a row slab are patched from the
    received x slabs before it is sent (on a slab of a few rows, not on
    the block), and a device with no neighbour on that side gets its own
    ghost rows back patched likewise.
    """
    token = as_token(token)
    w = width
    slabs = []

    def rows(region):
        """A row slab of ``arr`` with the x slabs for its ends."""
        west, east = slabs[:2]
        with jax.named_scope(PACK):
            slab = arr[region]
            if west is None and east is None:
                return slab
            return jnp.concatenate([
                slab[:, :w] if west is None else west[region],
                slab[:, w:-w],
                slab[:, -w:] if east is None else east[region],
            ], axis=1)

    for axis, disp, per, sent, received in _shifts(w, periodic):
        if not comm.sub(axis).shift_perm(axis, disp, periodic=per):
            slabs.append(None)  # a no-op on the whole axis: nothing to pack
            continue
        if axis == "x":
            parts = _pack([arr], sent, received)
        else:
            parts = [rows(sent)], [rows(received)]
        (slab,), token = _shift(
            *parts, comm, axis, disp, per, token, stack=False)
        slabs.append(slab)
    return tuple(slabs), token
