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
"""

import numpy as np

import jax
import jax.numpy as jnp

from mpi4jax_tpu.ops._core import as_token, publishes_token
from mpi4jax_tpu.ops.p2p import sendrecv, sendrecv_multi

__all__ = ["halo_exchange_2d", "halo_exchange_2d_batch"]

# The exchange's phases, as jax.named_scope segments inside the op's own
# ``mpi4jax_tpu.<op>`` scope (ops/_core.py publishes_token): a lowered
# instruction's op_name reads ``.../mpi4jax_tpu.halo_exchange_2d/pack/...``,
# which is how a device profile splits the op's time into slicing slabs,
# the permute and writing ghosts.  Metadata only; none may start with
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
    Ghost slabs are written with dynamic-update-slices.  (Measured on
    v5e: the alternatives — one minor-dim concatenate, or iota-masked
    jnp.where selects — are 10% slower than DUS even though DUS makes
    XLA flip some layouts; see docs/shallow-water.md.)
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


def _exchange(arrs, comm, *, periodic, token, width, stack):
    """Shared four-direction exchange body (x then y so corners fill
    transitively).  ``stack=True`` sends all arrays' slabs in one
    permute per direction; ``stack=False`` sends them one by one."""
    token = as_token(token)
    per_y, per_x = periodic
    w = width

    def shift(slabs, templates, axis, disp, per):
        nonlocal token
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
                return [None] * len(slabs)
            with jax.named_scope(WIRE):
                outs, token = sendrecv_multi(
                    slabs, templates, source=pairs, dest=pairs, comm=sub,
                    token=token,
                )
            return list(outs)
        if stack:
            with jax.named_scope(PACK):
                slab, template = jnp.stack(slabs), jnp.stack(templates)
            with jax.named_scope(WIRE):
                halo, token = _axis_shift(
                    slab, template, comm, axis, disp, per, token
                )
            if halo is None:
                return [None] * len(slabs)
            with jax.named_scope(UNPACK):
                return list(halo)
        out = []
        for slab, template in zip(slabs, templates):
            with jax.named_scope(WIRE):
                halo, token = _axis_shift(
                    slab, template, comm, axis, disp, per, token
                )
            out.append(halo)
        return out

    def pack(sent, received):
        with jax.named_scope(PACK):
            return [a[sent] for a in arrs], [a[received] for a in arrs]

    def write(arrs, halo, region):
        # halo[i] is None on a global no-op shift: ghosts already hold
        # the right values, skip the (identical) write
        with jax.named_scope(UNPACK):
            return [
                a if halo[i] is None else a.at[region].set(halo[i])
                for i, a in enumerate(arrs)
            ]

    # --- x direction: full-height column slabs (corners ride along) ---
    halo = shift(*pack(np.s_[:, -2 * w : -w], np.s_[:, :w]), "x", +1, per_x)
    arrs = write(arrs, halo, np.s_[:, :w])
    halo = shift(*pack(np.s_[:, w : 2 * w], np.s_[:, -w:]), "x", -1, per_x)
    arrs = write(arrs, halo, np.s_[:, -w:])

    # --- y direction: full-width row slabs (x halos already current) ---
    halo = shift(*pack(np.s_[-2 * w : -w, :], np.s_[:w, :]), "y", +1, per_y)
    arrs = write(arrs, halo, np.s_[:w, :])
    halo = shift(*pack(np.s_[w : 2 * w, :], np.s_[-w:, :]), "y", -1, per_y)
    arrs = write(arrs, halo, np.s_[-w:, :])

    return arrs, token
