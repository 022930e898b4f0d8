"""Halo (ghost-cell) exchange for 2-D domain decomposition.

The reference builds halo exchange by hand from token-ordered
send/recv/sendrecv in a deadlock-free clockwise order
(examples/shallow_water.py:173-271) — four blocking MPI calls per field
per step.  TPU-native equivalent (SURVEY §2.4 "Spatial / domain
decomposition"): each direction is one ``sendrecv`` over a mesh-axis
sub-communicator, which lowers to a single ``lax.ppermute`` — a
nearest-neighbour ICI transfer, the physically native communication
pattern on a TPU torus.

Order: ``pack`` the two column slabs (full height) and send them over
the x ``wire``; ``pack`` the two row slabs (full width), their ``width``
x ``width`` ends taken from the column slabs just received, and send
them over the y ``wire``; then, once, ``unpack``: west, east, south,
north written over the block's ghosts.  No ghost is written between the
two wires, and the corners are what the reference's clockwise order
makes them: a row slab carries the x ghosts its sender has just
received, patched on a slab of a few rows and not on the block.

Two forms of one exchange: :func:`halo_exchange_2d` (and its batched
sibling) runs all three phases; :func:`halo_slabs_2d` stops before
``unpack`` and returns the four slabs.  Both come out of
:func:`_received`, and on the mesh tier that is where both hold the
block row-major (:func:`_row_major`): wherever a column slab is cut, it
is cut from a block in the layout the rest of the program keeps, and no
form of the exchange has the compiler transpose a block.
"""

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental.layout import Layout, with_layout_constraint

from mpi4jax_tpu.ops._core import (
    as_token, both_modes, promote_vma, publishes_token, vma_of)
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
# The transposed exchange (what ``jax.grad`` / ``jax.vjp`` runs in place
# of an exchange, on the mesh tier: :func:`_adjoint`) names the same
# three phases inside this marker, so that a backward sweep's events
# read ``.../mpi4jax_tpu.halo_exchange_2d))/transpose/pack|wire|unpack``
# where the forward's read ``.../mpi4jax_tpu.halo_exchange_2d/pack|...``.
TRANSPOSE = "transpose"


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
    The ghosts are written once, after both wires, by four
    ``dynamic_update_slice`` on one value: in place where the caller
    gives the block up (``x = halo_exchange_2d(x, ...)[0]`` in a loop),
    on one copy of it where the caller keeps ``arr``.  On the mesh tier
    the block is held row-major for the exchange (:func:`_row_major`).
    On a v5e 2×2, ``width`` 2 on a 1804 x 3604 block (``PERF.md``,
    PR 37): 26 us a call in place, of which the two column writes are
    3.6 and 3.9 us (each the strip of lane tiles that holds its two
    columns, read and written whole: :func:`_place`), the row writes
    1 us each and the four permutes 8; 68 us where the input is kept,
    41 of them the copy.  A caller whose next kernel reads and writes
    the whole block anyway takes the slabs from :func:`halo_slabs_2d`
    and places them there: that saves the column writes and costs the
    kernel nothing (``models/sw_kernels.py wide_step``).
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
    per field.  Whether fewer, larger transfers win on a mesh of chips
    no chip has been asked: three fields stacked against three single
    exchanges is an A/B nobody has run (``ROADMAP.md`` D13 names its
    sizes).  The multi-process tier's coalesced frame goes through this
    form, and ``SWConfig(ghost=4)``'s step makes its one exchange with
    it.

    Returns ``(list_of_arrs, token)``.
    """
    return _exchange(
        list(arrs), comm, periodic=periodic, token=token, width=width,
        stack=True,
    )


def _shifts(width, periodic, depth=None):
    """The exchange's four shifts in order, as ``(axis, disp, per, sent,
    received)``: whether the axis wraps, the region a block sends and
    the ghost region its neighbour's lands in (west, east, south,
    north).  x first, full height; then y, full width, so that corners
    fill transitively.  ``depth`` (y, x), each the ring's ``width``
    unless given: the interior columns and rows next to an edge that a
    block sends, which may be more than its ring holds
    (:func:`halo_slabs_2d`)."""
    w = width
    dy, dx = depth or (w, w)
    per_y, per_x = periodic
    return (
        ("x", +1, per_x, np.s_[:, -(w + dx) : -w], np.s_[:, :w]),
        ("x", -1, per_x, np.s_[:, w : w + dx], np.s_[:, -w:]),
        ("y", +1, per_y, np.s_[-(w + dy) : -w, :], np.s_[:w, :]),
        ("y", -1, per_y, np.s_[w : w + dy, :], np.s_[-w:, :]),
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


def _beyond(ghosts, axis, disp, extra):
    """A block's own ghost columns or rows as the template of a slab
    ``extra`` deeper than its ring: zeros where the block holds
    nothing, on the side away from the interior (``disp`` +1: the slab
    lands west or south of the block)."""
    pads = [(0, 0, 0)] * ghosts.ndim
    pads[axis] = (extra, 0, 0) if disp > 0 else (0, extra, 0)
    return lax.pad(ghosts, jnp.zeros((), ghosts.dtype), pads)


def _received(arrs, comm, *, periodic, token, width, stack, depth=None):
    """The four shifts of every array with no ghost written between
    them: ``(arrs, slabs, token)``, ``slabs[k][i]`` what array ``i``
    receives from shift ``k`` of :func:`_shifts` (``None`` where that
    shift is a no-op on the whole axis).  The x slabs are sliced from
    the block; a y slab is the block's rows with its ``width`` x
    ``width`` ends taken from the x slabs just received, patched on a
    slab of a few rows.  ``arrs`` are the blocks as the slabs were cut
    from them: on the mesh tier held row-major (:func:`_row_major`), for
    a caller that goes on to write into them.  ``depth``: as
    :func:`_shifts` takes it; a slab deeper than the ring is
    :func:`halo_slabs_2d`'s."""
    if comm.backend == "mesh":
        with jax.named_scope(PACK):
            arrs = [_row_major(a) for a in arrs]
    token = as_token(token)
    w = width
    dy, dx = depth or (w, w)
    slabs = []

    def rows(i, region):
        """A row slab of array ``i`` with the x slabs for its ends."""
        west, east = slabs[0][i], slabs[1][i]
        with jax.named_scope(PACK):
            slab = arrs[i][region]
            if west is None and east is None:
                return slab
            return jnp.concatenate([
                slab[:, :w] if west is None else west[region],
                slab[:, w:-w],
                slab[:, -w:] if east is None else east[region],
            ], axis=1)

    for axis, disp, per, sent, received in _shifts(w, periodic, depth):
        if not comm.sub(axis).shift_perm(axis, disp, periodic=per):
            # a no-op on the whole axis: nothing to pack
            slabs.append([None] * len(arrs))
            continue
        if axis == "x":
            parts = _pack(arrs, sent, received)
        else:
            parts = tuple(
                [rows(i, region) for i in range(len(arrs))]
                for region in (sent, received))
        extra = (dx if axis == "x" else dy) - w
        if extra:
            # the templates' ghosts are the block's, its ring deep
            with jax.named_scope(PACK):
                parts = parts[0], [
                    _beyond(x, int(axis == "x"), disp, extra) for x in parts[1]]
        got, token = _shift(
            *parts, comm, axis, disp, per, token, stack=stack)
        slabs.append(got)
    return arrs, slabs, token


def _row_major(a):
    """``a`` held to the layout a block has everywhere else in a program.
    Left to itself the TPU compiler lays the whole carried block out
    column-major to suit the two-column slabs sliced from it, and then
    pays for it wherever the block is used row-major: in
    :func:`halo_exchange_2d` for every row slab's write (25 us for 29 KB
    on a v5e, ``PERF.md`` PR 35), and round a kernel that takes the block
    as it lies (:func:`halo_slabs_2d`'s caller) with a transpose of the
    whole block, once a field a step (three ``copy`` of 415 MB, a third
    of the step at 7204 x 14404 a chip on 2x2: ``PERF.md`` PR 52).  Held
    row-major it slices and writes the narrow slabs and transposes
    those.  Both forms are held, in :func:`_received`."""
    return with_layout_constraint(
        a, Layout(major_to_minor=tuple(range(a.ndim))))


# the last dimension of a tile of TPU memory, in elements of any dtype:
# f32 lies in (8, 128) tiles, bf16 in (16, 128), int8 in (32, 128)
LANES = 128


def _lane_tiles(block, slab, start):
    """The columns ``(s0, s1)`` of the whole lane tiles that hold a
    narrow column slab, or ``None`` where the slab is written as it is.
    From shapes alone: ``block`` and ``slab`` are the two shapes and
    ``start`` the slab's static offsets in the block.  A slab of every
    row and fewer than ``LANES`` columns, in a block wider than one
    tile, lies in the tiles from ``s0`` to ``s1`` (the block's edge cuts
    the last one short); a row slab, a slab a tile wide or more and a
    block of one tile have no tile path."""
    nx, w = block[1], slab[1]
    if slab[0] != block[0] or w >= LANES or nx <= LANES:
        return None
    s0 = start[1] // LANES * LANES
    s1 = min(nx, -(-(start[1] + w) // LANES) * LANES)
    return s0, s1


def _place(a, slab, region, *, tiles=False, add=False):
    """``a`` with ``slab`` written over ``region``, a pair of static
    slices: a ``dynamic_update_slice`` at constant offsets (``.at[].set``
    is a scatter, which XLA gives a bounds test, a mask and a select
    of the slab's size on every write).  With ``add`` the slab is added
    to what lies there (the transposed exchange's write: a returned
    cotangent into the edge it was copied from).

    With ``tiles`` (the mesh tier, whose block is row-major) a slab
    narrower than a lane tile is written as the whole tiles that hold
    it (:func:`_lane_tiles`): the strip is read where it lies, the slab
    selected into it and the strip written back at an aligned offset,
    one in-place fusion.  Two columns of a row-major ``(8, 128)``-tiled
    block are a piece of 8 bytes a row, and the TPU compiler writes each
    piece by itself: 14.0 and 12.5 us for the two ``[1804, 2]`` slabs of
    a 1804 x 3604 block on a v5e, 28 % of the whole exchange, where the
    two row slabs, twice the bytes, take 1.7 (``PERF.md``, PR 35; in
    HBM, for a caller in place, 7.7 and 7.6).  The strip is 226 tile
    rows of 4 KB, loaded, selected into and stored whole: 0.9 us for
    both slabs where XLA keeps the block in its faster memory, 3.6 and
    3.9 us in HBM (one strided DMA in, one out), and the exchange 95 ->
    68 us a call with its input kept and 34 -> 26 in place (``PERF.md``,
    PR 37).  The same bits either way: written with ``concatenate`` the
    TPU compiler pads the pieces and takes their ``maximum``, which is
    not the same bits for a NaN or a negative zero."""
    start = tuple(s.indices(n)[0] for s, n in zip(region, a.shape))
    cols = _lane_tiles(a.shape, slab.shape, start) if tiles else None
    if cols is None:
        if add:
            slab = slab + a[region]
        return lax.dynamic_update_slice(a, slab, start)
    s0, s1 = cols
    before, w = start[1] - s0, slab.shape[1]
    padded = lax.pad(slab, jnp.zeros((), slab.dtype),
                     [(0, 0, 0), (before, s1 - s0 - before - w, 0)])
    strip = lax.slice_in_dim(a, s0, s1, axis=1)
    if add:  # zeros beside the slab: the strip's other columns as they are
        return lax.dynamic_update_slice(a, strip + padded, (0, s0))
    # a constant, so that XLA carries a literal and computes no mask
    ghost = np.zeros(s1 - s0, bool)
    ghost[before:before + w] = True
    strip = lax.select(
        jnp.broadcast_to(ghost, (a.shape[0], s1 - s0)), padded, strip)
    return lax.dynamic_update_slice(a, strip, (0, s0))


def _exchange(arrs, comm, *, periodic, token, width, stack):
    """Both forms that write ghosts: the four received slabs of
    :func:`_received`, then one placement phase over each block: west,
    east, south, north onto one value, nothing else reading the values
    between, so the writes can share one buffer (the caller's own where
    it gives the block up, one copy of it where it keeps it).  On the
    mesh tier the block is row-major, so it is the column slabs that
    are narrow: :func:`_place` writes those as whole lane tiles; and
    there the exchange carries its own transpose (:func:`_transposable`)."""
    options = dict(periodic=periodic, width=width, stack=stack)
    if comm.backend != "mesh":
        return _placed(arrs, comm, token=token, **options)
    return _transposable(
        lambda arrs, token: _placed(arrs, comm, token=token, **options),
        lambda blocks, token: _adjoint(
            blocks, None, comm, token=token, **options),
        arrs, token)


def _placed(arrs, comm, *, periodic, token, width, stack):
    """:func:`_exchange` as every tier runs it."""
    mesh = comm.backend == "mesh"
    arrs, slabs, token = _received(
        arrs, comm, periodic=periodic, token=token, width=width, stack=stack,
    )
    regions = [received for *_, received in _shifts(width, periodic)]
    out = []
    with jax.named_scope(UNPACK):
        for i, a in enumerate(arrs):
            for got, region in zip(slabs, regions):
                # None on a global no-op shift: the ghosts already hold
                # the right values, skip the (identical) write
                if got[i] is not None:
                    a = _place(a, got[i], region, tiles=mesh)
            out.append(a)
    return out, token


@publishes_token
def halo_slabs_2d(arr, comm, *, periodic=(False, True), token=None, width=1,
                  depth=None):
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
    ghost rows back patched likewise.  On the mesh tier the block is held
    row-major where the slabs are cut from it (:func:`_row_major`), the
    layout a kernel takes it in: the caller's program transposes the
    sent slabs, a few rows or columns each, and never the block.

    ``arr`` may be a list of same-shaped blocks: the slabs of each, in a
    list, every array's x slabs over the wire before any y slab.

    ``depth`` (y, x): slabs cut **deeper than the block's ring**, for a
    caller that computes further out than its block holds (a walk of two
    time steps from one exchange): a block padded by ``width`` sends the
    ``depth`` interior columns and rows next to each edge, and the slabs
    are what an exchange ``depth`` deep would write round a block padded
    that much.  West holds the columns from ``depth[1] - width`` left of
    the block to its ring's last, over the block's rows; south the rows
    from ``depth[0] - width`` below the block likewise, as wide as the
    block **and its deeper x slabs**, whose rows are its ends, so that
    corners fill transitively, ``depth`` deep.  A device with no
    neighbour on a side gets its own ghosts back with zeros beyond them.
    With ``depth`` equal to ``width`` on both axes (or not given) the
    traced program is the same, equation for equation.
    """
    several = isinstance(arr, (list, tuple))
    arrs = list(arr) if several else [arr]
    options = dict(periodic=periodic, width=width, stack=False)
    if depth is not None:  # as deep as the ring: the exchange as it is
        depth = None if tuple(depth) == (width, width) else tuple(depth)

    def slabs_of(arrs, token):
        _, slabs, token = _received(
            arrs, comm, token=token, depth=depth, **options)
        return [tuple(got[i] for got in slabs) for i in range(len(arrs))], token

    def blocks_of(slabs, token):
        # the blocks' own cotangent is nothing: the slabs are all that
        # is handed back
        zeros = [promote_vma(jnp.zeros(a.shape, a.dtype), comm.axes)
                 for a in arrs]
        return _adjoint(zeros, slabs, comm, token=token, **options)

    if comm.backend == "mesh" and depth is None:
        slabs, token = _transposable(slabs_of, blocks_of, arrs, token)
    else:
        # the other tiers, and slabs deeper than the ring, differentiate
        # by the rules of what they are made of
        slabs, token = slabs_of(arrs, token)
    return (slabs if several else slabs[0]), token


def _transposable(forward, backward, arrs, token):
    """``forward(arrs, token)``, an exchange of the mesh tier, with
    ``backward`` for its transpose: ``jax.vjp`` and ``jax.grad`` through
    the exchange then run the adjoint exchange (:func:`_adjoint`) and
    not the transposes of the exchange's parts.  Those are right and
    dense: a slab sliced from a block transposes to a block of zeros
    with the slab padded into it and an add of two blocks, once a slab,
    and the lane-tile strip of :func:`_place` likewise, where the
    adjoint exchange touches the slabs it moves and nothing else.  An
    exchange is linear in its blocks and passes its token's stamp
    through: nothing is kept for either mode.  Forwards (``jax.jvp``,
    ``jax.linearize``, ``jax.jacfwd``) its tangent is the exchange
    itself on the tangents, under the same ``pack``, ``wire`` and
    ``unpack`` scopes (a tangent sweep's events carry them inside
    ``jvp(...)``), bit for bit what the exchange gives those blocks.
    Backwards the adjoint exchange threads the token's cotangent through
    its four shifts as their token, and a stamp's own cotangent is zero.
    Both by ``ops/_core.py both_modes``.  A call that is not
    differentiated lowers to what ``forward`` lowers to."""
    token = as_token(token)
    # a stamp's cotangent says nothing; it has to be of the stamp's type
    stamps = [(x.shape, x.dtype, vma_of(x) or ()) for x in jax.tree.leaves(token)]

    def transposed(_, cotangents):
        blocks, after = backward(*cotangents)
        nothing = [promote_vma(jnp.zeros(shape, dtype), vma)
                   for shape, dtype, vma in stamps]
        return blocks, jax.tree.unflatten(jax.tree.structure(token), nothing)

    exchange = both_modes(
        forward, lambda arrs, token: (),
        lambda _, arrs, token: forward(arrs, token), transposed)
    return exchange(list(arrs), token)


def _adjoint(blocks, slabs, comm, *, periodic, token, width, stack):
    """The transpose of both forms of the exchange on the mesh tier,
    written as an exchange: for every ghost cell, its cotangent added to
    the cell it was copied from.  ``blocks`` are the cotangents of the
    blocks that :func:`_exchange` returns; ``slabs``, where given, those
    of :func:`halo_slabs_2d`'s slabs (``slabs[i][k]`` for array ``i`` and
    shift ``k``, ``None`` where the shift is none), which lie over the
    blocks' ghost regions.  Returns the cotangents of the blocks handed
    to the exchange, and the token.

    The exchange is ``Y . X`` (the x ghosts over full height, then the y
    ghosts over full width, fresh x ghosts and so corners included), its
    transpose ``X^T . Y^T``: the shifts in reverse order, and for each
    the ghost region ``pack``-ed (read), sent over the ``wire`` with the
    displacement negated, so that it arrives where the ghosts came from,
    and ``unpack``-ed by an add into the edge strip that was sent; the
    ghost region itself is left zero, since the exchange overwrote it
    (on a device that received nothing there, a walled side's, it keeps
    what it holds: those ghosts passed through).  A corner's cotangent
    goes back through both shifts.  The same slab-sized work as the
    exchange: column strips by :func:`_place`'s aligned write, the block
    held row-major (:func:`_row_major`), no pass over a block."""
    w = width
    with jax.named_scope(TRANSPOSE):
        with jax.named_scope(PACK):
            blocks = [_row_major(g) for g in blocks]
        shifts = list(enumerate(_shifts(w, periodic)))
        for k, (axis, disp, per, sent, received) in reversed(shifts):
            sub = comm.sub(axis)
            pairs = sub.shift_perm(axis, disp, periodic=per)
            if not pairs:
                continue  # no exchange on this axis: the ghosts pass through
            with jax.named_scope(PACK):
                ghosts = [g[received] for g in blocks]
                if slabs is not None:
                    ghosts = [g if c[k] is None else g + c[k]
                              for g, c in zip(ghosts, slabs)]
                zeros = [jnp.zeros_like(g) for g in ghosts]
            back, token = _shift(
                ghosts, zeros, comm, axis, -disp, per, token, stack=stack)
            with jax.named_scope(UNPACK):
                kept = zeros
                if len(pairs) < sub.size:
                    # a device nothing came to kept its ghosts
                    got = np.zeros(sub.size, bool)
                    got[[d for _, d in pairs]] = True
                    got = jnp.asarray(got)[sub.rank()]
                    kept = [jnp.where(got, z, g) for z, g in zip(zeros, ghosts)]
                blocks = [
                    _place(_place(g, r, sent, tiles=True, add=True),
                           keep, received, tiles=True)
                    for g, r, keep in zip(blocks, back, kept)]
    return blocks, token
