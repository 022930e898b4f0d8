"""The wide-halo shallow-water step as one Pallas TPU kernel.

XLA compiles a stencil round of :func:`shallow_water._step_wide` into
fusions that write their shifted intermediates to HBM, and a second pass
that copies the interior back into the padded field.  The kernel here
(:func:`wide_step`) streams row tiles of the padded fields through VMEM
instead: every intermediate stays on the chip, and each of the six state
arrays (``h``, ``u``, ``v`` and their tendencies) is read once and
written once, in place: 12 passes over a field, the least a step can
move.

Schedule: three exchanges, not five
-----------------------------------
The array code exchanges ``u`` and ``v`` a second time between its two
rounds, because round 2 (lateral friction, a five-point stencil) reads
the first ghost ring of what round 1 has just updated.  The kernel
computes those values itself.  Round 1 at a cell reads the ring of
cells round it and nothing further (the staggered differences cancel
the compound stencil's second ring), and the step's first exchange hands
every device two fresh rings.  So a device runs round 1 on its interior
**and on ring 1 of** ``u`` **and** ``v`` (reading ring 2), which gives
it the ``u``, ``v`` its neighbours hold there after their round 1, from
the same inputs by the same code; round 2 follows in the same walk over
the rows, and the updated ``u``, ``v`` between the rounds never reach
HBM.  This holds on every mesh; nothing is chosen from the mesh's
shape.  Beyond a wall there is no neighbour: those ghost rows keep
their values, as the array code's exchange leaves them.

The Adams-Bashforth update of ring 1 needs last step's tendencies
there, so the kernel **stores** ``du``, ``dv`` **on ring 1 of the
padded tendencies**: they are what the neighbour holds for the same
cells.  A state's tendencies are therefore a step's own: hand a later
step the ones a step returned (a first step reads none).  Ring 2 of
``du``, ``dv``, ring 1 beyond a wall and all of ``dh``'s ghost ring are
zero.  Ring 1 of the returned ``u``, ``v`` holds round 1's values, not
round 2's (the next step's exchange overwrites it); ``h``'s ghost ring
and ring 2 of ``u``, ``v`` pass through.

Who writes the ghosts.  The exchange does not: a write of two ghost
columns touches a vector register's lanes in every row of the block, 29
us a slab on a v5e for 58 KB, six a step, next to a kernel that reads
and writes every one of those tiles anyway.  So the step calls
``parallel.halo_slabs_2d``, the exchange without its last phase, and
hands the kernel the fields **with stale ghosts and the received
slabs**; the kernel writes the slabs' cells over each row as it enters
the VMEM window (below), so that both rounds read, and the fields come
back with, the ghosts an exchange would have written.  On every mesh:
on one device a slab is a slice of the block itself, on four what the
neighbour sent; a slab that is ``None`` (no wrap on an axis of one
device) leaves those ghosts the block's own.

Tiling (:func:`_walk`)
----------------------
The field keeps its full width (x is not tiled), so an x-shift is a lane
rotation whose wrap lands in the outermost ghost columns, which no mask
admits.  A block is as wide as the field's columns fill vector
registers (:func:`_whole_registers`: the field's rows take that much of
VMEM anyway, and a rotation of rows that end inside a register costs
twice one of rows that fill theirs).  Rows are cut into tiles of :func:`tile_rows` (a multiple of 8,
chosen from the width, the dtype and the number of arrays so that the
call's blocks fit VMEM), and the grid walks them from the first row to
the last.

A tile's stencil needs the rows above it and below it as they were
before the update, and the field is written in place.  So the kernel
runs one tile behind its input: step ``i`` is handed tile ``i`` and
runs round 1 on tile ``i - 1``, which it kept in a VMEM window from the
step before, between the last strip of tile ``i - 2`` and the first
strip of tile ``i``: 8 rows of old values either side, of which round 1
reads one.  Every row is read from HBM once, before the step that writes
it, and no tile reads what another has written; a field is one operand
of the call and no other, so XLA has nothing to copy.  The received
slabs are operands of their own, as the exchange returns them: a slab of
columns ``(rows, 2)`` in blocks of a tile's rows, a slab of rows ``(2,
width)`` whole.  Where rows enter the window (the first strip of the
tile handed in, then the tile) the slabs' cells are stored over them,
x before y as an exchange writes them: a slab's columns into the first
or the last vector register of each row (two stores where they lie in
two), its rows in the grid step that holds their strip.  Each store is
lowered on its own, a few milliseconds of a program's set-up on a
chip's host, which is why they are as few as that.  (One ``(rows, 128)``
operand a field with both sides' columns, built by ``concatenate``, made
XLA transpose the whole field to slice it lane-dense.)  What is read and
written cell by cell (the tendencies) needs no window: its blocks are
the tile being written.  Inside a tile the kernel walks strips of 8 rows
(one float32 sublane tile), so that its working set is a few strips and
not the tile.

Round 2 of a strip needs round 1's ``u``, ``v`` of the strip below,
which at a tile's last strip is the next tile's first.  So round 2 runs
**a tile behind round 1**, in the same pass of the same loop: round 1's
``u``, ``v`` go to a ring of strips in VMEM (a tile's and two more, a
field), round 2 reads three of them, and the blocks of ``u`` and ``v``
are written two grid steps behind their input where the other four are
written one behind; the grid is one step longer for it.  Without
friction (``nu == 0``) the walk is the same with round 2 off.

Building a kernel is set-up a user waits for, so it is kept short:
``jax.experimental.pallas`` is imported by :func:`pallas` where a step is
built for TPU devices (the array code, which every other backend runs,
does not pay for it), a kernel's body is written in ``lax``, and the call
is jitted, so that the programs of one process trace it once.
"""

import functools
import itertools
import sys

import jax
import jax.numpy as jnp
from jax import lax

from mpi4jax_tpu.ops._core import promote_vma, union_vma_struct, vma_of

G = 2  # ghost width of the wide-halo schedule
STRIP = 8  # rows a kernel handles at once: float32's sublane tile
LANES = 128  # columns of a vector register

# of a v5e core's 128 MiB of VMEM: what a call's blocks and windows may
# take, and the limit the compiler is given for them and its temporaries
_VMEM_BLOCK_BUDGET = 40 * 2**20
_VMEM_LIMIT = 64 * 2**20


def pallas():
    """``(pl, pltpu)``: Pallas, imported for a TPU kernel.

    Call it before a step is traced: under a trace the import takes
    half as long again (0.56 against 0.40 s from bytecode on a v5e's
    host).  And jax 0.9's ``pallas_call`` module ends by importing the
    interpreter of Mosaic GPU kernels and, with it, the whole of
    ``jax.experimental.mosaic.gpu``: 0.21 s of those 0.40 s for code no
    TPU kernel reaches.  That module expects the import to fail where
    the GPU stack is missing and then does without, so the import is
    declined here, for this process's first import of Pallas only: who
    has imported Pallas before has the interpreter, and who imports
    ``jax.experimental.pallas.mosaic_gpu`` later gets the real modules,
    but ``pallas_call(interpret=mosaic_gpu.InterpretParams())`` is then
    unknown to this process.
    """
    gpu_interpreter = "jax._src.pallas.mosaic_gpu.interpret.interpret_pallas_call"
    decline = not {"jax.experimental.pallas", gpu_interpreter} & sys.modules.keys()
    if decline:
        sys.modules[gpu_interpreter] = None  # importing it raises ImportError
    try:
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu
    finally:
        if decline:
            del sys.modules[gpu_interpreter]
    return pl, pltpu


def _whole_registers(width):
    """``width`` columns as whole vector registers' columns: what a
    block's rows take in VMEM whatever their width, and the width the
    kernel gives its blocks.  The lanes past the field's last column
    are nobody's: a rotation's wrap lands there, and what a stage
    computes there is not written.  (A rotation of rows that end inside
    a register costs two rotations and two selections a register, of
    rows that fill their registers one and one: a step of 14404
    columns was bound by that, not by HBM.)"""
    return -(-width // LANES) * LANES


def tile_rows(rows, width, dtype, fields):
    """Rows of a tile: the most (a multiple of ``STRIP``, at most the
    field's whole strips) for which the blocks of ``fields`` fields
    updated in place (double-buffered blocks in and out, and the
    window) fit the VMEM budget; 0 if not even one strip does, or the
    field has none."""
    row_bytes = _whole_registers(width) * jnp.dtype(dtype).itemsize
    fit = _VMEM_BLOCK_BUDGET // (5 * fields * row_bytes)
    return min(fit, rows) // STRIP * STRIP


# lax, not operators, in a kernel's body: under a trace a jnp operator is
# a jitted call, traced anew for each new shape, and a body's hundred cost
# a step's first build half a second
add, sub, mul, div, eq, select = (
    lax.add, lax.sub, lax.mul, lax.div, lax.eq, lax.select)


def _walk(body, scalars, fields, slabs, pointwise, n_second, *, interpret):
    """One call on the tiling above: ``fields`` (one device's padded
    blocks, all of one shape and dtype) are updated in place behind
    their windows, and ``pointwise`` arrays of the same shape are read
    and written strip by strip where they lie, with no neighbours.
    ``scalars`` are small arrays kept in SMEM.  Returns the new
    ``fields`` and ``pointwise``, in that order.

    ``slabs`` holds, field by field, the ``(west, east, south, north)``
    that :func:`halo_slabs_2d` returned for it: the fields' own ghosts
    are stale, and the rows a window takes in get theirs from the
    slabs, so that the stages read, and the fields come back with, what
    an exchange would have written.  Where a slab is ``None`` those
    ghosts are the field's own.

    ``body(roll, *scalar_refs)`` runs once a grid step and returns the
    two stages ``(first, second)``.  ``first(g, col, fields,
    pointwise)`` is handed, for 8 rows: ``g`` and ``col``, each
    element's row and column in the block; for each field ``(c, n, s)``,
    the rows themselves and the rows north (``g + 1``) and south (``g -
    1``) of them, all as they were before the call; and each pointwise
    array's rows.  It returns the rows' new values, fields first.  The
    last ``n_second`` fields' values are not final: ``second(g, col,
    fresh)`` is handed ``(c, n, s)`` of each as ``first`` left them, a
    tile later, and returns the rows' final values (``n_second`` 0: no
    second stage).  East and west neighbours are lane rotations
    (``roll``, which is ``pltpu.roll``, along axis 1 by the strip's
    lanes less 1 and by 1), whose wrap lands in or past the outermost
    ghost columns.  A stage
    masks what it updates itself: what it returns for a ghost cell is
    written too.
    """
    pl, pltpu = pallas()
    rows, width = fields[0].shape
    lanes = _whole_registers(width)  # of a block
    dtype = fields[0].dtype
    n_scalars, n_fields, n_point = len(scalars), len(fields), len(pointwise)
    n_plain = n_fields - n_second
    tile = tile_rows(rows, width, dtype, fields=n_fields + n_point)
    tiles = -(-rows // tile)
    strips = tile // STRIP
    # a ring of the second stage: first's strips of a tile, the one
    # before them and the one being written
    slots = strips + 2
    axes = vma_of(fields[0]) or ()
    scalars = [promote_vma(x, axes) for x in scalars]
    # the slabs that came, field by field, each with what it is a slab
    # of (0: columns, 1: rows) and its first ghost column or row
    places = ((0, 0), (0, width - G), (1, 0), (1, rows - G))
    came = [[(*place, x) for place, x in zip(places, sides) if x is not None]
            for sides in slabs]
    n_slabs = [len(sides) for sides in came]
    arrived = [promote_vma(x, axes) for sides in came for *_, x in sides]

    def kernel(*refs):
        refs = iter(refs)
        scalar_refs, taken, *brought, old, out, new, windows, rings = (
            tuple(itertools.islice(refs, n)) for n in
            (n_scalars, n_fields, *n_slabs, n_point, n_fields, n_point,
             n_fields, n_second))
        i = pl.program_id(0)
        first, second = body(pltpu.roll, *scalar_refs)

        shape = (STRIP, lanes)
        r = lax.broadcasted_iota(jnp.int32, shape, 0)
        col = lax.broadcasted_iota(jnp.int32, shape, 1)
        top, bottom = eq(r, 0), eq(r, STRIP - 1)
        # the tile handed in: the walk's last steps are handed the
        # field's last again
        t = lax.min(i, tiles - 1)

        def place(win, at, count, k):
            """Rows ``[at, at + count)`` of field ``k``'s window have
            just taken the first ``count`` rows of tile ``t`` as the
            field holds them, ghosts stale: write the slabs' cells over
            them, x before y as an exchange does (the y slabs hold the
            corners).  A slab's columns in one store where they lie in
            one vector register, its rows where they lie in one strip,
            and in the grid step that holds that strip."""
            for (of_rows, lo, _), ref in zip(came[k], brought[k]):
                for start, n in _pieces(lo, G, STRIP if of_rows else LANES):
                    mine = pl.ds(start - lo, n)
                    if not of_rows:
                        win[pl.ds(at, count), pl.ds(start, n)] = (
                            ref[pl.ds(0, count), mine])
                        continue
                    holder, row = divmod(start, tile)
                    if row < count:
                        @pl.when(eq(t, holder))
                        def _(row=row, n=n, mine=mine, ref=ref):
                            win[pl.ds(at + row, n), :] = ref[mine, :]

        # a window's rows: the strip above tile i - 1, the tile, and the
        # strip below it, which is the first of the block just handed in
        for k, (ref, win) in enumerate(zip(taken, windows)):
            win[pl.ds(tile + STRIP, STRIP), :] = ref[pl.ds(0, STRIP), :]
            place(win, tile + STRIP, STRIP, k)

        def strip(k):
            """The rows of strip ``k`` of a block, a window or a ring."""
            return pl.ds(pl.multiple_of(mul(k, STRIP), STRIP), STRIP)

        def around(ref, above, c, below):
            """``(c, n, s)`` of strip ``c`` of ``ref``: the strips below
            and above give the row that a rotation of the strip lacks."""
            above, c, below = (ref[strip(k), :] for k in (above, c, below))
            n = pltpu.roll(select(top, below, c), STRIP - 1, 0)
            s = pltpu.roll(select(bottom, above, c), 1, 0)
            return c, n, s

        def strips_through(run_first, run_second):
            def run(j, carry):
                g = add(r, add(mul(sub(i, 1), tile), mul(j, STRIP)))
                # first's strip, counted from the block's first: its slot
                # in a ring and, a tile behind (`slots - 2` strips), the
                # slots of second's strip and of the two round it
                k = add(mul(sub(i, 1), strips), j)
                behind = [lax.rem(add(k, d), slots) for d in range(4)]
                if run_first:
                    values = first(
                        g, col,
                        [around(win, j, add(j, 1), add(j, 2)) for win in windows],
                        [ref[strip(j), :] for ref in old])
                    homes = ([(ref, j) for ref in out[:n_plain]]
                             + [(ring, behind[0]) for ring in rings]
                             + [(ref, j) for ref in new])
                    for (ref, at), value in zip(homes, values):
                        ref[strip(at), :] = value
                if run_second:
                    values = second(
                        sub(g, tile), col,
                        [around(ring, *behind[1:]) for ring in rings])
                    for ref, value in zip(out[n_plain:], values):
                        ref[strip(j), :] = value
                return carry

            lax.fori_loop(0, strips, run, 0)

        # tile i - 1 goes through first while tile i - 2 goes through
        # second (at i = 1 on the ring as it is found, into blocks that
        # step 2 writes again); the walk's last step is second's alone
        @pl.when(lax.bitwise_and(lax.gt(i, 0), lax.le(i, tiles)))
        def _():
            strips_through(True, n_second > 0)

        if n_second:
            @pl.when(eq(i, tiles + 1))
            def _():
                strips_through(False, True)

        for k, (ref, win) in enumerate(zip(taken, windows)):
            win[pl.ds(0, STRIP), :] = win[pl.ds(tile, STRIP), :]
            win[pl.ds(STRIP, tile), :] = ref[...]
            place(win, STRIP, tile, k)

    def block(lag):
        """Tile ``i - lag``, held to the field's own tiles."""
        return pl.BlockSpec(
            (tile, lanes), lambda i: (lax.clamp(0, i - lag, tiles - 1), 0))

    struct = union_vma_struct(fields[0].shape, dtype, *fields, *scalars)
    in_smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    # a tile's rows of a slab of columns; a slab of rows whole, once
    block_of_slab = (
        pl.BlockSpec((tile, G), lambda i: (lax.min(i, tiles - 1), 0)),
        pl.BlockSpec((G, lanes), lambda i: (0, 0)))
    results = pl.pallas_call(
        kernel,
        grid=(tiles + 1 + (n_second > 0),),
        in_specs=([in_smem] * n_scalars + [block(0)] * n_fields
                  + [block_of_slab[of_rows] for sides in came
                     for of_rows, *_ in sides]
                  + [block(1)] * n_point),
        out_specs=([block(1)] * n_plain + [block(2)] * n_second
                   + [block(1)] * n_point),
        out_shape=[struct] * (n_fields + n_point),
        scratch_shapes=(
            [pltpu.VMEM((tile + 2 * STRIP, lanes), dtype)] * n_fields
            + [pltpu.VMEM((slots * STRIP, lanes), dtype)] * n_second),
        input_output_aliases={
            **{n_scalars + k: k for k in range(n_fields)},
            **{n_scalars + n_fields + len(arrived) + k: n_fields + k
               for k in range(n_point)}},
        compiler_params=pltpu.CompilerParams(
            # in order: a step reads the window the step before left
            dimension_semantics=("arbitrary",), vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(*scalars, *fields, *arrived, *pointwise)
    return results


def _pieces(lo, n, every):
    """``[lo, lo + n)`` cut at the multiples of ``every``, as ``(start,
    length)``: the parts of a slab that lie in one vector register, or
    in one strip."""
    cuts = [lo, *range(-(-(lo + 1) // every) * every, lo + n, every), lo + n]
    return [(a, b - a) for a, b in zip(cuts, cuts[1:])]


def _walls(is_south, is_north):
    return jnp.stack([is_south, is_north]).astype(jnp.int32)


@functools.partial(
    jax.jit,
    static_argnames=("nu", "dx", "dy", "dt", "gravity", "coriolis_f",
                     "coriolis_beta", "interpret"))
def wide_step(h, u, v, dh, du, dv, slabs, is_south, is_north, first_row,
              a, b, *, nu, dx, dy, dt, gravity, coriolis_f, coriolis_beta,
              interpret=False):
    """A step of :func:`shallow_water._step_wide` after the wire of its
    first halo exchange, with no second one: the ghost writes of the
    first, the tendencies of ``h``, ``u`` and
    ``v``, the Adams-Bashforth update ``x += dt * (a * new + b * old)``,
    lateral friction of ``u`` and ``v`` where ``nu > 0``, and ``v = 0``
    on the northern wall row after each round.  What
    :func:`shallow_water._tendency_round`, an exchange of ``u`` and
    ``v`` and :func:`shallow_water._viscosity_round` compute, to
    roundoff (a division by ``dx`` or ``dy`` is a multiplication here).

    ``h``, ``u``, ``v``: one device's ``(ny_l + 4, nx_l + 4)`` blocks,
    their ghost rings stale wherever ``slabs`` brings them: ``slabs``
    holds for each the ``(west, east, south, north)`` that
    ``halo_slabs_2d(x, width=2)`` returned, which the kernel writes
    over the ghosts as it reads the rows (``None``: those ghosts are
    fresh as they are).  ``dh``, ``du``, ``dv``: the old tendencies
    **at the same padded shape** (a strip of a field and of an
    interior-shaped tendency would lie two rows and two lanes apart),
    as the last step returned them: ring 1 of ``du`` and ``dv`` holds
    the neighbours' (the module's docstring says why), the rest of the
    ghost ring is zero, and the new ones come back so.  ``is_south``,
    ``is_north``, ``first_row`` (the global row of the block's first
    interior row, for the Coriolis parameter), ``a`` and ``b`` are
    traced scalars: a first step is ``a = 1, b = 0`` on zero tendencies,
    exact in float32, so that a process's two programs trace one kernel,
    once.  The caller has checked :func:`tile_rows` for six fields.
    """
    rows, width = h.shape
    lanes = _whole_registers(width)  # of a strip in the kernel
    dtype = h.dtype
    inv_dx, inv_dy = 1.0 / dx, 1.0 / dy
    cx, cy = nu / dx, nu / dy
    floats = jnp.stack([jnp.asarray(x, dtype) for x in (a, b, first_row)])

    def half(x):
        return mul(x, 0.5)

    def body(roll, wall_ref, float_ref):
        a, b, first_row = float_ref[0], float_ref[1], float_ref[2]
        # the rows the walls single out, -1 where this device has no
        # wall: the southern wall's ghost row next to the interior, and
        # the last interior row, which is the northern wall's
        at_south, at_north = eq(wall_ref[0], 1), eq(wall_ref[1], 1)
        absent = jnp.int32(-1)
        south_ghost_row = select(at_south, jnp.int32(G - 1), absent)
        north_wall_row = select(at_north, jnp.int32(rows - G - 1), absent)
        # round 1's rows of u and v: the interior and ring 1, which is a
        # neighbour's edge row unless a wall stands there.  (A block
        # with a strip of 8 rows has four interior rows or more, so a
        # neighbour's edge row is never its wall row too.)
        reach_from = select(at_south, jnp.int32(G), jnp.int32(G - 1))
        reach_to = select(at_north, jnp.int32(rows - G), jnp.int32(rows - G + 1))

        def box(g, col, row_from, row_to, ring):
            return functools.reduce(lax.bitwise_and, (
                lax.ge(g, row_from), lax.lt(g, row_to),
                lax.ge(col, G - ring), lax.lt(col, width - G + ring)))

        def east(x):
            return roll(x, lanes - 1, 1)

        def west(x):
            return roll(x, 1, 1)

        def first(g, col, fields, old):
            """Round 1: the tendencies and the Adams-Bashforth update,
            of ``h`` on the interior, of ``u`` and ``v`` on ring 1 too."""
            (h, h_n, h_s), (u, u_n, u_s), (v, v_n, v_s) = fields
            zero = lax.full(h.shape, 0, dtype)
            interior = box(g, col, G, rows - G, 0)
            reach = box(g, col, reach_from, reach_to, 1)
            # the array code builds its ring-1 fields on every row and
            # zeroes them on the walls' ghost rows (the northward flux
            # on the northern wall's own row too); an interior row reads
            # those rows only as the last row under a northern wall and
            # as the first over a southern one
            north = eq(g, north_wall_row)
            south = eq(sub(g, 1), south_ghost_row)

            def unless(wall, x):
                return select(wall, zero, x)

            # mean depths: twice the one on the eastern face, on this
            # row and its neighbours; the array code's hc is h with the
            # walls' ghost rows set to the wall's row, and of those the
            # interior reads the northern one alone, in q's depth
            h_e = east(h)
            hx, hx_n, hx_s = add(h, h_e), add(h_n, east(h_n)), add(h_s, east(h_s))
            # mass fluxes through the eastern and the northern face
            fe = mul(half(hx), u)
            fe_n = unless(north, mul(half(hx_n), u_n))
            fn = unless(north, mul(half(add(h, h_n)), v))
            fn_s = unless(south, mul(half(add(h_s, h)), v_s))

            def vorticity(row, v, v_e, u_n, u, depth4):
                """Potential vorticity at a row's north-eastern corners."""
                y = mul(add(lax.convert_element_type(row, dtype), first_row), dy)
                planetary = add(mul(y, coriolis_beta), coriolis_f)
                relative = sub(mul(sub(v_e, v), inv_dx), mul(sub(u_n, u), inv_dy))
                return div(add(planetary, relative), mul(depth4, 0.25))

            q = vorticity(sub(g, G), v, east(v), u_n, u,
                          add(hx, select(north, hx, hx_n)))
            q_s = unless(south, vorticity(
                sub(g, G + 1), v_s, east(v_s), u, u_s, add(hx_s, hx)))

            # kinetic energy at the cell
            uu, vv = mul(u, u), mul(v, v)
            uu_n = mul(u_n, u_n)
            ke = half(add(half(add(uu, west(uu))), half(add(vv, mul(v_s, v_s)))))
            ke_n = unless(north, half(add(
                half(add(uu_n, west(uu_n))), half(add(mul(v_n, v_n), vv)))))

            fe_w = west(fe)
            dh_new = sub(mul(sub(fe_w, fe), inv_dx), mul(sub(fn, fn_s), inv_dy))
            du_new = sub(
                add(mul(sub(h_e, h), -gravity * inv_dx),
                    half(add(mul(q, half(add(fn, east(fn)))),
                             mul(q_s, half(add(fn_s, east(fn_s))))))),
                mul(sub(east(ke), ke), inv_dx))
            dv_new = sub(
                sub(mul(sub(h_n, h), -gravity * inv_dy),
                    half(add(mul(q, half(add(fe, fe_n))),
                             mul(west(q), half(add(fe_w, west(fe_n))))))),
                mul(sub(ke_n, ke), inv_dy))

            def stepped(where, x, new, old):
                new = select(where, new, zero)
                inc = select(where, mul(add(mul(new, a), mul(old, b)), dt), zero)
                return add(x, inc), new

            dh_old, du_old, dv_old = old
            h, dh_new = stepped(interior, h, dh_new, dh_old)
            u, du_new = stepped(reach, u, du_new, du_old)
            v, dv_new = stepped(reach, v, dv_new, dv_old)
            return h, u, unless(north, v), dh_new, du_new, dv_new

        def second(g, col, fresh):
            """Round 2: lateral friction of round 1's ``u`` and ``v``."""
            zero = lax.full(g.shape, 0, dtype)
            interior = box(g, col, G, rows - G, 0)
            # of the rows the array code zeroes in the y gradient, an
            # interior cell reads one: the southern wall's ghost row
            south_is_wall = eq(sub(g, 1), south_ghost_row)

            def friction(c, n, s):
                e, w = east(c), west(c)
                # the gradients at the cell, and west and south of it
                gx, gx_w = mul(sub(e, c), cx), mul(sub(c, w), cx)
                gy = mul(sub(n, c), cy)
                gy_s = select(south_is_wall, zero, mul(sub(c, s), cy))
                inc = mul(add(mul(sub(gx, gx_w), inv_dx),
                              mul(sub(gy, gy_s), inv_dy)), dt)
                return add(c, select(interior, inc, zero))

            u, v = fresh
            return friction(*u), select(
                eq(g, north_wall_row), zero, friction(*v))

        return first, second

    return _walk(body, [_walls(is_south, is_north), floats], [h, u, v],
                 slabs, [dh, du, dv], n_second=2 if nu > 0 else 0,
                 interpret=interpret)
