"""Pallas TPU kernels of the wide-halo shallow-water step.

XLA compiles a stencil round of :func:`shallow_water._step_wide` into
fusions that write their shifted intermediates to HBM, and a second pass
that copies the interior back into the padded field.  A kernel here
streams row tiles of the padded fields through VMEM instead: every
intermediate stays on the chip, and each field is read once and written
once, in place.  The step's two rounds are one kernel each, with the
exchange of ``u`` and ``v`` between them (real on a mesh of more than
one chip, so the two are not fused):

* :func:`tendency_round`, round 1: fluxes, potential vorticity and
  kinetic energy on the ring round each cell, the three tendencies, the
  Adams-Bashforth update of ``h``, ``u``, ``v`` and the wall condition.
  It reads the three fields and the three old tendencies and writes the
  same six: 12 passes over a field.
* :func:`viscosity_round`, round 2: lateral friction of ``u`` and ``v``
  and the wall condition: 4 passes.

Tiling, shared by every kernel of this module (:func:`_walk`)
-------------------------------------------------------------
The field keeps its full width (x is not tiled: a block's last dimension
may equal the array's own, whatever it is), so an x-shift is a lane
rotation whose wrap lands in ghost columns that the interior mask drops.
Rows are cut into tiles of :func:`tile_rows` (a multiple of 8, chosen
from the width, the dtype and the number of fields so that the call's
blocks fit VMEM), and the grid walks them from the first row to the
last.

A tile's stencil needs the rows above it and below it as they were
before the update, and the field is written in place.  So a kernel runs
one tile behind its input: step ``i`` is handed tile ``i`` and writes
tile ``i - 1``, which it kept in a VMEM window from the step before,
between the last strip of tile ``i - 2`` and the first strip of tile
``i``: 8 rows of old values either side, of which round 2 reads one and
round 1's compound stencil, at the ring-1 fields' rows ``j - 1`` and
``j + 1``, one as well.  Every row is read from HBM once, before the
step that writes it, and no tile reads what another has written; a
field is one operand of the call and no other, so XLA has nothing to
copy.  What a kernel reads and writes cell by cell (round 1's
tendencies) needs no window: its blocks are the tile being written.
Inside a tile the kernel walks strips of 8 rows (one float32 sublane
tile), so that its working set is a few strips and not the tile.

Building a kernel is set-up a user waits for, so it is kept short:
``jax.experimental.pallas`` is imported by :func:`pallas` where a step is
built for TPU devices (the array code, which every other backend runs,
does not pay for it), a kernel's body is written in ``lax``, and a call
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


def tile_rows(rows, width, dtype, fields):
    """Rows of a tile: the most (a multiple of ``STRIP``, at most the
    field's whole strips) for which the blocks of ``fields`` fields
    updated in place (double-buffered blocks in and out, and the
    window) fit the VMEM budget; 0 if not even one strip does, or the
    field has none."""
    row_bytes = -(-width // 128) * 128 * jnp.dtype(dtype).itemsize
    fit = _VMEM_BLOCK_BUDGET // (5 * fields * row_bytes)
    return min(fit, rows) // STRIP * STRIP


# lax, not operators, in a kernel's body: under a trace a jnp operator is
# a jitted call, traced anew for each new shape, and a body's hundred cost
# a step's first build half a second
add, sub, mul, div, eq, select = (
    lax.add, lax.sub, lax.mul, lax.div, lax.eq, lax.select)


def _walk(body, scalars, fields, pointwise=(), *, interpret):
    """One call on the tiling above: ``fields`` (one device's padded
    blocks, all of one shape and dtype) are updated in place behind
    their windows, and ``pointwise`` arrays of the same shape are read
    and written strip by strip where they lie, with no neighbours.
    ``scalars`` are small arrays kept in SMEM.  Returns the new
    ``fields`` and ``pointwise``, in that order.

    ``body(roll, *scalar_refs)`` runs once a grid step and returns
    ``strip(g, interior, fields, pointwise)``, which is handed, for 8
    rows: ``g``, each element's row in the block; ``interior``, whether
    it lies inside the ghost ring; for each field ``(c, n, s)``, the
    rows themselves and the rows north (``g + 1``) and south (``g - 1``)
    of them, all as they were before the call; and each pointwise
    array's rows.  It returns the rows' new values, fields first.  East
    and west neighbours are lane rotations (``roll``, which is
    ``pltpu.roll``, by ``width - 1`` and by 1 along axis 1), whose wrap
    lands in ghost columns.  What ``strip`` returns outside the
    interior is written too: it passes the ghost ring through itself.
    """
    pl, pltpu = pallas()
    rows, width = fields[0].shape
    dtype = fields[0].dtype
    n_scalars, n_fields, n_point = len(scalars), len(fields), len(pointwise)
    tile = tile_rows(rows, width, dtype, fields=n_fields + n_point)
    tiles = -(-rows // tile)
    axes = vma_of(fields[0]) or ()
    scalars = [promote_vma(x, axes) for x in scalars]

    def kernel(*refs):
        refs = iter(refs)
        scalar_refs, taken, old, out, new, windows = (
            tuple(itertools.islice(refs, n)) for n in
            (n_scalars, n_fields, n_point, n_fields, n_point, n_fields))
        i = pl.program_id(0)
        strip = body(pltpu.roll, *scalar_refs)
        # a window's rows: the strip above tile i - 1, the tile, and the
        # strip below it, which is the first of the block just handed in
        for ref, win in zip(taken, windows):
            win[pl.ds(tile + STRIP, STRIP), :] = ref[pl.ds(0, STRIP), :]

        def one(j, carry):
            r0 = pl.multiple_of(mul(j, STRIP), STRIP)
            shape = (STRIP, width)
            r = lax.broadcasted_iota(jnp.int32, shape, 0)
            col = lax.broadcasted_iota(jnp.int32, shape, 1)
            g = add(r, add(mul(sub(i, 1), tile), r0))
            interior = functools.reduce(lax.bitwise_and, (
                lax.ge(g, G), lax.lt(g, rows - G),
                lax.ge(col, G), lax.lt(col, width - G)))
            first, last = eq(r, 0), eq(r, STRIP - 1)

            def around(win):
                c = win[pl.ds(add(r0, STRIP), STRIP), :]
                below = win[pl.ds(add(r0, 2 * STRIP), STRIP), :]
                above = win[pl.ds(r0, STRIP), :]
                # the strips below and above give the row that a
                # rotation of this one lacks
                n = pltpu.roll(select(first, below, c), STRIP - 1, 0)
                s = pltpu.roll(select(last, above, c), 1, 0)
                return c, n, s

            values = strip(g, interior, [around(win) for win in windows],
                           [ref[pl.ds(r0, STRIP), :] for ref in old])
            for ref, value in zip(out + new, values):
                ref[pl.ds(r0, STRIP), :] = value
            return carry

        @pl.when(lax.gt(i, 0))
        def _():
            lax.fori_loop(0, tile // STRIP, one, 0)

        for ref, win in zip(taken, windows):
            win[pl.ds(0, STRIP), :] = win[pl.ds(tile, STRIP), :]
            win[pl.ds(STRIP, tile), :] = ref[...]

    struct = union_vma_struct(fields[0].shape, dtype, *fields, *scalars)
    ahead = pl.BlockSpec((tile, width), lambda i: (lax.min(i, tiles - 1), 0))
    behind = pl.BlockSpec((tile, width), lambda i: (lax.max(i - 1, 0), 0))
    in_smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    results = pl.pallas_call(
        kernel,
        grid=(tiles + 1,),
        in_specs=[in_smem] * n_scalars + [ahead] * n_fields + [behind] * n_point,
        out_specs=[behind] * (n_fields + n_point),
        out_shape=[struct] * (n_fields + n_point),
        scratch_shapes=[pltpu.VMEM((tile + 2 * STRIP, width), dtype)] * n_fields,
        input_output_aliases={
            n_scalars + k: k for k in range(n_fields + n_point)},
        compiler_params=pltpu.CompilerParams(
            # in order: a step reads the window the step before left
            dimension_semantics=("arbitrary",), vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(*scalars, *fields, *pointwise)
    return results


def _walls(is_south, is_north):
    return jnp.stack([is_south, is_north]).astype(jnp.int32)


def _wall_rows(wall_ref, ny_l):
    """The rows the walls single out, -1 where this device has no wall:
    the southern wall's ghost row next to the interior, and the last
    interior row, which is the northern wall's."""
    absent = jnp.int32(-1)
    south_ghost_row = select(eq(wall_ref[0], 1), jnp.int32(G - 1), absent)
    north_wall_row = select(eq(wall_ref[1], 1), jnp.int32(ny_l + G - 1), absent)
    return south_ghost_row, north_wall_row


@functools.partial(
    jax.jit,
    static_argnames=("dx", "dy", "dt", "gravity", "coriolis_f",
                     "coriolis_beta", "interpret"))
def tendency_round(h, u, v, dh, du, dv, is_south, is_north, first_row, a, b,
                   *, dx, dy, dt, gravity, coriolis_f, coriolis_beta,
                   interpret=False):
    """The tendencies of ``h``, ``u`` and ``v`` after their halo
    exchange, the Adams-Bashforth update ``x += dt * (a * new + b *
    old)`` of the interior, and ``v = 0`` on the northern wall row: what
    :func:`shallow_water._tendency_round` computes, to roundoff.

    ``h``, ``u``, ``v``: one device's ``(ny_l + 4, nx_l + 4)`` blocks,
    ghosts fresh.  ``dh``, ``du``, ``dv``: the old tendencies **at the
    same padded shape** (a strip of a field and of an interior-shaped
    tendency would lie two rows and two lanes apart); the new ones come
    back so, zero on the ghost ring.  ``is_south``, ``is_north``,
    ``first_row`` (the global row of the block's first interior row, for
    the Coriolis parameter), ``a`` and ``b`` are traced scalars: a first
    step is ``a = 1, b = 0`` on zero tendencies, exact in float32, so
    that a process's two programs trace one kernel, once.  The caller
    has checked :func:`tile_rows` for six fields.
    """
    rows, width = h.shape
    dtype = h.dtype
    inv_dx, inv_dy = 1.0 / dx, 1.0 / dy
    floats = jnp.stack([jnp.asarray(x, dtype) for x in (a, b, first_row)])

    def half(x):
        return mul(x, 0.5)

    def body(roll, wall_ref, float_ref):
        south_ghost_row, north_wall_row = _wall_rows(wall_ref, rows - 2 * G)
        a, b, first_row = float_ref[0], float_ref[1], float_ref[2]

        def east(x):
            return roll(x, width - 1, 1)

        def west(x):
            return roll(x, 1, 1)

        def strip(g, interior, fields, old):
            (h, h_n, h_s), (u, u_n, u_s), (v, v_n, v_s) = fields
            zero = lax.full(h.shape, 0, dtype)
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

            def inside(x):
                return select(interior, x, zero)

            def stepped(x, new, old):
                return add(x, inside(mul(add(mul(new, a), mul(old, b)), dt)))

            dh_old, du_old, dv_old = old
            return (stepped(h, dh_new, dh_old), stepped(u, du_new, du_old),
                    unless(north, stepped(v, dv_new, dv_old)),
                    inside(dh_new), inside(du_new), inside(dv_new))

        return strip

    return _walk(body, [_walls(is_south, is_north), floats], [h, u, v],
                 [dh, du, dv], interpret=interpret)


@functools.partial(
    jax.jit, static_argnames=("nu", "dx", "dy", "dt", "interpret"))
def viscosity_round(u, v, is_south, is_north, *, nu, dx, dy, dt,
                    interpret=False):
    """Lateral friction of ``u`` and ``v`` after their second halo
    exchange, and ``v = 0`` on the northern wall row: what
    :func:`shallow_water._viscosity_round` computes, to roundoff (a
    division by ``dx`` or ``dy`` is a multiplication here).

    ``u``, ``v``: one device's ``(ny_l + 4, nx_l + 4)`` blocks, ghosts
    fresh; ``is_south``, ``is_north``: whether this device holds a wall
    (traced under ``shard_map``).  The ghost ring comes back as it went
    in.  The caller has checked :func:`tile_rows`.  Jitted, so that a
    process's second program (the multistep after the first step) finds
    the round traced.
    """
    rows, width = u.shape
    dtype = u.dtype
    cx, cy = nu / dx, nu / dy
    inv_dx, inv_dy = 1.0 / dx, 1.0 / dy

    def body(roll, wall_ref):
        south_ghost_row, north_wall_row = _wall_rows(wall_ref, rows - 2 * G)

        def strip(g, interior, fields, _):
            zero = lax.full(g.shape, 0, dtype)
            # of the rows the array code zeroes in the y gradient, an
            # interior cell reads one: the southern wall's ghost row
            south_is_wall = eq(sub(g, 1), south_ghost_row)

            def friction(c, n, s):
                e = roll(c, width - 1, 1)
                w = roll(c, 1, 1)
                # the gradients at the cell, and west and south of it
                gx, gx_w = mul(sub(e, c), cx), mul(sub(c, w), cx)
                gy = mul(sub(n, c), cy)
                gy_s = select(south_is_wall, zero, mul(sub(c, s), cy))
                inc = mul(add(mul(sub(gx, gx_w), inv_dx),
                              mul(sub(gy, gy_s), inv_dy)), dt)
                return add(c, select(interior, inc, zero))

            u, v = fields
            return friction(*u), select(
                eq(g, north_wall_row), zero, friction(*v))

        return strip

    return _walk(body, [_walls(is_south, is_north)], [u, v],
                 interpret=interpret)
