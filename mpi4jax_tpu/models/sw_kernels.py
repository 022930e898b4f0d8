"""Pallas TPU kernels of the wide-halo shallow-water step.

XLA compiles a stencil round of :func:`shallow_water._step_wide` into
fusions that write their shifted intermediates to HBM, and a second pass
that copies the interior back into the padded field.  A kernel here
streams row tiles of the padded field through VMEM instead: every
intermediate stays on the chip, and the field is read once and written
once, in place.

Tiling, shared by every kernel of this module
---------------------------------------------
The field keeps its full width (x is not tiled: a block's last dimension
may equal the array's own, whatever it is), so an x-shift is a lane
rotation whose wrap lands in ghost columns that the interior mask drops.
Rows are cut into tiles of :func:`tile_rows` (a multiple of 8, chosen
from the width and the dtype so that the call's blocks fit VMEM), and
the grid walks them from the first row to the last.

A tile's stencil needs the row above it and the row below it as they
were before the update, and the field is written in place.  So the
kernel runs one tile behind its input: step ``i`` is handed tile ``i``
and writes tile ``i - 1``, which it kept in a VMEM window from the step
before, between the last strip of tile ``i - 2`` and the first strip of
tile ``i``.  Every row is read from HBM once, before the step that
writes it, and no tile reads what another has written; the field is the
call's only large operand, so XLA has nothing to copy.  Inside a tile
the kernel walks strips of 8 rows (one float32 sublane tile), so that
its working set is a few strips and not the tile.

Building a kernel is set-up a user waits for, so it is kept short:
``jax.experimental.pallas`` is imported by :func:`pallas` where a step is
built for TPU devices (the array code, which every other backend runs,
does not pay for it), a kernel's body is written in ``lax``, and a call
is jitted, so that the programs of one process trace it once.
"""

import functools
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
    pl, pltpu = pallas()
    rows, width = u.shape
    dtype = u.dtype
    ny_l, nx_l = rows - 2 * G, width - 2 * G
    tile = tile_rows(rows, width, dtype, fields=2)
    tiles = -(-rows // tile)
    cx, cy = nu / dx, nu / dy
    inv_dx, inv_dy = 1.0 / dx, 1.0 / dy
    wall = promote_vma(
        jnp.stack([is_south, is_north]).astype(jnp.int32), vma_of(u) or ())

    # lax, not operators, in the kernel's body: under a trace a jnp
    # operator is a jitted call, traced anew for each new shape, and the
    # body's sixty cost a step's first build a third of a second
    add, sub, mul, eq, select = lax.add, lax.sub, lax.mul, lax.eq, lax.select

    def both(*masks):
        return functools.reduce(lax.bitwise_and, masks)

    def kernel(wall_ref, u_ref, v_ref, u_out, v_out, u_win, v_win):
        i = pl.program_id(0)
        # the rows the walls single out, -1 where this device has no wall
        absent = jnp.int32(-1)
        south_ghost_row = select(eq(wall_ref[0], 1), jnp.int32(G - 1), absent)
        north_wall_row = select(eq(wall_ref[1], 1), jnp.int32(ny_l + G - 1), absent)
        # a window's rows: the strip above tile i - 1, the tile, and the
        # strip below it, which is the first of the block just handed in
        for ref, win in ((u_ref, u_win), (v_ref, v_win)):
            win[pl.ds(tile + STRIP, STRIP), :] = ref[pl.ds(0, STRIP), :]

        def strip(j, carry):
            r0 = pl.multiple_of(mul(j, STRIP), STRIP)
            shape = (STRIP, width)
            r = lax.broadcasted_iota(jnp.int32, shape, 0)
            col = lax.broadcasted_iota(jnp.int32, shape, 1)
            # the row's index in the block
            g = add(r, add(mul(sub(i, 1), tile), r0))
            interior = both(lax.ge(g, G), lax.lt(g, ny_l + G),
                            lax.ge(col, G), lax.lt(col, nx_l + G))
            # of the rows the array code zeroes in the y gradient, an
            # interior cell reads one: the southern wall's ghost row
            south_is_wall = eq(sub(g, 1), south_ghost_row)
            first, last = eq(r, 0), eq(r, STRIP - 1)
            zero = lax.full(shape, 0, dtype)

            def friction(win):
                c = win[pl.ds(add(r0, STRIP), STRIP), :]
                below = win[pl.ds(add(r0, 2 * STRIP), STRIP), :]
                above = win[pl.ds(r0, STRIP), :]
                # north, south, east, west neighbours: the strips below
                # and above give the row a rotation of this one lacks; a
                # lane rotation wraps into ghost columns, never written
                n = pltpu.roll(select(first, below, c), STRIP - 1, 0)
                s = pltpu.roll(select(last, above, c), 1, 0)
                e = pltpu.roll(c, width - 1, 1)
                w = pltpu.roll(c, 1, 1)
                # the gradients at the cell, and west and south of it
                gx, gx_w = mul(sub(e, c), cx), mul(sub(c, w), cx)
                gy = mul(sub(n, c), cy)
                gy_s = select(south_is_wall, zero, mul(sub(c, s), cy))
                inc = mul(add(mul(sub(gx, gx_w), inv_dx),
                              mul(sub(gy, gy_s), inv_dy)), dt)
                return add(c, select(interior, inc, zero))

            u_out[pl.ds(r0, STRIP), :] = friction(u_win)
            v_out[pl.ds(r0, STRIP), :] = select(
                eq(g, north_wall_row), zero, friction(v_win))
            return carry

        @pl.when(lax.gt(i, 0))
        def _():
            lax.fori_loop(0, tile // STRIP, strip, 0)

        for ref, win in ((u_ref, u_win), (v_ref, v_win)):
            win[pl.ds(0, STRIP), :] = win[pl.ds(tile, STRIP), :]
            win[pl.ds(STRIP, tile), :] = ref[...]

    out = union_vma_struct(u.shape, dtype, u, v, wall)
    taken = pl.BlockSpec((tile, width), lambda i: (lax.min(i, tiles - 1), 0))
    written = pl.BlockSpec((tile, width), lambda i: (lax.max(i - 1, 0), 0))
    window = pltpu.VMEM((tile + 2 * STRIP, width), dtype)
    return pl.pallas_call(
        kernel,
        grid=(tiles + 1,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), taken, taken],
        out_specs=[written, written],
        out_shape=[out, out],
        scratch_shapes=[window, window],
        input_output_aliases={1: 0, 2: 1},
        compiler_params=pltpu.CompilerParams(
            # in order: a step reads the window the step before left
            dimension_semantics=("arbitrary",), vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(wall, u, v)
