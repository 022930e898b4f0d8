"""The wide-halo shallow-water step as one Pallas TPU kernel.

XLA compiles a stencil round of :func:`shallow_water._step_wide` into
fusions that write their shifted intermediates to HBM, and a second pass
that copies the interior back into the padded field.  The kernel here
(:func:`wide_step`) streams row tiles of the padded fields through VMEM
instead: every intermediate stays on the chip, and each of the six state
arrays (``h``, ``u``, ``v`` and their tendencies) is read once and
written once, in place: 12 passes over a field a walk, the least a walk
can move.  A walk advances **two time steps**, on one device and beside
neighbours ("Two steps a walk", below): 12 passes where two walks move
24, which its vector work keeps up with to within a few per cent of
HBM's pace.  A step's **derivative** is a kernel as well, in either
mode (:func:`wide_step_vjp`, "The adjoint walk" below; :func:`wide_step_jvp`,
"The tangent walk"): 15 passes, the three kept fields and six
cotangents or tangents in, six out.

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

Each row's faces and corners once.  Round 1 at row ``g`` reads the
eastward flux and the kinetic energy of rows ``g`` and ``g + 1``, and
the northward flux and the vorticity of rows ``g`` and ``g - 1``.  A
strip makes each **once a row**: the first two for the row *north* of
each of its rows (from ``(c, n)`` of ``h``, ``u``, ``v``), the other two
for the rows themselves.  A row's own flux and energy, and its southern
neighbour's northward flux and vorticity (as the product with the flux
that ``du`` takes of it), are then the same values one row down: seven
rows by a rotation of sublanes, and the strip's first row from **the
strip before**, whose four values the stage hands itself in VMEM (four
strips a step's round 1, ``n_carried``; a walk runs the strips of a
field in order, across its tiles).  There is no lag to it: the strip
before has been through the same stage one iteration earlier, so a
ring of three strips at a strip's lag (``ROADMAP.md`` S17's sketch)
would hold two strips nobody reads, and blocks are written where they
were.  The walls' zeros ride on the values: the flux and the energy of
the row north are zeroed on the northern wall's row (where they belong
to the ghost row beyond it), the northward flux there and on the
southern wall's ghost row, the vorticity on that ghost row.  That is
where the stages that evaluated each neighbour again zeroed them, but
for a row's *own* values on the two ghost rows next to a wall, and
those rows nothing updates.  The rows' masks, the Coriolis parameter
and the strip's last-row mask are made on one vector register and laid
across the strip (a ``concatenate`` of registers: names, no
arithmetic), the columns' masks in the registers alone that hold a
ghost column; powers of two are folded where that is exact (a quarter
of the depth's sum under the division against the quarters of the sums
that take the vorticity; two halves of a sum of halves).  The
schedule's count is in ``PERF.md`` section 5: 142 bundles a vector
register for two steps became 95.

Two steps a walk
----------------
At 12 passes a step the kernel moved its bytes faster than a plain copy
does on a v5e and its vector units waited a quarter of the time.  So a
walk carries two steps (``wide_step(steps=2)``;
``shallow_water.make_multistep`` asks for it wherever
``_walks_two_steps`` holds, on one device and beside neighbours).  The
four stages (round 1 and round 2 of step *n*, round 1 and round 2 of
step *n + 1*) run in the same pass of the same loop, **each a tile
behind the one before it**, as round 2 runs behind round 1.  What step
*n* produces goes to rings of strips in VMEM and never to HBM: ``h`` and
the three new tendencies (step *n + 1*'s old ones) from its round 1, two
tiles and a strip or two long because round 1 of step *n + 1* is two
stages behind; the final ``u``, ``v`` from its round 2, a tile and two
strips.  Only step *n + 1*'s six arrays are written, three and four
grid steps behind their input; the grid is two steps longer.  The stages
are ``first`` and ``second`` as they are, applied twice: the same
operations on the same values in the same order, so a walk of two steps
returns bit for bit, on the interior of all six arrays, what two walks
of one return with the exchange between them.

Where the second step's ghosts come from.  Step *n + 1* reads ghosts
that an exchange would have brought after step *n*: the final ``h``,
``u``, ``v`` of step *n* on rings 1 and 2.  Nothing brings them; the
kernel makes them, axis by axis, from what ``comm`` says of the axis:

* **x, one device, periodic.**  A row's ghost columns are the same row's
  other end: as a strip of ``h``, ``u`` or ``v`` goes to its ring,
  columns ``width - 4, width - 3`` go to columns ``0, 1`` and columns
  ``2, 3`` to ``width - 2, width - 1``, a lane rotation and a selection
  on the first and the last vector register of the strip
  (:func:`_ends_meet`), every row, as ``halo_slabs_2d`` slices them
  there.
* **y, one device.**  Walls on both sides; the exchange brings nothing
  and those ghost rows keep their values.
* **an axis with a neighbour.**  Those cells are the neighbour's
  interior, and the kernel computes them **as the neighbour does, from
  the same inputs by the same code**, as it already makes ring 1 of
  ``u``, ``v`` between its two rounds.  Step *n*'s round 2 out to ring 2
  reads its round 1 out to ring 3 (``h`` to ring 2), which reads ring
  4; its Adams-Bashforth update out there reads last step's tendencies
  on rings 1 to 3, which no walk of this chip computed.  So the walk
  starts from **one exchange four cells deep of** ``h``, ``u``, ``v``
  **and of** ``dh``, ``du``, ``dv`` (``halo_slabs_2d(depth=)``), every
  second step, and the first application of ``first`` and ``second``
  gets the wider reach (two rings more of rows by the scalars in SMEM,
  of columns by ``_stages(out=)``), the second application a single
  walk's.  Beyond a wall nothing comes, there as on one device, and the
  reach ends at the wall.

Where rings 3 and 4 live.  **Not in HBM**: the state keeps its shape,
padded by 2, and everything that reads it (the monitor, snapshots,
saves, ``gather_global``) reads what it read.  In VMEM a block's rows
are :func:`_whole_registers` lanes wide, wider than the field, and a lane
rotation wraps over all of them: the two columns east of the block lie
in the lanes after its last column, the two west of it **in the row's
last two lanes**, where the rotation that brings column 0 its western
neighbour finds them (``_stages`` ``box`` knows).  The rows north of the
block lie in the rows after the field's last in its last tile, which the
strips' loop runs over anyway.  The rows south of it are **a strip
before the block's first**: the window's first strip, which the walk's
first grid step leaves free, takes the southern slabs' first two rows
into its last two, and the first step's first stage runs on that one
strip more, once a walk (``before_the_block``), for what row 0 reads of
row -1: round 1's ``u``, ``v`` there on their way to round 2, and the
fluxes, the energy and the vorticity the stage hands the strip after.
The slabs of a row reach over the deeper x slabs (their ends are those
slabs' rows, so that corners fill transitively, four deep) and are laid
along the block's lanes before the call, the western columns last.  The
tendencies have no window: their slabs are stored over the block of
tile ``i - 1`` where the pipeline brought it in (the lanes and rows past
the field's are the block's too), before the first stage reads it.
:func:`holds_further` is what all this needs of a shape: four lanes to
spare, two rows to spare in the last tile, a strip of interior rows (a
neighbour's ring computed here must not feel the neighbour's other
wall); a block without walks one step, as before PR 53.

The returned state is a single walk's on the interior, and on the
ghosts where the next walk reads them: ring 1 of ``du``, ``dv`` the
last step's own round 1 there, ring 1 of ``u``, ``v`` round 1's values
of the last step.  The ghost cells of ``h`` and ring 2 of ``u``, ``v``
hold what the walk computed for the second step (on one device the
row's other end as step *n* left it, beside a neighbour the kernel's
own step *n* there, which is the neighbour's bit for bit): what an
exchange before the last step would have written, and what the next
exchange overwrites.

Output in the last walk
-----------------------
A job that writes snapshots (``shallow_water.SolverJob``) wants, after
every call, the means of ``h``, ``u``, ``v`` over ``c × c`` blocks of
cells.  Every row of those fields passes through VMEM in final form in
the call's last walk, so that walk (``wide_step(coarsen=c)`` with
``summing`` set: "One kernel a job", below) also writes, for each
field, **the sums over** ``c`` **rows**, whole width: a ``c``-th of a
field, which the snapshot program finishes along the rows
(``shallow_water.make_snapshot``) instead of reading three fields
again.  Rows only, float32, by rotations of sublanes and
additions: sums along a row would have to bring every fourth lane of a
register together, which the vector units do not do without the matrix
unit or a pass of transposes.

When.  A tile's final values are in its output block when the grid step's
strips are through (``h`` and, without friction, ``u`` and ``v`` from the
last step's first stage, ``u`` and ``v`` from its second, a grid step
later), so the sums are made **after the strips' loop, a tile at a
time**, from the block in VMEM, and their blocks are written as many
grid steps behind as the field's own (``wrote_plain``, ``wrote_last``).
Not in the strips' loop: there every value is 113 vector registers wide
and what a stage keeps beyond its 64 registers it spills, a store each,
in a loop that the store slot already fills; after it the sums run over
the tile's registers eight at a time, and nothing is spilled.

Which rows.  The interior starts ``G`` = 2 rows into a block and a strip
is 8 rows, so with ``c`` = 4 a strip ends one group begun in the strip
before it (its rows 6 and 7, this strip's 0 and 1) and holds one whole
(rows 2 to 5).  Sums double (:func:`_spans`): each row plus the next,
each such pair plus the pair two rows on, for ``c`` = 8 each four plus
the four four rows on; before a doubling that reaches across a strip's
end, the sublanes of the groups astride it (:func:`_astride`) take **the
strip before's partial sums** in place of this strip's, so that the
doubling's rotation, which wraps round the strip, brings the right rows
together; a tile's last strip leaves its partial sums in VMEM for the
next tile's first, as ``first`` hands itself its row values
(``n_carried``).  A group's sum is therefore ``(r0 + r1) + (r2 + r3)``,
pairs first, whichever strips its rows lie in.  After the doublings a
strip holds the sums of the ``8 / c`` groups that *end* in it, on the
sublanes of their first rows (:func:`_finished`).

Where they go.  Row ``lead + m`` of a field's sums holds the sum of the
block's rows ``G + c m`` on, ``lead = ceil(G / c)`` (1 for every ``c``
that rides): the rows before it are the group that ends in the ghost
rows, the rows after the interior's last whatever the field's last tile
held.  A tile of 24 rows ends 6 groups and a block's rows have to be
whole strips, so a block of sums is 24 rows, the groups of four tiles
(``phases``), revisited over four grid steps and written back once.  A
tile's sums are rotated onto neighbouring sublanes, rotated once more
by the tile's place in the block (the one rotation whose amount is not
known when the kernel is built), and selected into the one or two strips
of the block they fall in (:func:`_row_sums`, jitted like the stages: a
process traces it once for three fields).  One loop over the registers
for the three fields; in the walk's first and last grid steps, where a
field has no new tile, its first or last tile is summed again, to the
same rows.  Some 10 bundles a vector register of 24 rows, 3 630 bundles
a tile beside the strips' 32 470 (``PERF.md``, PR 49).

One kernel a job.  A second kernel text in a job's programs is a second
trace and a second lowering in every run of a process (a program is
lowered before its cache key exists), 0.44 s on a chip's host, as much
as the sums save in ten seconds (``PERF.md``, PR 49).  So the walk that
can sum takes a scalar, ``summing``, before the grid starts
(``PrefetchScalarGridSpec``): where it is not set the sums' loop is
passed over and the blocks of sums stay on their first block, which is
written back once, as found, at the walk's end, and a job runs this
one kernel in its first step and in every walk of a call, the sums on
in the last.  A job without output runs the kernel without sums, whose
text this leaves as it was.

The caller brings the room.  The three arrays of sums (a ``c``-th of a
field each, 315 MB together at the benchmark's size) are operands the
kernel never reads (``pl.ANY``: no block of them is brought in) aliased
to the results it writes, as the fields are: a job makes them once,
hands them from walk to walk and from call to call and donates them
with the state, so that a call allocates nothing and the walks of a
call's loop, which name the sums as results like the last, need no
room of their own (fresh results every call were 315 MB allocated and
as much again as a temporary of the loop, and runs with them stalled:
``PERF.md``, PR 49).

The adjoint walk
----------------
Under ``jax.grad`` a step that ran as the kernel is transposed by a
kernel too (:func:`wide_step_vjp`, which ``shallow_water._step_wide``'s
backward calls once a step): the array code's derivative was XLA's
fusions, which wrote every shifted intermediate and every residual to
HBM, 143 passes over a field a step where this moves 15.  It **reads**
the ``h``, ``u``, ``v`` the step started from, ghosts fresh (the old
tendencies enter a step linearly: their values are not needed, so a
backward sweep keeps three arrays a step and not six), and the six
cotangents of the step's results; it **writes** the six cotangents of
what the step read, each where its own result's cotangent lay (aliased,
a tile behind, as a walk writes a field).  Every operand is read whole
once and every result written whole once: a call's signature is what it
moves.

What it transposes is this module's schedule, not the array code's:
round 1 on the interior and on ring 1 of ``u``, ``v``, then round 2,
with no exchange between.  So the cotangent of round 1's ``u``, ``v``
on ring 1 (which round 2's five points read) goes backwards through
round 1 on this chip, by the same code as the interior's, and comes out
as cotangents of the fields **after their exchange, ghost cells
included**, and of ring 1 of the old ``du``, ``dv`` (the neighbours',
"Schedule" above).  The caller sends those ghost cells home through the
exchange's own transpose (``parallel/halo.py``): the step's three
exchanges backwards, and two for ``du``, ``dv``.  The same derivative as
the array code's five exchanges and two rounds give, because it is the
same function of the mesh's interiors.  The masks are the stages': the
interior, round 1's reach (ring 1 where no wall stands; without
friction the interior, since nothing then reads ring 1), the rows a
wall singles out.

Tiling.  Tiles of :func:`adjoint_tile_rows` rows (32 at the benchmark's
width), full width, one grid step behind their input in a window with a
strip of the tiles before and after (:func:`_walk`'s window, for nine
arrays and read only; nothing here is placed or carried, so the walk is
not that function's: a dozen lines of its own).  A stage
(:func:`_adjoint_stage`) takes ``_ADJOINT_STRIPS`` strips of rows and a
strip of halo rows either side **as one value**: a row's neighbours are
rotations of that value along its rows, whose wrap spoils one row at
either end for every shift in a chain, three rows of the eight.  What
a transposed stencil needs at a row's neighbours (the tendencies'
cotangents, the fluxes', the vorticity's) is thereby computed where it
is read, twice the rows that are written: the kernel still runs at
HBM's pace (2.43 ms a step at 7204 columns, 78 % of the table's
bandwidth for its 15 passes: ``PERF.md``, PR 55), so no ring of strips
hands rows on here.  What lies beyond the block (the window's first
strip in a walk's first tile, the rows past the field's last, the
lanes past its width) is whatever VMEM held: the kept fields are set to
rest there (``h`` one, ``u``, ``v`` zero), so that a zero cotangent
times what is made of them is zero and not a NaN.

The tangent walk
----------------
Under ``jax.jvp`` (``jax.linearize``, ``jax.jacfwd``) a step that ran as
the kernel is pushed forwards by a kernel too (:func:`wide_step_jvp`,
which ``shallow_water._step_wide``'s tangent calls once a step): the
array code's tangent was XLA's fusions, 113 passes over a field a step
by the count that sees less than they read, 30 ms a step where this
moves 15 passes (``PERF.md``, PRs 59, 60).  The adjoint walk's mirror,
on the adjoint walk's blocks (:func:`_derivative_walk` is both): it
**reads** the ``h``, ``u``, ``v`` the step started from, ghosts fresh,
and the six tangents of what the step read, the fields' after the same
exchange and the old tendencies' with ring 1 of ``du``, ``dv`` the
neighbours'; it **writes** the six tangents of the step's results, each
where its own operand lay, a tile behind.

The stage (:func:`_tangent_stage`) is the step's two rounds on the
tangents, in the single walk's schedule: ``fe``, ``fn``, ``q`` and the
depth made again from the kept fields, as the adjoint stage makes them,
each product's tangent taken beside it (the step is linear in the old
tendencies, nonlinear in ``q (fn + fn_e)``, ``q (fe + fe_n)``, ``hx
u``, ``(h + h_n) v``, the squares and the division); round 1 on the
interior and, for ``u``, ``v``, on ring 1 too, ``v1 = 0`` on the
northern wall's row, friction after it with no exchange between.  Its
masks are the adjoint stage's, so that the two kernels are each other's
transpose cell for cell, ghost cells included
(``tests/test_sw_kernels_tangent.py``): what one reads of a ghost cell
the other writes there.  The results' ghost cells are the array code's
as far as one chip can say: ring 2 of ``u``, ``v`` lacks what only the
neighbour's round 1 knows, which the caller adds where anybody reads it
(``shallow_water._step_forwards``).  A stage takes rows with a strip of
halo rows either side as one value and throws the halo away, as the
adjoint stage does and for its reason: the kernel is bound by HBM, not
by its vector work (2.39 ms a step at 7204 columns, 79.5 % of the
table's bandwidth for its 15 passes, the adjoint kernel's 2.43 beside
it: ``PERF.md``, PR 60).  Nothing beyond the block reaches a cell that is
written (a cell reads the ring round it and selections drop the rest),
so nothing is set to rest here.

A walk of two steps is two calls with the state between them made again
by the forward kernel, one walk of one step, **not in place**
(``wide_step(in_place=False)``): the walk of two whose tangent this is
reads the same state, and a call in place would cost a copy of each of
its six arrays first.

Building a kernel is set-up a user waits for, so it is kept short:
``jax.experimental.pallas`` is imported by :func:`pallas` where a step is
built for TPU devices (the array code, which every other backend runs,
does not pay for it), a kernel's body is written in ``lax``, the call
is jitted, so that the programs of one process trace it once, and so
are the two stages (:func:`_stages`), which are most of a body: the
kernel of a single walk and both applications of a double one trace
each once in a process (beside a neighbour in x the first application
has its wider columns and is traced beside the second).  A process
builds one kernel, the double walk's: a run's first step is that walk
with its first step passed over by a scalar (``wide_step(lone=True)``),
which hands the second zero tendencies, forward Euler's (beside
neighbours from the same deep slabs of the fields, and zeros for the
tendencies').  Only an odd count's last step builds the single walk's
beside it.  (A kernel's trace is 0.15 to
0.4 s on a chip's host: PERF.md, PR 41.)
"""

import functools
import itertools
import math
import sys

import jax
import jax.numpy as jnp
from jax import lax

from mpi4jax_tpu.ops._core import promote_vma, union_vma_struct, vma_of

G = 2  # ghost width of the wide-halo schedule
STRIP = 8  # rows a kernel handles at once: float32's sublane tile
LANES = 128  # columns of a vector register

# of a v5e core's 128 MiB of VMEM: what a call's blocks and windows may
# take, and the limit the compiler is given for them and its temporaries.
# A walk of two steps keeps its rings beside the same blocks, seven
# buffers an array for five, and is given seven fifths of either, so
# that its tiles are a single walk's: at 16 rows for 24 the cell's step
# was 1 % slower (PERF.md, PR 41)
_VMEM_BLOCK_BUDGET = 40 * 2**20
_VMEM_LIMIT = 64 * 2**20
# vector registers' columns of a tile that the row sums are made of at
# once ("Output in the last walk")
_SUMMED_AT_ONCE = 8


@functools.cache
def pallas():
    """``(pl, pltpu)``: Pallas, imported for a TPU kernel, once a
    process, under a ``build/import`` span of ``utils.spans.builds``
    that parts it from a trace it lies in.  Call it before a step is
    traced: under a trace the import takes half as long again (0.56
    against 0.40 s from bytecode on a v5e's host).  jax 0.9's
    ``pallas_call`` module ends by importing the interpreter of Mosaic
    GPU kernels and with it ``jax.experimental.mosaic.gpu``: 0.21 s of
    those 0.40 s for code no TPU kernel reaches.  It expects that
    import to fail where the GPU stack is missing, so the import is
    declined here unless Pallas was imported before: who imports
    ``pallas.mosaic_gpu`` later gets the real modules, but to this process
    ``pallas_call(interpret=mosaic_gpu.InterpretParams())`` is unknown."""
    from mpi4jax_tpu.utils.spans import IMPORT, builds
    gpu_interpreter = "jax._src.pallas.mosaic_gpu.interpret.interpret_pallas_call"
    decline = not {"jax.experimental.pallas", gpu_interpreter} & sys.modules.keys()
    if decline:
        sys.modules[gpu_interpreter] = None  # importing it raises ImportError
    try:
        with builds.span(IMPORT, module="jax.experimental.pallas"):
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


def _buffers(steps):
    """Tiles an array takes in VMEM: blocks in and out, both
    double-buffered, and a window; two more for a second step."""
    return 5 + 2 * (steps - 1)


def tile_rows(rows, width, dtype, fields, steps=1):
    """Rows of a tile: the most (a multiple of ``STRIP``, at most the
    field's whole strips) for which the blocks of ``fields`` fields
    updated in place (double-buffered blocks in and out, and the
    window) fit the VMEM budget; 0 if not even one strip does, or the
    field has none.  A walk of two ``steps`` keeps what the first hands
    the second in rings beside them: two tiles more an array (a window
    or a stage's ring for the fields, a ring two tiles long for what
    waits a whole step's stages), seven where a single walk has five,
    out of a budget that much larger."""
    row_bytes = _whole_registers(width) * jnp.dtype(dtype).itemsize
    buffers = _buffers(steps)
    fit = _VMEM_BLOCK_BUDGET * buffers // 5 // (buffers * fields * row_bytes)
    return min(fit, rows) // STRIP * STRIP


# lax, not operators, in a kernel's body: under a trace a jnp operator is
# a jitted call, traced anew for each new shape, and a body's hundred cost
# a step's first build half a second
add, sub, mul, div, eq, select = (
    lax.add, lax.sub, lax.mul, lax.div, lax.eq, lax.select)


def _further(slabs):
    """``(rows, columns)`` by which the slabs of a call's first field
    are deeper than the ring: 0 on an axis that brings the ring's
    ``G``, or nothing."""
    west, _, south, _ = slabs[0]
    return (0 if south is None else south.shape[0] - G,
            0 if west is None else west.shape[1] - G)


def holds_further(rows, width, dtype, further, arrays=6):
    """Whether a walk of two steps over ``arrays`` arrays of ``rows`` x
    ``width`` has room for ``further`` (rows, columns) beyond the block
    on every side ("Two steps a walk"): the columns in the lanes past
    the field's last (the western ones at the very last, where a
    rotation's wrap finds them), the northern rows in the last tile's
    rows past the field's last; and enough interior rows that no ring
    of a neighbour's that is computed here feels the neighbour's other
    wall."""
    ey, ex = further
    tile = tile_rows(rows, width, dtype, arrays, steps=2)
    return bool(
        tile and _whole_registers(width) - width >= 2 * ex
        and -(-rows // tile) * tile - rows >= ey
        and (not ey or rows - 2 * G >= STRIP))


def _walk(body, scalars, fields, slabs, pointwise, n_second, n_carried,
          steps=1, summed=(), coarsen=0, summing=True, sums=(),
          point_slabs=(), *, in_place=True, interpret):
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
    two stages ``(first, second)`` of each of the walk's steps, a list
    of pairs.  ``first(g, fields, pointwise, before)`` is handed, for 8
    rows: ``g``, the rows' numbers in the block, on one vector register
    (a row's number is the same in all its columns); for each field
    ``(c, n)``, the rows themselves and the rows north (``g + 1``) of
    them, all as they were before the call; each pointwise array's
    rows; and ``before``, the ``n_carried`` strips it returned last for
    the strip before (the 8 rows south; at a walk's first strip
    whatever VMEM held, which only that strip's first row reads, a
    ghost row).  It returns the rows' new values, fields first, and
    then its ``n_carried`` strips for the strip after.  The last
    ``n_second`` fields' values are not final: ``second(g, fresh)`` is
    handed ``(c, n, s)`` of each (``s``: the rows south, ``g - 1``) as
    ``first`` left them, a tile later, and returns the rows' final
    values (``n_second`` 0: no second stage).  East and west neighbours
    are lane rotations (``roll``, which is ``pltpu.roll``, along axis 1
    by the strip's lanes less 1 and by 1), whose wrap lands in or past
    the outermost ghost columns.  A stage masks what it updates itself:
    what it returns for a ghost cell is written too.

    ``steps`` 2 (as many pointwise arrays as fields): two pairs of
    stages run in the one walk, the second pair a step's stages of
    tiles behind the first, on what the first returned: its fields and
    its pointwise arrays go to rings of strips and not to HBM, the
    fields with their ghost columns set to the row's other end (columns
    ``width - 2 G`` on to ``0`` on, ``G`` on to ``width - G`` on), which
    is what the next exchange brings a device that is alone on a
    periodic axis; ghost rows stay what they are, as beyond a wall.
    The second application's values are the call's results.

    Slabs deeper than ``G`` (``steps`` 2 alone; ``point_slabs``: the
    pointwise arrays' slabs beside the fields', as deep) are a walk's
    whose first step reaches further than the block ("Two steps a
    walk": where the rows and columns beyond the block lie, and the one
    strip more the first stage runs on).  ``body`` gives its first pair
    of stages the wider reach; this function places the slabs, the
    pointwise arrays' too, and leaves the ghost columns of what the
    first step hands on as that step computed them where the x slabs
    are deep (no other end to take them from).

    ``summed`` (the places in ``fields`` of those asked for),
    ``coarsen`` (a divisor of ``STRIP``) and ``sums`` (for each such
    field an array of :func:`row_sums_shape`, whatever it holds): after
    the new ``fields`` and ``pointwise``, for each such field the sums
    over ``coarsen`` rows of its new values, **written into** ``sums``,
    which the call consumes as it does the fields ("Output in the last
    walk", above, says which row holds which rows' sum, and why the
    caller brings the room).  The fields and the pointwise arrays come
    back as they do without.  ``summing`` (a traced scalar): where it
    is not set the walk passes the sums over, writes none of their
    blocks but the first, with whatever VMEM held, and hands ``sums``
    back otherwise as they came.

    ``in_place`` not set: the new ``fields`` and ``pointwise`` are
    arrays of their own and the call consumes none, for a caller that
    still reads what the walk started from (every block of a result is
    written whole, so nothing of it was ever the operand's: where the
    operands live on, a call in place costs a copy of each before it).
    """
    pl, pltpu = pallas()
    rows, width = fields[0].shape
    lanes = _whole_registers(width)  # of a block
    dtype = fields[0].dtype
    n_scalars, n_fields, n_point = len(scalars), len(fields), len(pointwise)
    n_plain = n_fields - n_second
    rounds = 1 + (n_second > 0)  # stages a step
    n_stages = steps * rounds
    n_kept = steps * n_carried
    tile = tile_rows(rows, width, dtype, n_fields + n_point, steps)
    tiles = -(-rows // tile)
    strips = tile // STRIP
    # a ring of the second stage: first's strips of a tile, the one
    # before them and the one being written
    slots = strips + 2
    # what a step hands the next: a ring for each field, as far behind
    # the stage that reads it as the stage that writes it is ahead (a
    # plain field's a whole step's stages, a second stage's one), and
    # one for each pointwise array, which has no strips round it
    field_slots = [lag * strips + 2
                   for lag in [rounds] * n_plain + [1] * n_second]
    point_slots = rounds * strips + 1
    if steps > 1 and n_point != n_fields:
        raise ValueError("a walk of two steps hands each field a pointwise array")
    axes = vma_of(fields[0]) or ()
    scalars = [promote_vma(x, axes) for x in scalars]
    # how far beyond the block the slabs reach: rows, columns
    ey, ex = _further(slabs)
    if (ey or ex) and not (
            steps > 1 and len(point_slabs) == n_point
            and holds_further(rows, width, dtype, (ey, ex), n_fields + n_point)):
        raise ValueError(
            "slabs deeper than the ring are a walk of two steps', with the "
            "pointwise arrays' beside the fields', on a block with lanes "
            "and rows to spare (holds_further)")

    def laid(of_rows, x):
        """A slab as the kernel takes it: a slab of rows that reaches
        beyond the block's columns as a block's rows lie in VMEM, the
        columns west of the block at the row's last lanes."""
        if not (of_rows and ex):
            return x
        spare = jnp.zeros_like(x, shape=(x.shape[0], lanes - x.shape[1]))
        return lax.concatenate([x[:, ex:], spare, x[:, :ex]], 1)

    # the slabs that came, field by field and then pointwise array by
    # array, each with what it is a slab of (0: columns, 1: rows) and
    # the first column or row it holds, counted from the block's first
    places = ((0, -ex), (0, width - G), (1, -ey), (1, rows - G))
    came = [[(*place, laid(place[0], x))
             for place, x in zip(places, sides) if x is not None]
            for sides in (*slabs, *point_slabs)]
    n_slabs = [len(sides) for sides in came]
    arrived = [promote_vma(x, axes) for sides in came for *_, x in sides]
    # the blocks of a stage are written as many grid steps behind their
    # input as the stage is: the last step's first, the last stage
    wrote_plain, wrote_last = n_stages - rounds + 1, n_stages
    # the row sums: a tile finishes `groups` of them; a block of theirs
    # holds `phases` tiles', so that its rows are whole strips; a tile
    # hands the next `held` strips of partial sums
    n_summed = len(summed)
    groups = tile // coarsen if summed else 0
    phases = STRIP // math.gcd(groups, STRIP) if summed else 1
    held = len(_spans(coarsen)) - 1 if summed else 0

    def kernel(*refs):
        refs = iter(refs)
        on = next(refs) if summed else None
        # `_room`: the sums as they came, which nobody reads
        (scalar_refs, taken, *brought, old, _room, out, new, totals, windows,
         rings, kept, partial, held_fields, held_point, rings_again,
         old_before) = (
            tuple(itertools.islice(refs, n)) for n in
            (n_scalars, n_fields, *n_slabs, n_point, n_summed, n_fields, n_point,
             n_summed, n_fields, n_second, n_kept, n_summed,
             *((n_fields, n_point, n_second) if steps > 1 else (0, 0, 0)),
             n_point * bool(ey)))
        i = pl.program_id(0)
        applied = body(pltpu.roll, *scalar_refs)

        # a strip's rows: on one vector register for the stages, which
        # need a row's number once, and on the strip for its ends
        r = lax.broadcasted_iota(jnp.int32, (STRIP, LANES), 0)
        row = lax.broadcasted_iota(jnp.int32, (STRIP, lanes), 0)
        top, bottom = eq(row, 0), eq(row, STRIP - 1)
        # the tile handed in: the walk's last steps are handed the
        # field's last again
        t = lax.min(i, tiles - 1)

        def pieces(k):
            """The slabs of array ``k`` (the fields, then the pointwise
            arrays) in the parts a store takes: ``(of_rows, start, n,
            ref, mine)``, ``n`` columns that lie in one vector register
            or rows that lie in one strip, from column or row ``start``
            of the block on, which are ``ref``'s ``mine``; x before y,
            as an exchange writes them (the y slabs hold the corners)."""
            for (of_rows, lo, x), ref in zip(came[k], brought[k]):
                for start, n in _pieces(
                        lo, x.shape[1 - of_rows], STRIP if of_rows else LANES):
                    yield of_rows, start, n, ref, pl.ds(start - lo, n)

        def place(win, at, count, k, rows):
            """Rows ``[at, at + count)`` of field ``k``'s window have
            just taken the first ``count`` rows of tile ``t`` as the
            field holds them, ghosts stale: write the slabs' cells over
            them.  A slab's columns in one store where they lie in
            one vector register (those west of the block at the row's
            last lanes); its rows where they lie in one strip, in the
            grid step that holds that strip, which ``in_their_tile``
            does for every array at once, from ``rows`` (those before
            the block are ``before_the_block``'s)."""
            for of_rows, start, n, ref, mine in pieces(k):
                if not of_rows:
                    win[pl.ds(at, count), pl.ds(start % lanes, n)] = (
                        ref[pl.ds(0, count), mine])
                    continue
                holder, row = divmod(start, tile)
                if start >= 0 and row < count:
                    rows.setdefault(holder, []).append(
                        (win, pl.ds(at + row, n), ref, mine))

        def in_their_tile(rows, held):
            """The slabs' rows over the arrays' rows, after their
            columns: ``rows`` by the tile that holds them, each
            ``(to, its rows, slab, the slab's rows)``; ``held``: the
            tile in VMEM.  One branch a tile, whatever the arrays
            (a branch an array a slab a place were 21 in the kernel of
            a walk beside neighbours, traced and lowered in every run
            of a process; these are 9)."""
            for holder, stores in rows.items():
                @pl.when(eq(held, holder))
                def _(stores=stores):
                    for to, where, ref, mine in stores:
                        to[where, :] = ref[mine, :]

        # a window's rows: the strip above tile i - 1, the tile, and the
        # strip below it, which is the first of the block just handed in
        rows = {}
        for k, (ref, win) in enumerate(zip(taken, windows)):
            win[pl.ds(tile + STRIP, STRIP), :] = ref[pl.ds(0, STRIP), :]
            place(win, tile + STRIP, STRIP, k, rows)
        in_their_tile(rows, t)

        # the pointwise arrays' slabs (a walk that reaches further): over
        # the block of tile i - 1 where it was brought in, which the
        # first stage is about to read; the lanes past the field's last
        # column and the rows past its last row are the block's too
        rows = {}
        for k, ref in enumerate(old if ey or ex else ()):
            for of_rows, start, n, slab, mine in pieces(n_fields + k):
                if not of_rows:
                    ref[:, pl.ds(start % lanes, n)] = slab[:, mine]
                elif start >= 0:
                    holder, row = divmod(start, tile)
                    rows.setdefault(holder, []).append(
                        (ref, pl.ds(row, n), slab, mine))
        if rows:
            in_their_tile(rows, sub(i, 1))

        def strip(k):
            """The rows of strip ``k`` of a block, a window or a ring."""
            return pl.ds(pl.multiple_of(mul(k, STRIP), STRIP), STRIP)

        def northward(ref, c, below):
            """``(c, n)`` of the rows ``c`` of ``ref``, a strip: the strip
            below gives the row that a rotation of the strip lacks."""
            c, below = ref[c, :], ref[below, :]
            return c, pltpu.roll(select(top, below, c), STRIP - 1, 0)

        def around(ref, above, c, below):
            """``(c, n, s)`` likewise, the last from the strip above."""
            c, n = northward(ref, c, below)
            return c, n, pltpu.roll(select(bottom, ref[above, :], c), 1, 0)

        # the lanes of a vector register that hold ghost columns, for
        # each stretch of them that `_ends_meet` fills from one place
        lane = lax.broadcasted_iota(jnp.int32, (STRIP, LANES), 1)
        ghosts = {
            (lo, n): lax.bitwise_and(lax.ge(lane, lo), lax.lt(lane, lo + n))
            for _, moves in (_ends_meet(width) if steps > 1 and not ex else ())
            for _, _, lo, n in moves}

        def of(value, start):
            """The vector register of a strip whose columns start there."""
            return lax.slice(value, (0, start), (STRIP, start + LANES))

        def put(ref, rows, value):
            ref[rows, :] = value

        def hand_on(ring, rows, value):
            """A field's strip as a step leaves it, into the ring the
            next step reads: its ghost columns take the row's other end.
            The strip as it is, then the vector registers that hold
            ghost columns again, each once, the columns brought there by
            a rotation of the register that holds them."""
            ring[rows, :] = value
            # (with a neighbour in x the step has computed them itself)
            for home, moves in () if ex else _ends_meet(width):
                register = of(value, home)
                for source, shift, lo, n in moves:
                    register = select(
                        ghosts[lo, n], pltpu.roll(of(value, source), shift, 1),
                        register)
                ring[rows, pl.ds(home, LANES)] = register

        def strips_through(stages):
            def run(j, carry):
                g = add(r, add(mul(sub(i, 1), tile), mul(j, STRIP)))
                # first's strip, counted from the block's first: the rows
                # of its slot in a ring and, as far behind as the ring
                # is long less its two spare strips, of the slots of a
                # later stage's strip and of the two round it
                # (one on where the strip before the block's first has
                # a slot of its own, the rings' first)
                k = add(mul(sub(i, 1), strips), add(j, 1) if ey else j)
                behind = functools.cache(
                    lambda slots, d: strip(lax.rem(add(k, d), slots)))
                here, north, beyond = strip(j), strip(add(j, 1)), strip(add(j, 2))
                for stage in stages:
                    step, is_second = divmod(stage, rounds)
                    first, second = applied[step]
                    last = step == steps - 1
                    mine = sub(g, stage * tile) if stage else g
                    second_rings = rings_again if step else rings
                    if is_second:
                        values = second(mine, [
                            around(ring, *(behind(slots, d) for d in (1, 2, 3)))
                            for ring in second_rings])
                        if last:
                            stores = [(put, ref, here) for ref in out[n_plain:]]
                        else:
                            stores = [(hand_on, ring, behind(n, 0)) for ring, n in zip(
                                held_fields[n_plain:], field_slots[n_plain:])]
                    else:
                        mine_kept = kept[step * n_carried:][:n_carried]
                        before = [ref[...] for ref in mine_kept]
                        if step:
                            values = first(
                                mine,
                                [northward(ring, *(behind(n, d) for d in (2, 3)))
                                 for ring, n in zip(held_fields, field_slots)],
                                [ring[behind(point_slots, 1), :]
                                 for ring in held_point], before)
                        else:
                            values = first(
                                mine,
                                [northward(win, north, beyond) for win in windows],
                                [ref[here, :] for ref in old], before)
                        if last:
                            plain = [(put, ref, here) for ref in out[:n_plain]]
                            point = [(put, ref, here) for ref in new]
                        else:
                            plain = [(hand_on, ring, behind(n, 0)) for ring, n in zip(
                                held_fields[:n_plain], field_slots)]
                            point = [(put, ring, behind(point_slots, 0))
                                     for ring in held_point]
                        stores = plain + [(put, ring, behind(slots, 0))
                                          for ring in second_rings] + point + [
                            (put, ref, slice(None)) for ref in mine_kept]
                    for (store, ref, where), value in zip(stores, values):
                        store(ref, where, value)
                return carry

            lax.fori_loop(0, strips, run, 0)

        def before_the_block():
            """The strip before the block's first, which only a walk
            that reaches ``ey`` rows further has: its last ``ey`` rows
            are the southern slabs' first.  The first step's first
            stage on it, for what the block's first row reads of the
            row south of it: round 1's values on their way to round 2,
            and what the stage hands the strip after."""
            for k, ref in enumerate((*windows, *old_before)):
                for of_rows, start, n, slab, mine in pieces(k):
                    if of_rows and start < 0:
                        ref[pl.ds(STRIP + start, n), :] = slab[mine, :]
            first, _ = applied[0]
            mine_kept = kept[:n_carried]
            values = first(
                sub(r, STRIP),
                [northward(win, pl.ds(0, STRIP), pl.ds(STRIP, STRIP))
                 for win in windows],
                [ref[...] for ref in old_before], [ref[...] for ref in mine_kept])
            for ring, value in zip(rings, values[n_plain:n_fields]):
                ring[pl.ds(0, STRIP), :] = value
            for ref, value in zip(mine_kept, values[n_fields + n_point:]):
                ref[...] = value

        def sum_rows():
            """The sums over ``coarsen`` rows of the tiles that the last
            step's stages have written to the summed fields' blocks,
            into the fields' blocks of sums ("Output in the last walk"
            has the arithmetic, :func:`_row_sums` makes it).  A tile's
            strips are loaded again from its block, which is in VMEM,
            some vector registers' columns at a time, so that what a sum
            passes through stays in registers; the last columns are
            done again with those before them where the registers do
            not divide, and a walk's first and last grid steps, which
            have written no new tile of a field, sum the field's first
            and last again: either changes nothing, because a tile
            reads the partial sums of the strip before it in one half
            of ``partial`` and writes its own to the other.  One loop
            for all the fields, and no branch: a process traces and
            lowers this on every run."""
            registers = lanes // LANES
            wide = min(_SUMMED_AT_ONCE, registers)
            sums = _row_sums(pltpu.roll, coarsen, wide * LANES)
            work = []
            for k, total, strips_kept in zip(summed, totals, partial):
                # the tile the field's block holds; the row of the
                # block of sums that its first group goes to, and the
                # strips its groups fall in: one past the block's end is
                # the block's last again
                t = lax.clamp(
                    0, sub(i, wrote_plain if k < n_plain else wrote_last), tiles - 1)
                at = mul(lax.rem(t, phases), groups)
                places = [
                    strip(lax.min(add(lax.div(at, STRIP), n),
                                  phases * groups // STRIP - 1))
                    for n in range(-(-groups // STRIP) + 1)]
                read = [strip(add(mul(lax.rem(t, 2), held), n)) for n in range(held)]
                write = [strip(add(mul(lax.rem(add(t, 1), 2), held), n))
                         for n in range(held)]
                work.append((out[k], total, strips_kept, lax.rem(at, STRIP),
                             places, read, write))

            def run(n, carry):
                columns = pl.ds(pl.multiple_of(mul(lax.min(
                    mul(n, wide), registers - wide), LANES), LANES), wide * LANES)
                for ref, total, strips_kept, at, places, read, write in work:
                    new, kept = sums(
                        at, ref[:, columns],
                        [strips_kept[where, columns] for where in read],
                        [total[where, columns] for where in places])
                    # from the last, which holds nothing new where it
                    # is the one before it again
                    for where, value in reversed(list(zip(places, new))):
                        total[where, columns] = value
                    for where, value in zip(write, kept):
                        strips_kept[where, columns] = value
                return carry

            lax.fori_loop(0, -(-registers // wide), run, 0)

        # stage q runs on tile i - 1 - q: tile i - 1 goes through first
        # while tile i - 2 goes through second, and so on (at the walk's
        # start on rings as they are found, into rings and blocks that a
        # later grid step writes again; at its end the stages that have
        # done the field's last tile run on, into rings nothing reads),
        # until the last stage that writes blocks beside another has
        # written the field's last; the walk's last step is second's alone
        beside = n_stages - 1 - (n_second > 0)
        if ey:
            pl.when(eq(i, 1))(before_the_block)

        @pl.when(lax.bitwise_and(lax.gt(i, 0), lax.le(i, tiles + beside)))
        def _():
            strips_through(range(n_stages))

        if n_second:
            @pl.when(eq(i, tiles + n_stages - 1))
            def _():
                strips_through([n_stages - 1])

        if summed:
            pl.when(eq(on[0], 1))(sum_rows)

        rows = {}
        for k, (ref, win) in enumerate(zip(taken, windows)):
            win[pl.ds(0, STRIP), :] = win[pl.ds(tile, STRIP), :]
            win[pl.ds(STRIP, tile), :] = ref[...]
            place(win, STRIP, tile, k, rows)
        in_their_tile(rows, t)

    def block(lag):
        """Tile ``i - lag``, held to the field's own tiles."""
        return pl.BlockSpec(
            (tile, lanes), lambda i, *_: (lax.clamp(0, i - lag, tiles - 1), 0))

    struct = union_vma_struct(fields[0].shape, dtype, *fields, *scalars)
    in_smem = pl.BlockSpec(memory_space=pltpu.SMEM)

    def block_of_slab(of_rows, x, lag=0):
        """A slab of rows whole, once; of a slab of columns the rows of
        the tile handed in, or of the tile ``lag`` steps behind it (a
        pointwise array's: its blocks are the tile being written)."""
        if of_rows:
            return pl.BlockSpec((x.shape[0], lanes), lambda i, *_: (0, 0))
        return pl.BlockSpec(
            (tile, x.shape[1]),
            lambda i, *_: (lax.clamp(0, i - lag, tiles - 1) if lag
                           else lax.min(i, tiles - 1), 0))

    def ring(slots):
        return pltpu.VMEM((slots * STRIP, lanes), dtype)

    def block_of_sums(lag):
        """The sums of ``phases`` tiles, the last of them tile ``i -
        lag``: a block stays while its tiles are written, and is
        written back when the walk moves on to the next.  A walk that
        does not sum stays on the first block, and so writes nothing
        back but that block, once, with whatever VMEM held."""
        return pl.BlockSpec(
            (phases * groups, lanes),
            lambda i, on: (mul(on[0], lax.div(
                lax.clamp(0, i - lag, tiles - 1), phases)), 0))

    if len(sums) != n_summed:
        raise ValueError(f"{n_summed} fields to sum and room for {len(sums)}")
    sums_struct = summed and union_vma_struct(
        row_sums_shape(fields[0].shape, dtype, coarsen, n_fields + n_point),
        dtype, *fields, *scalars)
    # the room is the caller's and comes back written: no block of it is
    # brought in, the results' blocks are written where it lies
    sums = [promote_vma(x, axes) for x in sums]
    specs = dict(
        grid=(tiles + n_stages,),
        in_specs=([in_smem] * n_scalars + [block(0)] * n_fields
                  + [block_of_slab(of_rows, x, lag=k >= n_fields)
                     for k, sides in enumerate(came)
                     for of_rows, _, x in sides]
                  + [block(1)] * n_point
                  + [pl.BlockSpec(memory_space=pl.ANY)] * n_summed),
        out_specs=([block(wrote_plain)] * n_plain + [block(wrote_last)] * n_second
                   + [block(wrote_plain)] * n_point
                   + [block_of_sums(wrote_plain if k < n_plain else wrote_last)
                      for k in summed]),
        scratch_shapes=(
            [pltpu.VMEM((tile + 2 * STRIP, lanes), dtype)] * n_fields
            + [ring(slots)] * n_second + [ring(1)] * n_kept
            + [ring(max(1, 2 * held))] * n_summed  # at `coarsen` 2 nobody's
            + ([ring(n) for n in field_slots] + [ring(point_slots)] * n_point
               + [ring(slots)] * n_second if steps > 1 else [])
            + [ring(1)] * (n_point * bool(ey))))
    # whether the walk sums is known to the blocks' index maps: an
    # operand before the others, in SMEM before the grid starts
    ahead = [promote_vma(jnp.reshape(summing, (1,)).astype(jnp.int32), axes)] * bool(summed)
    if summed:
        specs = dict(grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, **specs))
    first_field = len(ahead) + n_scalars
    results = pl.pallas_call(
        kernel,
        **specs,
        out_shape=[struct] * (n_fields + n_point) + [sums_struct] * n_summed,
        # the sums' room always; the fields and the pointwise arrays
        # where the walk is in place
        input_output_aliases={
            **({first_field + k: k for k in range(n_fields)} if in_place else {}),
            **{first_field + n_fields + len(arrived) + k: n_fields + k
               for k in range(0 if in_place else n_point, n_point + n_summed)}},
        compiler_params=pltpu.CompilerParams(
            # in order: a step reads the window the step before left
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT * _buffers(steps) // 5),
        interpret=interpret,
    )(*ahead, *scalars, *fields, *arrived, *pointwise, *sums)
    return results


def row_sums_shape(shape, dtype, coarsen, arrays=6):
    """The shape of a field's sums over ``coarsen`` rows as a walk over
    ``arrays`` arrays of ``shape`` (a step's six) writes them: whole
    blocks of ``phases`` tiles' groups ("Output in the last walk"), the
    same for a walk of one step and of two, whose tiles are the same
    (:func:`tile_rows`)."""
    rows, width = shape
    tile = tile_rows(rows, width, dtype, arrays)
    groups = tile // coarsen
    phases = STRIP // math.gcd(groups, STRIP)
    tiles = -(-rows // tile)
    return -(-tiles // phases) * phases * groups, width


def _spans(coarsen):
    """The rows a group's partial sums hold as they double: 1, 2, ...
    up to half of ``coarsen``."""
    return [2 ** k for k in range(coarsen.bit_length() - 1)]


def _astride(rows):
    """The sublanes at which a group of ``rows`` rows that starts in a
    strip ends in the next: groups start ``G`` rows into a block."""
    return tuple(k for k in range(G % rows, STRIP, rows) if k + rows > STRIP)


def _finished(coarsen):
    """The sublanes that hold, after a strip's doubling, the sums of the
    groups of ``coarsen`` rows that end in the strip, in the groups'
    order: the sublane of a group's first row, the group astride the
    strip before first."""
    astride = _astride(coarsen)
    starts = range(G % coarsen, STRIP, coarsen)
    return [*astride, *(k for k in starts if k not in astride)]


@functools.lru_cache
def _row_sums(roll, coarsen, width):
    """What makes a tile's sums over ``coarsen`` rows, on ``width``
    columns: ``sums(at, tile, before, block)`` takes the tile's
    rows, the partial sums the strip before the tile's
    first left (``before``: for each span of :func:`_spans` but the
    first, a strip), and the strips of the block of sums that the
    tile's groups go to (``block``: the strip that holds the row of the
    tile's first group, ``at`` rows into it, and those after it), and
    returns those strips with the groups' sums in place and the partial
    sums of the tile's last strip.  Jitted and kept, like the stages: a
    process traces it once, for three fields.  ``roll``: as
    :func:`_walk` hands it to a body."""
    registers = width // LANES

    def across(register):
        return lax.concatenate([register] * registers, 1)

    def sums(at, tile, before, block):
        row = lax.broadcasted_iota(jnp.int32, (STRIP, LANES), 0)
        found = []  # (sums, sublane) of the tile's groups, in order
        for j in range(0, tile.shape[0], STRIP):
            x, kept = lax.slice_in_dim(tile, j, j + STRIP), []
            for span in _spans(coarsen):
                if span > 1:
                    # the half sums of a group that ends in this strip
                    # and began in the one before
                    kept.append(x)
                    astride = functools.reduce(lax.bitwise_or, (
                        eq(row, k) for k in _astride(2 * span)))
                    x = select(across(astride), before[len(kept) - 1], x)
                x = add(x, roll(x, STRIP - span, 0))
            before = kept
            found += [(x, k) for k in _finished(coarsen)]
        block = list(block)
        # a strip's worth of groups at a time: their rows of the block
        # are as many sublanes, from `at` on and round to the strip after
        for first in range(0, len(found), STRIP):
            some = found[first:first + STRIP]
            placed = None
            for n, (x, k) in enumerate(some):
                if (n - k) % STRIP:
                    x = roll(x, (n - k) % STRIP, 0)
                placed = x if placed is None else select(across(eq(row, n)), x, placed)
            placed = roll(placed, at, 0)
            member = lax.lt(lax.rem(add(sub(row, at), STRIP), STRIP), len(some))
            low = lax.ge(row, at)
            for k, rows in ((first // STRIP, low), (first // STRIP + 1, lax.bitwise_not(low))):
                block[k] = select(
                    across(lax.bitwise_and(member, rows)), placed, block[k])
        return block, before

    return jax.jit(sums)


def _pieces(lo, n, every):
    """``[lo, lo + n)`` cut at the multiples of ``every``, as ``(start,
    length)``: the parts of a slab that lie in one vector register, or
    in one strip."""
    cuts = [lo, *range(-(-(lo + 1) // every) * every, lo + n, every), lo + n]
    return [(a, b - a) for a, b in zip(cuts, cuts[1:])]


def _ends_meet(width):
    """What sets a row's ghost columns to its other end, as an exchange
    along a periodic axis of one device does: for each vector register
    that holds ghost columns, the column it starts at and its ``(source,
    shift, lo, n)``: lanes ``[lo, lo + n)`` take the register that starts
    at column ``source``, rotated by ``shift`` lanes."""
    found = {}
    for to, of in ((0, width - 2 * G), (width - G, G)):
        for to, of in zip(range(to, to + G), range(of, of + G)):
            home, source = to // LANES * LANES, of // LANES * LANES
            moves = found.setdefault(home, [])
            shift = (to - of) % LANES
            if moves and moves[-1][:2] == (source, shift) and (
                    moves[-1][2] + moves[-1][3] == to - home):
                moves[-1] = (*moves[-1][:3], moves[-1][3] + 1)
            else:
                moves.append((source, shift, to - home, 1))
    return list(found.items())


def _across(lanes, register):
    """A vector register's value in every register of a strip ``lanes``
    wide: names registers, computes nothing."""
    return lax.concatenate([register] * (lanes // LANES), 1)


def _box(width, g, row_from, row_to, ring):
    """Rows ``[row_from, row_to)`` of a block ``width`` wide less its
    ghost columns outside ``ring``, for the rows ``g`` (their numbers on
    one vector register's columns): the rows' predicate, made on one
    register, in every register, and the columns' in those alone that
    hold a column outside (the first and the last of a row; two a side
    where the ghost columns lie astride them)."""
    lanes = _whole_registers(width)
    rows = lax.bitwise_and(lax.ge(g, row_from), lax.lt(g, row_to))
    lane = lax.broadcasted_iota(jnp.int32, g.shape, 1)
    lo, hi = G - ring, width - G + ring
    if lo < 0:
        # the columns west of the block lie at a row's last lanes
        def columns(at):
            inside = lax.lt(lane, hi - at)
            if at + LANES <= lanes + lo:
                return inside
            return lax.bitwise_or(inside, lax.ge(lane, lanes + lo - at))

        return lax.concatenate([
            rows if at + LANES <= hi else lax.bitwise_and(rows, columns(at))
            for at in range(0, lanes, LANES)], 1)
    return lax.concatenate([
        rows if lo <= at and at + LANES <= hi else functools.reduce(
            lax.bitwise_and,
            (rows, lax.ge(lane, lo - at), lax.lt(lane, hi - at)))
        for at in range(0, lanes, LANES)], 1)


@functools.lru_cache
def _stages(roll, rows, width, dtype, nu, dx, dy, dt, gravity, coriolis_f,
            coriolis_beta, out=0):
    """The step's two stages for :func:`_walk`, ``(first, second)``, each
    taking before its own arguments the ``scalars`` a kernel reads from
    SMEM (:func:`wide_step` ``body``).  Jitted and kept: a process
    traces each once, for the kernel of a single walk and both
    applications of a double one (a body's hundred and twenty
    operations are most of a kernel's trace); in a kernel's text they
    are inlined.  ``roll``: as
    :func:`_walk` hands it to a body.  ``out``: the rings of columns
    further out than a single walk's that the stages update (the first
    step's of a walk of two with a neighbour in x: ``h`` and round 2 on
    rings 1 and 2, round 1 of ``u``, ``v`` on ring 3 too); the rows'
    reach is the scalars'."""
    lanes = _whole_registers(width)  # of a strip in the kernel
    inv_dx, inv_dy = 1.0 / dx, 1.0 / dy
    cx, cy = nu / dx, nu / dy

    across = functools.partial(_across, lanes)
    box = functools.partial(_box, width)

    def east(x):
        return roll(x, lanes - 1, 1)

    def west(x):
        return roll(x, 1, 1)

    def first(scalars, g, fields, old, before):
        """Round 1: the tendencies and the Adams-Bashforth update,
        of ``h`` on the interior, of ``u`` and ``v`` on ring 1 too.
        What lives on a face or a corner is made once a row: the
        fluxes, the energy and the vorticity's product with the flux are
        returned after the rows' new values, for the strip after, which
        is handed them as ``before`` and takes its first row's southern
        (or own) values from them, its other rows' from its own by a
        rotation of sublanes."""
        (a, b, first_row, south_ghost_row, north_wall_row,
         inner_from, inner_to, reach_from, reach_to) = scalars
        (h, h_n), (u, u_n), (v, v_n) = fields
        zero = lax.full(h.shape, 0, dtype)
        interior = box(g, inner_from, inner_to, out)
        reach = box(g, reach_from, reach_to, 1 + out)
        # the array code builds its ring-1 fields on every row and
        # zeroes them on the walls' ghost rows (the northward flux
        # on the northern wall's own row too); an interior row reads
        # those rows only as the last row under a northern wall and
        # as the first over a southern one.  So a row's values are
        # zeroed where the row north of the northern wall's holds
        # them or the southern wall's ghost row does: those rows
        # themselves nothing updates
        north = across(eq(g, north_wall_row))
        wall = across(lax.bitwise_or(
            eq(g, north_wall_row), eq(g, south_ghost_row)))
        south = across(eq(g, south_ghost_row))
        last = across(eq(
            lax.broadcasted_iota(jnp.int32, g.shape, 0), STRIP - 1))

        def south_of(x, before):
            """The rows south of ``x``'s: its own but for the first,
            which is the last of ``before``, the strip before."""
            return roll(select(last, before, x), 1, 0)

        def unless(where, x):
            return select(where, zero, x)

        # mean depths: twice the one on the eastern face, on this
        # row and the one north of it; the array code's hc is h with
        # the walls' ghost rows set to the wall's row, and of those
        # the interior reads the northern one alone, in q's depth
        h_e = east(h)
        hx, hx_n = add(h, h_e), add(h_n, east(h_n))
        # mass fluxes through the eastern face, of the row north and
        # (from it) of this row, and through the northern face, of this
        # row and of the one south of it
        fe_n = unless(north, mul(mul(hx_n, 0.5), u_n))
        fn = unless(wall, mul(mul(add(h, h_n), 0.5), v))
        before_fe, before_fn, before_qf, before_ke = before
        fe, fn_s = south_of(fe_n, before_fe), south_of(fn, before_fn)

        # potential vorticity at the row's north-eastern corners, a
        # quarter of it: the depth is four times the mean depth there,
        # and the tendencies take a quarter of their sums instead
        y = mul(add(lax.convert_element_type(sub(g, G), dtype), first_row), dy)
        planetary = across(add(mul(y, coriolis_beta), coriolis_f))
        relative = sub(mul(sub(east(v), v), inv_dx), mul(sub(u_n, u), inv_dy))
        q = unless(south, div(
            add(planetary, relative), add(hx, select(north, hx, hx_n))))
        # what du takes of it, on this row and the one south, and dv,
        # at this corner and the one west
        qf = mul(q, add(fn, east(fn)))
        qf_s = south_of(qf, before_qf)
        qe = mul(q, add(fe, fe_n))

        # kinetic energy at the cell, of the row north and of this row
        uu_n = mul(u_n, u_n)
        ke_n = unless(north, mul(add(
            add(uu_n, west(uu_n)), add(mul(v_n, v_n), mul(v, v))), 0.25))
        ke = south_of(ke_n, before_ke)

        dh_new = sub(mul(sub(west(fe), fe), inv_dx), mul(sub(fn, fn_s), inv_dy))
        du_new = sub(
            add(mul(sub(h_e, h), -gravity * inv_dx), add(qf, qf_s)),
            mul(sub(east(ke), ke), inv_dx))
        dv_new = sub(
            sub(mul(sub(h_n, h), -gravity * inv_dy), add(qe, west(qe))),
            mul(sub(ke_n, ke), inv_dy))

        def stepped(where, x, new, old):
            new = select(where, new, zero)
            inc = select(where, mul(add(mul(new, a), mul(old, b)), dt), zero)
            return add(x, inc), new

        dh_old, du_old, dv_old = old
        h, dh_new = stepped(interior, h, dh_new, dh_old)
        u, du_new = stepped(reach, u, du_new, du_old)
        v, dv_new = stepped(reach, v, dv_new, dv_old)
        return (h, u, unless(north, v), dh_new, du_new, dv_new,
                fe_n, fn, qf, ke_n)

    def second(scalars, g, fresh):
        """Round 2: lateral friction of round 1's ``u`` and ``v``."""
        _, _, _, south_ghost_row, north_wall_row, inner_from, inner_to, _, _ = scalars
        interior = box(g, inner_from, inner_to, out)
        zero = lax.full(interior.shape, 0, dtype)
        # of the rows the array code zeroes in the y gradient, an
        # interior cell reads one: the southern wall's ghost row
        south_is_wall = across(eq(sub(g, 1), south_ghost_row))

        def friction(c, n, s):
            # the gradients at the cell, and west and south of it
            gx = mul(sub(east(c), c), cx)
            gy = mul(sub(n, c), cy)
            gy_s = select(south_is_wall, zero, mul(sub(c, s), cy))
            inc = mul(add(mul(sub(gx, west(gx)), inv_dx),
                          mul(sub(gy, gy_s), inv_dy)), dt)
            return add(c, select(interior, inc, zero))

        u, v = fresh
        return friction(*u), select(
            across(eq(g, north_wall_row)), zero, friction(*v))

    return jax.jit(first), jax.jit(second)


@functools.partial(
    jax.jit,
    static_argnames=("nu", "dx", "dy", "dt", "gravity", "coriolis_f",
                     "coriolis_beta", "steps", "coarsen", "in_place",
                     "interpret"))
def wide_step(h, u, v, dh, du, dv, slabs, is_south, is_north, first_row,
              a, b, lone=False, summing=True, sums=(), *, nu, dx, dy, dt,
              gravity, coriolis_f, coriolis_beta, steps=1, coarsen=0,
              in_place=True, interpret=False):
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
    fresh as they are); with ``steps`` 2 beside neighbours, of all six
    arrays what ``halo_slabs_2d(depth=)`` returned, two cells deeper on
    each axis that has a neighbour.  ``dh``, ``du``, ``dv``: the old
    tendencies **at the same padded shape** (a strip of a field and of an
    interior-shaped tendency would lie two rows and two lanes apart),
    as the last step returned them: ring 1 of ``du`` and ``dv`` holds
    the neighbours' (the module's docstring says why), the rest of the
    ghost ring is zero, and the new ones come back so.  ``is_south``,
    ``is_north``, ``first_row`` (the global row of the block's first
    interior row, for the Coriolis parameter), ``a`` and ``b`` are
    traced scalars: a first step is ``a = 1, b = 0`` on zero tendencies,
    exact in float32, so that a process's two programs trace one kernel,
    once.  The caller has checked :func:`tile_rows` for six fields.

    ``steps`` 2: the call advances two time steps (both with ``a``,
    ``b``) and returns what two calls return with ``halo_slabs_2d``
    between them.  On an axis of one device from the ring's slabs as a
    single walk takes them (in x the block's own columns, in y none:
    on a mesh of one device bit for bit on the whole padded blocks);
    on an axis with a neighbour from slabs ``2 G`` deep **of all six
    arrays**, ``slabs[3:]`` the tendencies' (bit for bit on the
    interior, and on the ghosts a next walk reads; the module's
    docstring says what the others hold).  The caller has checked
    :func:`holds_further`.  ``lone`` (traced, like the
    walls): the first of the two steps is passed over, its stages
    updating nothing and handing on zero tendencies, so that the call
    returns what a call of one step returns from zero tendencies, which
    is what a run's first step is: a process then builds one kernel for
    its first step and the rest (the walk takes a double walk's time,
    once a run).  ``in_place``: :func:`_walk`'s; not set by who reads
    the state again after the call (a tangent walk's state between its
    two steps, ``shallow_water._walk_forwards``).
    """
    rows, width = h.shape
    dtype = h.dtype
    floats = jnp.stack([jnp.asarray(x, dtype) for x in (a, b, first_row)])

    ey, ex = _further(slabs)

    def body(roll, flag_ref, float_ref):
        # a first step that reaches further, and a step as a single walk's
        stages = [_stages(
            roll, rows, width, dtype, nu, dx, dy, dt, gravity, coriolis_f,
            coriolis_beta, out) for out in (ex, 0)]
        a, b, first_row = float_ref[0], float_ref[1], float_ref[2]
        at_south, at_north = eq(flag_ref[0], 1), eq(flag_ref[1], 1)

        def scalars(live, ey=0):
            """What the stages of one step read.  ``live``: a flag, or
            ``None`` for a step that always runs; where it is not set
            the step updates nothing: no row is a wall's, and the
            interior and round 1's rows are empty.  ``ey``: the rows
            further out than a single walk's that the step updates
            where no wall stands (``_stages`` has the columns)."""
            def row(x, where=None, otherwise=-1 - STRIP if ey else -1):
                """Row ``x`` as a scalar; ``otherwise`` (a row no strip
                has: the strip before the block's first is one, where
                the step reaches further) where ``where`` or ``live`` is
                given and not set."""
                flags = [flag for flag in (where, live) if flag is not None]
                if not flags:
                    return jnp.int32(x)
                return select(functools.reduce(lax.bitwise_and, flags),
                              jnp.int32(x), jnp.int32(otherwise))

            # the rows the walls single out, -1 where this device has no
            # wall: the southern wall's ghost row next to the interior,
            # and the last interior row, which is the northern wall's
            south_ghost_row = row(G - 1, at_south)
            north_wall_row = row(rows - G - 1, at_north)
            # the interior's rows, and round 1's rows of u and v: the
            # interior and ring 1, which is a neighbour's edge row
            # unless a wall stands there.  (A block with a strip of 8
            # rows has four interior rows or more, so a neighbour's edge
            # row is never its wall row too.)
            inner_from, inner_to = row(G, otherwise=0), row(rows - G, otherwise=0)
            reach_from = select(at_south, inner_from, row(G - 1 - ey, otherwise=0))
            reach_to = select(at_north, inner_to, row(rows - G + 1 + ey, otherwise=0))
            if ey:
                # h, and round 2 of u and v: on the two rings that the
                # walk's second step reads, where a neighbour holds them
                inner_from = select(at_south, inner_from, row(G - ey, otherwise=0))
                inner_to = select(at_north, inner_to, row(rows - G + ey, otherwise=0))
            return (a, b, first_row, south_ghost_row, north_wall_row,
                    inner_from, inner_to, reach_from, reach_to)

        # of a walk of two steps the first is passed over where `lone`,
        # and reaches as much further as the slabs do
        lives = [eq(flag_ref[2], 0)] * (steps - 1) + [None]
        reaches = [ey] * (steps - 1) + [0]
        return [(functools.partial(first, x), functools.partial(second, x))
                for (first, second), x in zip(
                    stages[2 - steps:], map(scalars, lives, reaches))]

    flags = [is_south, is_north] + [lone] * (steps == 2)
    return _walk(body, [jnp.stack(flags).astype(jnp.int32), floats], [h, u, v],
                 slabs[:3], [dh, du, dv], n_second=2 if nu > 0 else 0,
                 n_carried=4, steps=steps,
                 summed=(0, 1, 2) if coarsen else (), coarsen=coarsen,
                 summing=summing, sums=sums, point_slabs=slabs[3:],
                 in_place=in_place, interpret=interpret)


# what the adjoint walk's blocks take of VMEM, in a forward walk's
# fields of five buffers: nine arrays in (two blocks and a window) and
# six out (two blocks), 39 tiles
_ADJOINT_FIELDS = 8
# strips of rows an adjoint walk's stage writes at once, beside a strip
# of halo rows either side that it computes on and throws away
_ADJOINT_STRIPS = 2
# a row no block has, for a wall this device does not stand at: further
# from the block's rows than the stage's rows reach beyond them
_NO_ROW = -4 * STRIP


def adjoint_tile_rows(rows, width, dtype):
    """Rows of a tile of the adjoint walk (:func:`wide_step_vjp`) over
    its fifteen arrays, 0 where its blocks do not fit VMEM: the step's
    derivative is then its array code's."""
    return tile_rows(rows, width, dtype, _ADJOINT_FIELDS)


@functools.lru_cache
def _adjoint_stage(roll, rows, width, dtype, nu, dx, dy, dt, gravity,
                   coriolis_f, coriolis_beta):
    """The transpose of :func:`_stages`' ``second`` and ``first``, in
    that order, on rows taller than a strip: ``back(scalars, g, kept,
    cotangents)`` is handed the rows' numbers ``g`` (on one vector
    register's columns), the kept ``h``, ``u``, ``v`` with fresh ghosts
    and the six cotangents of a step's results on the same rows, and
    returns the six cotangents of what the step read (the fields after
    their exchange, the old tendencies).  A row's neighbours are
    rotations of the rows handed in, whose wrap spoils a row at either
    end for every shift in a chain: three rows, of the strip of halo
    rows the caller brings either side.  Jitted and kept like the
    stages.

    Forwards (``_stages``, whose names these are): ``fe``, ``fn``, ``q``
    and ``ke`` from the fields, the tendencies from those, ``x1 = x +
    dt (a T + b old)`` on the interior (``u``, ``v``: on ring 1 too),
    ``v1 = 0`` on the northern wall's row, friction.  Backwards: the
    friction's transpose gives the cotangent of ``u1``, ``v1``; ``g_*``
    is the tendencies' (``a dt`` of a field's on the rows the step
    updated, and the new tendency's own on the interior); from those
    the cotangents of the fluxes, the vorticity's quarter and the
    energy, and from those the fields'.  The step is nonlinear in the
    products ``q (fn + fn_e)``, ``q (fe + fe_n)``, ``hx u``, ``(h + h_n)
    v``, the squares and the division: ``fe``, ``fn``, ``q`` and the
    depth are made again from the kept fields, nothing else."""
    lanes = _whole_registers(width)
    inv_dx, inv_dy = 1.0 / dx, 1.0 / dy
    kx, ky = nu * dt / (dx * dx), nu * dt / (dy * dy)
    across = functools.partial(_across, lanes)
    box = functools.partial(_box, width)

    def back(scalars, g, kept, cotangents):
        (a, b, first_row, south_ghost_row, south_wall_row, north_wall_row,
         north_ghost_row, inner_from, inner_to, reach_from, reach_to,
         open_from, open_to) = scalars
        h, u, v = kept
        ch, cu, cv, cdh, cdu, cdv = cotangents
        tall = h.shape[0]
        zero = lax.full(h.shape, 0, dtype)

        def east(x):
            return roll(x, lanes - 1, 1)

        def west(x):
            return roll(x, 1, 1)

        def north(x):
            return roll(x, tall - 1, 0)

        def south(x):
            return roll(x, 1, 0)

        def only(where, x):
            return select(where, x, zero)

        def unless(where, x):
            return select(where, zero, x)

        interior = box(g, inner_from, inner_to, 0)
        # round 1's cells of u, v: ring 1 too where round 2 reads it;
        # and the rows no wall's ghost rows are among, every column
        reach = box(g, reach_from, reach_to, 1) if nu > 0 else interior
        open_rows = box(g, open_from, open_to, G) if nu > 0 else interior
        # what lies beyond the block (rows before its first in the walk's
        # first tile, rows past its last, lanes past its width) is
        # whatever VMEM held: the kept fields are set to rest there, so
        # that a cotangent of zero times what is made of them is zero
        inside = box(g, 0, rows, G)
        h = select(inside, h, lax.full(h.shape, 1, dtype))
        u, v = only(inside, u), only(inside, v)
        sg, sw = across(eq(g, south_ghost_row)), across(eq(g, south_wall_row))
        nw, ng = across(eq(g, north_wall_row)), across(eq(g, north_ghost_row))
        wall = lax.bitwise_or(nw, sg)

        # round 2 backwards: u' = u1 + dt L u1 on the interior, L the
        # five points' with no gradient across the southern wall's face;
        # v' zero on the northern wall's row
        cv = unless(nw, cv)
        if nu > 0:
            def friction(c):
                w = only(interior, c)
                wk = unless(sw, w)
                return add(c, add(
                    mul(add(sub(west(w), add(w, w)), east(w)), kx),
                    mul(add(sub(sub(south(w), w), wk), north(wk)), ky)))

            cu, cv = friction(cu), unless(nw, friction(cv))

        # round 1 backwards.  The tendencies' cotangents, and what the
        # old ones take
        a_dt, b_dt = mul(a, dt), mul(b, dt)
        g_h = only(interior, add(mul(ch, a_dt), cdh))
        g_u = only(reach, add(mul(cu, a_dt), only(interior, cdu)))
        g_v = only(reach, add(mul(cv, a_dt), only(interior, cdv)))
        # (with friction a result's ghost cell is a neighbour's u1, v1,
        # so its cotangent is b dt of that neighbour's old tendency's:
        # ring 1's is this walk's own, ring 2's only passes through here)
        old = (only(interior, mul(ch, b_dt)), only(open_rows, mul(cu, b_dt)),
               only(open_rows, mul(cv, b_dt)))

        # the step's products again, from the kept fields
        hx = add(h, east(h))
        hy = add(h, north(h))
        fe = unless(ng, mul(mul(hx, 0.5), u))
        fn = unless(wall, mul(mul(hy, 0.5), v))
        depth = div(lax.full(h.shape, 1, dtype),
                    add(hx, select(nw, hx, north(hx))))
        y = mul(add(lax.convert_element_type(sub(g, G), dtype), first_row), dy)
        vorticity = add(
            across(add(mul(y, coriolis_beta), coriolis_f)),
            sub(mul(sub(east(v), v), inv_dx), mul(sub(north(u), u), inv_dy)))
        q = unless(sg, mul(vorticity, depth))

        # du = .. + qf + qf_s - (ke_e - ke) / dx, qf = q (fn + fn_e);
        # dv = .. - qe - qe_w - (ke_n - ke) / dy, qe = q (fe + fe_n)
        c_qf = add(g_u, north(g_u))
        c_qe = add(g_v, east(g_v))  # less
        q_qf, q_qe = mul(q, c_qf), mul(q, c_qe)
        c_fe = sub(mul(sub(east(g_h), g_h), inv_dx), add(q_qe, south(q_qe)))
        c_fn = add(mul(sub(north(g_h), g_h), inv_dy), add(q_qf, west(q_qf)))
        c_ke = unless(ng, add(mul(sub(g_u, west(g_u)), inv_dx),
                              mul(sub(g_v, south(g_v)), inv_dy)))
        c_q = sub(mul(c_qf, add(fn, east(fn))), mul(c_qe, add(fe, north(fe))))
        c_vorticity = unless(sg, mul(c_q, depth))
        c_depth = mul(mul(c_vorticity, vorticity), depth)  # less
        c_fe, c_fn = unless(ng, mul(c_fe, 0.5)), unless(wall, mul(c_fn, 0.5))
        c_hx = sub(mul(u, c_fe), add(
            add(c_depth, only(nw, c_depth)), south(unless(nw, c_depth))))
        c_hy = mul(v, c_fn)
        c_h = add(
            add(add(c_hx, west(c_hx)), add(c_hy, south(c_hy))),
            add(mul(sub(g_u, west(g_u)), gravity * inv_dx),
                mul(sub(g_v, south(g_v)), gravity * inv_dy)))
        c_u = add(
            add(mul(sub(c_vorticity, south(c_vorticity)), inv_dy), mul(hx, c_fe)),
            mul(mul(u, 0.5), add(c_ke, east(c_ke))))
        c_v = add(
            add(mul(sub(west(c_vorticity), c_vorticity), inv_dx), mul(hy, c_fn)),
            mul(mul(v, 0.5), add(c_ke, north(c_ke))))
        return (add(ch, c_h), add(cu, c_u), add(cv, c_v), *old)

    return jax.jit(back)


@functools.lru_cache
def _tangent_stage(roll, rows, width, dtype, nu, dx, dy, dt, gravity,
                   coriolis_f, coriolis_beta):
    """The tangent of :func:`_stages`' ``first`` and ``second``, in that
    order, on rows taller than a strip, as :func:`_adjoint_stage` is
    their transpose: ``push(scalars, g, kept, tangents)`` is handed the
    rows' numbers, the kept ``h``, ``u``, ``v`` with fresh ghosts and
    the six tangents of what the step read (the fields' after their
    exchange, the old tendencies' with their ghost cells the
    neighbours'), and returns the six tangents of the step's results.
    A row's neighbours are rotations of the rows handed in, as there:
    two rows spoilt at either end, of the strip the caller brings.
    Jitted and kept like the stages.

    ``fe``, ``fn``, ``q`` and the depth are made again from the kept
    fields, as the adjoint stage makes them, and each product's tangent
    is taken beside it; the update, the northern wall's row and the
    friction are the step's own on the tangents, under the step's
    masks.  What lies beyond the block is whatever VMEM held, and
    reaches no cell that is updated: a row or a column reads the ring
    round it, a selection drops the rest."""
    lanes = _whole_registers(width)
    inv_dx, inv_dy = 1.0 / dx, 1.0 / dy
    kx, ky = nu * dt / (dx * dx), nu * dt / (dy * dy)
    across = functools.partial(_across, lanes)
    box = functools.partial(_box, width)

    def push(scalars, g, kept, tangents):
        (a, b, first_row, south_ghost_row, south_wall_row, north_wall_row,
         north_ghost_row, inner_from, inner_to, reach_from, reach_to,
         open_from, open_to) = scalars
        h, u, v = kept
        th, tu, tv, tdh, tdu, tdv = tangents
        tall = h.shape[0]
        zero = lax.full(h.shape, 0, dtype)

        def east(x):
            return roll(x, lanes - 1, 1)

        def west(x):
            return roll(x, 1, 1)

        def north(x):
            return roll(x, tall - 1, 0)

        def south(x):
            return roll(x, 1, 0)

        def only(where, x):
            return select(where, x, zero)

        def unless(where, x):
            return select(where, zero, x)

        # the adjoint stage's cells: the interior; round 1's of u, v
        # (ring 1 too where round 2 reads it); every cell of the rows no
        # wall's ghost rows are among, where a neighbour's old tendency
        # steps what the neighbour computes
        interior = box(g, inner_from, inner_to, 0)
        reach = box(g, reach_from, reach_to, 1) if nu > 0 else interior
        open_rows = box(g, open_from, open_to, G) if nu > 0 else interior
        sg, sw = across(eq(g, south_ghost_row)), across(eq(g, south_wall_row))
        nw, ng = across(eq(g, north_wall_row)), across(eq(g, north_ghost_row))
        wall = lax.bitwise_or(nw, sg)

        # the step's products again, from the kept fields
        hx = add(h, east(h))
        hy = add(h, north(h))
        fe = unless(ng, mul(mul(hx, 0.5), u))
        fn = unless(wall, mul(mul(hy, 0.5), v))
        depth = div(lax.full(h.shape, 1, dtype),
                    add(hx, select(nw, hx, north(hx))))
        y = mul(add(lax.convert_element_type(sub(g, G), dtype), first_row), dy)
        vorticity = add(
            across(add(mul(y, coriolis_beta), coriolis_f)),
            sub(mul(sub(east(v), v), inv_dx), mul(sub(north(u), u), inv_dy)))
        q = unless(sg, mul(vorticity, depth))

        # their tangents: of the fluxes, of the vorticity's quarter (a
        # quotient's: the depth's sum takes q of it), of the products
        # that du and dv take of those, of the energy
        thx = add(th, east(th))
        t_fe = unless(ng, mul(add(mul(thx, u), mul(hx, tu)), 0.5))
        t_fn = unless(wall, mul(
            add(mul(add(th, north(th)), v), mul(hy, tv)), 0.5))
        t_vorticity = sub(
            mul(sub(east(tv), tv), inv_dx), mul(sub(north(tu), tu), inv_dy))
        t_q = unless(sg, mul(
            sub(t_vorticity, mul(q, add(thx, select(nw, thx, north(thx))))),
            depth))
        t_qf = add(mul(t_q, add(fn, east(fn))), mul(q, add(t_fn, east(t_fn))))
        t_qe = add(mul(t_q, add(fe, north(fe))), mul(q, add(t_fe, north(t_fe))))
        ut, vt = mul(u, tu), mul(v, tv)
        t_ke = unless(ng, mul(add(add(ut, west(ut)), add(vt, south(vt))), 0.5))

        # the tendencies', as `first` makes the tendencies
        t_dh = sub(mul(sub(west(t_fe), t_fe), inv_dx),
                   mul(sub(t_fn, south(t_fn)), inv_dy))
        t_du = sub(
            add(mul(sub(east(th), th), -gravity * inv_dx), add(t_qf, south(t_qf))),
            mul(sub(east(t_ke), t_ke), inv_dx))
        t_dv = sub(
            sub(mul(sub(north(th), th), -gravity * inv_dy), add(t_qe, west(t_qe))),
            mul(sub(north(t_ke), t_ke), inv_dy))

        # x1 = x + dt (a T + b old): the new tendency on the cells this
        # walk computes, the old one wherever a neighbour's walk does
        a_dt, b_dt = mul(a, dt), mul(b, dt)
        th = add(th, only(interior, add(mul(t_dh, a_dt), mul(tdh, b_dt))))
        tu = add(tu, add(only(reach, mul(t_du, a_dt)),
                         only(open_rows, mul(tdu, b_dt))))
        tv = unless(nw, add(tv, add(only(reach, mul(t_dv, a_dt)),
                                    only(open_rows, mul(tdv, b_dt)))))
        if nu > 0:
            def friction(c):
                # the five points', no gradient across the southern
                # wall's face
                return add(c, only(interior, add(
                    mul(add(sub(east(c), add(c, c)), west(c)), kx),
                    mul(sub(sub(north(c), c), unless(sw, sub(c, south(c)))), ky))))

            tu, tv = friction(tu), unless(nw, friction(tv))
        return (th, tu, tv, only(interior, t_dh), only(interior, t_du),
                only(interior, t_dv))

    return jax.jit(push)


def _derivative_walk(stage, h, u, v, six, is_south, is_north, first_row, a, b,
                     constants, interpret):
    """The walk of a step's derivative, either mode's: the kept ``h``,
    ``u``, ``v`` and ``six`` arrays in, six out where the six lay, a
    tile behind ("The adjoint walk", "Tiling").  ``stage``: the builder
    of what maps a group of strips of the nine to the six
    (:func:`_adjoint_stage`, :func:`_tangent_stage`), handed
    ``constants`` after the block's shape and dtype."""
    pl, pltpu = pallas()
    rows, width = h.shape
    lanes = _whole_registers(width)
    dtype = h.dtype
    arrays = [h, u, v, *six]
    n_in, n_out = len(arrays), len(six)
    tile = adjoint_tile_rows(rows, width, dtype)
    tiles = -(-rows // tile)
    strips = tile // STRIP
    group = max(n for n in range(1, _ADJOINT_STRIPS + 1) if strips % n == 0)
    tall = (group + 2) * STRIP
    axes = vma_of(h) or ()
    flags = promote_vma(jnp.stack([is_south, is_north]).astype(jnp.int32), axes)
    floats = promote_vma(
        jnp.stack([jnp.asarray(x, dtype) for x in (a, b, first_row)]), axes)

    def kernel(flag_ref, float_ref, *refs):
        taken, out, windows = refs[:n_in], refs[n_in:n_in + n_out], refs[n_in + n_out:]
        i = pl.program_id(0)
        through = stage(pltpu.roll, rows, width, dtype, *constants)
        at_south, at_north = eq(flag_ref[0], 1), eq(flag_ref[1], 1)

        def row(x, where):
            return select(where, jnp.int32(x), jnp.int32(_NO_ROW))

        scalars = (
            float_ref[0], float_ref[1], float_ref[2],
            row(G - 1, at_south), row(G, at_south),
            row(rows - G - 1, at_north), row(rows - G, at_north),
            jnp.int32(G), jnp.int32(rows - G),
            select(at_south, jnp.int32(G), jnp.int32(G - 1)),
            select(at_north, jnp.int32(rows - G), jnp.int32(rows - G + 1)),
            select(at_south, jnp.int32(G), jnp.int32(0)),
            select(at_north, jnp.int32(rows - G), jnp.int32(rows)))
        r = lax.broadcasted_iota(jnp.int32, (tall, LANES), 0)

        # a window's rows: the last strip of tile i - 2, tile i - 1, and
        # the first strip of the tile just handed in (_walk's window)
        for ref, win in zip(taken, windows):
            win[pl.ds(tile + STRIP, STRIP), :] = ref[pl.ds(0, STRIP), :]

        @pl.when(lax.gt(i, 0))
        def _():
            def run(j, carry):
                first = pl.multiple_of(mul(j, group * STRIP), STRIP)
                # the rows' numbers in the block, from the halo strip on
                g = add(r, add(mul(sub(i, 1), tile), sub(first, STRIP)))
                values = [win[pl.ds(first, tall), :] for win in windows]
                new = through(scalars, g, values[:3], values[3:])
                for ref, x in zip(out, new):
                    ref[pl.ds(first, group * STRIP), :] = lax.slice_in_dim(
                        x, STRIP, tall - STRIP)
                return carry

            lax.fori_loop(0, strips // group, run, 0)

        for ref, win in zip(taken, windows):
            win[pl.ds(0, STRIP), :] = win[pl.ds(tile, STRIP), :]
            win[pl.ds(STRIP, tile), :] = ref[...]

    struct = union_vma_struct(h.shape, dtype, *arrays, flags, floats)
    in_smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    return pl.pallas_call(
        kernel,
        grid=(tiles + 1,),
        in_specs=[in_smem] * 2 + [pl.BlockSpec(
            (tile, lanes), lambda i: (lax.min(i, tiles - 1), 0))] * n_in,
        out_specs=[pl.BlockSpec(
            (tile, lanes), lambda i: (lax.max(i - 1, 0), 0))] * n_out,
        scratch_shapes=[pltpu.VMEM((tile + 2 * STRIP, lanes), dtype)] * n_in,
        out_shape=[struct] * n_out,
        # each of the six is written where it was read, a tile behind
        input_output_aliases={2 + 3 + k: k for k in range(n_out)},
        compiler_params=pltpu.CompilerParams(
            # in order: a step reads the window the step before left
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT * 3 // 2),
        interpret=interpret,
    )(flags, floats, *(promote_vma(x, axes) for x in arrays))


_DERIVATIVE_STATICS = ("nu", "dx", "dy", "dt", "gravity", "coriolis_f",
                       "coriolis_beta", "interpret")


@functools.partial(jax.jit, static_argnames=_DERIVATIVE_STATICS)
def wide_step_vjp(h, u, v, cotangents, is_south, is_north, first_row, a, b,
                  *, nu, dx, dy, dt, gravity, coriolis_f, coriolis_beta,
                  interpret=False):
    """The transpose of one step of :func:`wide_step` at the state it
    started from, as one kernel ("The adjoint walk" in the module's
    docstring).

    ``h``, ``u``, ``v``: the kept fields **with fresh ghosts**, as the
    step's first exchange left them.  ``cotangents``: of the step's six
    results, padded like them, **all taken as they are**.  The
    interior's go through both rounds backwards.  A ghost cell's passes
    through to the same cell of the field it came from (all of ``h``'s,
    a wall's ghost rows of ``u``, ``v``, and without friction all of
    theirs are the first exchange's values; the caller's transposed
    exchange takes them home).  With friction the ghost cells of the
    results ``u``, ``v`` are the neighbours' round 1: on ring 1, which
    this walk computes itself, the cotangent also goes backwards
    through round 1 here; on ring 2, which only the neighbour
    computes, it passes through to the field and, ``b dt`` of it, to the
    old tendency, and **the caller has sent** ``a dt`` **of it home on
    the new tendency's cotangent** before the call
    (``shallow_water._outermost_ring``), which is the one thing of a
    ghost cell that cannot be done here.  The ghost cells of the
    tendencies' cotangents are not read: a result's are the kernel's
    own means (ring 1) or zero.

    Returns the six cotangents of ``(h, u, v)`` **after their
    exchange**, ghost cells included, and of the old tendencies, which
    are ``b dt`` of a field's cotangent where the step updated it: on
    the ghost cells of ``du``, ``dv`` too, which are the neighbours' and
    go home with the fields' through the exchange's transpose.  The scalars are
    :func:`wide_step`'s; the caller has checked
    :func:`adjoint_tile_rows`.
    """
    return _derivative_walk(
        _adjoint_stage, h, u, v, cotangents, is_south, is_north, first_row, a, b,
        (nu, dx, dy, dt, gravity, coriolis_f, coriolis_beta), interpret)


@functools.partial(jax.jit, static_argnames=_DERIVATIVE_STATICS)
def wide_step_jvp(h, u, v, tangents, is_south, is_north, first_row, a, b,
                  *, nu, dx, dy, dt, gravity, coriolis_f, coriolis_beta,
                  interpret=False):
    """The tangent of one step of :func:`wide_step` at the state it
    started from, as one kernel ("The tangent walk" in the module's
    docstring): :func:`wide_step_vjp`'s transpose, cell for cell.

    ``h``, ``u``, ``v``: the kept fields **with fresh ghosts**, as the
    step's first exchange left them.  ``tangents``: of the six arrays
    the step read, padded like them: the fields' after the same
    exchange, the old tendencies' with ring 1 of ``du``, ``dv`` the
    neighbours' (an exchange's, or what a step before computed there:
    ``sw_kernels``, "Schedule"); ``dh``'s ghost cells are not read.

    Returns the six tangents of the step's results.  On the interior
    both rounds', and the new tendencies'.  On the ghost cells what the
    array code's results hold there, as far as one chip can say: all of
    ``h``'s, a wall's ghost rows of ``u``, ``v`` and without friction
    all of theirs pass through; with friction ring 1 of ``u``, ``v`` is
    round 1's tangent, computed here as the neighbour computes it, and
    ring 2, which only the neighbour computes, the field's tangent and
    ``b dt`` of the old tendency's, to which **the caller adds** ``a
    dt`` **of the neighbour's new tendency's** after the call (an
    exchange of the results ``du``, ``dv``:
    ``shallow_water._step_forwards``, where the state is read whole).
    The ghost cells of the tendencies' tangents come back zero.  The
    scalars are :func:`wide_step`'s; the caller has checked
    :func:`adjoint_tile_rows`.
    """
    return _derivative_walk(
        _tangent_stage, h, u, v, tangents, is_south, is_north, first_row, a, b,
        (nu, dx, dy, dt, gravity, coriolis_f, coriolis_beta), interpret)
