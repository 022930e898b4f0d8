"""The solver's Pallas kernel against the array code it replaces.

Everything here runs on the CPU backend, the kernel in Pallas's
interpret mode: it shows that the kernel computes what the array code's
two rounds compute with an exchange between them, at block shapes that
exercise the tiling's edges and on meshes where ring 1 is a neighbour's,
and that the step picks the kernel only where it can run.  That the
kernel compiles for the chip is ``tests/test_tpu_compile.py``'s to
show; how fast it is, only a chip run's (``PERF.md``).
"""

import functools
import os
import subprocess
import sys
import types
from dataclasses import dataclass, replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mpi4jax_tpu as m
from mpi4jax_tpu.analysis import verify_comm
from mpi4jax_tpu.models import shallow_water as sw
from mpi4jax_tpu.models import sw_kernels

G = 2

# rows x width of one device's padded block, and the VMEM budget the
# tiling is given (None: its own): 52 rows leave a last tile of 4 under
# tiles of 48; 184 x 364 is the demo grid's block, one tile; 21 rows are
# no multiple of 8; the small budgets cut 100 rows into tiles of 8, 24;
# 256 columns fill their vector registers, so that a rotation's wrap
# lands in the ghost columns and not past them; 33 rows of 129 columns
# have their northern ghost rows in two tiles and their eastern ghost
# columns in two vector registers
SHAPES = {
    "aligned-36x256": (36, 256, None),
    "astride-33x129": (33, 129, None),
    "ragged-52x100": (52, 100, None),
    "demo-184x364": (184, 364, None),
    "odd-21x40": (21, 40, None),
    "tiles-of-8-100x140": (100, 140, 8 * 10 * 1024),
    "tiles-of-24-100x140": (100, 140, 24 * 10 * 1024),
}
WALLS = {"south": (True, False), "north": (False, True),
         "both": (True, True), "neither": (False, False)}
# round 1 in units of its own, so that every term of every tendency is
# of order one and float32's roundoff of order 1e-7: a rotation that
# changes by half from the first row to the last of the tallest block
UNIT = dict(dx=1.0, dy=0.8, gravity=1.0, depth=1.0, coriolis_f=1.0,
            coriolis_beta=4e-3, ghost=G)


def _budget(monkeypatch, shape, steps=1):
    """``SHAPES[shape]`` with its VMEM budget in place, scaled to the
    call's six arrays so that the tiles are the name's, of a walk of two
    ``steps`` as of one."""
    rows, width, budget = SHAPES[shape]
    if budget is not None:
        # the budget is no argument of the jitted call: a trace under
        # another budget, of the same shapes, would be taken for this one's
        sw_kernels.wide_step.clear_cache()
        monkeypatch.setattr(sw_kernels, "_VMEM_BLOCK_BUDGET", budget * 3)
        tile = sw_kernels.tile_rows(rows, width, jnp.float32, 6, steps)
        assert tile == int(shape.split("-")[2]) and rows > 3 * tile
    return rows, width


def _ring(shape, ring):
    """The cells of a padded block's ghost ring ``ring`` (2: outermost)."""
    inside = np.zeros(shape, bool)
    inside[G - ring:shape[0] - G + ring, G - ring:shape[1] - G + ring] = True
    inside[G - ring + 1:shape[0] - G + ring - 1,
           G - ring + 1:shape[1] - G + ring - 1] = False
    return inside


@dataclass(frozen=True)
class _Viscous(sw.SWConfig):
    """A configuration whose friction is set apart from its rotation:
    round 1 in ``UNIT`` with a friction strong enough to see (``dt * nu
    / dx**2`` is 0.02, not the 1e-4 of the unit rotation's own), so that
    an error in a stencil or a mask of either round is four orders of
    magnitude over float32's roundoff."""

    nu: float = 0.0

    @property
    def lateral_viscosity(self):
        return self.nu


def _as_a_step_finds_it(fresh, south, north, stale):
    """A block with fresh ghosts as the step's kernel is handed it: its
    ghost cells ``stale`` wherever a slab brings them, and the four
    slabs an exchange would bring, west, east, south, north.  Beyond a
    wall no neighbour sends: that slab is ``None``, as on a mesh one
    device high, and those ghost rows are the block's own but for their
    ends, which the x slabs bring."""
    fresh = np.asarray(fresh)
    block = fresh.copy()
    block[:, :G] = block[:, -G:] = stale
    if not south:
        block[:G] = stale
    if not north:
        block[-G:] = stale
    slabs = (fresh[:, :G], fresh[:, -G:],
             None if south else fresh[:G], None if north else fresh[-G:])
    return block, slabs


def _interpreted(cfg):
    """The kernel's keywords for ``cfg``, in Pallas's interpret mode."""
    return dict(
        nu=cfg.nu, dx=cfg.dx, dy=cfg.dy, dt=cfg.dt, gravity=cfg.gravity,
        coriolis_f=cfg.coriolis_f, coriolis_beta=cfg.coriolis_beta,
        interpret=True)


@functools.lru_cache
def _definition(cfg, first_step, south, north):
    """The array code of ``sw._step_wide`` after its first exchange on
    one device's block of ``cfg.ny + 4`` x ``cfg.nx + 4``, which it asks
    its mesh the place of.  Round 1 runs on a block **one ring larger**
    wherever no wall stands (a row more on a side without a wall, a
    column more on either side), whose interior is the block's interior
    and ring 1: there ring 1 is fresh, as the second exchange would
    make it.  Round 2 runs on that result cut back to the block.
    Returns the block's ``h``, ``u``, ``v`` after both rounds and the
    tendencies at the larger interior's shape."""
    mesh = jax.make_mesh(
        (1, 1), ("y", "x"), axis_types=(jax.sharding.AxisType.Auto,) * 2)
    comm = m.MeshComm.from_mesh(mesh)
    below, above = int(not south), int(not north)
    larger = replace(cfg, ny=cfg.ny + below + above, nx=cfg.nx + 2)
    walls = jnp.bool_(south), jnp.bool_(north)

    def rounds(h, u, v, dh, du, dv):
        h, u, v, dh, du, dv = sw._tendency_round(
            h, u, v, dh, du, dv, larger, comm, *walls, first_step)
        h, u, v = (x[below:x.shape[0] - above, 1:-1] for x in (h, u, v))
        if cfg.nu > 0:
            u, v = sw._viscosity_round(u, v, cfg, *walls)
        return h, u, v, dh, du, dv

    block = jax.P("y", "x")
    return jax.jit(jax.shard_map(
        rounds, mesh=mesh, in_specs=(block,) * 6, out_specs=(block,) * 6))


@pytest.mark.parametrize("nu", [0.2, 0.0], ids=["nu", "nu0"])
@pytest.mark.parametrize("first_step", [False, True], ids=["ab2", "euler"])
@pytest.mark.parametrize("walls", sorted(WALLS))
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_the_kernel_matches_the_array_code(
        shape, walls, first_step, nu, monkeypatch):
    rows, width = _budget(monkeypatch, shape)
    south, north = WALLS[walls]
    cfg = _Viscous(ny=rows - 2 * G, nx=width - 2 * G, nu=nu, **UNIT)
    below, above = int(not south), int(not north)
    # the larger block, and where the kernel's lies in it
    big = (rows + below + above, width + 2)
    cut = (slice(below, below + rows), slice(1, 1 + width))
    keys = jax.random.split(jax.random.PRNGKey(1), 6)
    fields = [
        mean + spread * jax.random.normal(key, big, jnp.float32)
        for key, mean, spread in zip(keys, (1.0, 0.0, 0.0), (0.1, 0.5, 0.5))]
    old = [0.5 * jax.random.normal(
        key, (big[0] - 2 * G, big[1] - 2 * G), jnp.float32)
        for key in keys[3:]]
    want = [np.asarray(x) for x in _definition(
        cfg, first_step, south, north)(*fields, *old)]

    ring1, ring2 = _ring((rows, width), 1), _ring((rows, width), 2)
    inner = ~(ring1 | ring2)
    # ring 1 beyond a wall is no neighbour's: nothing there is touched
    beyond = np.zeros((rows, width), bool)
    beyond[G - 1], beyond[rows - G] = south, north
    beyond &= ring1
    fresh = ring1 & ~beyond

    def padded(x, name):
        """A tendency of the larger block's interior at the kernel's
        block's shape: zero on ring 2, beyond a wall and, dh's, on
        ring 1."""
        x = np.pad(np.asarray(x), G)[cut]
        return np.where(inner | (fresh if name != "dh" else False), x, 0)

    names = ("dh", "du", "dv")
    if first_step:
        a, b, mine = 1.0, 0.0, [np.zeros((rows, width), np.float32)] * 3
    else:
        a, b = cfg.ab_a, cfg.ab_b
        mine = [padded(x, name) for x, name in zip(old, names)]
    # the kernel starts from ghosts that would wreck every stencil next
    # to them, and from the slabs that hold the fresh ones
    blocks, slabs = zip(*(
        _as_a_step_finds_it(x[cut], south, north, 1e3) for x in fields))
    got = sw_kernels.wide_step(
        *blocks, *mine, slabs, jnp.bool_(south), jnp.bool_(north),
        below, a, b, **_interpreted(cfg))
    got = [np.asarray(x) for x in got]

    before = [np.asarray(x[cut]) for x in fields]
    round1 = want[:3] if not nu else [np.asarray(x) for x in _definition(
        replace(cfg, nu=0.0), first_step, south, north)(*fields, *old)[:3]]
    for name, x0, x1, x2, x in zip("huv", before, round1, want, got):
        # what the kernel steps: the interior, and ring 1 of u and v
        # where it is a neighbour's
        stepped = inner | (fresh if name != "h" else False)
        # the rounds did something there, and the kernel did the same
        assert np.abs(x1 - x0)[inner].max() > 0.1, name
        if name != "h":
            assert np.abs(x1 - x0)[fresh].max() > 0.05, name
            assert (np.abs(x2 - x1)[inner].max() > 0.01) == (nu > 0), name
        np.testing.assert_allclose(
            x[stepped], x2[stepped], rtol=0, atol=2e-6, err_msg=name)
        # the rest goes through, bit for bit (the wall condition zeroes
        # its row from end to end, as the array code's)
        still = ~stepped
        if name == "v":
            still[-(G + 1)] = False
        np.testing.assert_array_equal(x[still], x0[still], err_msg=name)
    # the new tendencies at the fields' shape: du's and dv's ring 1 the
    # neighbour's, the rest of the ghost ring zero
    for name, x, x1 in zip(names, got[3:], want[3:]):
        x1 = padded(x1, name)
        kept = inner | (fresh if name != "dh" else False)
        assert min(np.abs(x1[zone]).max()
                   for zone in (inner, kept & ring1) if zone.any()) > 0.5, name
        np.testing.assert_allclose(x, x1, rtol=0, atol=2e-6, err_msg=name)
        assert not x[~kept].any(), name
    wall_row = got[2][-(G + 1)]
    assert (wall_row == 0).all() == north


@pytest.mark.parametrize("nu", [0.2, 0.0], ids=["nu", "nu0"])
@pytest.mark.parametrize("walls", sorted(WALLS))
@pytest.mark.parametrize("shape", ["astride-33x129", "tiles-of-8-100x140"])
def test_the_kernel_reads_no_ghost_the_slabs_did_not_bring(
        shape, walls, nu, monkeypatch):
    """The incoming ghost ring is NaN wherever a slab brings the cell:
    the kernel returns, bit for bit, what it returns from fresh ghosts
    and no slabs, so it read none of them (a NaN would have gone
    through a sum, a product or a selection's untaken side into a
    neighbour) and it leaves none behind.  Beyond a wall nothing is
    brought, and the ghost rows stay the block's own."""
    rows, width = _budget(monkeypatch, shape)
    south, north = WALLS[walls]
    cfg = _Viscous(ny=rows - 2 * G, nx=width - 2 * G, nu=nu, **UNIT)
    keys = jax.random.split(jax.random.PRNGKey(2), 6)
    fields = [
        mean + spread * jax.random.normal(key, (rows, width), jnp.float32)
        for key, mean, spread in zip(keys, (1.0, 0.0, 0.0), (0.1, 0.5, 0.5))]
    inner = ~(_ring((rows, width), 1) | _ring((rows, width), 2))
    old = [jnp.where(inner, 0.5 * jax.random.normal(key, (rows, width)), 0)
           for key in keys[3:]]

    def step(fields, slabs):
        return [np.asarray(x) for x in sw_kernels.wide_step(
            *fields, *old, slabs, jnp.bool_(south), jnp.bool_(north), 0,
            cfg.ab_a, cfg.ab_b, **_interpreted(cfg))]

    want = step(fields, ((None,) * 4,) * 3)
    blocks, slabs = zip(*(
        _as_a_step_finds_it(x, south, north, np.nan) for x in fields))
    assert all(np.isnan(x[:, 0]).all() and np.isnan(x[G:-G, -1]).all()
               and np.isnan(x[0, G:-G]).all() != south
               and np.isnan(x[-1, G:-G]).all() != north for x in blocks)
    got = step(blocks, slabs)
    for name, a, b in zip(sw.SWState._fields, got, want):
        assert np.isfinite(b).all(), name
        np.testing.assert_array_equal(a, b, err_msg=name)


@pytest.mark.parametrize("nu", [0.2, 0.0], ids=["nu", "nu0"])
@pytest.mark.parametrize("start", ["ab2", "euler"])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_a_walk_of_two_steps_is_two_walks_of_one_bit_for_bit(
        shape, start, nu, monkeypatch):
    """On one device (walls on both sides, a row's ghost columns its own
    other end) ``wide_step(steps=2)`` returns, bit for bit and on the
    whole padded block of all six arrays, what two calls return with the
    exchange between them that ``_step_wide`` makes there: the same
    operations on the same values in the same order, the first step's
    results never in HBM.  A pair in the middle of a run, and one that
    starts from forward Euler's tendencies (what a run's second and
    third steps read), with the Euler step itself both ways."""
    rows, width = _budget(monkeypatch, shape, steps=2)
    cfg = _Viscous(ny=rows - 2 * G, nx=width - 2 * G, nu=nu, **UNIT)
    keys = jax.random.split(jax.random.PRNGKey(3), 6)
    fields = [
        mean + spread * jax.random.normal(key, (rows, width), jnp.float32)
        for key, mean, spread in zip(keys, (1.0, 0.0, 0.0), (0.1, 0.5, 0.5))]
    inner = ~(_ring((rows, width), 1) | _ring((rows, width), 2))
    old = [jnp.where(inner, 0.5 * jax.random.normal(key, (rows, width)), 0)
           for key in keys[3:]]
    wall = jnp.bool_(True)

    def walk(state, steps, a=cfg.ab_a, b=cfg.ab_b, lone=False):
        # what halo_slabs_2d hands the kernel on a mesh of one device:
        # in x the block's own columns, in y nothing
        slabs = tuple((x[:, -2 * G:-G], x[:, G:2 * G], None, None)
                      for x in state[:3])
        return sw_kernels.wide_step(
            *state, slabs, wall, wall, 0, a, b, lone, steps=steps,
            **_interpreted(cfg))

    def one_by_one(state):
        return walk(walk(state, 1), 1)

    def at_once(state):
        return walk(state, 2)

    # unoptimised: the CPU backend contracts a product and a sum into one
    # rounding in one program and not in another (a single walk's results
    # differ in their last bit between two tilings of one block), and
    # this compares programs, not roundings
    plain = {"xla_backend_optimization_level": 0}
    state = [*fields, *old]
    if start == "euler":
        # a run's first step, as a walk of one step and as `lone`, the
        # walk of two with its first passed over, which is how a run on
        # one device makes it: the same block, bit for bit
        rest = [*fields, *(jnp.zeros_like(x) for x in old)]
        state = jax.jit(
            lambda rest: walk(rest, 1, 1.0, 0.0), compiler_options=plain)(rest)
        alone = jax.jit(
            lambda rest: walk(rest, 2, 1.0, 0.0, lone=True),
            compiler_options=plain)(rest)
        for name, x0, a, b in zip(sw.SWState._fields, rest, alone, state):
            assert np.abs(np.asarray(b) - x0)[inner].max() > 0.001, name
            np.testing.assert_array_equal(a, b, err_msg=name)
    want = jax.jit(one_by_one, compiler_options=plain)(state)
    got = jax.jit(at_once, compiler_options=plain)(state)
    for name, x0, a, b in zip(sw.SWState._fields, state, got, want):
        x0, a, b = np.asarray(x0), np.asarray(a), np.asarray(b)
        assert np.isfinite(b).all(), name
        # two steps did something everywhere they should
        assert np.abs(b - x0)[inner].max() > 0.01, name
        np.testing.assert_array_equal(a, b, err_msg=name)
    # the ghost columns are the row's other end as the first step left
    # it: the second step's exchange, which nothing outside the kernel made
    between = jax.jit(lambda state: walk(state, 1), compiler_options=plain)(state)
    np.testing.assert_array_equal(
        np.asarray(got[0])[:, :G], np.asarray(between[0])[:, -2 * G:-G])


# rows x width whose interior rows divide by `coarsen`, for the walk that
# writes its row sums: the widths above that do (ghost columns inside
# their registers and in a register that they fill), tiles of one strip
# (every group astride two) and of three (a block of sums four tiles
# long), and 129 columns, whose eastern ghost columns lie astride two
# vector registers, on 36 rows
SUMMED = [
    ("astride-36x129", 2), ("astride-36x129", 4), ("astride-36x129", 8),
    ("tiles-of-8-100x140", 2), ("tiles-of-8-100x140", 4),
    ("tiles-of-8-100x140", 8), ("tiles-of-24-100x140", 4),
    ("tiles-of-24-100x140", 8), ("ragged-52x100", 4), ("aligned-36x256", 8),
]


def _rows_summed(field, coarsen):
    """The sums over ``coarsen`` rows of the interior rows of a padded
    block, whole width, in the kernel's order of additions: neighbours
    first, then neighbouring pairs, then fours."""
    rows = np.asarray(field)[G:-G]
    parts = [rows[k::coarsen] for k in range(coarsen)]
    while len(parts) > 1:
        parts = [a + b for a, b in zip(parts[::2], parts[1::2])]
    return parts[0]


@pytest.mark.parametrize("nu", [0.2, 0.0], ids=["nu", "nu0"])
@pytest.mark.parametrize("walk", ["one", "two", "lone"])
@pytest.mark.parametrize("shape,coarsen", SUMMED, ids=lambda x: str(x))
def test_a_walk_that_writes_its_row_sums_returns_the_plain_walks_state(
        shape, coarsen, walk, nu, monkeypatch):
    """``wide_step(coarsen=c, sums=room)``: the six arrays of the state
    bit for bit, ghosts and all, what the walk without returns (with
    the sums switched off by ``summing`` too), and after them, written
    into the room the caller brought (of ``row_sums_shape``, the same
    for a walk of one step and of two),
    the sums over ``c`` rows of the new ``h``, ``u``, ``v``: row ``1 +
    m`` of a field's sums is, exactly, the sum in the kernel's order of
    the block's rows ``2 + c m`` on, ghost columns included; the rows
    before and after are nobody's."""
    if shape == "astride-36x129":
        monkeypatch.setitem(SHAPES, shape, (36, 129, None))
    steps = 1 if walk == "one" else 2
    rows, width = _budget(monkeypatch, shape, steps=steps)
    cfg = _Viscous(ny=rows - 2 * G, nx=width - 2 * G, nu=nu, **UNIT)
    keys = jax.random.split(jax.random.PRNGKey(4), 6)
    fields = [
        mean + spread * jax.random.normal(key, (rows, width), jnp.float32)
        for key, mean, spread in zip(keys, (1.0, 0.0, 0.0), (0.1, 0.5, 0.5))]
    inner = ~(_ring((rows, width), 1) | _ring((rows, width), 2))
    old = [jnp.where(inner, 0.5 * jax.random.normal(key, (rows, width)), 0)
           for key in keys[3:]]
    wall = jnp.bool_(True)
    assert (sw_kernels.tile_rows(rows, width, jnp.float32, 6, 1)
            == sw_kernels.tile_rows(rows, width, jnp.float32, 6, 2))

    def walked(coarsen, summing=True):
        def run(*state):
            slabs = tuple((x[:, -2 * G:-G], x[:, G:2 * G], None, None)
                          for x in state[:3])
            # the room: whatever it holds, here something no sum is
            room = coarsen and [jnp.full(sw_kernels.row_sums_shape(
                (rows, width), jnp.float32, coarsen), jnp.nan)] * 3
            return sw_kernels.wide_step(
                *state, slabs, wall, wall, 0, cfg.ab_a, cfg.ab_b, walk == "lone",
                summing, room or (), steps=steps, coarsen=coarsen, **_interpreted(cfg))

        # unoptimised, as two programs are compared bit for bit
        return [np.asarray(x) for x in jax.jit(run, compiler_options={
            "xla_backend_optimization_level": 0})(*fields, *old)]

    want, got = walked(0), walked(coarsen)
    assert len(want) == 6 and len(got) == 9
    for name, x0, a, b in zip(sw.SWState._fields, [*fields, *old], got, want):
        assert np.abs(b - np.asarray(x0))[inner].max() > 1e-3, name
        np.testing.assert_array_equal(a, b, err_msg=name)
    groups = (rows - 2 * G) // coarsen
    for name, x, sums in zip("huv", got[:3], got[6:]):
        assert sums.shape == sw_kernels.row_sums_shape(
            (rows, width), jnp.float32, coarsen), name
        assert sums.shape[1] == width and sums.shape[0] >= groups + 1, name
        np.testing.assert_array_equal(
            sums[1:1 + groups], _rows_summed(x, coarsen), err_msg=name)
    if walk == "two" and nu:
        # the same kernel with its sums switched off, as the walks of a
        # call's loop run it: the state again, the sums nobody's
        for name, a, b in zip(sw.SWState._fields, walked(coarsen, False), want):
            np.testing.assert_array_equal(a, b, err_msg=name)


@pytest.mark.parametrize("platform,ghost,snapshot,rows,width,expected", [
    ("tpu", 2, sw.Snapshot(coarsen=4), 7204, 14404, True),
    ("tpu", 2, sw.Snapshot(coarsen=2), 184, 364, True),
    ("tpu", 2, sw.Snapshot(coarsen=8), 7204, 14404, True),
    ("tpu", 2, sw.Snapshot(fields=("v", "h", "u"), coarsen=4), 7204, 14404, True),
    ("tpu", 2, None, 7204, 14404, False),               # a job without output
    ("tpu", 2, sw.Snapshot(coarsen=1), 7204, 14404, False),  # the field as it is
    ("tpu", 2, sw.Snapshot(coarsen=3), 7204, 14404, False),  # no divisor of a strip
    ("tpu", 2, sw.Snapshot(coarsen=16), 7204, 14404, False),
    ("tpu", 2, sw.Snapshot(coarsen=8), 184, 364, False),     # nor of the block
    ("tpu", 2, sw.Snapshot(fields=("h",), coarsen=4), 7204, 14404, False),
    ("tpu", 2, sw.Snapshot(fields=("h", "u", "dv"), coarsen=4), 7204, 14404, False),
    ("cpu", 2, sw.Snapshot(coarsen=4), 7204, 14404, False),  # the array code
    ("tpu", 4, sw.Snapshot(coarsen=4), 7208, 14408, False),
    ("tpu", 1, sw.Snapshot(coarsen=4), 7202, 14402, False),
], ids=lambda x: str(x))
def test_the_last_walk_sums_rows_where_the_step_is_the_kernel_and_the_blocks_fit(
        platform, ghost, snapshot, rows, width, expected):
    """The rule of ``make_multistep(snapshot=)`` and ``make_snapshot``:
    from the configuration, the devices and the job's ``Snapshot``."""
    cfg = sw.SWConfig(ny=rows - 2 * ghost, nx=width - 2 * ghost, ghost=ghost)
    assert sw._sums_in_step(cfg, _comm_on(platform), snapshot) is expected
    if rows > 7200:  # a quarter of that domain a device: the same answer
        assert sw._sums_in_step(cfg, _comm_on(platform, (2, 2)), snapshot) is expected


def _as_first_written(cfg, g, col, south, north, fields, old, a, b):
    """One step of the kernel by the formulae its stages had before each
    row's faces and corners were made once (PR 47): every value of the
    row north and of the row south evaluated again from ``(c, n, s)``,
    the halves where they stood, on the whole block at once (``g``,
    ``col``: each cell's row and column).  Plain ``jax.numpy``; a
    shift's wrap lands in the outermost ghost ring, which no mask
    admits."""
    dtype = jnp.float32
    inv_dx, inv_dy = 1.0 / cfg.dx, 1.0 / cfg.dy
    cx, cy = cfg.nu / cfg.dx, cfg.nu / cfg.dy
    rows, width = g.shape
    south_ghost_row = jnp.where(south, G - 1, -1)
    north_wall_row = jnp.where(north, rows - G - 1, -1)
    reach_from = jnp.where(south, G, G - 1)
    reach_to = jnp.where(north, rows - G, rows - G + 1)

    def box(row_from, row_to, ring):
        return ((g >= row_from) & (g < row_to)
                & (col >= G - ring) & (col < width - G + ring))

    def around(x):
        return x, jnp.roll(x, -1, 0), jnp.roll(x, 1, 0)

    def east(x):
        return jnp.roll(x, -1, 1)

    def west(x):
        return jnp.roll(x, 1, 1)

    def half(x):
        return x * dtype(0.5)

    (h, h_n, h_s), (u, u_n, u_s), (v, v_n, v_s) = map(around, fields)
    zero = jnp.zeros((rows, width), dtype)
    interior, reach = box(G, rows - G, 0), box(reach_from, reach_to, 1)
    at_north, at_south = g == north_wall_row, g - 1 == south_ghost_row

    def unless(wall, x):
        return jnp.where(wall, zero, x)

    h_e = east(h)
    hx, hx_n, hx_s = h + h_e, h_n + east(h_n), h_s + east(h_s)
    fe = half(hx) * u
    fe_n = unless(at_north, half(hx_n) * u_n)
    fn = unless(at_north, half(h + h_n) * v)
    fn_s = unless(at_south, half(h_s + h) * v_s)

    def vorticity(row, v, v_e, u_n, u, depth4):
        y = (row.astype(dtype) + dtype(0)) * dtype(cfg.dy)
        planetary = y * dtype(cfg.coriolis_beta) + dtype(cfg.coriolis_f)
        relative = (v_e - v) * dtype(inv_dx) - (u_n - u) * dtype(inv_dy)
        return (planetary + relative) / (depth4 * dtype(0.25))

    q = vorticity(g - G, v, east(v), u_n, u, hx + jnp.where(at_north, hx, hx_n))
    q_s = unless(at_south, vorticity(
        g - (G + 1), v_s, east(v_s), u, u_s, hx_s + hx))
    uu, vv, uu_n = u * u, v * v, u_n * u_n
    ke = half(half(uu + west(uu)) + half(vv + v_s * v_s))
    ke_n = unless(at_north, half(half(uu_n + west(uu_n)) + half(v_n * v_n + vv)))
    fe_w = west(fe)
    dh_new = (fe_w - fe) * dtype(inv_dx) - (fn - fn_s) * dtype(inv_dy)
    du_new = (((h_e - h) * dtype(-cfg.gravity * inv_dx)
               + half(q * half(fn + east(fn)) + q_s * half(fn_s + east(fn_s))))
              - (east(ke) - ke) * dtype(inv_dx))
    dv_new = (((h_n - h) * dtype(-cfg.gravity * inv_dy)
               - half(q * half(fe + fe_n) + west(q) * half(fe_w + west(fe_n))))
              - (ke_n - ke) * dtype(inv_dy))

    def stepped(where, x, new, old):
        new = jnp.where(where, new, zero)
        inc = jnp.where(
            where, (new * dtype(a) + old * dtype(b)) * dtype(cfg.dt), zero)
        return x + inc, new

    h, dh_new = stepped(interior, h, dh_new, old[0])
    u, du_new = stepped(reach, u, du_new, old[1])
    v, dv_new = stepped(reach, v, dv_new, old[2])
    v = unless(at_north, v)
    if cfg.nu > 0:
        def friction(x):
            c, n, s = around(x)
            e, w = east(c), west(c)
            gx, gx_w = (e - c) * dtype(cx), (c - w) * dtype(cx)
            gy = (n - c) * dtype(cy)
            gy_s = jnp.where(at_south, zero, (c - s) * dtype(cy))
            inc = ((gx - gx_w) * dtype(inv_dx)
                   + (gy - gy_s) * dtype(inv_dy)) * dtype(cfg.dt)
            return c + jnp.where(interior, inc, zero)

        u, v = friction(u), unless(at_north, friction(v))
    return h, u, v, dh_new, du_new, dv_new


@pytest.mark.parametrize("nu", [0.2, 0.0], ids=["nu", "nu0"])
@pytest.mark.parametrize("walls", ["both", "neither"])
@pytest.mark.parametrize("shape", ["astride-33x129", "ragged-52x100"])
def test_a_rows_values_made_once_are_the_values_made_twice_bit_for_bit(
        shape, walls, nu, monkeypatch):
    """The stages make what lives on a face or a corner once a row and
    take the neighbouring row's from the strip before or by a rotation
    of sublanes, with the powers of two folded: the same roundings of
    the same values as the formulae that evaluated each neighbour again,
    so the whole padded block of all six arrays comes back bit for bit
    (where a zeroed row's value differs, nothing is updated from it)."""
    rows, width = _budget(monkeypatch, shape)
    south, north = WALLS[walls]
    cfg = _Viscous(ny=rows - 2 * G, nx=width - 2 * G, nu=nu, **UNIT)
    keys = jax.random.split(jax.random.PRNGKey(4), 6)
    fields = [
        mean + spread * jax.random.normal(key, (rows, width), jnp.float32)
        for key, mean, spread in zip(keys, (1.0, 0.0, 0.0), (0.1, 0.5, 0.5))]
    inner = ~(_ring((rows, width), 1) | _ring((rows, width), 2))
    old = [jnp.where(inner, 0.5 * jax.random.normal(key, (rows, width)), 0)
           for key in keys[3:]]
    a, b = cfg.ab_a, cfg.ab_b
    plain = {"xla_backend_optimization_level": 0}  # as the walks' test
    # the block's rows, columns and walls are the programs' arguments,
    # so that neither folds a mask away and rounds otherwise for it
    g, col = jnp.indices((rows, width), dtype=jnp.int32)
    want = jax.jit(
        functools.partial(_as_first_written, cfg), static_argnums=(6, 7),
        compiler_options=plain)(g, col, south, north, fields, old, a, b)
    got = jax.jit(
        lambda fields, old, south, north: sw_kernels.wide_step(
            *fields, *old, ((None,) * 4,) * 3, south, north, 0, a, b,
            **_interpreted(cfg)),
        compiler_options=plain)(fields, old, jnp.bool_(south), jnp.bool_(north))
    for name, x0, x, y in zip(sw.SWState._fields, [*fields, *old], got, want):
        x0, x, y = np.asarray(x0), np.asarray(x), np.asarray(y)
        assert np.isfinite(y).all(), name
        assert np.abs(y - x0)[inner].max() > 0.01, name
        np.testing.assert_array_equal(x, y, err_msg=name)


def _vector_primitives(jaxpr, shape):
    """The equations of ``jaxpr`` (those of its inner jaxprs with them)
    that compute a value of ``shape``, counted by primitive; laying one
    register's value across a strip computes nothing and is left out."""
    found = {}
    for eqn in jaxpr.eqns:
        inner = [x.jaxpr for x in eqn.params.values() if hasattr(x, "jaxpr")]
        for sub in inner:
            for name, n in _vector_primitives(sub, shape).items():
                found[name] = found.get(name, 0) + n
        name = eqn.primitive.name
        if not inner and name not in ("concatenate", "broadcast_in_dim") and any(
                getattr(x.aval, "shape", None) == shape for x in eqn.outvars):
            found[name] = found.get(name, 0) + 1
    return found


def test_a_strips_stages_make_no_value_twice():
    """The vector work of one strip's two stages at the benchmark cells'
    width, counted on the CPU from their jaxprs: operations on a whole
    strip of 113 vector registers.  200 before PR 47 (round 1 151: two
    divisions, 13 lane rotations, 54 products; round 2 49), when every
    face's and corner's value was made for its own row and again for the
    neighbour's, every mask on the whole strip and every factor of a
    half where it stood.  An edit that puts a second evaluation back
    moves these numbers: say why with the new ones."""
    _, pltpu = sw_kernels.pallas()
    width = 14404
    lanes = sw_kernels._whole_registers(width)
    first, second = sw_kernels._stages(
        pltpu.roll, 7204, width, jnp.dtype(jnp.float32), 0.2, 1250.0, 1250.0,
        2.0, 9.81, 2e-4, 2e-11)
    strip = jax.ShapeDtypeStruct((sw_kernels.STRIP, lanes), jnp.float32)
    g = jax.ShapeDtypeStruct((sw_kernels.STRIP, sw_kernels.LANES), jnp.int32)
    scalars = (*(jnp.float32(x) for x in (1.6, -0.6, 0.0)),
               *(jnp.int32(x) for x in (1, 7201, 2, 7202, 2, 7202)))
    round1 = jax.make_jaxpr(
        lambda g, *x: first(scalars, g, [x[0:2], x[2:4], x[4:6]], x[6:9], x[9:]))(
            g, *[strip] * 13)
    round2 = jax.make_jaxpr(
        lambda g, *x: second(scalars, g, [x[0:3], x[3:6]]))(g, *[strip] * 6)
    round1, round2 = (
        _vector_primitives(x.jaxpr, strip.shape) for x in (round1, round2))
    assert round1 == {"add": 19, "sub": 13, "mul": 27, "div": 1,
                      "select_n": 16, "roll": 12}, round1
    assert round2 == {"add": 4, "sub": 10, "mul": 12, "select_n": 5,
                      "roll": 4}, round2
    assert sum(round1.values()) + sum(round2.values()) == 88 + 35


def _exchanges_a_step(multistep, state):
    """The halo exchanges in the traced one-step program, with their
    ghost writes or without."""
    report = verify_comm(lambda: multistep(state))()
    kinds = [e.kind for e in report.events]
    return kinds.count("halo_exchange_2d") + kinds.count("halo_slabs_2d")


@pytest.mark.parametrize("mesh_shape,num_steps", [
    ((1, 1), 10), ((1, 1), 7), ((2, 1), 10), ((1, 2), 10), ((2, 2), 10)])
def test_multistep_through_the_kernels_matches_the_array_path(
        mesh_shape, num_steps, monkeypatch):
    """``make_init``, ``make_first_step`` and ``make_multistep`` with
    the step forced through the kernel (interpreted) against the array
    path, after 1 + 10 steps: walls and the Coriolis parameter's rows on
    the right devices, ring 1 recomputed where the array path exchanges
    a second time, the first step and the rest through one kernel.  On
    one device a walk of the kernel is two steps (the first step's with
    one passed over), and an odd count's last step a walk of one."""
    mesh = jax.make_mesh(
        mesh_shape, ("y", "x"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2)
    comm = m.MeshComm.from_mesh(mesh)
    py, px = mesh_shape
    # a fast rotation and a deep layer: friction moves u by 1e-2 m/s in
    # these steps (the published coefficients: 1e-4), and h stays
    # positive; a beta plane on which the rotation doubles from wall to
    # wall, so that a device that took another's rows would show
    cfg = sw.SWConfig(ny=40, nx=48, ghost=G, coriolis_f=2e-2, depth=1e3,
                      coriolis_beta=1e-7)
    ny_l, nx_l = 40 // py, 48 // px
    block = (ny_l + 2 * G, nx_l + 2 * G)

    def run():
        state = sw.make_init(cfg, comm)()
        state = sw.make_first_step(cfg, comm)(state)
        return jax.tree.map(
            np.asarray, sw.make_multistep(cfg, comm, num_steps)(state))

    def blocks(x):
        """A global array of padded blocks, a block at a time."""
        return x.reshape(py, block[0], px, block[1]).transpose(0, 2, 1, 3)

    def interiors(x):
        """A global array of padded blocks without their ghost rings."""
        return blocks(x)[..., G:-G, G:-G].transpose(0, 2, 1, 3).reshape(40, 48)

    want = run()
    assert want.dh.shape == (40, 48)
    state = sw.make_init(cfg, comm)()
    assert _exchanges_a_step(sw.make_multistep(cfg, comm, 1), state) == 5
    calls, walks = [], []
    wide_step = sw_kernels.wide_step
    # another case of this test traced these shapes: count this one's
    wide_step.clear_cache()

    def interpreted(*args, **kwargs):
        calls.append(args[0].shape)
        walks.append(kwargs["steps"])
        return wide_step(*args, **dict(kwargs, interpret=True))

    monkeypatch.setattr(sw_kernels, "wide_step", interpreted)
    monkeypatch.setattr(sw, "_runs_as_kernels", lambda cfg, comm: True)
    # Pallas's interpreter slices blocks at indices that vary over no
    # mesh axis, which shard_map's checker refuses; the compiled kernel
    # is checked (tests/test_tpu_compile.py)
    monkeypatch.setattr(
        jax, "shard_map", functools.partial(jax.shard_map, check_vma=False))
    imports = []
    pallas = sw_kernels.pallas
    monkeypatch.setattr(
        sw_kernels, "pallas", lambda: imports.append(1) or pallas())
    got = run()
    # the step is built once in each of the two programs, on one
    # device's block; Pallas is asked for where each program is built,
    # and once more where the kernel is traced: the second program
    # reuses the first's trace.  On one device both are the kernel that
    # walks two steps (the first step's with its first passed over),
    # and only an odd count's last step is a walk of one, another
    # kernel and another trace
    alone = mesh_shape == (1, 1)
    odd = alone and num_steps % 2
    assert walks == [2, 2] + [1] * odd if alone else walks == [1, 1]
    assert calls == [block] * len(walks)
    assert len(imports) == 3 + odd
    # three exchanges a step where the array path has five
    state = sw.make_init(cfg, comm)()
    assert _exchanges_a_step(sw.make_multistep(cfg, comm, 1), state) == 3
    # where the step is a kernel the state carries padded tendencies,
    # from make_init on; a first step takes them interior-shaped too, as
    # who builds a state of their own hands them in (the benchmark)
    assert got.dh.shape == got.h.shape
    bare = sw.SWState(
        *sw.make_init(cfg, comm)()[:3], *(jnp.zeros((40, 48)),) * 3)
    np.testing.assert_array_equal(
        sw.make_first_step(cfg, comm)(bare).dv,
        sw.make_first_step(cfg, comm)(sw.make_init(cfg, comm)()).dv)
    with pytest.raises(ValueError, match="carries them padded"):
        sw.make_multistep(cfg, comm, 1)(bare)
    # what a broken step would leave: a kernel that took every block
    # for the mesh's first, a friction that did nothing
    monkeypatch.setattr(
        sw_kernels, "wide_step",
        lambda *args, **kwargs: interpreted(*args[:9], 0, *args[10:], **kwargs))
    misplaced = run()
    monkeypatch.setattr(
        sw_kernels, "wide_step",
        lambda *args, **kwargs: interpreted(*args, **dict(kwargs, nu=0.0)))
    smooth = run()
    for name, a, b, c, d in zip(
            sw.SWState._fields, got, want, misplaced, smooth):
        assert np.isfinite(b).all()
        tolerance = 2e-5 * max(1.0, np.abs(b).max())
        if name.startswith("d"):
            # the tendencies' ghost ring: ring 2 zero; ring 1 of du and
            # dv what the neighbour holds for those cells (periodic in
            # x), zero beyond a wall; all of dh's zero
            held = np.pad(interiors(a), ((1, 1), (0, 0)))
            held = np.pad(held, ((0, 0), (1, 1)), mode="wrap")
            for (iy, ix), mine in np.ndenumerate(np.empty((py, px))):
                mine = blocks(a)[iy, ix]
                theirs = np.pad(held[iy * ny_l:(iy + 1) * ny_l + 2,
                                     ix * nx_l:(ix + 1) * nx_l + 2], 1)
                if name == "dh":
                    theirs[_ring(block, 1)] = 0
                else:
                    assert np.abs(theirs[_ring(block, 1)]).max() > 0, name
                np.testing.assert_allclose(
                    mine, theirs, rtol=0, atol=1e-6 * np.abs(b).max(),
                    err_msg=name)
                assert not mine[_ring(block, 2)].any(), name
            a, c, d = interiors(a), interiors(c), interiors(d)
        elif name in "uv":
            # the array path's second exchange refreshes ring 2 as well,
            # which nothing reads before the next step's exchange
            a, b, c, d = (interiors(x) for x in (a, b, c, d))
        np.testing.assert_allclose(a, b, rtol=0, atol=tolerance, err_msg=name)
        if name in "uv":
            assert np.abs(d - b).max() > 20 * tolerance, name
            assert (np.abs(c - b).max() > 20 * tolerance) == (py > 1), name


@pytest.mark.parametrize("saved_as", ["array code", "kernel"])
@pytest.mark.parametrize("mesh_shape", [(1, 1), (2, 2)])
def test_a_save_resumes_where_the_step_is_the_other_backends(
        mesh_shape, saved_as, monkeypatch, tmp_path):
    """A checkpoint holds the model, not one backend's buffers (D14): a
    job saved where the step is array code (interior-shaped tendencies)
    resumes where it is the kernel (padded ones, ring 1 of ``du``,
    ``dv`` the neighbours'), and the reverse; the run goes on as the
    uninterrupted one does, to the rounding by which the two backends
    differ anyway, where a resume that dropped the tendencies would be
    off by a thousand times that."""
    mesh = jax.make_mesh(
        mesh_shape, ("y", "x"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2,
        devices=jax.devices()[:mesh_shape[0] * mesh_shape[1]])
    comm = m.MeshComm.from_mesh(mesh)
    py, px = mesh_shape
    cfg = sw.SWConfig(ny=40, nx=48, ghost=G, coriolis_f=2e-2, depth=1e3,
                      coriolis_beta=1e-7)
    ck = sw.Checkpoint(tmp_path / "run", every_calls=0)
    wide_step = sw_kernels.wide_step

    def as_kernel(patch):
        patch.setattr(sw_kernels, "wide_step", lambda *args, **kwargs: wide_step(
            *args, **dict(kwargs, interpret=True)))
        patch.setattr(sw, "_runs_as_kernels", lambda cfg, comm: True)
        patch.setattr(
            jax, "shard_map", functools.partial(jax.shard_map, check_vma=False))

    def job(kernel, patch):
        if kernel:
            as_kernel(patch)
        made = sw.make_job(cfg, comm, 5, checkpoint=ck)
        assert made.form()["tendencies"] == ("padded" if kernel else "interior")
        return made

    def interiors(x):
        x = np.asarray(x)
        if x.shape == (40, 48):
            return x
        return x.reshape(py, 40 // py + 2 * G, px, 48 // px + 2 * G)[
            :, G:-G, :, G:-G].reshape(40, 48)

    with monkeypatch.context() as patch:
        first = job(saved_as == "kernel", patch)
        first.start(sw.make_init(cfg, comm)())
        first.advance(1)
        first.save()
        first.advance(1)  # the uninterrupted run, on the backend that saved
        first.drain()
        want = [interiors(a) for a in first.state]
    with monkeypatch.context() as patch:
        second = job(saved_as != "kernel", patch)
        assert second.resume() == 6
        assert second.state.dh.shape == (
            second.state.h.shape if saved_as != "kernel" else (40, 48))
        kept = jax.tree.map(jnp.copy, second.state)  # the call donates its input
        second.advance(1)
        got = [interiors(a) for a in second.state]
        # the same resume with its tendencies dropped
        second.start(kept._replace(**{
            k: jnp.zeros_like(getattr(kept, k)) for k in ("dh", "du", "dv")}),
            step=6)
        second.advance(1)
        dropped = [interiors(a) for a in second.state]
    for name, a, b, c in zip(sw.SWState._fields, got, want, dropped):
        tolerance = 2e-5 * max(1.0, np.abs(b).max())
        np.testing.assert_allclose(a, b, rtol=0, atol=tolerance, err_msg=name)
        if name in "huv":
            assert np.abs(c - b).max() > 100 * tolerance, name


def _comm_on(platform, mesh_shape=(1, 1)):
    devices = np.full(mesh_shape, types.SimpleNamespace(platform=platform))
    return types.SimpleNamespace(
        mesh=types.SimpleNamespace(devices=devices), axis_sizes=mesh_shape)


@pytest.mark.parametrize("platform,dtype,rows,width,expected", [
    ("tpu", "float32", 7204, 14404, True),
    ("tpu", "float32", 184, 364, True),
    ("cpu", "float32", 7204, 14404, False),   # a Mosaic kernel cannot run
    ("gpu", "float32", 7204, 14404, False),
    ("tpu", "float64", 7204, 14404, False),   # the strips are float32's
    ("tpu", "bfloat16", 7204, 14404, False),
    ("tpu", "float32", 7, 364, False),        # not one strip of 8 rows
    # one interior row, whose ring 1 would be a neighbour's wall row: a
    # block with a strip has four or more
    ("tpu", "float32", 5, 364, False),
    ("tpu", "float32", 7204, 300_000, False),  # a strip over the budget
    # the state's six arrays go through one call: a strip of two this
    # wide would fit, one of six does not
    ("tpu", "float32", 7204, 40_000, True),
    ("tpu", "float32", 7204, 100_000, False),
], ids=lambda x: str(x))
def test_the_step_picks_the_kernels_from_platform_dtype_and_shape(
        platform, dtype, rows, width, expected):
    cfg = sw.SWConfig(ny=rows - 2 * G, nx=width - 2 * G, dtype=dtype, ghost=G)
    assert sw._runs_as_kernels(cfg, _comm_on(platform)) is expected
    # the other two schedules are array code everywhere
    assert not sw._runs_as_kernels(replace(cfg, ghost=4), _comm_on(platform))


@pytest.mark.parametrize("platform,mesh_shape,rows,width,expected", [
    ("tpu", (1, 1), 7204, 14404, True),
    ("tpu", (1, 1), 184, 364, True),
    # a neighbour on either axis: the second step's ghosts are its
    ("tpu", (2, 1), 7204, 14404, False),
    ("tpu", (1, 2), 7204, 14404, False),
    ("tpu", (2, 2), 1804, 3604, False),
    ("cpu", (1, 1), 7204, 14404, False),   # no kernel, no walk
    ("tpu", (1, 1), 7204, 40_000, True),   # one strip a tile
    ("tpu", (1, 1), 7204, 100_000, False),  # not one
], ids=lambda x: str(x))
def test_a_walk_takes_two_steps_on_a_mesh_of_one_device_alone(
        platform, mesh_shape, rows, width, expected):
    py, px = mesh_shape
    cfg = sw.SWConfig(ny=(rows - 2 * G) * py, nx=(width - 2 * G) * px, ghost=G)
    comm = _comm_on(platform, mesh_shape)
    assert sw._walks_two_steps(cfg, comm) is expected
    assert sw._runs_as_kernels(cfg, comm) or not expected
    assert not sw._walks_two_steps(replace(cfg, ghost=4), comm)


def test_a_step_on_cpu_devices_is_the_array_code():
    mesh = jax.make_mesh(
        (1, 1), ("y", "x"), axis_types=(jax.sharding.AxisType.Auto,) * 2)
    comm = m.MeshComm.from_mesh(mesh)
    cfg = sw.SWConfig(ny=24, nx=48, ghost=G)
    state = jax.eval_shape(sw.make_init(cfg, comm))
    assert not sw._runs_as_kernels(cfg, comm)
    assert state.dh.shape == (24, 48)  # interior-shaped, as the array code's
    text = sw.make_multistep(cfg, comm, 1).lower(state).as_text()
    assert "custom_call" not in text or "tpu_custom_call" not in text


@pytest.mark.parametrize("rows,width,fields,steps,expected", [
    (7204, 14404, 2, 1, 72),   # the benchmark's block: 40 MiB / (10 x 57856 B)
    (1804, 3604, 2, 1, 280),
    (184, 364, 2, 1, 184),     # the whole block when it fits
    (52, 100, 2, 1, 48),       # whole strips only
    (7, 100, 2, 1, 0),
    (7204, 14404, 6, 1, 24),   # round 1's six fields get shorter tiles
    (1804, 3604, 6, 1, 88),
    (184, 364, 6, 1, 184),
    (52, 100, 6, 1, 48),
    (7204, 100_000, 6, 1, 0),  # 8 rows x 30 blocks of 400 KB: over the budget
    # a walk of two steps keeps two tiles more an array in rings, out
    # of 56 MiB: 42 x 57856 B a row, and the tiles are a single walk's
    (7204, 14404, 6, 2, 24),
    (1804, 3604, 6, 2, 88),
    (184, 364, 6, 2, 184),
    (52, 100, 6, 2, 48),
    (7204, 40_000, 6, 1, 8),
    (7204, 40_000, 6, 2, 8),
    (7204, 100_000, 6, 2, 0),
])
def test_tile_rows(rows, width, fields, steps, expected):
    tile = sw_kernels.tile_rows(rows, width, jnp.float32, fields, steps)
    assert tile == expected and tile % sw_kernels.STRIP == 0
    if steps == 1:
        assert tile == sw_kernels.tile_rows(rows, width, jnp.float32, fields)


def _fresh_interpreter(code):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = root + os.pathsep + env.get("PYTHONPATH", "")
    done = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True,
        text=True, timeout=300)
    assert done.returncode == 0, done.stderr[-2000:]
    return done.stdout.strip()


def test_pallas_is_imported_only_by_who_runs_the_kernel():
    """In a fresh interpreter the package, the model and a step built
    and run on CPU devices leave ``jax.experimental.pallas`` out:
    its import (0.4 s from bytecode, 1.2 s from source, on the chip's
    machine) is paid by a step built for TPU devices and by nothing
    else (``ops/flash.py`` has its own, for who imports that)."""
    out = _fresh_interpreter("""
import sys
import jax
import mpi4jax_tpu as m
from mpi4jax_tpu.models import shallow_water as sw
mesh = jax.make_mesh((1, 1), ("y", "x"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
comm = m.MeshComm.from_mesh(mesh)
cfg = sw.SWConfig(ny=16, nx=24, ghost=2)
state = sw.make_first_step(cfg, comm)(sw.make_init(cfg, comm)())
jax.block_until_ready(sw.make_multistep(cfg, comm, 2)(state))
loaded = sorted(k for k in sys.modules if "pallas" in k)
assert not loaded, loaded
print("no pallas")
""")
    assert out.endswith("no pallas")


GPU_INTERPRETER = "jax._src.pallas.mosaic_gpu.interpret.interpret_pallas_call"


@pytest.mark.parametrize("first", ["ours", "theirs"])
def test_pallas_declines_the_gpu_interpreter_on_a_first_import_only(first):
    """``sw_kernels.pallas()`` leaves out the Mosaic GPU interpreter that
    jax's ``pallas_call`` module would import (half the import's time,
    for code a TPU kernel cannot reach) when it is this process's first
    import of Pallas, and leaves no trace in ``sys.modules``: a later
    import of the GPU package gets the real modules.  Where Pallas was
    imported before, nothing is touched."""
    out = _fresh_interpreter(f"""
import sys
import jax
if {first == "theirs"!r}:
    from jax.experimental import pallas
from mpi4jax_tpu.models import sw_kernels
pl, pltpu = sw_kernels.pallas()
assert pl.pallas_call and pltpu.roll and pltpu.VMEM
print("interpreter", {GPU_INTERPRETER!r} in sys.modules,
      "gpu", "jax.experimental.mosaic.gpu" in sys.modules)
assert sys.modules.get({GPU_INTERPRETER!r}, "absent") is not None
from jax.experimental.pallas import mosaic_gpu
import {GPU_INTERPRETER}
print("later", "jax.experimental.mosaic.gpu" in sys.modules)
""")
    loaded = first == "theirs"
    assert out.splitlines() == [
        f"interpreter {loaded} gpu {loaded}", "later True"]


@pytest.mark.parametrize("platform,ghost,nu,expected", [
    ("tpu", 2, 1, 1), ("cpu", 2, 1, 0), ("tpu", 1, 1, 0), ("tpu", 4, 1, 0),
    ("tpu", 2, 0, 1),  # without friction the step is a kernel still
])
def test_a_step_built_for_tpu_devices_imports_pallas_before_it_is_traced(
        platform, ghost, nu, expected, monkeypatch):
    comm = _comm_on(platform)
    imports = []
    monkeypatch.setattr(sw_kernels, "pallas", lambda: imports.append(1))
    sw._kernels_ahead(
        sw.SWConfig(ny=64, nx=128, ghost=ghost, coriolis_f=2e-4 * nu), comm)
    assert len(imports) == expected
