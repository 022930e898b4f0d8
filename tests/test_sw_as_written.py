"""Upstream's step as written (``SWConfig()``: ``ghost`` 1, array code,
twelve one-field exchanges a step) against the plain reference the
benchmark holds it to, through ``make_state``; ``make_state`` against
``make_init``; and the scopes the as-written step carries.

The reference is the benchmark's own file, loaded by path
(``perfbench/references/shallow-water-as-written.py``: upstream's scheme
on one device with its one ghost cell, nothing of mpi4jax_tpu), and the
seeded fields are the benchmark's (``perfbench/drivers/shallow_water.py
mode_table``, ``make_fields``), at a size the CPU runs in a second.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mpi4jax_tpu as m
from mpi4jax_tpu.models import shallow_water as sw
from perfbench.harness import files

NY, NX, CALLS = 24, 48, 2
CONFIG = files.load_json("configs", "shallow-water-as-written")
MODEL = CONFIG["model"]
# The configuration's own limits, the ones a chip run of the cell is
# held to.  The reason for their size is the chip's (PERF.md, PR 23: the
# largest difference between the program and the reference over seeds at
# 14400x7200 was a fifth of them, and the reference carried in bfloat16
# missed them 30 to 5000 times over); here, at 24x48 cells and 21 steps,
# the float32 programs differ by 3e-5 in h (a few units in the last
# place of 100 m) and 1e-5 in u and v, the orders of summation of two
# compilers, and bfloat16, whose last place of 100 m is half a metre,
# fails every one of them.
LIMITS = CONFIG["check"]["limits"]
MESHES = [(1, 1), (2, 2), (1, 4)]
FIELDS = ("h", "u", "v")


def _comm(shape):
    mesh = jax.make_mesh(
        shape, ("y", "x"), axis_types=(jax.sharding.AxisType.Auto,) * 2,
        devices=jax.devices()[:shape[0] * shape[1]])
    return m.MeshComm.from_mesh(mesh)


def _cfg(ghost=1, ny=NY, nx=NX):
    return sw.SWConfig(ny=ny, nx=nx, dx=MODEL["dx"], dy=MODEL["dy"], ghost=ghost)


@pytest.fixture(scope="module")
def seeded():
    """The benchmark's seeded interior fields ``(h, u, v)`` and the
    reference's arguments for them."""
    plain = files.load_module("drivers", "shallow_water")
    ref = files.load_module("references", CONFIG["reference"])
    modes = plain.mode_table(2421, CONFIG["assumed"]["perturbation"])
    fields = plain.make_fields(MODEL, NY, NX, MODEL["dx"], MODEL["dy"])(modes)
    params = ref.parameters(MODEL, MODEL["dx"], MODEL["dy"])
    return ref, fields, params


def _columns(a, shape, ghost=1):
    """The domain's rows of a state's global array (every device's
    block with its own ghost ring) at all ``nx + 2`` columns: the cells
    and the domain's two ghost columns."""
    py, px = shape
    G = ghost
    a = np.asarray(a)
    ly, lx = a.shape[0] // py - 2 * G, a.shape[1] // px - 2 * G
    rows = a.reshape(py, ly + 2 * G, px, lx + 2 * G)[:, G:-G]
    return np.concatenate([
        rows[:, :, 0, :G], rows[:, :, :, G:-G].reshape(py, ly, px * lx),
        rows[:, :, -1, -G:]], axis=2).reshape(py * ly, px * lx + 2 * G)


def _as_written(shape, fields):
    """First step, then ``CALLS`` donated 10-step calls, through
    ``make_state``: the final ``(h, u, v)`` as ``_columns`` gives them."""
    comm, cfg = _comm(shape), _cfg()
    state = sw.make_first_step(cfg, comm)(sw.make_state(cfg, comm)(*fields))
    multi = sw.make_multistep(cfg, comm, MODEL["num_multisteps"], donate=True)
    for _ in range(CALLS):
        state = multi(state)
    assert state.dh.shape == state.h.shape  # upstream's arrays: padded
    return [_columns(getattr(state, k), shape) for k in FIELDS]


def _worst(got, want):
    """Largest absolute difference a field: over the cells, and over
    the two ghost columns."""
    off = [np.abs(g - np.asarray(w)) for g, w in zip(got, want)]
    return ({k: float(d[:, 1:-1].max()) for k, d in zip(FIELDS, off)},
            {k: float(d[:, [0, -1]].max()) for k, d in zip(FIELDS, off)})


@pytest.mark.parametrize("shape", MESHES)
def test_the_as_written_program_agrees_with_the_plain_reference(seeded, shape):
    ref, fields, params = seeded
    steps = 1 + CALLS * MODEL["num_multisteps"]
    got = _as_written(shape, fields)
    cells, ghosts = _worst(got, ref.run(*fields, params, steps))
    for k in FIELDS:
        assert cells[k] <= LIMITS[k] and ghosts[k] <= LIMITS[k], (cells, ghosts)
    assert all(np.isfinite(g).all() and g.shape == (NY, NX + 2) for g in got)
    # the jet moved: agreement is not that of two states left unchanged
    assert np.abs(got[0][:, 1:-1] - np.asarray(fields[0])).max() > 100 * LIMITS["h"]
    # the control: the same reference in bfloat16 fails, cells and columns
    cells, ghosts = _worst(got, ref.run(*fields, params, steps, "bfloat16"))
    assert any(cells[k] > LIMITS[k] for k in FIELDS), cells
    assert any(ghosts[k] > LIMITS[k] for k in FIELDS), ghosts


def test_the_ghost_columns_are_stale_as_upstreams_program_leaves_them(seeded):
    """The friction update is not followed by an exchange: the state's
    ghost columns of ``u`` are the exchange's before it.  The as-written
    reference says so and the plain solver, which refreshes them, does
    not: the program's columns lie nearer the first, and on the
    reference's side the two differ only there."""
    ref, fields, params = seeded
    steps = 1 + CALLS * MODEL["num_multisteps"]
    got = _as_written((1, 1), fields)
    stale = [np.asarray(a) for a in ref.run(*fields, params, steps)]
    fresh = [np.asarray(a) for a in ref.solver.run(*fields, params, steps)]
    # the interiors are one trajectory to rounding (friction's increment
    # on two columns a step is some 1e-5 of u here)
    for s, f, k in zip(stale, fresh, FIELDS):
        assert np.abs(s[:, 1:-1] - f).max() <= LIMITS[k]
    u, u_stale, u_fresh = got[1], stale[1], fresh[1]
    west_fresh, east_fresh = u_fresh[:, -1], u_fresh[:, 0]  # periodic in x
    assert np.abs(u_stale[:, 0] - west_fresh).max() > 0
    near = np.abs(u[:, 0] - u_stale[:, 0]).max() + np.abs(u[:, -1] - u_stale[:, -1]).max()
    far = np.abs(u[:, 0] - west_fresh).max() + np.abs(u[:, -1] - east_fresh).max()
    assert near < far


def test_the_references_step_differs_from_the_plain_solvers_in_two_columns(seeded):
    ref, fields, params = seeded
    params = dict(params, nu=1e3 * params["nu"])  # friction one can see
    rng = np.random.default_rng(7)
    # the plain solver's state: padded fields, interior-shaped tendencies
    state = tuple(jnp.asarray(rng.normal(size=shape), jnp.float32)
                  for shape in ((NY + 2, NX + 2),) * 3 + ((NY, NX),) * 3)
    coriolis = jnp.zeros((NY + 2, NX + 2), jnp.float32)
    mine = ref._step(state, params, coriolis, first=False)
    plain = ref.solver._step(state, params, coriolis, first=False)
    for i, (a, b) in enumerate(zip(mine, plain)):
        a, b = np.asarray(a), np.asarray(b)
        if i in (1, 2):  # u, v: the ghost columns are the exchange's
            np.testing.assert_array_equal(a[:, 1:-1], b[:, 1:-1])
            assert (a[1:-1, 0] != b[1:-1, 0]).any()
            assert (a[1:-1, -1] != b[1:-1, -1]).any()
        else:
            np.testing.assert_array_equal(a, b)


# -- the ring, and the step as it was ---------------------------------------
#
# The ``ghost`` 1 step makes every field at the padded shape and selects
# what the ring holds (PR 43).  What the ring holds is the layout's
# guarantee: checked here on every block of every mesh, and against the
# step as it was written before, kept below as the definition.


def _blocks(a, shape):
    """A state's global array as ``[iy][ix]`` blocks, ghost ring and all."""
    py, px = shape
    a = np.asarray(a)
    by, bx = a.shape[0] // py, a.shape[1] // px
    return [[a[iy * by:(iy + 1) * by, ix * bx:(ix + 1) * bx]
             for ix in range(px)] for iy in range(py)]


def _flat(a, shape):
    """Those blocks as one list."""
    return [block for row in _blocks(a, shape) for block in row]


def _ring(block):
    """The ghost ring of a block as one vector."""
    return np.concatenate(
        [block[0], block[-1], block[1:-1, 0], block[1:-1, -1]])


def _with_ring(a, shape, value):
    """``a`` with ``value`` on the ghost ring of every device's block."""
    sharding = a.sharding
    py, px = shape
    a = np.array(a)
    by, bx = a.shape[0] // py, a.shape[1] // px
    for edge in range(0, a.shape[0], by):
        a[edge] = a[edge + by - 1] = value
    for edge in range(0, a.shape[1], bx):
        a[:, edge] = a[:, edge + bx - 1] = value
    return jax.device_put(a, sharding)


def _ringed(state, shape, rings):
    """``state`` with ``rings[k]`` on the ghost ring of its array ``k``."""
    return state._replace(**{
        k: _with_ring(getattr(state, k), shape, value)
        for k, value in (rings or {}).items()})


@functools.lru_cache(maxsize=None)
def _programs(shape, cfg):
    comm = _comm(shape)
    return (sw.make_state(cfg, comm), sw.make_first_step(cfg, comm),
            sw.make_multistep(cfg, comm, MODEL["num_multisteps"], donate=True))


def _after(program, shape, fields, cfg, rings=None):
    """The state after ``program`` (``"first"``: the first step;
    ``"call"``: the first step and a 10-step call) from the seeded
    fields, the tendencies' rings set to ``rings`` before each program."""
    make, first, multi = _programs(shape, cfg)
    state = first(_ringed(make(*fields), shape, rings))
    if program == "call":
        state = multi(_ringed(state, shape, rings))
    return state


def _seams(blocks, shape):
    """``(ghost cells, the neighbour's cells they mirror)`` over every
    seam between two devices' blocks, the domain's own in x (periodic)
    among them; a wall's ghost rows mirror nothing."""
    py, px = shape
    for iy in range(py):
        for ix in range(px):
            mine, east = blocks[iy][ix], blocks[iy][(ix + 1) % px]
            yield mine[1:-1, -1], east[1:-1, 1]
            yield east[1:-1, 0], mine[1:-1, -2]
            if iy + 1 < py:
                north = blocks[iy + 1][ix]
                yield mine[-1, 1:-1], north[1, 1:-1]
                yield north[0, 1:-1], mine[-2, 1:-1]


RINGS = {"dh": 7.0, "du": -3.0, "dv": 0.5}


@pytest.mark.parametrize("shape", MESHES)
@pytest.mark.parametrize("program", ["first", "call"])
def test_the_ring_holds_what_upstreams_program_leaves_there(
        seeded, program, shape):
    _, fields, _ = seeded
    state = _after(program, shape, fields, _cfg())
    # the tendencies are never exchanged and a step writes their cells:
    # the ring is what was handed in, make_state's zeros ...
    for k in ("dh", "du", "dv"):
        a = np.asarray(getattr(state, k))
        assert a.any(), k
        for block in _flat(a, shape):
            assert not _ring(block).any(), k
    # ... or a caller's own, to the bit, with the cells what they were
    ringed = _after(program, shape, fields, _cfg(), RINGS)
    for k, value in RINGS.items():
        for mine, plain in zip(_flat(getattr(ringed, k), shape),
                               _flat(getattr(state, k), shape)):
            assert (_ring(mine) == np.float32(value)).all(), k
            np.testing.assert_array_equal(mine[1:-1, 1:-1], plain[1:-1, 1:-1])
    for k in FIELDS:  # a tendency's ring is read by no cell
        np.testing.assert_array_equal(
            np.asarray(getattr(ringed, k)), np.asarray(getattr(state, k)))
    h, u, v = (_blocks(getattr(state, k), shape) for k in FIELDS)
    # v = 0 on the northern wall row, every column of it
    for block in v[-1]:
        assert not block[-2].any() and block[-3, 1:-1].any()
    # fn = 0 on both walls' rows: the cells' dh sums to nothing (the
    # fluxes telescope, periodic in x), where the terms are 1e-4 each
    dh = np.concatenate([np.concatenate([b[1:-1, 1:-1] for b in row], axis=1)
                         for row in _blocks(state.dh, shape)]).astype(np.float64)
    assert abs(dh.sum()) < 1e-4 * np.abs(dh).sum()
    # the ghost cells of h are the exchange's: the neighbour's cells, to
    # the bit (periodic in x; a wall's ghost rows keep what they held);
    # those of u and v are the exchange's of before friction, stale
    fresh = {k: max(np.abs(ghost - cell).max()
                    for ghost, cell in _seams(blocks, shape))
             for k, blocks in zip(FIELDS, (h, u, v))}
    assert fresh["h"] == 0.0
    assert 0.0 < fresh["u"] < LIMITS["u"] and 0.0 < fresh["v"] < LIMITS["v"]


@pytest.mark.parametrize("shape", [(1, 1), (2, 2)])
def test_without_friction_every_ghost_is_the_neighbours_cell(seeded, shape):
    """``nu`` 0 (no Coriolis parameter, so no viscosity): the step ends
    with the exchanges of ``h``, ``u``, ``v`` and nothing is stale."""
    _, fields, _ = seeded
    cfg = dataclasses.replace(_cfg(), coriolis_f=0.0)
    assert cfg.lateral_viscosity == 0
    state = _after("call", shape, fields, cfg)
    for k in FIELDS:
        for ghost, cell in _seams(_blocks(getattr(state, k), shape), shape):
            np.testing.assert_array_equal(ghost, cell, err_msg=k)
    assert not np.asarray(state.v)[-2].any()
    want = _run(_step_as_it_was, shape, fields, cfg, 11)
    for k, limit in zip(FIELDS, AS_IT_WAS):
        np.testing.assert_allclose(
            np.asarray(getattr(state, k)), np.asarray(getattr(want, k)),
            rtol=0, atol=limit)


def _step_as_it_was(state, cfg, comm, first_step):
    """The ``ghost`` 1 step as ``models/shallow_water.py`` wrote it up to
    PR 42, the definition of what the step computes and of what its ring
    holds: every result an interior-shaped array placed into a padded
    one (``.at[1:-1, 1:-1]``), the six intermediate fields ringed with
    zeros, the tendencies and ``h``, ``u``, ``v`` keeping their ring."""
    from mpi4jax_tpu.parallel.halo import halo_exchange_2d

    def i(a): return a[1:-1, 1:-1]
    def e(a): return a[1:-1, 2:]
    def w(a): return a[1:-1, :-2]
    def n(a): return a[2:, 1:-1]
    def s(a): return a[:-2, 1:-1]

    def ringed(val):
        return jnp.zeros_like(state.h).at[1:-1, 1:-1].set(val)

    per = (False, cfg.periodic_x)
    is_north, _ = sw._wall_masks(comm)
    dx, dy, g = cfg.dx, cfg.dy, cfg.gravity
    h, u, v, dh, du, dv = state

    def exchange(a):
        return halo_exchange_2d(a, comm=comm, periodic=per)[0]

    def wall_v(a):
        return jnp.where(is_north, a.at[-2, :].set(0.0), a)

    hc = exchange(jnp.pad(h[1:-1, 1:-1], 1, mode="edge"))
    fe = exchange(ringed(0.5 * (i(hc) + e(hc)) * i(u)))
    fn = wall_v(exchange(ringed(0.5 * (i(hc) + n(hc)) * i(v))))
    dh_new = dh.at[1:-1, 1:-1].set(
        -(i(fe) - w(fe)) / dx - (i(fn) - s(fn)) / dy)
    yy, _xx = sw._local_mesh_coords(cfg, comm)
    rel_vort = (e(v) - i(v)) / dx - (n(u) - i(u)) / dy
    q = exchange(ringed(
        (sw._coriolis(cfg, yy)[1:-1, 1:-1] + rel_vort)
        / (0.25 * (i(hc) + e(hc) + n(hc) + hc[2:, 2:]))))
    du_new = du.at[1:-1, 1:-1].set(
        -g * (e(h) - i(h)) / dx
        + 0.5 * (i(q) * 0.5 * (i(fn) + e(fn))
                 + s(q) * 0.5 * (s(fn) + fn[:-2, 2:])))
    dv_new = dv.at[1:-1, 1:-1].set(
        -g * (n(h) - i(h)) / dy
        - 0.5 * (i(q) * 0.5 * (i(fe) + n(fe))
                 + w(q) * 0.5 * (w(fe) + fe[2:, :-2])))
    ke = exchange(ringed(
        0.5 * (0.5 * (i(u) ** 2 + w(u) ** 2) + 0.5 * (i(v) ** 2 + s(v) ** 2))))
    du_new = du_new.at[1:-1, 1:-1].add(-(e(ke) - i(ke)) / dx)
    dv_new = dv_new.at[1:-1, 1:-1].add(-(n(ke) - i(ke)) / dy)
    dt = jnp.asarray(cfg.dt, h.dtype)
    if first_step:
        u = u.at[1:-1, 1:-1].add(dt * i(du_new))
        v = v.at[1:-1, 1:-1].add(dt * i(dv_new))
        h = h.at[1:-1, 1:-1].add(dt * i(dh_new))
    else:
        a, b = cfg.ab_a, cfg.ab_b
        u = u.at[1:-1, 1:-1].add(dt * (a * i(du_new) + b * i(du)))
        v = v.at[1:-1, 1:-1].add(dt * (a * i(dv_new) + b * i(dv)))
        h = h.at[1:-1, 1:-1].add(dt * (a * i(dh_new) + b * i(dh)))
    h, u, v = exchange(h), exchange(u), wall_v(exchange(v))
    nu = cfg.lateral_viscosity
    if nu > 0:

        def friction(f):
            gx = exchange(ringed(nu * (e(f) - i(f)) / dx))
            gy = exchange(ringed(nu * (n(f) - i(f)) / dy))
            return f.at[1:-1, 1:-1].add(
                dt * ((i(gx) - w(gx)) / dx + (i(gy) - s(gy)) / dy))

        u, v = friction(u), wall_v(friction(v))
    return sw.SWState(h, u, v, dh_new, du_new, dv_new)


def _run(step, shape, fields, cfg, steps, rings=None, options=None):
    """``steps`` steps of ``step(state, first_step)`` from the seeded
    fields in one program: the first, then a loop of the rest."""
    comm = _comm(shape)

    def local_fn(state):
        state = step(state, cfg, comm, True)
        return jax.lax.fori_loop(
            0, steps - 1, lambda _, s: step(s, cfg, comm, False), state)

    specs = sw._mesh_specs(comm)
    program = jax.jit(jax.shard_map(
        local_fn, mesh=comm.mesh, in_specs=(specs,), out_specs=specs))
    state = _ringed(sw.make_state(cfg, comm)(*fields), shape, rings)
    return program.lower(state).compile(compiler_options=options)(state)


def _the_step(state, cfg, comm, first_step):
    return sw.shallow_water_step(state, cfg, comm, first_step=first_step)[0]


# the largest difference of h, u, v between the step and the step as it
# was after 21 steps, cells and ring: the last places two compilations of
# the same expressions leave (the CPU's compiler contracts a multiply and
# an add across different fusion boundaries), a tenth of LIMITS
AS_IT_WAS = (6e-5, 1.5e-5, 1.5e-5)
# compiled without the CPU compiler's optimizations, contraction among
# them, the two programs are one arithmetic: every array to the bit
PLAIN = {"xla_backend_optimization_level": 0}
SIZES = [((24, 48), (1, 1)), ((24, 48), (2, 2)),
         ((21, 40), (1, 1)), ((21, 40), (1, 4))]


@pytest.fixture(scope="module")
def seeded_at(seeded):
    """The benchmark's seeded fields at another size."""
    plain = files.load_module("drivers", "shallow_water")
    modes = plain.mode_table(2431, CONFIG["assumed"]["perturbation"])

    def fields(ny, nx):
        return plain.make_fields(MODEL, ny, nx, MODEL["dx"], MODEL["dy"])(modes)

    return fields


@pytest.mark.parametrize("options", [None, PLAIN], ids=["compiled", "plain"])
@pytest.mark.parametrize("size,shape", SIZES)
def test_the_step_is_the_step_as_it_was(seeded_at, size, shape, options):
    """24 x 48, and 21 x 40, whose padded rows end inside a register of
    eight rows and whose 42 columns end inside one of 128 lanes: 21
    steps of the step against 21 of the definition, from a state whose
    tendencies carry a ring of a caller's own."""
    ny, nx = size
    cfg = _cfg(1, ny, nx)
    fields = seeded_at(ny, nx)
    got = _run(_the_step, shape, fields, cfg, 21, RINGS, options)
    want = _run(_step_as_it_was, shape, fields, cfg, 21, RINGS, options)
    for k in ("dh", "du", "dv"):
        for mine, theirs in zip(_flat(getattr(got, k), shape),
                                _flat(getattr(want, k), shape)):
            np.testing.assert_array_equal(_ring(mine), _ring(theirs), err_msg=k)
            assert (_ring(mine) == np.float32(RINGS[k])).all()
    if options is PLAIN:
        for k, a, b in zip(sw.SWState._fields, got, want):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=k)
        return
    for k, limit in zip(FIELDS, AS_IT_WAS):
        a, b = np.asarray(getattr(got, k)), np.asarray(getattr(want, k))
        assert np.isfinite(a).all()
        assert np.abs(a - b).max() <= limit, (k, np.abs(a - b).max())
    # and it moved: 21 steps of both are not a state left unchanged
    start = sw.make_state(cfg, _comm(shape))(*fields)
    assert np.abs(np.asarray(got.h) - np.asarray(start.h)).max() > 100 * AS_IT_WAS[0]


# -- make_state -----------------------------------------------------------


def _interiors(state, cfg, comm):
    """The domain's cells of ``h``, ``u``, ``v``, sharded as the state is."""
    G = cfg.ghost
    spec = jax.P(*comm.axes)
    return jax.jit(jax.shard_map(
        lambda *fields: tuple(a[G:-G, G:-G] for a in fields),
        mesh=comm.mesh, in_specs=(spec,) * 3, out_specs=(spec,) * 3))(
            state.h, state.u, state.v)


@pytest.mark.parametrize("shape", [(1, 1), (2, 2)])
@pytest.mark.parametrize("ghost", [1, 2, 4])
def test_make_state_is_make_inits_state_from_make_inits_fields(ghost, shape):
    """Shapes for every ``ghost`` (the tendencies padded at 1 and 4,
    interior-shaped at 2 where the step is array code, as here), and,
    from the jet's own interior, the jet's state bit for bit: every cell
    and every ghost an exchange fills.  A wall's ghost rows hold its
    edge row, as the accepted drivers pad them; ``make_init`` evaluates
    the jet there."""
    comm, cfg = _comm(shape), _cfg(ghost, 32, 64)
    jet = sw.make_init(cfg, comm)()
    made = sw.make_state(cfg, comm)(*_interiors(jet, cfg, comm))
    assert jax.tree.map(lambda a: (a.shape, a.dtype, a.sharding), made) == (
        jax.tree.map(lambda a: (a.shape, a.dtype, a.sharding), jet))
    padded = (ghost != 2)
    assert (made.dh.shape == made.h.shape) == padded
    G = ghost
    rows = made.h.shape[0]
    wall = np.zeros(rows, bool)
    wall[:G] = wall[-G:] = True
    for k, a, b in zip(sw.SWState._fields, made, jet):
        a, b = np.asarray(a), np.asarray(b)
        if k in FIELDS:
            np.testing.assert_array_equal(a[~wall], b[~wall], err_msg=k)
            np.testing.assert_array_equal(
                a[:G], np.broadcast_to(a[G], a[:G].shape), err_msg=k)
            np.testing.assert_array_equal(
                a[-G:], np.broadcast_to(a[-G - 1], a[-G:].shape), err_msg=k)
        else:
            assert not a.any() and not b.any()
    # fields that are not a device's interior are refused by name
    with pytest.raises(ValueError, match=r"the interior of a 32x64 grid"):
        sw.make_state(cfg, comm)(*(jnp.zeros((32 + 2 * G, 64 + 2 * G)),) * 3)


def test_a_solver_job_starts_from_make_states_state(seeded):
    """What the docs tell a caller with fields of their own: ``make_state``,
    then ``job.start``; the job's form is the state's."""
    _, fields, _ = seeded
    comm, cfg = _comm((1, 1)), _cfg()
    job = sw.make_job(cfg, comm, 10)
    job.start(sw.make_state(cfg, comm)(*fields))
    job.advance(1)
    assert job.step == 11 and job.form()["tendencies"] == "padded"
    assert job.state.dh.shape == job.state.h.shape == (NY + 2, NX + 2)


# -- the scopes -----------------------------------------------------------


@pytest.mark.parametrize("first", [True, False])
def test_the_as_written_step_carries_its_phases_and_its_exchanges(first):
    comm, cfg = _comm((2, 2)), _cfg()
    state = sw.make_init(cfg, comm)()
    program = (sw.make_first_step(cfg, comm) if first
               else sw.make_multistep(cfg, comm, 10))
    text = program.lower(state).compile().as_text()
    assert len(sw.STEP_PHASES) == 7 and len(sw.STEP_EXCHANGES) == 12
    for phase in sw.STEP_PHASES:
        assert f'/{sw.STEP_SCOPE}/{phase}/' in text, phase
    halo = sw.SCOPE_PREFIX + "halo_exchange_2d"
    for field in sw.STEP_EXCHANGES:
        scope = f"/{sw.STEP_SCOPE}/exchange.{field}/{halo}/"
        # each of the twelve round its own exchange, whose phases stay
        for part in ("pack", "wire", "unpack"):
            assert scope + part in text, (field, part)
    # none is a communication op's scope: the contract analyzer and the
    # benchmark's op surface take the prefix for one
    assert not sw.STEP_SCOPE.startswith(sw.SCOPE_PREFIX)
    assert f"{sw.SCOPE_PREFIX}{sw.STEP_SCOPE}" not in text


@pytest.mark.parametrize("ghost", [2, 4])
def test_the_other_schedules_carry_no_such_scope(ghost):
    comm, cfg = _comm((2, 2)), _cfg(ghost)
    text = sw.make_multistep(cfg, comm, 10).lower(
        sw.make_init(cfg, comm)()).compile().as_text()
    assert f"/{sw.STEP_SCOPE}/" not in text
