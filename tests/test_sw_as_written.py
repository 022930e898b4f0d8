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
