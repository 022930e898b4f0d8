"""The step's adjoint kernel (``sw_kernels.wide_step_vjp``, interpreted)
against ``jax.vjp`` of the array code it transposes
(``shallow_water._walk_as_arrays``), through the seam that calls it
(``_step_wide``'s backward): random states and random cotangents on
every cell of all six arrays, ghost cells included, on CPU meshes whose
blocks stand at both walls, one or none; a run's first step, a walk of
two, no friction, widths that fill their vector registers and that end
inside one, more tiles than one, the dot-product identity, and the block
that falls back to the array code.  Small blocks: a case is a second or
two of the interpreter."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mpi4jax_tpu as m
from mpi4jax_tpu.models import shallow_water as sw
from mpi4jax_tpu.models import sw_kernels
from mpi4jax_tpu.parallel import halo
from tests.sw_kernels_cases import UNIT, G, _Viscous

# float32's rounding of sums of a few dozen terms of order one, in two
# orders: the array code against itself in float64 reads the same
# (test_the_kernels_rounding_is_the_array_codes)
CLOSE = 2e-5


def _comm(mesh_shape):
    py, px = mesh_shape
    mesh = jax.make_mesh(
        mesh_shape, ("y", "x"), axis_types=(jax.sharding.AxisType.Auto,) * 2,
        devices=jax.devices()[:py * px])
    return m.MeshComm.from_mesh(mesh)


def _interpreted(monkeypatch):
    """The step forced through both kernels, interpreted; returns the
    list that the adjoint kernel's calls are noted in."""
    calls = []
    wide_step, wide_step_vjp = sw_kernels.wide_step, sw_kernels.wide_step_vjp
    wide_step_jvp = sw_kernels.wide_step_jvp
    for kernel in (wide_step, wide_step_vjp, wide_step_jvp):
        kernel.clear_cache()

    def adjoint(*args, **kwargs):
        calls.append(args[0].shape)
        return wide_step_vjp(*args, **dict(kwargs, interpret=True))

    monkeypatch.setattr(
        sw_kernels, "wide_step",
        lambda *args, **kwargs: wide_step(*args, **dict(kwargs, interpret=True)))
    monkeypatch.setattr(sw_kernels, "wide_step_vjp", adjoint)
    monkeypatch.setattr(
        sw_kernels, "wide_step_jvp",
        lambda *args, **kwargs: wide_step_jvp(*args, **dict(kwargs, interpret=True)))
    monkeypatch.setattr(sw, "_runs_as_kernels", lambda cfg, comm: True)
    # Pallas's interpreter slices blocks at indices that vary over no
    # mesh axis, which shard_map's checker refuses
    monkeypatch.setattr(
        jax, "shard_map", functools.partial(jax.shard_map, check_vma=False))
    return calls


def _tiles_of(monkeypatch, tile, rows, width):
    """The adjoint walk's tiles cut to ``tile`` rows of a block."""
    row_bytes = sw_kernels._whole_registers(width) * 4
    monkeypatch.setattr(
        sw_kernels, "_VMEM_BLOCK_BUDGET",
        5 * sw_kernels._ADJOINT_FIELDS * row_bytes * tile)
    assert sw_kernels.adjoint_tile_rows(rows, width, jnp.float32) == tile
    assert rows > tile


def _random(comm, cfg, seed, only=None):
    """A state of order one with noise on every cell of its six padded
    arrays, and cotangents likewise (``only``: a mask of the block's
    cells that keep theirs)."""
    rng = np.random.default_rng(seed)
    py, px = comm.axis_sizes
    ny_l, nx_l = cfg.local_interior(comm)
    shape = (py * (ny_l + 2 * G), px * (nx_l + 2 * G))
    sharding = jax.sharding.NamedSharding(comm.mesh, jax.P(*comm.axes))

    def array(mean, spread, mask=None):
        x = mean + spread * rng.normal(size=shape)
        if mask is not None:
            x = x * np.tile(mask, (py, px))
        return jax.device_put(jnp.asarray(x, jnp.float32), sharding)

    state = sw.SWState(array(1.0, 0.1), array(0.0, 0.5), array(0.0, 0.5),
                       *(array(0.0, 0.5) for _ in range(3)))

    def as_a_step_leaves_them(*tendencies):
        # ring 1 of a state's du, dv is the neighbours' (the forward
        # kernel steps u, v from it there: a walk of two runs it)
        return tuple(halo.halo_exchange_2d(x, comm, width=G)[0]
                     for x in tendencies)

    spec = jax.P(*comm.axes)
    state = sw.SWState(*state[:3], *jax.jit(jax.shard_map(
        as_a_step_leaves_them, mesh=comm.mesh, in_specs=(spec,) * 3,
        out_specs=(spec,) * 3))(*state[3:]))
    return state, sw.SWState(*(array(0.0, 1.0, only) for _ in range(6)))


def _transposed(comm, step, state, cotangents):
    """``step`` (a device's state -> its new state) run and transposed
    inside the model's ``shard_map``: the cotangents of ``state``."""
    spec = sw._mesh_specs(comm)

    def local(state, ct):
        _, vjp = jax.vjp(step, state)
        return vjp(ct)[0]

    return jax.jit(jax.shard_map(
        local, mesh=comm.mesh, in_specs=(spec, spec), out_specs=spec))(
            state, cotangents)


def _steps(cfg, comm, first_step=False, steps=1):
    """``(through the kernels, as array code)``: one walk both ways."""
    how = dict(first_step=first_step, steps=steps)

    def kernels(state):
        return sw._step_wide(state, cfg, comm, **how)[0][0]

    def arrays(state):
        return sw._walk_as_arrays(
            state, m.create_token(), cfg=cfg, comm=comm, **how)[0]

    return kernels, arrays


def _rel(a, b):
    return float(np.linalg.norm(np.asarray(a) - np.asarray(b))
                 / max(float(np.linalg.norm(np.asarray(b))), 1e-30))


def _assert_the_same(got, want, first_step=False):
    for name, a, b in zip(sw.SWState._fields, got, want):
        assert bool(jnp.isfinite(a).all()), name
        if first_step and name.startswith("d"):
            # forward Euler reads no tendency: both say nothing of them
            assert not np.asarray(a).any() and not np.asarray(b).any()
        else:
            assert _rel(a, b) < CLOSE, (name, _rel(a, b))


# a mesh's blocks stand at: both walls; the southern or the northern
# alone; one of them beside neighbours in x; and, the middle two of four,
# at neither
MESHES = [(1, 1), (2, 1), (2, 2), (4, 1)]
WALKS = {"ab2": dict(), "euler": dict(first_step=True), "two": dict(steps=2)}


@pytest.mark.parametrize("walk", sorted(WALKS))
@pytest.mark.parametrize("mesh_shape", MESHES)
def test_the_adjoint_kernel_is_the_array_codes_transpose(
        mesh_shape, walk, monkeypatch):
    """A walk of the kernel under ``jax.vjp``, on cotangents that are
    random on every cell of the six results (a cost that reads ghost
    cells hands such): the cotangents of the state the walk started from
    are those of the array code.  A step in the middle of a run, a run's
    first, and a walk of two, which is the forward kernel once and the
    adjoint kernel twice."""
    comm = _comm(mesh_shape)
    cfg = _Viscous(ny=10 * mesh_shape[0], nx=20 * mesh_shape[1], nu=0.2, **UNIT)
    calls = _interpreted(monkeypatch)
    assert sw._walks_two_steps(cfg, comm)  # blocks a walk of two is asked of
    state, cotangents = _random(comm, cfg, seed=55)
    kernels, arrays = _steps(cfg, comm, **WALKS[walk])
    got = _transposed(comm, kernels, state, cotangents)
    assert len(calls) == (2 if walk == "two" else 1)
    _assert_the_same(got, _transposed(comm, arrays, state, cotangents),
                     first_step=walk == "euler")


@pytest.mark.parametrize("mesh_shape", [(1, 1), (2, 2)])
def test_without_friction_the_ghost_cells_cotangents_pass_through(
        mesh_shape, monkeypatch):
    """``nu == 0``: no second round and no second exchange, so the ghost
    cells of the results ``u``, ``v`` are the first exchange's and their
    cotangents go home with the fields'."""
    comm = _comm(mesh_shape)
    cfg = _Viscous(ny=10 * mesh_shape[0], nx=20 * mesh_shape[1], nu=0.0, **UNIT)
    calls = _interpreted(monkeypatch)
    state, cotangents = _random(comm, cfg, seed=56)
    kernels, arrays = _steps(cfg, comm)
    got = _transposed(comm, kernels, state, cotangents)
    assert calls
    _assert_the_same(got, _transposed(comm, arrays, state, cotangents))


@pytest.mark.parametrize("where", ["ghosts", "interior"])
@pytest.mark.parametrize("mesh_shape", [(1, 1), (2, 2)])
def test_a_cost_that_reads_ghost_cells_alone_or_none(
        mesh_shape, where, monkeypatch):
    """The two kinds of cotangent apart: on the ghost cells alone, which
    the wrapper sends home (``u``, ``v``: through the second exchange's
    transpose, before the kernel) or the kernel passes through (``h``, a
    wall's ghost rows), and on the interior alone, which is all a sweep
    hands a step."""
    comm = _comm(mesh_shape)
    cfg = _Viscous(ny=10 * mesh_shape[0], nx=20 * mesh_shape[1], nu=0.2, **UNIT)
    calls = _interpreted(monkeypatch)
    ny_l, nx_l = cfg.local_interior(comm)
    interior = np.zeros((ny_l + 2 * G, nx_l + 2 * G), bool)
    interior[G:-G, G:-G] = True
    state, cotangents = _random(
        comm, cfg, seed=57, only=interior if where == "interior" else ~interior)
    kernels, arrays = _steps(cfg, comm)
    got = _transposed(comm, kernels, state, cotangents)
    want = _transposed(comm, arrays, state, cotangents)
    assert calls
    _assert_the_same(got, want)


@pytest.mark.parametrize("nx, tile", [
    (252, 0),  # 256 columns fill their registers: a rotation's wrap lands
    #            in the ghost columns and not past them
    (125, 0),  # 129 columns: the eastern ghost columns in two registers
    (20, 8), (20, 16), (20, 24),  # tiles of one, two and three strips: the
    #            stage writes two strips at once where they divide a tile
])
def test_widths_and_tiles(nx, tile, monkeypatch):
    """One device, both walls: the block's width against the vector
    registers', and more tiles than one, the last of them cut short by
    the block's end."""
    comm = _comm((1, 1))
    cfg = _Viscous(ny=29 if tile else 12, nx=nx, nu=0.2, **UNIT)
    calls = _interpreted(monkeypatch)
    if tile:
        _tiles_of(monkeypatch, tile, cfg.ny + 2 * G, nx + 2 * G)
    state, cotangents = _random(comm, cfg, seed=58)
    kernels, arrays = _steps(cfg, comm)
    got = _transposed(comm, kernels, state, cotangents)
    assert calls == [(cfg.ny + 2 * G, nx + 2 * G)]
    _assert_the_same(got, _transposed(comm, arrays, state, cotangents))


@pytest.mark.parametrize("through", ["arrays", "walk"])
@pytest.mark.parametrize("walk", sorted(WALKS))
@pytest.mark.parametrize("mesh_shape", [(1, 1), (2, 2)])
def test_the_dot_product_identity(mesh_shape, walk, through, monkeypatch):
    """``<J x, y> == <x, J^T y>``: ``J^T y`` by the adjoint kernel, ``J
    x`` by ``jax.jvp`` of the array code (its exchanges' tangents the
    exchanges of the tangents) and by ``jax.jvp`` of the kernel's walk
    itself, whose tangent is the tangent kernel's at what the adjoint
    kernel keeps (``_walk_forwards``)."""
    comm = _comm(mesh_shape)
    cfg = _Viscous(ny=10 * mesh_shape[0], nx=20 * mesh_shape[1], nu=0.2, **UNIT)
    calls = _interpreted(monkeypatch)
    state, y = _random(comm, cfg, seed=59)
    _, x = _random(comm, cfg, seed=60)
    kernels, arrays = _steps(cfg, comm, **WALKS[walk])
    back = _transposed(comm, kernels, state, y)
    assert calls
    spec = sw._mesh_specs(comm)
    pushed = jax.jit(jax.shard_map(
        lambda state, x: jax.jvp(
            kernels if through == "walk" else arrays, (state,), (x,))[1],
        mesh=comm.mesh, in_specs=(spec, spec), out_specs=spec))(state, x)

    def dot(a, b, of=lambda p: p):
        return sum(float(jnp.vdot(of(p), of(q))) for p, q in zip(a, b))

    if walk == "euler":  # forward Euler reads no tendency
        x = x[:3]
    # float32's rounding of a sum of thousands of products of either
    # sign, against the sum of their sizes
    # (the twelve cases read 2e-9 to 2e-8 of it)
    assert dot(pushed, y) == pytest.approx(
        dot(x, back), abs=1e-6 * dot(x, back, of=jnp.abs))


def test_the_kernels_rounding_is_the_array_codes(monkeypatch):
    """What ``CLOSE`` allows is rounding: against the array code's
    derivative in float64, the adjoint kernel is as far off as the array
    code in float32 is, to a small factor."""
    comm = _comm((1, 1))
    cfg = _Viscous(ny=12, nx=20, nu=0.2, **UNIT)
    calls = _interpreted(monkeypatch)
    state, cotangents = _random(comm, cfg, seed=61)
    kernels, arrays = _steps(cfg, comm)
    got = _transposed(comm, kernels, state, cotangents)
    single = _transposed(comm, arrays, state, cotangents)
    assert calls
    with jax.enable_x64(True):
        cfg64 = _Viscous(ny=12, nx=20, nu=0.2, dtype="float64", **UNIT)
        wide = [jax.tree.map(lambda a: jnp.asarray(a, jnp.float64), x)
                for x in (state, cotangents)]
        monkeypatch.setattr(sw, "_runs_as_kernels", lambda cfg, comm: False)
        double = _transposed(comm, _steps(cfg64, comm)[1], *wide)
    for name, a, b, c in zip(sw.SWState._fields, got, single, double):
        mine, theirs = _rel(a, c), _rel(b, c)
        assert mine < 4 * theirs + 1e-7, (name, mine, theirs)


def test_a_block_too_wide_for_the_adjoint_walk_is_its_array_code(monkeypatch):
    """Where the adjoint walk's fifteen arrays have no tile in VMEM but
    the step's six have, the step is the kernel and its derivative the
    array code's, decided from the block's shape."""
    comm = _comm((1, 1))
    cfg = _Viscous(ny=12, nx=20, nu=0.2, **UNIT)
    calls = _interpreted(monkeypatch)
    # a strip of the step's six arrays fits, one of the adjoint's does not
    row_bytes = sw_kernels._whole_registers(cfg.nx + 2 * G) * 4
    monkeypatch.setattr(
        sw_kernels, "_VMEM_BLOCK_BUDGET", 5 * 6 * row_bytes * sw_kernels.STRIP)
    rows, width = cfg.ny + 2 * G, cfg.nx + 2 * G
    assert sw_kernels.tile_rows(rows, width, jnp.float32, 6) == sw_kernels.STRIP
    assert sw_kernels.adjoint_tile_rows(rows, width, jnp.float32) == 0
    monkeypatch.setattr(
        sw, "_runs_as_kernels", lambda cfg, comm: sw_kernels.tile_rows(
            rows, width, jnp.float32, 6) > 0)
    assert not sw._derives_as_kernels(cfg, comm)
    state, cotangents = _random(comm, cfg, seed=62)
    kernels, arrays = _steps(cfg, comm)
    got = _transposed(comm, kernels, state, cotangents)
    assert not calls
    _assert_the_same(got, _transposed(comm, arrays, state, cotangents))
