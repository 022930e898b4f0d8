"""The step's tangent kernel (``sw_kernels.wide_step_jvp``, interpreted)
against ``jax.jvp`` of the array code it is the tangent of
(``shallow_water._walk_as_arrays``), through the seam that calls it
(``_step_wide``'s tangent, ``_walk_forwards``): random states and random
tangents on every cell of all six arrays, ghost cells included, on CPU
meshes whose blocks stand at both walls, one or none; a run's first
step, a walk of two, friction on and off, widths that fill their vector
registers and that end inside one, more tiles than one; the two
derivative kernels as each other's transpose, cell for cell; and the
block that falls back to the array code.  What the files of the adjoint
kernel's tests share is theirs (``tests/test_sw_kernels_adjoint.py``).
Small blocks: a case is a second or two of the interpreter."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi4jax_tpu.models import shallow_water as sw
from mpi4jax_tpu.models import sw_kernels
from tests.sw_kernels_cases import UNIT, WALLS, G, _interpreted as _keywords, _Viscous
from tests.test_sw_kernels_adjoint import (
    CLOSE, MESHES, WALKS, _comm, _interpreted, _random, _rel, _steps, _tiles_of)


def _noted(monkeypatch):
    """The step forced through its three kernels, interpreted; returns
    the lists that the tangent kernel's calls and the forward kernel's
    are noted in (the latter as ``(steps, in_place)``)."""
    _interpreted(monkeypatch)
    pushes, walks = [], []
    wide_step, wide_step_jvp = sw_kernels.wide_step, sw_kernels.wide_step_jvp

    def tangent(*args, **kwargs):
        pushes.append(args[0].shape)
        return wide_step_jvp(*args, **kwargs)

    def forwards(*args, **kwargs):
        walks.append((kwargs["steps"], kwargs.get("in_place", True)))
        return wide_step(*args, **kwargs)

    monkeypatch.setattr(sw_kernels, "wide_step_jvp", tangent)
    monkeypatch.setattr(sw_kernels, "wide_step", forwards)
    return pushes, walks


def _pushed(comm, step, state, tangents):
    """``step`` (a device's state -> its new state) pushed forwards
    inside the model's ``shard_map``: the tangents of its results."""
    spec = sw._mesh_specs(comm)
    return jax.jit(jax.shard_map(
        lambda state, t: jax.jvp(step, (state,), (t,))[1],
        mesh=comm.mesh, in_specs=(spec, spec), out_specs=spec))(state, tangents)


def _assert_the_same(got, want):
    for name, a, b in zip(sw.SWState._fields, got, want):
        assert bool(jnp.isfinite(a).all()), name
        assert _rel(a, b) < CLOSE, (name, _rel(a, b))


@pytest.mark.parametrize("nu", [0.2, 0.0])
@pytest.mark.parametrize("walk", sorted(WALKS))
@pytest.mark.parametrize("mesh_shape", MESHES)
def test_the_tangent_kernel_is_the_array_codes_tangent(
        mesh_shape, walk, nu, monkeypatch):
    """A walk of the kernel under ``jax.jvp``, on tangents that are
    random on every cell of the six arrays it reads: the tangents of its
    results are those of the array code, on every cell (the state is
    read whole: with friction ring 2 of ``u``, ``v`` is the neighbours'
    round 1, which one exchange more of the new tendencies' tangents
    completes).  A step in the middle of a run, a run's first, and a
    walk of two, which is the tangent kernel twice at the state the
    forward kernel makes again between, not in place."""
    comm = _comm(mesh_shape)
    cfg = _Viscous(ny=10 * mesh_shape[0], nx=20 * mesh_shape[1], nu=nu, **UNIT)
    pushes, walks = _noted(monkeypatch)
    state, tangents = _random(comm, cfg, seed=60)
    kernels, arrays = _steps(cfg, comm, **WALKS[walk])
    got = _pushed(comm, kernels, state, tangents)
    assert len(pushes) == (2 if walk == "two" else 1)
    assert walks.count((1, False)) == (walk == "two")
    assert all(in_place for steps, in_place in walks if steps == 2)
    _assert_the_same(got, _pushed(comm, arrays, state, tangents))


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
    pushes, _walks = _noted(monkeypatch)
    if tile:
        _tiles_of(monkeypatch, tile, cfg.ny + 2 * G, nx + 2 * G)
    state, tangents = _random(comm, cfg, seed=61)
    kernels, arrays = _steps(cfg, comm)
    got = _pushed(comm, kernels, state, tangents)
    assert pushes == [(cfg.ny + 2 * G, nx + 2 * G)]
    _assert_the_same(got, _pushed(comm, arrays, state, tangents))


@pytest.mark.parametrize("walls", sorted(WALLS))
@pytest.mark.parametrize("nu", [0.2, 0.0])
@pytest.mark.parametrize("a, b", [(1.6, -0.6), (1.0, 0.0)])
def test_the_two_derivative_kernels_are_each_others_transpose(
        walls, nu, a, b):
    """``<wide_step_jvp(t), w> == <t, wide_step_vjp(w)>`` at one kept
    state, the kernels called bare on one block that stands at the
    southern wall, the northern, both or neither: ``t`` and ``w`` random
    on every cell of the six arrays, ghost cells and wall rows included
    (what either reads of a ghost cell the other writes there).
    Adams-Bashforth's step and a run's first."""
    rng = np.random.default_rng(62)
    rows, width = 28, 44
    cfg = _Viscous(ny=rows - 2 * G, nx=width - 2 * G, nu=nu, **UNIT)
    south, north = WALLS[walls]

    def block(mean, spread):
        return jnp.asarray(mean + spread * rng.normal(size=(rows, width)), jnp.float32)

    kept = block(1.0, 0.1), block(0.0, 0.5), block(0.0, 0.5)
    t = tuple(block(0.0, 1.0) for _ in range(6))
    w = tuple(block(0.0, 1.0) for _ in range(6))
    scalars = (jnp.bool_(south), jnp.bool_(north), 3.0, a, b)
    pushed = sw_kernels.wide_step_jvp(*kept, t, *scalars, **_keywords(cfg))
    pulled = sw_kernels.wide_step_vjp(*kept, w, *scalars, **_keywords(cfg))

    def dot(xs, ys, of=lambda x: x):
        return sum(float(jnp.vdot(of(x), of(y))) for x, y in zip(xs, ys))

    assert all(bool(jnp.isfinite(x).all()) for x in (*pushed, *pulled))
    assert abs(dot(pushed, w)) > 1.0
    # float32's rounding of a sum of thousands of products of either
    # sign, against the sum of their sizes
    assert dot(pushed, w) == pytest.approx(
        dot(t, pulled), abs=1e-6 * dot(t, pulled, of=jnp.abs))


@pytest.mark.parametrize("mesh_shape", [(1, 1), (2, 2)])
def test_a_state_nobody_reads_whole_is_right_where_it_is_read(
        mesh_shape, monkeypatch):
    """``read_whole=False``, a sweep's steps: the tangents are the array
    code's on the interior of all six arrays, which is what the next
    step (through its exchanges) and the observation's mean read."""
    comm = _comm(mesh_shape)
    cfg = _Viscous(ny=10 * mesh_shape[0], nx=20 * mesh_shape[1], nu=0.2, **UNIT)
    pushes, _walks = _noted(monkeypatch)
    state, tangents = _random(comm, cfg, seed=63)
    _kernels, arrays = _steps(cfg, comm)

    def kernels(state):
        return sw._step_wide(state, cfg, comm, read_whole=False)[0][0]

    got = _pushed(comm, kernels, state, tangents)
    assert len(pushes) == 1
    want = _pushed(comm, arrays, state, tangents)
    py, px = mesh_shape
    ny_l, nx_l = cfg.local_interior(comm)
    inside = np.zeros((ny_l + 2 * G, nx_l + 2 * G), bool)
    inside[G:-G, G:-G] = True
    inside = np.tile(inside, (py, px))
    for name, a, b in zip(sw.SWState._fields, got, want):
        a, b = np.asarray(a)[inside], np.asarray(b)[inside]
        assert _rel(a, b) < CLOSE, name


def test_a_block_without_room_pushes_its_array_code(monkeypatch):
    """Where the derivative walks' fifteen arrays have no tile in VMEM
    but the step's six have, the step is the kernel and its tangent
    ``jax.jvp`` of its array code, decided from the block's shape: no
    switch of any kind."""
    comm = _comm((1, 1))
    cfg = _Viscous(ny=12, nx=20, nu=0.2, **UNIT)
    pushes, walks = _noted(monkeypatch)
    # a strip of the step's six arrays fits, one of the derivative's does not
    row_bytes = sw_kernels._whole_registers(cfg.nx + 2 * G) * 4
    monkeypatch.setattr(
        sw_kernels, "_VMEM_BLOCK_BUDGET", 5 * 6 * row_bytes * sw_kernels.STRIP)
    rows, width = cfg.ny + 2 * G, cfg.nx + 2 * G
    assert sw_kernels.adjoint_tile_rows(rows, width, jnp.float32) == 0
    monkeypatch.setattr(
        sw, "_runs_as_kernels", lambda cfg, comm: sw_kernels.tile_rows(
            rows, width, jnp.float32, 6) > 0)
    assert not sw._derives_as_kernels(cfg, comm)
    state, tangents = _random(comm, cfg, seed=64)
    kernels, arrays = _steps(cfg, comm)
    got = _pushed(comm, kernels, state, tangents)
    assert walks and not pushes
    _assert_the_same(got, _pushed(comm, arrays, state, tangents))


@pytest.mark.parametrize("how", ["kernel", "no room", "array code"])
def test_the_inner_loop_says_what_pushes_its_walks(how, monkeypatch):
    """``InnerLoop.tangent_walks``: the predicate's say, on the host,
    before any program has run: ``"kernel"`` where the step's derivative is the kernels',
    ``"arrays"`` for a block without room for them and where the step is
    array code to begin with (this CPU's own)."""
    comm = _comm((1, 1))
    cfg = sw.SWConfig(ghost=2, ny=12, nx=20, dx=2500.0, dy=2500.0)
    pushes = []
    if how != "array code":
        pushes, _walks = _noted(monkeypatch)
    if how == "no room":
        monkeypatch.setattr(sw_kernels, "_VMEM_BLOCK_BUDGET", 5 * 6 * 128 * 4 * 8)
        assert sw_kernels.tile_rows(16, 24, jnp.float32, 6) == 8
    rng = np.random.default_rng(65)
    at = tuple(jnp.asarray(mean + 0.1 * rng.normal(size=(cfg.ny, cfg.nx)), jnp.float32)
               for mean in (100.0, 0.0, 0.0))
    obs = jnp.asarray(100 + 0.1 * rng.normal(size=(2, cfg.ny, cfg.nx)), jnp.float32)
    fit = sw.InnerLoop(cfg, comm, calls=1, num_steps=2, weight=0.11)
    assert fit.tangent_walks == ("kernel" if how == "kernel" else "arrays")
    fit.linearise(*at, obs)
    fit.iterate()
    fit.wait()
    assert bool(pushes) == (how == "kernel")
    assert all(np.isfinite(fit.costs()))
