"""The differentiated run: ``make_gradient`` against ``jax.grad`` of the
plain reference, the Taylor test, two checkpoint levels against plain
``jax.value_and_grad`` of the same window (built here),
the kernel's ``custom_vjp`` forced through the interpreted kernel
against plain AD, ``jax.grad`` through ``make_multistep``'s program on
every schedule, and the fit's counters.  CPU meshes, small grids;
float64 where a finite difference is taken."""

import functools
import importlib.util
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mpi4jax_tpu as m
from mpi4jax_tpu.models import shallow_water as sw
from mpi4jax_tpu.models import sw_kernels

ROOT = pathlib.Path(__file__).resolve().parents[1]
CALLS, STEPS, OBSERVE = 2, 3, 2


def _comm(mesh_shape):
    py, px = mesh_shape
    mesh = jax.make_mesh(
        mesh_shape, ("y", "x"), axis_types=(jax.sharding.AxisType.Auto,) * 2,
        devices=jax.devices()[:py * px])
    return m.MeshComm.from_mesh(mesh)


@functools.cache
def _reference():
    path = ROOT / "perfbench/references/shallow-water-adjoint.py"
    spec = importlib.util.spec_from_file_location("plain_adjoint", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _seeded(ny, nx, dtype, seed=54):
    """A jet, noise on all three fields, and observations near ``h``."""
    rng = np.random.default_rng(seed)
    y = (np.arange(ny)[:, None] + 0.5) / ny
    fields = (
        100 + 0.2 * rng.normal(size=(ny, nx)),
        10 * np.exp(-((y - 0.5) ** 2) / 0.02) + 0.1 * rng.normal(size=(ny, nx)),
        0.1 * rng.normal(size=(ny, nx)))
    obs = 100 + 0.2 * rng.normal(
        size=(CALLS + 1, ny // OBSERVE, nx // OBSERVE))
    return tuple(jnp.asarray(a, dtype) for a in (*fields, obs))


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


CFG = dict(ny=44, nx=48, dx=2500.0, dy=2500.0)


def _plain(cfg, comm):
    """``make_gradient``'s function as one program that keeps what jax
    keeps: plain ``jax.value_and_grad`` of the window's cost, the steps
    ``shallow_water_step`` one by one, from the window's own first step
    and misfit (``_window``).  No state is kept by hand and nothing is
    run again."""
    window = sw._window(cfg, comm, STEPS, OBSERVE)

    def local(h0, u0, v0, obs):
        def cost(h0, u0, v0):
            state = window.first(h0, u0, v0)
            total = window.misfit(state.h, obs[0])
            for k in range(CALLS):
                for _ in range(STEPS):
                    state = sw.shallow_water_step(state, cfg, comm)[0]
                total = total + window.misfit(state.h, obs[k + 1])
            return total

        mine, grads = jax.value_and_grad(cost, argnums=(0, 1, 2))(h0, u0, v0)
        total, _tok = m.allreduce(mine, m.SUM, comm=comm)
        return (total.reshape(1, 1), *grads)

    spec = jax.P(*comm.axes)
    return jax.jit(jax.shard_map(
        local, mesh=comm.mesh, in_specs=(spec,) * 3 + (jax.P(None, *comm.axes),),
        out_specs=(spec,) * 4))


@pytest.mark.parametrize("ghost", [1, 2, 4])
@pytest.mark.parametrize("mesh_shape", [(1, 1), (2, 2)])
def test_the_gradient_is_the_plain_references(mesh_shape, ghost):
    comm = _comm(mesh_shape)
    cfg = sw.SWConfig(ghost=ghost, **CFG)
    h0, u0, v0, obs = _seeded(cfg.ny, cfg.nx, jnp.float32)
    cost, *grads = sw.make_gradient(
        cfg, comm, calls=CALLS, num_steps=STEPS, observe=OBSERVE)(h0, u0, v0, obs)
    ref = _reference()
    model = dict(gravity=cfg.gravity, depth=cfg.depth, coriolis_f=cfg.coriolis_f,
                 coriolis_beta=cfg.coriolis_beta, ab_a=cfg.ab_a, ab_b=cfg.ab_b)
    want = ref.gradient(h0, u0, v0, obs, ref.parameters(model, cfg.dx, cfg.dy),
                        CALLS, STEPS, OBSERVE)
    # every device holds the mesh's sum
    assert cost.shape == mesh_shape
    np.testing.assert_allclose(np.asarray(cost), float(want[0]), rtol=1e-5)
    for got, w in zip(grads, want[1:]):
        assert got.shape == (cfg.ny, cfg.nx)
        assert _rel(got, w) < 1e-4


@pytest.mark.parametrize("ghost", [1, 2])
def test_the_taylor_test(ghost):
    """``(J(x + e d) - J(x - e d)) / 2e`` against ``<grad J, d>``: the
    error falls as ``e^2`` until rounding takes over."""
    with jax.enable_x64(True):
        comm = _comm((2, 2))
        cfg = sw.SWConfig(ghost=ghost, dtype="float64", **CFG)
        h0, u0, v0, obs = _seeded(cfg.ny, cfg.nx, jnp.float64)
        gradient = sw.make_gradient(
            cfg, comm, calls=CALLS, num_steps=STEPS, observe=OBSERVE)
        _cost, *grads = gradient(h0, u0, v0, obs)
        rng = np.random.default_rng(1)
        d = [jnp.asarray(rng.normal(size=h0.shape)) for _ in range(3)]
        slope = sum(float(jnp.vdot(g, di)) for g, di in zip(grads, d))
        errors = []
        for e in (1e-2, 1e-3, 1e-4):
            plus = gradient(h0 + e * d[0], u0 + e * d[1], v0 + e * d[2], obs)[0]
            minus = gradient(h0 - e * d[0], u0 - e * d[1], v0 - e * d[2], obs)[0]
            quotient = float(plus[0, 0] - minus[0, 0]) / (2 * e)
            errors.append(abs(quotient - slope) / abs(slope))
        assert errors[0] < 1e-3 and errors[2] < 1e-7, errors
        # second order: a step ten times smaller, an error a hundred times
        assert errors[1] < errors[0] / 50 and errors[2] < errors[1] / 50, errors


@pytest.mark.parametrize("ghost", [1, 2])
@pytest.mark.parametrize("mesh_shape", [(1, 1), (2, 2)])
def test_two_checkpoint_levels_give_what_none_gives(mesh_shape, ghost):
    """``make_gradient``'s two programs (the forward sweep, the backward
    sweep that runs every step again at its kept state) against the one
    program of plain ``jax.value_and_grad``: the same cost and the same
    gradient to float32's rounding.  Not bit for bit: the cotangents
    are the same sums added in another order (a state's, at a kept
    step, is handed over whole where jax adds its parts as they come),
    and the compiler fuses, and so rounds, three programs three ways."""
    comm = _comm(mesh_shape)
    cfg = sw.SWConfig(ghost=ghost, **CFG)
    args = _seeded(cfg.ny, cfg.nx, jnp.float32)
    two = sw.make_gradient(
        cfg, comm, calls=CALLS, num_steps=STEPS, observe=OBSERVE)
    assert hasattr(two, "forward") and hasattr(two, "backward")
    got, want = two(*args), _plain(cfg, comm)(*args)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]), rtol=2e-6)
    for a, b in zip(got[1:], want[1:]):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=0,
            atol=5e-5 * float(jnp.abs(b).max()))


def _through_the_interpreted_kernel(monkeypatch):
    """The step forced through its kernels, interpreted, the walk's and
    the adjoint walk's (``tests/test_sw_kernels.py`` does the same);
    returns the list the walks' ``steps`` are noted in, and the list of
    the adjoint kernel's calls."""
    walks, transposed = [], []
    wide_step, wide_step_vjp = sw_kernels.wide_step, sw_kernels.wide_step_vjp
    wide_step.clear_cache()
    wide_step_vjp.clear_cache()

    def interpreted(*args, **kwargs):
        walks.append(kwargs["steps"])
        return wide_step(*args, **dict(kwargs, interpret=True))

    def backwards(*args, **kwargs):
        transposed.append(args[0].shape)
        return wide_step_vjp(*args, **dict(kwargs, interpret=True))

    monkeypatch.setattr(sw_kernels, "wide_step", interpreted)
    monkeypatch.setattr(sw_kernels, "wide_step_vjp", backwards)
    monkeypatch.setattr(sw, "_runs_as_kernels", lambda cfg, comm: True)
    # Pallas's interpreter slices blocks at indices that vary over no
    # mesh axis, which shard_map's checker refuses
    monkeypatch.setattr(
        jax, "shard_map", functools.partial(jax.shard_map, check_vma=False))
    return walks, transposed


@pytest.mark.parametrize("mesh_shape", [(1, 1), (2, 1), (2, 2)])
def test_the_kernels_walk_differentiates_as_its_array_code(mesh_shape, monkeypatch):
    """Where the step is the kernel, ``_step_wide`` is a ``custom_vjp``:
    the kernel forwards (two steps a walk in the forward sweep, one in
    the backward sweep's second run, the first step's with one passed
    over), **the adjoint kernel** at the fields each step started from
    backwards (``sw_kernels.wide_step_vjp``; a walk of two: the forward
    kernel once more for the state between, the adjoint kernel twice).
    Against plain AD of the array code, to float32's rounding of the
    kernels."""
    comm = _comm(mesh_shape)
    cfg = sw.SWConfig(ghost=2, **CFG)
    args = _seeded(cfg.ny, cfg.nx, jnp.float32)
    want = _plain(cfg, comm)(*args)  # the array code, plain AD
    walks, transposed = _through_the_interpreted_kernel(monkeypatch)
    assert sw._walks_two_steps(cfg, comm) and sw._derives_as_kernels(cfg, comm)
    got = sw.make_gradient(
        cfg, comm, calls=CALLS, num_steps=STEPS, observe=OBSERVE)(*args)
    # a call's steps' derivative in its scan, each call's, and the first
    # step's: every one the adjoint kernel, on a device's padded block
    block = tuple(n + 2 * cfg.ghost for n in cfg.local_interior(comm))
    assert len(transposed) >= CALLS + 1 and set(transposed) == {block}
    # the forward sweep: the first step's walk (of two, one passed over)
    # and each call's (two steps, then the odd one); the backward sweep:
    # a call's steps one by one, traced once in its scan, for each call,
    # and the first step's again
    # (jax traces a scan's body more than once on its way to a vjp)
    ahead = 1 + 2 * CALLS
    assert walks[:ahead] == [2] + [2, 1] * CALLS and walks[-1] == 2
    assert set(walks[ahead:-1]) == {1} and len(walks[ahead:-1]) >= CALLS
    np.testing.assert_allclose(
        np.asarray(got[0]), np.asarray(want[0]), rtol=1e-6)
    for a, b in zip(got[1:], want[1:]):
        assert _rel(a, b) < 2e-5
    # and plain jax.value_and_grad through the same walks
    del transposed[:]
    for a, b in zip(_plain(cfg, comm)(*args)[1:], want[1:]):
        assert _rel(a, b) < 2e-5
    assert len(transposed) == 1 + CALLS * STEPS


@pytest.mark.parametrize("ghost", [1, 2, 4])
def test_jax_grad_goes_through_make_multisteps_program(ghost):
    """The program every forward cell runs, under ``jax.grad``: the
    ``fori_loop``, the first step's forward Euler and every exchange."""
    comm = _comm((2, 2))
    cfg = sw.SWConfig(ghost=ghost, **CFG)
    first, multi = sw.make_first_step(cfg, comm), sw.make_multistep(cfg, comm, 4)
    state = sw.make_init(cfg, comm)()

    def energy(h):
        out = multi(first(state._replace(h=h)))
        return 0.5 * jnp.sum(out.u ** 2 + out.v ** 2)

    slope = jax.grad(energy)(state.h)
    assert slope.shape == state.h.shape and bool(jnp.isfinite(slope).all())
    d = jnp.asarray(np.random.default_rng(2).normal(size=state.h.shape), jnp.float32)
    e = 1e-2
    quotient = float(energy(state.h + e * d) - energy(state.h - e * d)) / (2 * e)
    assert float(jnp.vdot(slope, d)) == pytest.approx(quotient, rel=5e-2)


def test_jax_grad_goes_through_the_kernels_double_walk(monkeypatch):
    """``make_multistep`` where the step is the kernel: a walk of two
    steps is differentiated a step at a time, the forward kernel once
    for the state between the two and the adjoint kernel twice: no
    array code is left on the kernel's path."""
    comm = _comm((1, 1))
    cfg = sw.SWConfig(ghost=2, **CFG)
    state = sw.make_first_step(cfg, comm)(sw.make_init(cfg, comm)())

    G = cfg.ghost

    def energy(multi, state, h):
        # of the interior: a kernel's walk leaves other ghosts behind
        out = multi(state._replace(h=h))
        return 0.5 * jnp.sum(out.u[G:-G, G:-G] ** 2 + out.v[G:-G, G:-G] ** 2)

    want = jax.grad(functools.partial(
        energy, sw.make_multistep(cfg, comm, 2), state))(state.h)
    walks, transposed = _through_the_interpreted_kernel(monkeypatch)
    monkeypatch.setattr(sw, "_walk_as_arrays", None)  # nobody calls it
    padded = sw.make_first_step(cfg, comm)(sw.make_init(cfg, comm)())
    got = jax.grad(functools.partial(
        energy, sw.make_multistep(cfg, comm, 2), padded))(padded.h)
    # the walk of two, and a walk of one for the state between its steps
    assert walks.count(2) >= 1 and 1 in walks and len(transposed) == 2
    assert _rel(got[G:-G, G:-G], want[G:-G, G:-G]) < 2e-5


def test_the_fits_counters():
    comm = _comm((2, 2))
    cfg = sw.SWConfig(ghost=2, **CFG)
    h0, u0, v0, obs = _seeded(cfg.ny, cfg.nx, jnp.float32)
    fit = sw.Descent(cfg, comm, calls=CALLS, num_steps=STEPS, observe=OBSERVE)
    cost, *grads = fit.gradient(h0, u0, v0, obs)
    norm2 = sum(float(jnp.vdot(g, g)) for g in grads)
    fit.start(h0, u0, v0, obs, 0.1 * float(cost[0, 0]) / norm2)
    fit.iterate(3)
    fit.wait()
    counted = fit.stats()
    window = 1 + CALLS * STEPS
    assert counted["gradients"] == 3 and counted["window_steps"] == window
    assert set(counted) == {
        "gradients", "window_steps", "trajectory_bytes", "costs"}
    # the array code's state on a 2x2 mesh: three padded fields and three
    # interior-shaped tendencies a device
    state = 4 * 3 * ((22 + 4) * (24 + 4) + 22 * 24) * 4
    assert counted["trajectory_bytes"] == (CALLS + STEPS) * state
    costs = counted["costs"]
    assert costs[0] == pytest.approx(float(cost[0, 0]))
    assert costs[2] < costs[1] < costs[0]
    names = [s.name for s in fit.trace.spans()]
    assert names == ["adjoint/enqueue"] * 3 + ["adjoint/wait"]


def test_observations_of_another_shape_are_refused():
    comm = _comm((1, 1))
    cfg = sw.SWConfig(ghost=2, **CFG)
    h0, u0, v0, obs = _seeded(cfg.ny, cfg.nx, jnp.float32)
    gradient = sw.make_gradient(cfg, comm, calls=CALLS, num_steps=STEPS, observe=OBSERVE)
    with pytest.raises(ValueError, match="observations of"):
        gradient(h0, u0, v0, obs[:-1])
    with pytest.raises(ValueError, match="does not divide"):
        sw.make_gradient(cfg, comm, calls=CALLS, num_steps=STEPS, observe=5)
