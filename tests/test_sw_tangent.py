"""The linearised run, forwards: ``jax.jvp`` through the solver's own
programs (a step, a call of ten) against central differences in float64
and against ``jax.jvp`` of the plain ``jax.numpy`` solver, as array code
on every schedule and through the interpreted kernel, on meshes of one
to four devices; ``jax.jacfwd`` against ``jax.jacrev``.  The window's
sweeps and their product are ``tests/test_sw_product.py``'s, the inner
loop ``tests/test_sw_inner_loop.py``'s; both take their helpers from
here.  CPU meshes, small grids."""

import functools
import importlib.util
import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mpi4jax_tpu as m
from mpi4jax_tpu.models import shallow_water as sw
from mpi4jax_tpu.models import sw_kernels

ROOT = pathlib.Path(__file__).resolve().parents[1]
CALLS, STEPS, OBSERVE = 2, 3, 2
CFG = dict(ny=44, nx=48, dx=2500.0, dy=2500.0)
MESHES = [(1, 1), (2, 1), (2, 2)]


def _comm(mesh_shape):
    py, px = mesh_shape
    mesh = jax.make_mesh(
        mesh_shape, ("y", "x"), axis_types=(jax.sharding.AxisType.Auto,) * 2,
        devices=jax.devices()[:py * px])
    return m.MeshComm.from_mesh(mesh)


@functools.cache
def _reference():
    path = ROOT / "perfbench/references/shallow-water-incremental.py"
    spec = importlib.util.spec_from_file_location("plain_incremental", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _parameters(cfg):
    model = dict(gravity=cfg.gravity, depth=cfg.depth, coriolis_f=cfg.coriolis_f,
                 coriolis_beta=cfg.coriolis_beta, ab_a=cfg.ab_a, ab_b=cfg.ab_b)
    return _reference().parameters(model, cfg.dx, cfg.dy)


def _seeded(ny, nx, dtype, seed=59, comm=None):
    """A jet with noise on all three fields, a direction of order one,
    and observations near ``h``; on ``comm``'s mesh where given."""
    rng = np.random.default_rng(seed)
    y = (np.arange(ny)[:, None] + 0.5) / ny
    fields = (
        100 + 0.2 * rng.normal(size=(ny, nx)),
        10 * np.exp(-((y - 0.5) ** 2) / 0.02) + 0.1 * rng.normal(size=(ny, nx)),
        0.1 * rng.normal(size=(ny, nx)))
    direction = tuple(rng.normal(size=(ny, nx)) for _ in range(3))
    obs = 100 + 0.2 * rng.normal(size=(CALLS + 1, ny // OBSERVE, nx // OBSERVE))
    made = (tuple(jnp.asarray(a, dtype) for a in fields),
            tuple(jnp.asarray(a, dtype) for a in direction),
            jnp.asarray(obs, dtype))
    if comm is None:
        return made
    put = lambda a: jax.device_put(a, jax.NamedSharding(  # noqa: E731
        comm.mesh, jax.P(*([None] * (a.ndim - 2)), *comm.axes)))
    return jax.tree.map(put, made)


def _rel(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def _interpreted(monkeypatch):
    """The step forced through its kernels, interpreted
    (``tests/test_sw_adjoint.py`` does the same)."""
    wide_step, wide_step_vjp = sw_kernels.wide_step, sw_kernels.wide_step_vjp
    wide_step_jvp = sw_kernels.wide_step_jvp
    for kernel in (wide_step, wide_step_vjp, wide_step_jvp):
        kernel.clear_cache()
    walks = []

    def forwards(*args, **kwargs):
        walks.append(kwargs["steps"])
        return wide_step(*args, **dict(kwargs, interpret=True))

    monkeypatch.setattr(sw_kernels, "wide_step", forwards)
    monkeypatch.setattr(
        sw_kernels, "wide_step_vjp",
        lambda *args, **kwargs: wide_step_vjp(*args, **dict(kwargs, interpret=True)))
    monkeypatch.setattr(
        sw_kernels, "wide_step_jvp",
        lambda *args, **kwargs: wide_step_jvp(*args, **dict(kwargs, interpret=True)))
    monkeypatch.setattr(sw, "_runs_as_kernels", lambda cfg, comm: True)
    # Pallas's interpreter slices blocks at indices that vary over no
    # mesh axis, which shard_map's checker refuses
    monkeypatch.setattr(
        jax, "shard_map", functools.partial(jax.shard_map, check_vma=False))
    return walks


def _solver(cfg, comm, steps):
    """``(h0, u0, v0) -> (h, u, v)``, interiors: the first step and a
    call of ``steps - 1`` steps by the programs every forward cell runs."""
    state_of = sw.make_state(cfg, comm)
    first = sw.make_first_step(cfg, comm)
    multi = sw.make_multistep(cfg, comm, steps - 1) if steps > 1 else (lambda s: s)
    interior = sw.make_snapshot(cfg, comm, sw.Snapshot(coarsen=1))

    def run(h0, u0, v0):
        state = multi(first(state_of(h0, u0, v0)))
        return interior(state.h, state.u, state.v)

    return run


# -- jax.jvp through the solver's programs ---------------------------------


@pytest.mark.parametrize("steps", [1, 11])
@pytest.mark.parametrize("ghost", [1, 2, 4])
def test_jvp_through_the_programs_is_the_central_difference(ghost, steps):
    """In float64 on a 2x2 mesh: ``(f(x + e d) - f(x - e d)) / 2e``
    against ``jax.jvp``, the error falling as ``e^2``."""
    with jax.enable_x64(True):
        comm = _comm((2, 2))
        cfg = sw.SWConfig(ghost=ghost, dtype="float64", **CFG)
        at, d, _ = _seeded(cfg.ny, cfg.nx, jnp.float64)
        run = _solver(cfg, comm, steps)
        _, pushed = jax.jvp(run, at, d)
        errors = []
        for e in (1e-2, 1e-3):
            plus = run(*(a + e * b for a, b in zip(at, d)))
            minus = run(*(a - e * b for a, b in zip(at, d)))
            errors.append(max(
                _rel((p - q) / (2 * e), t) for p, q, t in zip(plus, minus, pushed)))
        assert errors[0] < 1e-3 and errors[1] < errors[0] / 50, errors


@pytest.mark.parametrize("steps", [1, 11])
@pytest.mark.parametrize("path", ["arrays", "kernel"])
@pytest.mark.parametrize("mesh_shape", MESHES)
def test_jvp_through_the_programs_is_the_plain_solvers(
        mesh_shape, path, steps, monkeypatch):
    """``jax.jvp``, ``jax.linearize`` through a step and a call against
    ``jax.jvp`` of the plain ``jax.numpy`` solver: as array code, and
    where the step is the kernel (interpreted: the first step a walk of
    two with one passed over, the call walks of two, each step's tangent
    the tangent kernel's at the fields the step started from)."""
    comm = _comm(mesh_shape)
    cfg = sw.SWConfig(ghost=2, **CFG)
    at, d, _ = _seeded(cfg.ny, cfg.nx, jnp.float32)
    walks = _interpreted(monkeypatch) if path == "kernel" else None
    run = _solver(cfg, comm, steps)
    out, pushed = jax.jvp(run, at, d)
    if path == "kernel":
        # the call's walks of two, and for each the state between its
        # two steps made again by a walk of one, which the tangent
        # kernel's second step is taken at
        assert walks and set(walks) == ({2} if steps == 1 else {1, 2})
    ref = _reference()
    want_out, want = jax.jvp(
        lambda *fields: ref.run(*fields, _parameters(cfg), steps), at, d)
    for got, w in zip(out, want_out):
        np.testing.assert_allclose(np.asarray(got), np.asarray(w), atol=2e-4)
    # float32's rounding but at the northern wall's rows, where on a
    # field this rough the plain solver's u and v stand 6e-5 from the
    # program's after one step, in float64 too (every schedule's alike;
    # the accepted `check.limits` hold it), and their tangents likewise
    for got, w in zip(pushed, want):
        assert _rel(got, w) < 1e-4
        assert _rel(got[:-2], w[:-2]) < 2e-5
    _, linear = jax.linearize(run, *at)
    for got, w in zip(linear(*d), pushed):
        assert _rel(got, w) < 1e-6


def test_jacfwd_through_a_step_is_jacrevs_matrix():
    """``jax.jacfwd`` and ``jax.jacrev`` of a scalar function of a few
    cells through a step on a 2x2 mesh: the same numbers, by the tangent
    and by the adjoint exchange."""
    comm = _comm((2, 2))
    cfg = sw.SWConfig(ghost=2, ny=8, nx=8, dx=2500.0, dy=2500.0)
    at, _, _ = _seeded(cfg.ny, cfg.nx, jnp.float32)
    run = _solver(cfg, comm, 2)

    def of_a_corner(corner):
        h0 = at[0].at[3:5, 3:5].set(corner)  # astride all four devices
        return jnp.stack([jnp.sum(x[2:6, 2:6] ** 2) for x in run(h0, *at[1:])])

    corner = at[0][3:5, 3:5]
    forwards = jax.jacfwd(of_a_corner)(corner)
    backwards = jax.jacrev(of_a_corner)(corner)
    assert forwards.shape == (3, 2, 2) and float(jnp.abs(forwards).min()) > 0
    np.testing.assert_allclose(
        np.asarray(forwards), np.asarray(backwards), rtol=1e-4)


# -- whose an instruction is --------------------------------------------------


def _named_elsewhere(text):
    """The ``op_name`` of every instruction of a compiled text that is no
    parameter and lies under no ``sw/adjoint`` scope."""
    return [name for line in text.splitlines() if " parameter(" not in line
            for name in re.findall(r'op_name="([^"]*)"', line)
            if "sw/adjoint/" not in name]


def test_jax_names_a_loops_scatter_add_without_the_scopes_around_it():
    """Why the solver's array code adds into a block's interior by a
    slice, a sum and a write (``_add_inside``) and not by ``x.at[].add``:
    a scatter's own ``add`` is an instruction of a nested computation,
    and inside a loop's body jax 0.9 names it without the scopes around
    it, with or without a derivative, none of this library's code
    involved; XLA then makes the scatter a slice, that ``add`` and a
    write, and a trace cannot say whose the ``add`` is.  (Should a later
    jax name it in full this fails, and ``_add_inside`` may go.)"""
    def f(x, y):
        return x.at[2:-2, 2:-2].add(3.0 * y) * 2.0

    def g(x, y):
        with jax.named_scope("outer/scope"):
            return jax.lax.fori_loop(0, 3, lambda i, c: f(c, y), x)

    x, y = jnp.ones((8, 8)), jnp.ones((4, 4))
    names = re.findall(
        r'op_name="([^"]*)"', jax.jit(g).lower(x, y).compile().as_text())
    assert "add" in names  # the scatter's own, bare
    assert all("outer/scope" in name for name in names
               if name not in ("add", "x", "y"))


@pytest.mark.parametrize("G", [1, 2])
def test_adding_inside_is_the_scatter_add_and_its_derivatives(G):
    rng = np.random.default_rng(59)
    a = jnp.asarray(rng.normal(size=(12, 16)), jnp.float32)
    inner = jnp.asarray(rng.normal(size=(12 - 2 * G, 16 - 2 * G)), jnp.float32)
    ta, ti = jnp.cos(a), jnp.sin(inner)

    def scatter(a, inner):
        return a.at[G:-G, G:-G].add(inner)

    ours = functools.partial(sw._add_inside, G=G)
    np.testing.assert_array_equal(ours(a, inner), scatter(a, inner))
    for got, want in zip(jax.jvp(ours, (a, inner), (ta, ti)),
                         jax.jvp(scatter, (a, inner), (ta, ti))):
        np.testing.assert_array_equal(got, want)
    for got, want in zip(jax.vjp(ours, a, inner)[1](ta),
                         jax.vjp(scatter, a, inner)[1](ta)):
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("pushed", ["the walk's array code", "the step's own"])
def test_the_array_code_that_is_pushed_forwards_names_every_instruction(pushed):
    """``jax.jvp`` of the kernel walk's array code (``_walk_as_arrays``,
    which adds by ``_add_inside``) in a loop's body under a scope: every
    instruction of the compiled program that is named lies under the
    scope, so a trace places each event of a tangent sweep by its
    ``op_name``; the step's own array code, which keeps its scatters (an
    undifferentiated program is what it was), leaves ``jvp()/add`` there.
    The compiled tangent sweep is pinned for a described v5e in
    ``tests/test_tpu_compile.py``."""
    comm = _comm((1, 1))
    cfg = sw.SWConfig(ghost=2, **CFG)
    state = sw.SWState(*sw.make_init(cfg, comm)())
    how = dict(cfg=cfg, comm=comm, first_step=False, steps=1)

    def walk(state):
        if pushed == "the step's own":
            return sw._step_wide_arrays(
                state, cfg, comm, False, m.create_token())[0][0]
        padded = sw.SWState(*state[:3], *(jnp.pad(a, 2) for a in state[3:]))
        out = sw._walk_as_arrays(padded, m.create_token(), **how)[0]
        return sw.SWState(*out[:3], *(a[2:-2, 2:-2] for a in out[3:]))

    def sweep(state, t):
        with jax.named_scope("sw/adjoint/tangent"):
            return jax.lax.fori_loop(
                0, 3, lambda i, c: jax.jvp(walk, (c[0],), (c[1],)),
                (sw.SWState(*state), sw.SWState(*t)))[1]

    spec = sw._mesh_specs(comm)
    text = jax.jit(jax.shard_map(
        sweep, mesh=comm.mesh, in_specs=(spec, spec), out_specs=spec)).lower(
            state, state).compile().as_text()
    elsewhere = {name for name in _named_elsewhere(text) if "shard_map" not in name}
    assert "sw/adjoint/tangent/" in text
    assert elsewhere == (set() if pushed != "the step's own" else {"jvp()/add"})
