"""The linearised window's two sweeps: ``make_tangent`` and
``make_adjoint`` as each other's transpose, ``make_product`` against
``jax.jvp`` and ``jax.vjp`` of the same window built here from the same
parts and against the plain reference's, as array code and through the
interpreted kernel, 1x1 and 2x2; the adjoint sweep of the residuals is
the gradient.  CPU meshes, small grids."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi4jax_tpu.models import shallow_water as sw

from test_sw_tangent import (
    CALLS, CFG, OBSERVE, STEPS, _comm, _interpreted, _parameters, _reference,
    _rel, _seeded)


# -- the window's sweeps and their product ----------------------------------


def _parts(cfg, comm, weight):
    """``make_product``'s function as one program, by jax's own rules:
    ``jax.jvp`` then ``jax.vjp`` of the window's observed means, the
    steps ``shallow_water_step`` one by one from the window's own first
    step (``_window``); no state kept by hand."""
    window = sw._window(cfg, comm, STEPS, OBSERVE)

    def local(h0, u0, v0, ph, pu, pv):
        def seen(h0, u0, v0):
            state = window.first(h0, u0, v0)
            out = [sw._observed(state.h, cfg.ghost, OBSERVE)]
            for _ in range(CALLS):
                for _ in range(STEPS):
                    state = sw.shallow_water_step(state, cfg, comm)[0]
                out.append(sw._observed(state.h, cfg.ghost, OBSERVE))
            return jnp.stack(out)

        _, pushed = jax.jvp(seen, (h0, u0, v0), (ph, pu, pv))
        pulled = jax.vjp(seen, h0, u0, v0)[1](pushed)
        return (pushed, *(g + weight * p for g, p in zip(pulled, (ph, pu, pv))))

    spec = jax.P(*comm.axes)
    return jax.jit(jax.shard_map(
        local, mesh=comm.mesh, in_specs=(spec,) * 6,
        out_specs=(jax.P(None, *comm.axes),) + (spec,) * 3))


@pytest.mark.parametrize("path", ["arrays", "kernel"])
@pytest.mark.parametrize("mesh_shape", [(1, 1), (2, 2)])
def test_the_product_is_jvp_then_vjp_of_the_window(mesh_shape, path, monkeypatch):
    """``make_product``'s three programs (the tangent-linear sweep from
    the kept call starts, the adjoint sweep with its two checkpoint
    levels, ``weight p``) against one program of jax's own ``jvp`` and
    ``vjp`` of the same window, and against the plain reference's; the
    two sweeps are each other's transpose."""
    comm = _comm(mesh_shape)
    cfg = sw.SWConfig(ghost=2, **CFG)
    at, d, obs = _seeded(cfg.ny, cfg.nx, jnp.float32, comm=comm)
    weight = 0.11
    if path == "kernel":
        _interpreted(monkeypatch)
    how = dict(calls=CALLS, num_steps=STEPS, observe=OBSERVE)
    _, starts, _ = sw.make_gradient(cfg, comm, **how).forward(*at, obs)
    product = sw.make_product(cfg, comm, weight=weight, **how)
    seen = product.tangent(*at, starts, *d)
    got = product(*at, starts, *d)
    want_seen, *want = _parts(cfg, comm, weight)(*at, *d)
    assert seen.shape == (CALLS + 1, cfg.ny // OBSERVE, cfg.nx // OBSERVE)
    assert _rel(seen, want_seen) < 2e-5
    for a, b in zip(got, want):
        assert a.shape == (cfg.ny, cfg.nx) and _rel(a, b) < 5e-5
    one = jax.devices()[0]
    plain = _reference().product(
        *(jax.device_put(a, one) for a in (*at, *d)), _parameters(cfg), CALLS,
        STEPS, OBSERVE, weight)
    assert float(plain[0]) < 1e-5  # the reference's own adjoint test
    assert _rel(seen, plain[1]) < 2e-5
    for a, b in zip(got, plain[2:]):
        assert _rel(a, b) < 5e-5
    # <M p, w> == <p, M^T w>
    w = jnp.asarray(np.random.default_rng(3).normal(size=seen.shape), jnp.float32)
    back = product.adjoint(*at, starts, jax.device_put(w, obs.sharding))
    there = float(np.vdot(np.asarray(seen), np.asarray(w)))
    home = sum(float(jnp.vdot(a, b)) for a, b in zip(d, back))
    assert there == pytest.approx(home, rel=1e-5)


def test_the_adjoint_sweep_of_the_residuals_is_the_gradient():
    comm = _comm((2, 2))
    cfg = sw.SWConfig(ghost=2, **CFG)
    at, _, obs = _seeded(cfg.ny, cfg.nx, jnp.float32, comm=comm)
    how = dict(calls=CALLS, num_steps=STEPS, observe=OBSERVE)
    gradient = sw.make_gradient(cfg, comm, **how)
    _, *want = gradient(*at, obs)
    _, starts, last_h = gradient.forward(*at, obs)
    observe = sw.make_snapshot(
        cfg, comm, sw.Snapshot(fields=("h",), coarsen=OBSERVE))
    residuals = jnp.stack(
        [observe(s.h)[0] for s in starts] + [observe(last_h)[0]]) - obs
    got = sw.make_adjoint(cfg, comm, **how)(
        *at, starts, jax.device_put(residuals, obs.sharding))
    for a, b in zip(got, want):
        assert _rel(a, b) < 1e-5


def test_a_vector_of_another_shape_is_refused():
    comm = _comm((1, 1))
    cfg = sw.SWConfig(ghost=2, **CFG)
    at, d, obs = _seeded(cfg.ny, cfg.nx, jnp.float32)
    how = dict(calls=CALLS, num_steps=STEPS)
    _, starts, _ = sw.make_gradient(cfg, comm, observe=OBSERVE, **how).forward(*at, obs)
    with pytest.raises(ValueError, match="observation-space vector"):
        sw.make_adjoint(cfg, comm, observe=OBSERVE, **how)(*at, starts, obs[:-1])
    with pytest.raises(ValueError, match="does not divide"):
        sw.make_tangent(cfg, comm, observe=5, **how)
