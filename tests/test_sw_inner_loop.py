"""``InnerLoop``: conjugate gradients on a toy window against the
direct solve of the small dense ``A`` built column by column from
``make_product``, the loop's counters, costs and spans, its cap and its
new beginning.  CPU meshes, small grids."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi4jax_tpu.models import shallow_water as sw

from test_sw_tangent import (
    CALLS, CFG, OBSERVE, STEPS, _comm, _interpreted, _parameters, _reference,
    _rel, _seeded)


# -- the inner loop ----------------------------------------------------------


@pytest.mark.parametrize("mesh_shape", [(1, 1), (2, 2)])
def test_conjugate_gradients_reach_the_direct_solve(mesh_shape):
    """On a toy window (8x8 cells, 192 unknowns): ``A`` built column by
    column from ``make_product``, symmetric and positive definite, ``A
    x = b`` solved by numpy, and ``InnerLoop`` there in fewer iterations
    than unknowns, its quadratic cost falling to the minimum's."""
    comm = _comm(mesh_shape)
    cfg = sw.SWConfig(ghost=2, ny=8, nx=8, dx=2500.0, dy=2500.0)
    calls, steps, observe, weight = 1, 2, 2, 0.11
    rng = np.random.default_rng(4)
    y = (np.arange(cfg.ny)[:, None] + 0.5) / cfg.ny
    at = tuple(jnp.asarray(a, jnp.float32) for a in (
        100 + 0.2 * rng.normal(size=(cfg.ny, cfg.nx)),
        10 * np.exp(-((y - 0.5) ** 2) / 0.02) + 0.1 * rng.normal(size=(cfg.ny, cfg.nx)),
        0.1 * rng.normal(size=(cfg.ny, cfg.nx))))
    obs = jnp.asarray(100 + 0.2 * rng.normal(
        size=(calls + 1, cfg.ny // observe, cfg.nx // observe)), jnp.float32)
    fit = sw.InnerLoop(cfg, comm, calls=calls, num_steps=steps, observe=observe,
                       weight=weight, iterations=200)
    fit.linearise(*at, obs)
    b = np.concatenate([np.asarray(r).ravel() for r in fit.vectors[1]])
    n = b.size
    assert n == 3 * cfg.ny * cfg.nx

    def column(i):
        e = np.zeros(n, np.float32)
        e[i] = 1.0
        q = fit.product(*fit.fields, fit.starts,
                        *(jnp.asarray(x.reshape(cfg.ny, cfg.nx))
                          for x in np.split(e, 3)))
        return np.concatenate([np.asarray(x).ravel() for x in q])

    A = np.stack([column(i) for i in range(n)], axis=1).astype(np.float64)
    assert np.abs(A - A.T).max() < 1e-4 * np.abs(A).max()
    A = 0.5 * (A + A.T)
    assert np.linalg.eigvalsh(A).min() > 0.9 * weight
    want = np.linalg.solve(A, b.astype(np.float64))
    fit.iterate(80)
    fit.wait()
    got = np.concatenate([np.asarray(x).ravel() for x in fit.increment()])
    assert np.linalg.norm(got - want) < 2e-3 * np.linalg.norm(want)
    costs, curvatures = fit.costs(), fit.curvatures()
    assert len(costs) == 81 and len(curvatures) == 80
    assert all(b <= a for a, b in zip(costs, costs[1:])) and costs[5] < costs[0]
    # (a loop this small ends at a residual of nothing: p = 0 there)
    assert all(c > 0 for c in curvatures[:20])
    # J(dx) = J(0) - b.dx + dx.A dx / 2, at its minimum
    least = costs[0] - 0.5 * float(b @ want)
    assert costs[-1] == pytest.approx(least, rel=1e-4, abs=1e-5 * costs[0])
    stats = fit.stats()
    assert stats["iterations"] == 80 and stats["window_steps"] == 1 + calls * steps
    assert stats["vector_bytes"] == 4 * 3 * cfg.ny * cfg.nx * 4
    names = [s.name for s in fit.trace.spans()]
    assert names.count("incremental/enqueue") == 80
    assert "incremental/linearise" in names and "incremental/wait" in names


def test_the_loop_is_capped_and_begins_again():
    comm = _comm((1, 1))
    cfg = sw.SWConfig(ghost=2, ny=8, nx=16, dx=2500.0, dy=2500.0)
    at, _, obs = _seeded(cfg.ny, cfg.nx, jnp.float32)
    fit = sw.InnerLoop(cfg, comm, calls=CALLS, num_steps=STEPS, observe=OBSERVE,
                       weight=0.11, iterations=3)
    fit.linearise(*at, obs)
    fit.iterate(3)
    with pytest.raises(ValueError, match="capped at 3"):
        fit.iterate()
    first = fit.costs()
    fit.begin()
    assert fit.stats()["iterations"] == 0 and fit.costs() == first[:1]
    assert not any(float(jnp.abs(x).max()) for x in fit.increment())
    fit.iterate(3)
    assert fit.costs() == pytest.approx(first, rel=1e-6)
