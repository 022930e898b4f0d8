"""A walk of the step's kernel over two time steps against two walks of
one (``tests/test_sw_kernels.py`` says what runs where; a file of its
own because its 28 interpreted cases are minutes of one worker)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi4jax_tpu.models import shallow_water as sw
from mpi4jax_tpu.models import sw_kernels
from tests.sw_kernels_cases import (
    SHAPES, UNIT, G, _budget, _interpreted, _ring, _Viscous,
)


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
