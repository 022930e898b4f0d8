"""The step's kernel against the array code it replaces, block by block
(``tests/test_sw_kernels.py`` says what runs where; a file of its own
because its 112 interpreted cases are minutes of one worker)."""

import functools
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mpi4jax_tpu as m
from mpi4jax_tpu.models import shallow_water as sw
from mpi4jax_tpu.models import sw_kernels
from tests.sw_kernels_cases import (
    SHAPES, UNIT, WALLS, G, _as_a_step_finds_it, _budget, _interpreted, _ring,
    _Viscous,
)


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
