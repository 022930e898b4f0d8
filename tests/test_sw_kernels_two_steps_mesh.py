"""A walk of the step's kernel over two time steps **with neighbours**:
from slabs four deep of the fields and the tendencies against two walks
of one with the two-deep exchange between them, on a global field cut by
hand into a 2 x 2 of blocks (``tests/test_sw_kernels.py`` says what runs
where; a file of its own, so a worker of its own: its interpreted cases
are minutes)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi4jax_tpu.models import shallow_water as sw
from mpi4jax_tpu.models import sw_kernels
from tests.sw_kernels_cases import UNIT, G, _budget, _interpreted, _Viscous

D = 2 * G  # the slabs' depth: the ring and as much again
MESH = (2, 2)


def _around(whole, iy, ix, interior, pad):
    """Device ``(iy, ix)``'s block of ``whole`` (the global interior
    between its walls' ``G`` ghost rows) with ``pad`` cells round its
    interior: periodic in x, zeros past the walls' ghost rows."""
    ny, nx = interior
    rows = np.arange(iy * ny - pad, (iy + 1) * ny + pad) + G
    cols = np.arange(ix * nx - pad, (ix + 1) * nx + pad) % whole.shape[1]
    inside = (rows >= 0) & (rows < whole.shape[0])
    block = np.asarray(whole)[np.clip(rows, 0, whole.shape[0] - 1)][:, cols]
    return np.where(inside[:, None], block, 0).astype(whole.dtype)


def _slabs(block, deep):
    """The ``(west, east, south, north)`` that ``halo_slabs_2d`` brings
    the block inside ``block`` (padded by ``deep``; the block by ``G``),
    ``deep`` deep."""
    e = deep - G
    rows = slice(e, block.shape[0] - e)
    return (block[rows, :deep], block[rows, -deep:], block[:deep], block[-deep:])


def _blocks(wholes, interior, deep):
    """Every device's padded blocks of the six ``wholes`` and their
    slabs ``deep`` deep, stacked over the devices in row-major order."""
    e = deep - G
    blocks, slabs = [], []
    for iy in range(MESH[0]):
        for ix in range(MESH[1]):
            around = [_around(x, iy, ix, interior, deep) for x in wholes]
            blocks.append([x[e:x.shape[0] - e, e:x.shape[1] - e] for x in around])
            slabs.append([_slabs(x, deep) for x in around])
    return tuple(jax.tree.map(lambda *xs: jnp.stack(xs), *per_device)
                 for per_device in (blocks, slabs))


def _whole(blocks, like):
    """The global arrays of the devices' ``blocks``' interiors, between
    ``like``'s walls' ghost rows."""
    out = []
    for x, old in zip(blocks, like):
        x = np.asarray(x)[:, G:-G, G:-G]
        x = x.reshape(*MESH, *x.shape[1:])
        rows = np.concatenate([np.concatenate(list(r), axis=1) for r in x], axis=0)
        out.append(np.concatenate([old[:G], rows, old[-G:]], axis=0))
    return out


@pytest.mark.parametrize("nu", [0.2, 0.0], ids=["nu", "nu0"])
@pytest.mark.parametrize("start", ["ab2", "euler"])
@pytest.mark.parametrize("shape", ["astride-33x129", "tiles-of-8-100x140"])
def test_a_walk_of_two_steps_from_deep_slabs_is_two_walks_with_an_exchange(
        shape, start, nu, monkeypatch):
    """On a 2 x 2 of blocks (periodic in x, so a neighbour on both sides;
    in y a neighbour on one side and a wall on the other: a block with a
    southern wall and one with a northern wall are both run)
    ``wide_step(steps=2)`` from slabs four deep of ``h, u, v, dh, du,
    dv`` returns, bit for bit on the interior of all six arrays, what
    two ``wide_step(steps=1)`` return with the two-deep exchange of ``h,
    u, v`` between them: the rings that exchange would bring the kernel
    makes itself, from the same inputs by the same code.  An
    Adams-Bashforth pair in the middle of a run, and the pair after a
    run's first step, with that forward-Euler step itself made both
    ways (a walk of one, and ``lone``: the walk of two with its first
    step passed over)."""
    rows, width = _budget(monkeypatch, shape, steps=2)
    interior = ny, nx = rows - 2 * G, width - 2 * G
    assert sw_kernels.holds_further(rows, width, jnp.float32, (G, G))
    cfg = _Viscous(ny=MESH[0] * ny, nx=MESH[1] * nx, nu=nu, **UNIT)
    keys = jax.random.split(jax.random.PRNGKey(5), 6)
    shape_whole = (MESH[0] * ny + 2 * G, MESH[1] * nx)
    fields = [
        np.asarray(mean + spread * jax.random.normal(key, shape_whole, jnp.float32))
        for key, mean, spread in zip(keys, (1.0, 0.0, 0.0), (0.1, 0.5, 0.5))]
    between_walls = np.zeros(shape_whole, bool)
    between_walls[G:-G] = True
    old = [np.where(between_walls, 0.5 * np.asarray(
        jax.random.normal(key, shape_whole, jnp.float32)), 0).astype(np.float32)
        for key in keys[3:]]
    is_south = jnp.asarray([iy == 0 for iy in range(MESH[0]) for _ in range(MESH[1])])
    is_north = jnp.asarray(
        [iy == MESH[0] - 1 for iy in range(MESH[0]) for _ in range(MESH[1])])
    first_row = jnp.asarray(
        [iy * ny for iy in range(MESH[0]) for _ in range(MESH[1])], jnp.float32)
    # the state a single walk carries: ring 1 of du and dv the
    # neighbours', the rest of the tendencies' ghost ring zero
    ring_1 = np.zeros((rows, width), bool)
    ring_1[G - 1:rows - G + 1, G - 1:width - G + 1] = True
    inner = np.zeros((rows, width), bool)
    inner[G:-G, G:-G] = True

    def carried(blocks):
        h, u, v, dh, du, dv = blocks
        return [h, u, v, jnp.where(inner, dh, 0), jnp.where(ring_1, du, 0),
                jnp.where(ring_1, dv, 0)]

    def walks(state, slabs, steps, a=cfg.ab_a, b=cfg.ab_b, lone=False):
        """Every device's walk, one after another in one program."""
        def walk(per_device):
            state, slabs, south, north, row = per_device
            return sw_kernels.wide_step(
                *state, slabs, south, north, row, a, b, lone, steps=steps,
                **_interpreted(cfg))
        return jax.lax.map(walk, (state, slabs, is_south, is_north, first_row))

    plain = {"xla_backend_optimization_level": 0}
    single = jax.jit(lambda state, slabs, a, b: walks(state, slabs, 1, a, b),
                     compiler_options=plain)
    double = jax.jit(
        lambda state, slabs, a, b, lone: walks(state, slabs, 2, a, b, lone),
        compiler_options=plain)

    def exchanged(state, like, deep):
        """The devices' blocks after the exchange a step starts with:
        the state as the walks returned it, and the slabs of ``h, u,
        v`` (``deep`` ``G``) or of all six arrays (``D``) cut from the
        global arrays of the blocks' interiors."""
        _, slabs = _blocks(_whole(state, like), interior, deep)
        return slabs[:3] if deep == G else slabs

    wholes = [*fields, *old]
    state, _ = _blocks(wholes, interior, G)
    state = carried(state)
    if start == "euler":
        zeros = [jnp.zeros_like(x) for x in state[3:]]
        rest = [*state[:3], *zeros]
        like = [*fields, *(np.zeros_like(x) for x in old)]
        state = single(rest, exchanged(rest, like, G), 1.0, 0.0)
        alone = double(rest, exchanged(rest, like, D), 1.0, 0.0, True)
        for name, a, b in zip(sw.SWState._fields, alone, state):
            a, b = (np.asarray(x)[:, G:-G, G:-G] for x in (a, b))
            np.testing.assert_array_equal(a, b, err_msg=name)
        wholes = like
    # two walks of one with the two-deep exchange between them
    between = single(state, exchanged(state, wholes, G), cfg.ab_a, cfg.ab_b)
    want = single(between, exchanged(between, wholes, G), cfg.ab_a, cfg.ab_b)
    # one walk of two from slabs four deep of all six arrays
    got = double(state, exchanged(state, wholes, D), cfg.ab_a, cfg.ab_b, False)
    for name, x0, a, b in zip(sw.SWState._fields, state, got, want):
        x0, a, b = (np.asarray(x)[:, G:-G, G:-G] for x in (x0, a, b))
        assert np.isfinite(b).all(), name
        assert np.abs(b - x0).max() > 0.01, name
        np.testing.assert_array_equal(a, b, err_msg=name)
    # and what the next single walk reads of the tendencies' ghosts:
    # ring 1 of du, dv, the step's own round 1 there
    for a, b in zip(got[4:], want[4:]):
        np.testing.assert_array_equal(
            np.where(ring_1, a, 0), np.where(ring_1, b, 0))


def test_a_block_without_lanes_to_spare_walks_one_step():
    """Rings 3 and 4 live in the lanes past a row's last column and in
    the last tile's rows past the field's last: a block whose columns
    fill their vector registers has none, and is refused."""
    assert not sw_kernels.holds_further(36, 256, jnp.float32, (G, G))
    assert sw_kernels.holds_further(36, 256, jnp.float32, (G, 0))
    assert sw_kernels.holds_further(36, 252, jnp.float32, (G, G))
    # fewer than a strip of interior rows: a ring of the neighbour's
    # computed here would feel the neighbour's other wall
    assert not sw_kernels.holds_further(11, 40, jnp.float32, (G, G))
    assert sw_kernels.holds_further(11, 40, jnp.float32, (0, G))
