"""The walk of the step's kernel that also writes its row sums against
the plain walk (``tests/test_sw_kernels.py`` says what runs where; a
file of its own because its 60 interpreted cases are minutes of one
worker)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi4jax_tpu.models import shallow_water as sw
from mpi4jax_tpu.models import sw_kernels
from tests.sw_kernels_cases import (
    SHAPES, UNIT, G, _budget, _interpreted, _ring, _Viscous,
)


# rows x width whose interior rows divide by `coarsen`, for the walk that
# writes its row sums: the widths above that do (ghost columns inside
# their registers and in a register that they fill), tiles of one strip
# (every group astride two) and of three (a block of sums four tiles
# long), and 129 columns, whose eastern ghost columns lie astride two
# vector registers, on 36 rows
SUMMED = [
    ("astride-36x129", 2), ("astride-36x129", 4), ("astride-36x129", 8),
    ("tiles-of-8-100x140", 2), ("tiles-of-8-100x140", 4),
    ("tiles-of-8-100x140", 8), ("tiles-of-24-100x140", 4),
    ("tiles-of-24-100x140", 8), ("ragged-52x100", 4), ("aligned-36x256", 8),
]


def _rows_summed(field, coarsen):
    """The sums over ``coarsen`` rows of the interior rows of a padded
    block, whole width, in the kernel's order of additions: neighbours
    first, then neighbouring pairs, then fours."""
    rows = np.asarray(field)[G:-G]
    parts = [rows[k::coarsen] for k in range(coarsen)]
    while len(parts) > 1:
        parts = [a + b for a, b in zip(parts[::2], parts[1::2])]
    return parts[0]


@pytest.mark.parametrize("nu", [0.2, 0.0], ids=["nu", "nu0"])
@pytest.mark.parametrize("walk", ["one", "two", "lone"])
@pytest.mark.parametrize("shape,coarsen", SUMMED, ids=lambda x: str(x))
def test_a_walk_that_writes_its_row_sums_returns_the_plain_walks_state(
        shape, coarsen, walk, nu, monkeypatch):
    """``wide_step(coarsen=c, sums=room)``: the six arrays of the state
    bit for bit, ghosts and all, what the walk without returns (with
    the sums switched off by ``summing`` too), and after them, written
    into the room the caller brought (of ``row_sums_shape``, the same
    for a walk of one step and of two),
    the sums over ``c`` rows of the new ``h``, ``u``, ``v``: row ``1 +
    m`` of a field's sums is, exactly, the sum in the kernel's order of
    the block's rows ``2 + c m`` on, ghost columns included; the rows
    before and after are nobody's."""
    if shape == "astride-36x129":
        monkeypatch.setitem(SHAPES, shape, (36, 129, None))
    steps = 1 if walk == "one" else 2
    rows, width = _budget(monkeypatch, shape, steps=steps)
    cfg = _Viscous(ny=rows - 2 * G, nx=width - 2 * G, nu=nu, **UNIT)
    keys = jax.random.split(jax.random.PRNGKey(4), 6)
    fields = [
        mean + spread * jax.random.normal(key, (rows, width), jnp.float32)
        for key, mean, spread in zip(keys, (1.0, 0.0, 0.0), (0.1, 0.5, 0.5))]
    inner = ~(_ring((rows, width), 1) | _ring((rows, width), 2))
    old = [jnp.where(inner, 0.5 * jax.random.normal(key, (rows, width)), 0)
           for key in keys[3:]]
    wall = jnp.bool_(True)
    assert (sw_kernels.tile_rows(rows, width, jnp.float32, 6, 1)
            == sw_kernels.tile_rows(rows, width, jnp.float32, 6, 2))

    def walked(coarsen, summing=True):
        def run(*state):
            slabs = tuple((x[:, -2 * G:-G], x[:, G:2 * G], None, None)
                          for x in state[:3])
            # the room: whatever it holds, here something no sum is
            room = coarsen and [jnp.full(sw_kernels.row_sums_shape(
                (rows, width), jnp.float32, coarsen), jnp.nan)] * 3
            return sw_kernels.wide_step(
                *state, slabs, wall, wall, 0, cfg.ab_a, cfg.ab_b, walk == "lone",
                summing, room or (), steps=steps, coarsen=coarsen, **_interpreted(cfg))

        # unoptimised, as two programs are compared bit for bit
        return [np.asarray(x) for x in jax.jit(run, compiler_options={
            "xla_backend_optimization_level": 0})(*fields, *old)]

    want, got = walked(0), walked(coarsen)
    assert len(want) == 6 and len(got) == 9
    for name, x0, a, b in zip(sw.SWState._fields, [*fields, *old], got, want):
        assert np.abs(b - np.asarray(x0))[inner].max() > 1e-3, name
        np.testing.assert_array_equal(a, b, err_msg=name)
    groups = (rows - 2 * G) // coarsen
    for name, x, sums in zip("huv", got[:3], got[6:]):
        assert sums.shape == sw_kernels.row_sums_shape(
            (rows, width), jnp.float32, coarsen), name
        assert sums.shape[1] == width and sums.shape[0] >= groups + 1, name
        np.testing.assert_array_equal(
            sums[1:1 + groups], _rows_summed(x, coarsen), err_msg=name)
    if walk == "two" and nu:
        # the same kernel with its sums switched off, as the walks of a
        # call's loop run it: the state again, the sums nobody's
        for name, a, b in zip(sw.SWState._fields, walked(coarsen, False), want):
            np.testing.assert_array_equal(a, b, err_msg=name)
