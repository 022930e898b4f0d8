"""The observation operator's transpose (``shallow_water._spread``,
``_observed``'s backward rule): a coarse cotangent spread over its
cells by a 0/1 matrix product and a broadcast of rows, against its
definition, the broadcast over ``[ny, c, nx, c]`` that it replaced
(written here), and against numpy's ``repeat``, bit for bit; the
dot-product identity with the block mean; and the gradient of a window
with either form.  CPU meshes, small blocks."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mpi4jax_tpu as m
from mpi4jax_tpu.models import shallow_water as sw

# interior cells of a block, rows x columns, for a coarse row that lies
# in one vector register's 128 columns, ends on a register's edge, and
# ends inside its second (at ``coarsen`` 2 and 4: 20 and 10, 128 and
# 64 or 128, 130 and 65 columns); the kernels' astride-33x129 and
# odd-21x40 kind
WIDTHS = {"single-40": 40, "edge-256": 256, "edge-512": 512, "astride-260": 260}
ROWS = 36


def _numpy_form(coarse, ghost, c):
    cells = np.repeat(np.repeat(coarse, c, 0), c, 1) * coarse.dtype.type(
        1.0 / (c * c))
    return np.pad(cells, ghost)


def _broadcast_form(coarse, ghost, c):
    """``_observed``'s transpose as it stood until PR 58, the
    definition: broadcast, reshape and pad (on a TPU its ``[ny, c, nx,
    c]`` lies ``c`` columns to a tile of 128 lanes)."""
    ny, nx = coarse.shape
    cells = jnp.broadcast_to(
        coarse[:, None, :, None], (ny, c, nx, c)).reshape(ny * c, nx * c)
    return jnp.pad(cells * jnp.asarray(1.0 / (c * c), cells.dtype), ghost)


def _coarse(shape, seed, dtype=np.float32):
    """Values of every size a float32 holds a cotangent at."""
    rng = np.random.default_rng(seed)
    return (rng.normal(size=shape) * 10.0 ** rng.integers(-8, 8, size=shape)
            ).astype(dtype)


def _comm(mesh_shape):
    py, px = mesh_shape
    mesh = jax.make_mesh(
        mesh_shape, ("y", "x"), axis_types=(jax.sharding.AxisType.Auto,) * 2,
        devices=jax.devices()[:py * px])
    return m.MeshComm.from_mesh(mesh)


def _transposed(ny, nx, ghost, c, coarse):
    """``_observed``'s backward rule at a padded block of ``ny`` x
    ``nx`` interior cells, jitted as the sweep holds it."""
    block = jnp.zeros((ny + 2 * ghost, nx + 2 * ghost), coarse.dtype)

    def transposed(block, coarse):
        _, vjp = jax.vjp(lambda b: sw._observed(b, ghost, c), block)
        return vjp(coarse)[0]

    return jax.jit(transposed)(block, coarse)


@pytest.mark.parametrize("width", sorted(WIDTHS))
@pytest.mark.parametrize("ghost", [1, 2])
@pytest.mark.parametrize("c", [1, 2, 4])
def test_the_spread_is_its_definition_bit_for_bit(c, ghost, width):
    """Through ``_observed``'s backward rule, every cell of the padded
    block: numpy's ``repeat`` of the cotangent over ``c * c`` with zeros
    round it, and the broadcast form's values."""
    ny, nx = ROWS, WIDTHS[width]
    coarse = _coarse((ny // c, nx // c), seed=57 + c)
    got = np.asarray(_transposed(ny, nx, ghost, c, jnp.asarray(coarse)))
    assert got.shape == (ny + 2 * ghost, nx + 2 * ghost)
    np.testing.assert_array_equal(got, _numpy_form(coarse, ghost, c))
    np.testing.assert_array_equal(
        got, np.asarray(_broadcast_form(jnp.asarray(coarse), ghost, c)))


@pytest.mark.parametrize("dtype", ["float32", "float64"])
@pytest.mark.parametrize("c", [3, 8])
def test_the_spread_over_any_cells_in_either_precision(c, dtype):
    """A mean over cells that are no power of two divides by a float
    that is none, once, as the definition does; float64 (the finite
    differences' precision) goes the same way."""
    with jax.enable_x64(dtype == "float64"):
        ghost, ny, nx = 2, 6 * c, 50 * c  # a register of coarse columns is not full
        coarse = _coarse((ny // c, nx // c), seed=c, dtype=np.dtype(dtype))
        got = sw._spread(jnp.asarray(coarse), ghost, c)
        assert got.dtype == coarse.dtype
        np.testing.assert_array_equal(
            np.asarray(got), _numpy_form(coarse, ghost, c))
        np.testing.assert_array_equal(
            np.asarray(got),
            np.asarray(_broadcast_form(jnp.asarray(coarse), ghost, c)))


def test_the_spread_holds_no_array_with_the_cells_for_its_last_dimension():
    """What the form is for: no array of the traced program ends in
    ``c`` (the broadcast form's ``[ny, c, nx, c]`` does), and the
    product is asked for at the highest precision."""
    text = str(jax.make_jaxpr(lambda a: sw._spread(a, 2, 2))(
        jnp.zeros((18, 130), jnp.float32)))
    assert ",2]" not in text and "Precision.HIGHEST" in text
    assert "f32[18,2,130,2]" in str(jax.make_jaxpr(
        lambda a: _broadcast_form(a, 2, 2))(jnp.zeros((18, 130), jnp.float32)))


@pytest.mark.parametrize("c", [2, 4])
def test_the_spread_is_the_block_means_transpose(c):
    """``<observe(x), y> = <x, observeT(y)>``, the products summed in
    float64: every cell's weight is in both or in neither."""
    ghost, ny, nx = 2, 48, 264
    rng = np.random.default_rng(c)
    x = rng.normal(size=(ny + 2 * ghost, nx + 2 * ghost)).astype(np.float32)
    y = rng.normal(size=(ny // c, nx // c)).astype(np.float32)
    mean = np.asarray(sw._block_mean(jnp.asarray(x), ghost, c), np.float64)
    spread = np.asarray(sw._spread(jnp.asarray(y), ghost, c), np.float64)
    left, right = np.sum(mean * y), np.sum(x.astype(np.float64) * spread)
    assert left == pytest.approx(right, rel=1e-6) and abs(left) > 1e-3


def test_a_cotangent_that_is_not_finite_spoils_its_register_and_no_more():
    """The one difference from the definition: 0 times a NaN is a NaN,
    so the 128 coarse columns multiplied beside it in its row read NaN;
    every other row and register reads what numpy reads."""
    coarse = _coarse((6, 300), seed=1)
    coarse[2, 130] = np.nan
    got = np.asarray(sw._spread(jnp.asarray(coarse), 2, 2))
    want = _numpy_form(coarse, 2, 2)
    spoiled = np.zeros(want.shape, bool)
    spoiled[2 + 2 * 2:2 + 2 * 3, 2 + 2 * 128:2 + 2 * 256] = True
    np.testing.assert_array_equal(got[~spoiled], want[~spoiled])
    assert np.isnan(got[spoiled]).all()


@pytest.mark.parametrize("mesh_shape", [(1, 1), (2, 2)])
def test_a_windows_gradient_is_the_broadcast_forms(monkeypatch, mesh_shape):
    """``make_gradient`` against the same programs with the broadcast
    form in the transpose's place, inside the model's ``shard_map``:
    the cost the same float, the three gradients to the rounding of two
    compilations of the sweep (the spread itself is the same bits, the
    tests above; the compiler fuses what stands round it in its own
    way)."""
    py, px = mesh_shape
    cfg = sw.SWConfig(ny=32 * py, nx=40 * px, dx=2500.0, dy=2500.0, ghost=2)
    comm = _comm(mesh_shape)
    rng = np.random.default_rng(57)
    fields = [jnp.asarray(a, jnp.float32) for a in (
        100 + 0.2 * rng.normal(size=(cfg.ny, cfg.nx)),
        0.5 * rng.normal(size=(cfg.ny, cfg.nx)),
        0.1 * rng.normal(size=(cfg.ny, cfg.nx)))]
    obs = jnp.asarray(
        100 + 0.2 * rng.normal(size=(3, cfg.ny // 2, cfg.nx // 2)), jnp.float32)
    got = sw.make_gradient(cfg, comm, calls=2, num_steps=2, observe=2)(
        *fields, obs)
    monkeypatch.setattr(sw, "_spread", _broadcast_form)
    plain = sw.make_gradient(cfg, comm, calls=2, num_steps=2, observe=2)(
        *fields, obs)
    assert float(got[0][0, 0]) == float(plain[0][0, 0])
    for a, b in zip(got[1:], plain[1:]):
        assert float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b)) < 1e-6
