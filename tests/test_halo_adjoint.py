"""The halo exchange differentiates both ways on the mesh tier: forwards
its tangent is the exchange of the tangents; backwards it transposes to
the adjoint exchange: ``jax.vjp``
through ``halo_exchange_2d``, ``halo_exchange_2d_batch`` and
``halo_slabs_2d`` on the mesh tier gives, for every ghost cell, its
cotangent added to the cell it was copied from (corners through both
shifts, a walled side's ghosts giving nothing back), with the token
threaded and returned.

The exchange is a gather, ``(E x)[cell] = x[source(cell)]``, and its
transpose the scatter-add over the same map: ``source`` is written here
from the definition in numpy, cell by cell, and knows nothing of slabs.
Fields hold small whole numbers, so every sum is exact in float32 and
both comparisons are equalities.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mpi4jax_tpu as m
from mpi4jax_tpu.ops._core import Token
from mpi4jax_tpu.parallel import halo
from mpi4jax_tpu.parallel.halo import (
    halo_exchange_2d, halo_exchange_2d_batch, halo_slabs_2d,
)

N = 8  # interior cells a device and axis
MESHES = [(1, 1), (2, 2), (2, 4)]
WIDTHS = [1, 2, 4]
PERIODIC = {"walled_y": (False, True), "periodic": (True, True),
            "walled": (False, False)}


def _comm(mesh_shape):
    py, px = mesh_shape
    mesh = jax.make_mesh(
        mesh_shape, ("y", "x"), axis_types=(jax.sharding.AxisType.Auto,) * 2,
        devices=jax.devices()[:py * px])
    return m.MeshComm.from_mesh(mesh)


def _sources(mesh_shape, w, periodic, nx=N):
    """``(x_source, source)``: for every cell of every device's padded
    block, as arrays of shape ``(py, px, H, W)`` of flat indices into
    such an array, the cell that the x shifts alone copy into it and the
    cell the whole exchange does (itself where nothing is copied)."""
    py, px = mesh_shape
    H, W = N + 2 * w, nx + 2 * w
    cells = np.arange(py * px * H * W).reshape(py, px, H, W)
    per_y, per_x = periodic

    def shifted(of, axis, n_dev, per):
        """Ghost cells along ``axis`` take the neighbour's edge cells."""
        out = of.copy()
        n = nx if axis == "x" else N
        for dev in range(n_dev):
            for side, nb in ((0, dev - 1), (1, dev + 1)):
                if not per and not 0 <= nb < n_dev:
                    continue  # a wall: the ghosts keep what they hold
                if n_dev == 1 and not per:
                    continue
                nb %= n_dev
                ghosts = np.arange(w) if side == 0 else np.arange(n + w, n + 2 * w)
                edge = ghosts + n if side == 0 else ghosts - n
                if axis == "x":
                    out[:, dev, :, ghosts] = of[:, nb, :, edge]
                else:
                    out[dev, :, ghosts, :] = of[nb, :, edge, :]
        return out

    x_source = shifted(cells, "x", px, per_x)
    # the y shifts carry rows of the x-exchanged block: E = Y . X
    return x_source, shifted(x_source, "y", py, per_y)


def _blocks(a, mesh_shape):
    """``(py * H, px * W)`` as ``(py, px, H, W)``."""
    py, px = mesh_shape
    H, W = a.shape[0] // py, a.shape[1] // px
    return np.asarray(a).reshape(py, H, px, W).transpose(0, 2, 1, 3)


def _numbers(shape, seed):
    return np.random.default_rng(seed).integers(-4, 5, shape).astype(np.float32)


def _sharded(fn, comm, n_in, n_out):
    spec = jax.P("y", "x")
    return jax.jit(jax.shard_map(
        fn, mesh=comm.mesh, in_specs=(spec,) * n_in, out_specs=(spec,) * n_out))


# a block wider than a lane tile: there the column slabs, a ghost
# region's zeros and an edge's added cotangent, land as the strips of
# whole lane tiles that hold them (parallel/halo.py _place)
WIDE = 300


@pytest.mark.parametrize("per", sorted(PERIODIC))
@pytest.mark.parametrize("w", WIDTHS)
@pytest.mark.parametrize("mesh_shape,nx", [
    (shape, N) for shape in MESHES] + [((1, 1), WIDE), ((2, 2), WIDE)])
@pytest.mark.parametrize("form", ["single", "batch"])
def test_the_exchanges_transpose_is_the_scatter_add_of_its_gather(
        form, mesh_shape, nx, w, per):
    comm = _comm(mesh_shape)
    periodic = PERIODIC[per]
    py, px = mesh_shape
    shape = (py * (N + 2 * w), px * (nx + 2 * w))
    _, source = _sources(mesh_shape, w, periodic, nx)
    assert (halo._lane_tiles((N + 2 * w, nx + 2 * w), (N + 2 * w, w), (0, w))
            is not None) == (nx == WIDE)

    def exchange(a, b):
        if form == "single":
            out_a, token = halo_exchange_2d(a, comm, periodic=periodic, width=w)
            out_b, token = halo_exchange_2d(
                b, comm, periodic=periodic, width=w, token=token)
        else:
            (out_a, out_b), token = halo_exchange_2d_batch(
                [a, b], comm, periodic=periodic, width=w)
        assert isinstance(token, Token)
        return out_a, out_b

    def local(a, b, wa, wb):
        outs, vjp = jax.vjp(exchange, a, b)
        return (*outs, *vjp((wa, wb)))

    xs = [_numbers(shape, seed) for seed in (1, 2)]
    ws = [_numbers(shape, seed) for seed in (3, 4)]
    got = _sharded(local, comm, 4, 4)(*xs, *ws)
    for x, wt, out, back in zip(xs, ws, got[:2], got[2:]):
        xb, wb = _blocks(x, mesh_shape), _blocks(wt, mesh_shape)
        # the exchange is the gather
        np.testing.assert_array_equal(_blocks(out, mesh_shape), xb.ravel()[source])
        # its transpose the scatter-add, cell by cell
        want = np.bincount(source.ravel(), weights=wb.ravel(), minlength=xb.size)
        np.testing.assert_array_equal(
            _blocks(back, mesh_shape), want.reshape(xb.shape))
        # <E x, w> = <x, E^T w>
        assert np.vdot(np.asarray(out), wt) == np.vdot(x, np.asarray(back))


@pytest.mark.parametrize("per", sorted(PERIODIC))
@pytest.mark.parametrize("w", WIDTHS)
@pytest.mark.parametrize("mesh_shape", MESHES)
def test_the_slabs_transpose_is_the_scatter_add_of_their_gather(mesh_shape, w, per):
    comm = _comm(mesh_shape)
    periodic = PERIODIC[per]
    py, px = mesh_shape
    H = W = N + 2 * w
    x_source, source = _sources(mesh_shape, w, periodic)
    regions = [np.s_[:, :, :, :w], np.s_[:, :, :, -w:],
               np.s_[:, :, :w, :], np.s_[:, :, -w:, :]]
    # west and east are the x shifts' alone; south and north the whole
    # exchange's rows (corners through both shifts)
    maps = [x_source[regions[0]], x_source[regions[1]],
            source[regions[2]], source[regions[3]]]

    def program(a, *cts):
        def slabs(a):
            got, token = halo_slabs_2d(a, comm, periodic=periodic, width=w)
            assert isinstance(token, Token)
            return got

        got, vjp = jax.vjp(slabs, a)
        back, = vjp(tuple(None if s is None else c for s, c in zip(got, cts)))
        # a shift that is none on the whole axis has no slab
        return (back, *(0 * c if s is None else s for s, c in zip(got, cts)))

    x = _numbers((py * H, px * W), 5)
    cts = [_numbers((py * H, px * w), 6), _numbers((py * H, px * w), 7),
           _numbers((py * w, px * W), 8), _numbers((py * w, px * W), 9)]
    exists = [px > 1 or periodic[1]] * 2 + [py > 1 or periodic[0]] * 2
    back, *slabs = _sharded(program, comm, 5, 5)(x, *cts)
    xb = _blocks(x, mesh_shape)
    want = np.zeros(xb.size)
    for k, (slab, ct, index) in enumerate(zip(slabs, cts, maps)):
        if not exists[k]:
            continue
        np.testing.assert_array_equal(
            _blocks(slab, mesh_shape), xb.ravel()[index])
        want += np.bincount(
            index.ravel(), weights=_blocks(ct, mesh_shape).ravel(),
            minlength=xb.size)
    np.testing.assert_array_equal(
        _blocks(back, mesh_shape), want.reshape(xb.shape))


def test_a_walled_sides_ghosts_pass_through_and_give_nothing_back():
    """2x2, walls in y: the cotangent of a southern device's southern
    ghost rows stays where it is (those ghosts were kept), and no edge
    row receives from beyond a wall."""
    comm = _comm((2, 2))
    w = 2
    H = W = N + 2 * w

    def local(ct):
        _, vjp = jax.vjp(
            lambda a: halo_exchange_2d(a, comm, periodic=(False, True), width=w)[0],
            jnp.zeros_like(ct))
        return vjp(ct)

    ct = np.zeros((2 * H, 2 * W), np.float32)
    ct[:w, :] = 1.0  # the southern wall's ghost rows, both devices
    back, = _sharded(local, comm, 1, 1)(ct)
    blocks = _blocks(back, (2, 2))
    # interior columns of those rows keep their cotangent; their ghost
    # columns, which the x shifts overwrote, hand theirs to the edge
    # columns of the same rows (periodic x)
    np.testing.assert_array_equal(blocks[0, :, :w, w:-w][..., 2 * w:-2 * w], 1.0)
    np.testing.assert_array_equal(blocks[0, :, :w, :w], 0.0)
    np.testing.assert_array_equal(blocks[0, :, :w, w:2 * w], 2.0)
    assert float(np.asarray(back).sum()) == float(ct.sum())
    np.testing.assert_array_equal(blocks[1], 0.0)


def test_the_token_is_threaded_through_the_backward_sweep():
    """Two exchanges chained by their token inside the differentiated
    function: the token's stamp comes back from ``vjp`` as a zero of the
    stamp's own type, and the transposed exchanges lie under the op's
    scope with the ``transpose`` marker and the three phases."""
    comm = _comm((2, 2))
    w = 2
    shape = (N + 2 * w, N + 2 * w)

    def chained(a, stamp):
        out, token = halo_exchange_2d(
            a, comm, periodic=(False, True), width=w, token=Token(stamp))
        out, token = halo_exchange_2d(
            2.0 * out, comm, periodic=(False, True), width=w, token=token)
        return out, token.stamp

    def local(a):
        (out, stamp), vjp = jax.vjp(chained, a, jnp.zeros((), jnp.float32))
        back, stamp_ct = vjp((jnp.ones_like(out), jnp.zeros_like(stamp)))
        return back, stamp_ct.reshape(1, 1), out

    program = _sharded(local, comm, 1, 3)
    x = jnp.zeros((2 * shape[0], 2 * shape[1]), jnp.float32)
    back, stamp_ct, _ = program(x)
    assert float(jnp.abs(stamp_ct).max()) == 0.0
    assert float(back.sum()) > 0
    text = program.lower(x).as_text(debug_info=True)
    scope = "transpose(jvp(mpi4jax_tpu.halo_exchange_2d))"
    for phase in (halo.PACK, halo.WIRE, halo.UNPACK):
        assert f"{scope}/{halo.TRANSPOSE}/{phase}" in text, phase
    # and the exchange as it runs forwards keeps its own (under a vjp,
    # inside jax's jvp(...))
    assert "jvp(mpi4jax_tpu.halo_exchange_2d)/pack" in text


FORMS = ["single", "batch", "slabs"]


def _form(form, comm, w, per):
    """``blocks -> arrays`` of one form of the exchange on ``comm``, as
    a function of three blocks and of their results stacked (a slab
    that is ``None`` left out): the single exchange on the first block,
    the batch on all three, the slabs of the first."""
    how = dict(periodic=per, width=w)

    def run(a, b, c):
        if form == "single":
            return [halo_exchange_2d(a, comm, **how)[0]]
        if form == "batch":
            return halo_exchange_2d_batch([a, b, c], comm, **how)[0]
        slabs, _ = halo_slabs_2d(a, comm, **how)
        return [s for s in slabs if s is not None]

    return run


@pytest.mark.parametrize("per", ["walled_y", "periodic"])
@pytest.mark.parametrize("w", [1, 2])
@pytest.mark.parametrize("mesh_shape", [(2, 2), (2, 4)])
@pytest.mark.parametrize("form", FORMS)
def test_forward_mode_is_the_exchange_of_the_tangent_and_the_adjoints_transpose(
        form, mesh_shape, w, per):
    """``jax.jvp`` through each form of the exchange gives the exchange
    of the tangents, bit for bit, and satisfies ``<J x, y> == <x, J^T
    y>`` with the adjoint exchange that ``jax.vjp`` runs: whole numbers,
    so both sides are exact; ``jax.linearize`` gives the same map."""
    comm = _comm(mesh_shape)
    py, px = mesh_shape
    shape = (py * (N + 2 * w), px * (N + 2 * w))
    run = _form(form, comm, w, PERIODIC[per])

    def local(a, b, c, ta, tb, tc, *cts):
        at, push = (a, b, c), (ta, tb, tc)
        out, pushed = jax.jvp(run, at, push)
        _, linear = jax.linearize(run, *at)
        exchanged = run(*push)
        pulled = jax.vjp(run, *at)[1](list(cts))
        there = sum(jnp.sum(t * ct) for t, ct in zip(pushed, cts))
        home = sum(jnp.sum(t * ct) for t, ct in zip(push, pulled))
        same = jnp.stack(
            [jnp.all(x == y) for x, y in zip(pushed, exchanged)]
            + [jnp.all(x == y) for x, y in zip(pushed, linear(*push))])
        return (there.reshape(1, 1), home.reshape(1, 1),
                jnp.all(same).reshape(1, 1))

    # cotangents shaped as the results are, a device: made inside
    def program(a, b, c, ta, tb, tc):
        key = jax.random.key(7)
        shapes = [x.shape for x in run(a, b, c)]
        me = jax.lax.axis_index("y") * px + jax.lax.axis_index("x")
        cts = [jnp.round(4 * jax.random.normal(
            jax.random.fold_in(jax.random.fold_in(key, i), me), shape))
            for i, shape in enumerate(shapes)]
        return local(a, b, c, ta, tb, tc, *cts)

    fields = [jnp.asarray(_numbers(shape, seed)) for seed in range(6)]
    there, home, same = _sharded(program, comm, 6, 3)(*fields)
    assert bool(jnp.all(same))
    assert float(there.sum()) == float(home.sum()) != 0.0


@pytest.mark.parametrize("form", FORMS)
def test_jacfwd_through_the_exchange_is_jacrevs_matrix(form):
    """``jax.jacfwd`` (``jax.vmap`` of the tangent) through each form on
    a 2x2 mesh builds the matrix that ``jax.jacrev`` builds from the
    adjoint exchange, of a function that is not linear in the blocks
    (whole numbers: the same, exactly)."""
    comm = _comm((2, 2))
    w, n = 1, 2
    run = _form(form, comm, w, (False, True))

    # results of one shape: a slab's ends patched to a block's
    def of_first(a):
        return sum(jnp.sum(x, keepdims=True) * a for x in run(a, 2 * a, 3 * a))

    spec = jax.P("y", "x")
    program = jax.jit(jax.shard_map(
        of_first, mesh=comm.mesh, in_specs=spec, out_specs=spec))
    x = jnp.asarray(_numbers((2 * (n + 2 * w),) * 2, 3))
    forwards, backwards = jax.jacfwd(program)(x), jax.jacrev(program)(x)
    np.testing.assert_array_equal(np.asarray(forwards), np.asarray(backwards))
    assert float(jnp.abs(forwards).sum()) > 0


def test_the_tangent_keeps_the_exchanges_scopes():
    """A tangent's instructions lie under the op's scope inside jax's
    ``jvp(...)``, with the three phases and without the ``transpose``
    marker."""
    comm = _comm((2, 2))

    def local(a):
        return jax.jvp(
            lambda a: halo_exchange_2d(a, comm, periodic=(False, True), width=2)[0],
            (a,), (a,))[1:]

    program = _sharded(local, comm, 1, 1)
    text = program.lower(
        jnp.zeros((2 * (N + 4),) * 2, jnp.float32)).as_text(debug_info=True)
    for phase in (halo.PACK, halo.WIRE, halo.UNPACK):
        assert f"jvp(mpi4jax_tpu.halo_exchange_2d)/{phase}" in text, phase
    assert f"/{halo.TRANSPOSE}/" not in text


def test_slabs_deeper_than_the_ring_differentiate_by_their_parts():
    """``halo_slabs_2d(depth=)`` keeps plain AD (its one caller's
    backward pass is array code that calls ``halo_exchange_2d``)."""
    comm = _comm((2, 2))
    w = 2
    H = N + 2 * w

    def local(a):
        def total(a):
            slabs, _ = halo_slabs_2d(
                a, comm, periodic=(False, True), width=w, depth=(4, 4))
            return sum(jnp.sum(s) for s in slabs if s is not None)

        return (jax.grad(total)(a),)

    back, = _sharded(local, comm, 1, 1)(jnp.ones((2 * H, 2 * H), jnp.float32))
    # every cell sent, counted as often as a slab holds it
    assert float(back.sum()) > 0 and float(back.min()) >= 0
