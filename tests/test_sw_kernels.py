"""The solver's Pallas kernel against the array code it replaces.

Everything here runs on the CPU backend, the kernel in Pallas's
interpret mode: it shows that the kernel computes what the array code's
two rounds compute with an exchange between them, at block shapes that
exercise the tiling's edges and on meshes where ring 1 is a neighbour's,
and that the step picks the kernel only where it can run.  That the
kernel compiles for the chip is ``tests/test_tpu_compile.py``'s to
show; how fast it is, only a chip run's (``PERF.md``).
"""

import functools
import os
import subprocess
import sys
import types
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mpi4jax_tpu as m
from mpi4jax_tpu.analysis import verify_comm
from mpi4jax_tpu.models import shallow_water as sw
from mpi4jax_tpu.models import sw_kernels
from tests.sw_kernels_cases import (
    UNIT, WALLS, G, _as_a_step_finds_it, _budget, _interpreted, _ring, _Viscous,
)


@pytest.mark.parametrize("nu", [0.2, 0.0], ids=["nu", "nu0"])
@pytest.mark.parametrize("walls", sorted(WALLS))
@pytest.mark.parametrize("shape", ["astride-33x129", "tiles-of-8-100x140"])
def test_the_kernel_reads_no_ghost_the_slabs_did_not_bring(
        shape, walls, nu, monkeypatch):
    """The incoming ghost ring is NaN wherever a slab brings the cell:
    the kernel returns, bit for bit, what it returns from fresh ghosts
    and no slabs, so it read none of them (a NaN would have gone
    through a sum, a product or a selection's untaken side into a
    neighbour) and it leaves none behind.  Beyond a wall nothing is
    brought, and the ghost rows stay the block's own."""
    rows, width = _budget(monkeypatch, shape)
    south, north = WALLS[walls]
    cfg = _Viscous(ny=rows - 2 * G, nx=width - 2 * G, nu=nu, **UNIT)
    keys = jax.random.split(jax.random.PRNGKey(2), 6)
    fields = [
        mean + spread * jax.random.normal(key, (rows, width), jnp.float32)
        for key, mean, spread in zip(keys, (1.0, 0.0, 0.0), (0.1, 0.5, 0.5))]
    inner = ~(_ring((rows, width), 1) | _ring((rows, width), 2))
    old = [jnp.where(inner, 0.5 * jax.random.normal(key, (rows, width)), 0)
           for key in keys[3:]]

    def step(fields, slabs):
        return [np.asarray(x) for x in sw_kernels.wide_step(
            *fields, *old, slabs, jnp.bool_(south), jnp.bool_(north), 0,
            cfg.ab_a, cfg.ab_b, **_interpreted(cfg))]

    want = step(fields, ((None,) * 4,) * 3)
    blocks, slabs = zip(*(
        _as_a_step_finds_it(x, south, north, np.nan) for x in fields))
    assert all(np.isnan(x[:, 0]).all() and np.isnan(x[G:-G, -1]).all()
               and np.isnan(x[0, G:-G]).all() != south
               and np.isnan(x[-1, G:-G]).all() != north for x in blocks)
    got = step(blocks, slabs)
    for name, a, b in zip(sw.SWState._fields, got, want):
        assert np.isfinite(b).all(), name
        np.testing.assert_array_equal(a, b, err_msg=name)


@pytest.mark.parametrize("platform,ghost,snapshot,rows,width,expected", [
    ("tpu", 2, sw.Snapshot(coarsen=4), 7204, 14404, True),
    ("tpu", 2, sw.Snapshot(coarsen=2), 184, 364, True),
    ("tpu", 2, sw.Snapshot(coarsen=8), 7204, 14404, True),
    ("tpu", 2, sw.Snapshot(fields=("v", "h", "u"), coarsen=4), 7204, 14404, True),
    ("tpu", 2, None, 7204, 14404, False),               # a job without output
    ("tpu", 2, sw.Snapshot(coarsen=1), 7204, 14404, False),  # the field as it is
    ("tpu", 2, sw.Snapshot(coarsen=3), 7204, 14404, False),  # no divisor of a strip
    ("tpu", 2, sw.Snapshot(coarsen=16), 7204, 14404, False),
    ("tpu", 2, sw.Snapshot(coarsen=8), 184, 364, False),     # nor of the block
    ("tpu", 2, sw.Snapshot(fields=("h",), coarsen=4), 7204, 14404, False),
    ("tpu", 2, sw.Snapshot(fields=("h", "u", "dv"), coarsen=4), 7204, 14404, False),
    ("cpu", 2, sw.Snapshot(coarsen=4), 7204, 14404, False),  # the array code
    ("tpu", 4, sw.Snapshot(coarsen=4), 7208, 14408, False),
    ("tpu", 1, sw.Snapshot(coarsen=4), 7202, 14402, False),
], ids=lambda x: str(x))
def test_the_last_walk_sums_rows_where_the_step_is_the_kernel_and_the_blocks_fit(
        platform, ghost, snapshot, rows, width, expected):
    """The rule of ``make_multistep(snapshot=)`` and ``make_snapshot``:
    from the configuration, the devices and the job's ``Snapshot``."""
    cfg = sw.SWConfig(ny=rows - 2 * ghost, nx=width - 2 * ghost, ghost=ghost)
    assert sw._sums_in_step(cfg, _comm_on(platform), snapshot) is expected
    if rows > 7200:  # a quarter of that domain a device: the same answer
        assert sw._sums_in_step(cfg, _comm_on(platform, (2, 2)), snapshot) is expected


def _as_first_written(cfg, g, col, south, north, fields, old, a, b):
    """One step of the kernel by the formulae its stages had before each
    row's faces and corners were made once (PR 47): every value of the
    row north and of the row south evaluated again from ``(c, n, s)``,
    the halves where they stood, on the whole block at once (``g``,
    ``col``: each cell's row and column).  Plain ``jax.numpy``; a
    shift's wrap lands in the outermost ghost ring, which no mask
    admits."""
    dtype = jnp.float32
    inv_dx, inv_dy = 1.0 / cfg.dx, 1.0 / cfg.dy
    cx, cy = cfg.nu / cfg.dx, cfg.nu / cfg.dy
    rows, width = g.shape
    south_ghost_row = jnp.where(south, G - 1, -1)
    north_wall_row = jnp.where(north, rows - G - 1, -1)
    reach_from = jnp.where(south, G, G - 1)
    reach_to = jnp.where(north, rows - G, rows - G + 1)

    def box(row_from, row_to, ring):
        return ((g >= row_from) & (g < row_to)
                & (col >= G - ring) & (col < width - G + ring))

    def around(x):
        return x, jnp.roll(x, -1, 0), jnp.roll(x, 1, 0)

    def east(x):
        return jnp.roll(x, -1, 1)

    def west(x):
        return jnp.roll(x, 1, 1)

    def half(x):
        return x * dtype(0.5)

    (h, h_n, h_s), (u, u_n, u_s), (v, v_n, v_s) = map(around, fields)
    zero = jnp.zeros((rows, width), dtype)
    interior, reach = box(G, rows - G, 0), box(reach_from, reach_to, 1)
    at_north, at_south = g == north_wall_row, g - 1 == south_ghost_row

    def unless(wall, x):
        return jnp.where(wall, zero, x)

    h_e = east(h)
    hx, hx_n, hx_s = h + h_e, h_n + east(h_n), h_s + east(h_s)
    fe = half(hx) * u
    fe_n = unless(at_north, half(hx_n) * u_n)
    fn = unless(at_north, half(h + h_n) * v)
    fn_s = unless(at_south, half(h_s + h) * v_s)

    def vorticity(row, v, v_e, u_n, u, depth4):
        y = (row.astype(dtype) + dtype(0)) * dtype(cfg.dy)
        planetary = y * dtype(cfg.coriolis_beta) + dtype(cfg.coriolis_f)
        relative = (v_e - v) * dtype(inv_dx) - (u_n - u) * dtype(inv_dy)
        return (planetary + relative) / (depth4 * dtype(0.25))

    q = vorticity(g - G, v, east(v), u_n, u, hx + jnp.where(at_north, hx, hx_n))
    q_s = unless(at_south, vorticity(
        g - (G + 1), v_s, east(v_s), u, u_s, hx_s + hx))
    uu, vv, uu_n = u * u, v * v, u_n * u_n
    ke = half(half(uu + west(uu)) + half(vv + v_s * v_s))
    ke_n = unless(at_north, half(half(uu_n + west(uu_n)) + half(v_n * v_n + vv)))
    fe_w = west(fe)
    dh_new = (fe_w - fe) * dtype(inv_dx) - (fn - fn_s) * dtype(inv_dy)
    du_new = (((h_e - h) * dtype(-cfg.gravity * inv_dx)
               + half(q * half(fn + east(fn)) + q_s * half(fn_s + east(fn_s))))
              - (east(ke) - ke) * dtype(inv_dx))
    dv_new = (((h_n - h) * dtype(-cfg.gravity * inv_dy)
               - half(q * half(fe + fe_n) + west(q) * half(fe_w + west(fe_n))))
              - (ke_n - ke) * dtype(inv_dy))

    def stepped(where, x, new, old):
        new = jnp.where(where, new, zero)
        inc = jnp.where(
            where, (new * dtype(a) + old * dtype(b)) * dtype(cfg.dt), zero)
        return x + inc, new

    h, dh_new = stepped(interior, h, dh_new, old[0])
    u, du_new = stepped(reach, u, du_new, old[1])
    v, dv_new = stepped(reach, v, dv_new, old[2])
    v = unless(at_north, v)
    if cfg.nu > 0:
        def friction(x):
            c, n, s = around(x)
            e, w = east(c), west(c)
            gx, gx_w = (e - c) * dtype(cx), (c - w) * dtype(cx)
            gy = (n - c) * dtype(cy)
            gy_s = jnp.where(at_south, zero, (c - s) * dtype(cy))
            inc = ((gx - gx_w) * dtype(inv_dx)
                   + (gy - gy_s) * dtype(inv_dy)) * dtype(cfg.dt)
            return c + jnp.where(interior, inc, zero)

        u, v = friction(u), unless(at_north, friction(v))
    return h, u, v, dh_new, du_new, dv_new


@pytest.mark.parametrize("nu", [0.2, 0.0], ids=["nu", "nu0"])
@pytest.mark.parametrize("walls", ["both", "neither"])
@pytest.mark.parametrize("shape", ["astride-33x129", "ragged-52x100"])
def test_a_rows_values_made_once_are_the_values_made_twice_bit_for_bit(
        shape, walls, nu, monkeypatch):
    """The stages make what lives on a face or a corner once a row and
    take the neighbouring row's from the strip before or by a rotation
    of sublanes, with the powers of two folded: the same roundings of
    the same values as the formulae that evaluated each neighbour again,
    so the whole padded block of all six arrays comes back bit for bit
    (where a zeroed row's value differs, nothing is updated from it)."""
    rows, width = _budget(monkeypatch, shape)
    south, north = WALLS[walls]
    cfg = _Viscous(ny=rows - 2 * G, nx=width - 2 * G, nu=nu, **UNIT)
    keys = jax.random.split(jax.random.PRNGKey(4), 6)
    fields = [
        mean + spread * jax.random.normal(key, (rows, width), jnp.float32)
        for key, mean, spread in zip(keys, (1.0, 0.0, 0.0), (0.1, 0.5, 0.5))]
    inner = ~(_ring((rows, width), 1) | _ring((rows, width), 2))
    old = [jnp.where(inner, 0.5 * jax.random.normal(key, (rows, width)), 0)
           for key in keys[3:]]
    a, b = cfg.ab_a, cfg.ab_b
    plain = {"xla_backend_optimization_level": 0}  # as the walks' test
    # the block's rows, columns and walls are the programs' arguments,
    # so that neither folds a mask away and rounds otherwise for it
    g, col = jnp.indices((rows, width), dtype=jnp.int32)
    want = jax.jit(
        functools.partial(_as_first_written, cfg), static_argnums=(6, 7),
        compiler_options=plain)(g, col, south, north, fields, old, a, b)
    got = jax.jit(
        lambda fields, old, south, north: sw_kernels.wide_step(
            *fields, *old, ((None,) * 4,) * 3, south, north, 0, a, b,
            **_interpreted(cfg)),
        compiler_options=plain)(fields, old, jnp.bool_(south), jnp.bool_(north))
    for name, x0, x, y in zip(sw.SWState._fields, [*fields, *old], got, want):
        x0, x, y = np.asarray(x0), np.asarray(x), np.asarray(y)
        assert np.isfinite(y).all(), name
        assert np.abs(y - x0)[inner].max() > 0.01, name
        np.testing.assert_array_equal(x, y, err_msg=name)


def _vector_primitives(jaxpr, shape):
    """The equations of ``jaxpr`` (those of its inner jaxprs with them)
    that compute a value of ``shape``, counted by primitive; laying one
    register's value across a strip computes nothing and is left out."""
    found = {}
    for eqn in jaxpr.eqns:
        inner = [x.jaxpr for x in eqn.params.values() if hasattr(x, "jaxpr")]
        for sub in inner:
            for name, n in _vector_primitives(sub, shape).items():
                found[name] = found.get(name, 0) + n
        name = eqn.primitive.name
        if not inner and name not in ("concatenate", "broadcast_in_dim") and any(
                getattr(x.aval, "shape", None) == shape for x in eqn.outvars):
            found[name] = found.get(name, 0) + 1
    return found


def test_a_strips_stages_make_no_value_twice():
    """The vector work of one strip's two stages at the benchmark cells'
    width, counted on the CPU from their jaxprs: operations on a whole
    strip of 113 vector registers.  200 before PR 47 (round 1 151: two
    divisions, 13 lane rotations, 54 products; round 2 49), when every
    face's and corner's value was made for its own row and again for the
    neighbour's, every mask on the whole strip and every factor of a
    half where it stood.  An edit that puts a second evaluation back
    moves these numbers: say why with the new ones."""
    _, pltpu = sw_kernels.pallas()
    width = 14404
    lanes = sw_kernels._whole_registers(width)
    first, second = sw_kernels._stages(
        pltpu.roll, 7204, width, jnp.dtype(jnp.float32), 0.2, 1250.0, 1250.0,
        2.0, 9.81, 2e-4, 2e-11)
    strip = jax.ShapeDtypeStruct((sw_kernels.STRIP, lanes), jnp.float32)
    g = jax.ShapeDtypeStruct((sw_kernels.STRIP, sw_kernels.LANES), jnp.int32)
    scalars = (*(jnp.float32(x) for x in (1.6, -0.6, 0.0)),
               *(jnp.int32(x) for x in (1, 7201, 2, 7202, 2, 7202)))
    round1 = jax.make_jaxpr(
        lambda g, *x: first(scalars, g, [x[0:2], x[2:4], x[4:6]], x[6:9], x[9:]))(
            g, *[strip] * 13)
    round2 = jax.make_jaxpr(
        lambda g, *x: second(scalars, g, [x[0:3], x[3:6]]))(g, *[strip] * 6)
    round1, round2 = (
        _vector_primitives(x.jaxpr, strip.shape) for x in (round1, round2))
    assert round1 == {"add": 19, "sub": 13, "mul": 27, "div": 1,
                      "select_n": 16, "roll": 12}, round1
    assert round2 == {"add": 4, "sub": 10, "mul": 12, "select_n": 5,
                      "roll": 4}, round2
    assert sum(round1.values()) + sum(round2.values()) == 88 + 35


def _exchanges_a_step(multistep, state):
    """The halo exchanges in the traced one-step program, with their
    ghost writes or without."""
    report = verify_comm(lambda: multistep(state))()
    kinds = [e.kind for e in report.events]
    return kinds.count("halo_exchange_2d") + kinds.count("halo_slabs_2d")


@pytest.mark.parametrize("mesh_shape,num_steps", [
    ((1, 1), 10), ((1, 1), 7), ((2, 1), 10), ((1, 2), 10), ((2, 2), 10)])
def test_multistep_through_the_kernels_matches_the_array_path(
        mesh_shape, num_steps, monkeypatch):
    """``make_init``, ``make_first_step`` and ``make_multistep`` with
    the step forced through the kernel (interpreted) against the array
    path, after 1 + 10 steps: walls and the Coriolis parameter's rows on
    the right devices, ring 1 recomputed where the array path exchanges
    a second time, the first step and the rest through one kernel.  A
    walk of the kernel is two steps (the first step's with one passed
    over), on one device and, through ``halo_slabs_2d``'s slabs four
    deep of all six arrays, beside neighbours on either axis or both
    (PR 53; 44 rows leave a block of a mesh two devices high two rows
    to spare in its last tile); an odd count's last step is a walk of
    one."""
    mesh = jax.make_mesh(
        mesh_shape, ("y", "x"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2)
    comm = m.MeshComm.from_mesh(mesh)
    py, px = mesh_shape
    # a fast rotation and a deep layer: friction moves u by 1e-2 m/s in
    # these steps (the published coefficients: 1e-4), and h stays
    # positive; a beta plane on which the rotation doubles from wall to
    # wall, so that a device that took another's rows would show
    cfg = sw.SWConfig(ny=44, nx=48, ghost=G, coriolis_f=2e-2, depth=1e3,
                      coriolis_beta=1e-7)
    ny_l, nx_l = 44 // py, 48 // px
    block = (ny_l + 2 * G, nx_l + 2 * G)

    def run():
        state = sw.make_init(cfg, comm)()
        state = sw.make_first_step(cfg, comm)(state)
        return jax.tree.map(
            np.asarray, sw.make_multistep(cfg, comm, num_steps)(state))

    def blocks(x):
        """A global array of padded blocks, a block at a time."""
        return x.reshape(py, block[0], px, block[1]).transpose(0, 2, 1, 3)

    def interiors(x):
        """A global array of padded blocks without their ghost rings."""
        return blocks(x)[..., G:-G, G:-G].transpose(0, 2, 1, 3).reshape(44, 48)

    want = run()
    assert want.dh.shape == (44, 48)
    state = sw.make_init(cfg, comm)()
    assert _exchanges_a_step(sw.make_multistep(cfg, comm, 1), state) == 5
    calls, walks = [], []
    wide_step = sw_kernels.wide_step
    # another case of this test traced these shapes: count this one's
    wide_step.clear_cache()

    def interpreted(*args, **kwargs):
        calls.append(args[0].shape)
        walks.append(kwargs["steps"])
        return wide_step(*args, **dict(kwargs, interpret=True))

    monkeypatch.setattr(sw_kernels, "wide_step", interpreted)
    monkeypatch.setattr(sw, "_runs_as_kernels", lambda cfg, comm: True)
    # Pallas's interpreter slices blocks at indices that vary over no
    # mesh axis, which shard_map's checker refuses; the compiled kernel
    # is checked (tests/test_tpu_compile.py)
    monkeypatch.setattr(
        jax, "shard_map", functools.partial(jax.shard_map, check_vma=False))
    imports = []
    pallas = sw_kernels.pallas
    monkeypatch.setattr(
        sw_kernels, "pallas", lambda: imports.append(1) or pallas())
    got = run()
    # the step is built once in each of the two programs, on one
    # device's block; Pallas is asked for where each program is built,
    # and once more where the kernel is traced: the second program
    # reuses the first's trace.  Both are the kernel that walks two
    # steps (the first step's with its first passed over), and only an
    # odd count's last step is a walk of one, another kernel and
    # another trace
    assert sw._walks_two_steps(cfg, comm)
    odd = num_steps % 2
    assert walks == [2, 2] + [1] * odd
    assert calls == [block] * len(walks)
    assert len(imports) == 3 + odd
    # three exchanges a step where the array path has five
    state = sw.make_init(cfg, comm)()
    assert _exchanges_a_step(sw.make_multistep(cfg, comm, 1), state) == 3
    # where the step is a kernel the state carries padded tendencies,
    # from make_init on; a first step takes them interior-shaped too, as
    # who builds a state of their own hands them in (the benchmark)
    assert got.dh.shape == got.h.shape
    bare = sw.SWState(
        *sw.make_init(cfg, comm)()[:3], *(jnp.zeros((44, 48)),) * 3)
    np.testing.assert_array_equal(
        sw.make_first_step(cfg, comm)(bare).dv,
        sw.make_first_step(cfg, comm)(sw.make_init(cfg, comm)()).dv)
    with pytest.raises(ValueError, match="carries them padded"):
        sw.make_multistep(cfg, comm, 1)(bare)
    # what a broken step would leave: a kernel that took every block
    # for the mesh's first, a friction that did nothing
    monkeypatch.setattr(
        sw_kernels, "wide_step",
        lambda *args, **kwargs: interpreted(*args[:9], 0, *args[10:], **kwargs))
    misplaced = run()
    monkeypatch.setattr(
        sw_kernels, "wide_step",
        lambda *args, **kwargs: interpreted(*args, **dict(kwargs, nu=0.0)))
    smooth = run()
    for name, a, b, c, d in zip(
            sw.SWState._fields, got, want, misplaced, smooth):
        assert np.isfinite(b).all()
        tolerance = 2e-5 * max(1.0, np.abs(b).max())
        if name.startswith("d"):
            # the tendencies' ghost ring: ring 2 zero; ring 1 of du and
            # dv what the neighbour holds for those cells (periodic in
            # x), zero beyond a wall; all of dh's zero
            held = np.pad(interiors(a), ((1, 1), (0, 0)))
            held = np.pad(held, ((0, 0), (1, 1)), mode="wrap")
            for (iy, ix), mine in np.ndenumerate(np.empty((py, px))):
                mine = blocks(a)[iy, ix]
                theirs = np.pad(held[iy * ny_l:(iy + 1) * ny_l + 2,
                                     ix * nx_l:(ix + 1) * nx_l + 2], 1)
                if name == "dh":
                    theirs[_ring(block, 1)] = 0
                else:
                    assert np.abs(theirs[_ring(block, 1)]).max() > 0, name
                np.testing.assert_allclose(
                    mine, theirs, rtol=0, atol=1e-6 * np.abs(b).max(),
                    err_msg=name)
                assert not mine[_ring(block, 2)].any(), name
            a, c, d = interiors(a), interiors(c), interiors(d)
        elif name in "uv":
            # the array path's second exchange refreshes ring 2 as well,
            # which nothing reads before the next step's exchange
            a, b, c, d = (interiors(x) for x in (a, b, c, d))
        np.testing.assert_allclose(a, b, rtol=0, atol=tolerance, err_msg=name)
        if name in "uv":
            assert np.abs(d - b).max() > 20 * tolerance, name
            assert (np.abs(c - b).max() > 20 * tolerance) == (py > 1), name


@pytest.mark.parametrize("saved_as", ["array code", "kernel"])
@pytest.mark.parametrize("mesh_shape", [(1, 1), (2, 2)])
def test_a_save_resumes_where_the_step_is_the_other_backends(
        mesh_shape, saved_as, monkeypatch, tmp_path):
    """A checkpoint holds the model, not one backend's buffers (D14): a
    job saved where the step is array code (interior-shaped tendencies)
    resumes where it is the kernel (padded ones, ring 1 of ``du``,
    ``dv`` the neighbours'), and the reverse; the run goes on as the
    uninterrupted one does, to the rounding by which the two backends
    differ anyway, where a resume that dropped the tendencies would be
    off by a thousand times that."""
    mesh = jax.make_mesh(
        mesh_shape, ("y", "x"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2,
        devices=jax.devices()[:mesh_shape[0] * mesh_shape[1]])
    comm = m.MeshComm.from_mesh(mesh)
    py, px = mesh_shape
    cfg = sw.SWConfig(ny=40, nx=48, ghost=G, coriolis_f=2e-2, depth=1e3,
                      coriolis_beta=1e-7)
    ck = sw.Checkpoint(tmp_path / "run", every_calls=0)
    wide_step = sw_kernels.wide_step

    def as_kernel(patch):
        patch.setattr(sw_kernels, "wide_step", lambda *args, **kwargs: wide_step(
            *args, **dict(kwargs, interpret=True)))
        patch.setattr(sw, "_runs_as_kernels", lambda cfg, comm: True)
        patch.setattr(
            jax, "shard_map", functools.partial(jax.shard_map, check_vma=False))

    def job(kernel, patch):
        if kernel:
            as_kernel(patch)
        made = sw.make_job(cfg, comm, 5, checkpoint=ck)
        assert made.form()["tendencies"] == ("padded" if kernel else "interior")
        return made

    def interiors(x):
        x = np.asarray(x)
        if x.shape == (40, 48):
            return x
        return x.reshape(py, 40 // py + 2 * G, px, 48 // px + 2 * G)[
            :, G:-G, :, G:-G].reshape(40, 48)

    with monkeypatch.context() as patch:
        first = job(saved_as == "kernel", patch)
        first.start(sw.make_init(cfg, comm)())
        first.advance(1)
        first.save()
        first.advance(1)  # the uninterrupted run, on the backend that saved
        first.drain()
        want = [interiors(a) for a in first.state]
    with monkeypatch.context() as patch:
        second = job(saved_as != "kernel", patch)
        assert second.resume() == 6
        assert second.state.dh.shape == (
            second.state.h.shape if saved_as != "kernel" else (40, 48))
        kept = jax.tree.map(jnp.copy, second.state)  # the call donates its input
        second.advance(1)
        got = [interiors(a) for a in second.state]
        # the same resume with its tendencies dropped
        second.start(kept._replace(**{
            k: jnp.zeros_like(getattr(kept, k)) for k in ("dh", "du", "dv")}),
            step=6)
        second.advance(1)
        dropped = [interiors(a) for a in second.state]
    for name, a, b, c in zip(sw.SWState._fields, got, want, dropped):
        tolerance = 2e-5 * max(1.0, np.abs(b).max())
        np.testing.assert_allclose(a, b, rtol=0, atol=tolerance, err_msg=name)
        if name in "huv":
            assert np.abs(c - b).max() > 100 * tolerance, name


def _comm_on(platform, mesh_shape=(1, 1)):
    devices = np.full(mesh_shape, types.SimpleNamespace(platform=platform))
    return types.SimpleNamespace(
        mesh=types.SimpleNamespace(devices=devices), axis_sizes=mesh_shape)


@pytest.mark.parametrize("platform,dtype,rows,width,expected", [
    ("tpu", "float32", 7204, 14404, True),
    ("tpu", "float32", 184, 364, True),
    ("cpu", "float32", 7204, 14404, False),   # a Mosaic kernel cannot run
    ("gpu", "float32", 7204, 14404, False),
    ("tpu", "float64", 7204, 14404, False),   # the strips are float32's
    ("tpu", "bfloat16", 7204, 14404, False),
    ("tpu", "float32", 7, 364, False),        # not one strip of 8 rows
    # one interior row, whose ring 1 would be a neighbour's wall row: a
    # block with a strip has four or more
    ("tpu", "float32", 5, 364, False),
    ("tpu", "float32", 7204, 300_000, False),  # a strip over the budget
    # the state's six arrays go through one call: a strip of two this
    # wide would fit, one of six does not
    ("tpu", "float32", 7204, 40_000, True),
    ("tpu", "float32", 7204, 100_000, False),
], ids=lambda x: str(x))
def test_the_step_picks_the_kernels_from_platform_dtype_and_shape(
        platform, dtype, rows, width, expected):
    cfg = sw.SWConfig(ny=rows - 2 * G, nx=width - 2 * G, dtype=dtype, ghost=G)
    assert sw._runs_as_kernels(cfg, _comm_on(platform)) is expected
    # the other two schedules are array code everywhere
    assert not sw._runs_as_kernels(replace(cfg, ghost=4), _comm_on(platform))


@pytest.mark.parametrize("platform,mesh_shape,rows,width,expected", [
    ("tpu", (1, 1), 7204, 14404, True),
    ("tpu", (1, 1), 184, 364, True),
    # a neighbour on either axis: the second step's ghosts are its
    # first step's results, which the walk computes from deeper slabs
    ("tpu", (2, 1), 7204, 14404, True),
    ("tpu", (1, 2), 7204, 14404, True),
    ("tpu", (2, 2), 7204, 14404, True),
    ("tpu", (2, 2), 1804, 3604, True),
    # no lanes past a row's last column for the two columns more
    ("tpu", (1, 2), 7204, 14336, False),
    ("tpu", (2, 1), 7204, 14336, True),    # none needed
    # no rows past the field's last in its last tile (300 tiles of 24)
    ("tpu", (2, 1), 7200, 14404, False),
    ("tpu", (1, 2), 7200, 14404, True),
    ("cpu", (1, 1), 7204, 14404, False),   # no kernel, no walk
    ("tpu", (1, 1), 7204, 40_000, True),   # one strip a tile
    ("tpu", (1, 1), 7204, 100_000, False),  # not one
], ids=lambda x: str(x))
def test_a_walk_takes_two_steps_where_the_block_has_room_for_its_rings(
        platform, mesh_shape, rows, width, expected):
    py, px = mesh_shape
    cfg = sw.SWConfig(ny=(rows - 2 * G) * py, nx=(width - 2 * G) * px, ghost=G)
    comm = _comm_on(platform, mesh_shape)
    assert sw._walks_two_steps(cfg, comm) is expected
    assert sw._runs_as_kernels(cfg, comm) or not expected
    assert not sw._walks_two_steps(replace(cfg, ghost=4), comm)


def test_a_step_on_cpu_devices_is_the_array_code():
    mesh = jax.make_mesh(
        (1, 1), ("y", "x"), axis_types=(jax.sharding.AxisType.Auto,) * 2)
    comm = m.MeshComm.from_mesh(mesh)
    cfg = sw.SWConfig(ny=24, nx=48, ghost=G)
    state = jax.eval_shape(sw.make_init(cfg, comm))
    assert not sw._runs_as_kernels(cfg, comm)
    assert state.dh.shape == (24, 48)  # interior-shaped, as the array code's
    text = sw.make_multistep(cfg, comm, 1).lower(state).as_text()
    assert "custom_call" not in text or "tpu_custom_call" not in text


@pytest.mark.parametrize("rows,width,fields,steps,expected", [
    (7204, 14404, 2, 1, 72),   # the benchmark's block: 40 MiB / (10 x 57856 B)
    (1804, 3604, 2, 1, 280),
    (184, 364, 2, 1, 184),     # the whole block when it fits
    (52, 100, 2, 1, 48),       # whole strips only
    (7, 100, 2, 1, 0),
    (7204, 14404, 6, 1, 24),   # round 1's six fields get shorter tiles
    (1804, 3604, 6, 1, 88),
    (184, 364, 6, 1, 184),
    (52, 100, 6, 1, 48),
    (7204, 100_000, 6, 1, 0),  # 8 rows x 30 blocks of 400 KB: over the budget
    # a walk of two steps keeps two tiles more an array in rings, out
    # of 56 MiB: 42 x 57856 B a row, and the tiles are a single walk's
    (7204, 14404, 6, 2, 24),
    (1804, 3604, 6, 2, 88),
    (184, 364, 6, 2, 184),
    (52, 100, 6, 2, 48),
    (7204, 40_000, 6, 1, 8),
    (7204, 40_000, 6, 2, 8),
    (7204, 100_000, 6, 2, 0),
])
def test_tile_rows(rows, width, fields, steps, expected):
    tile = sw_kernels.tile_rows(rows, width, jnp.float32, fields, steps)
    assert tile == expected and tile % sw_kernels.STRIP == 0
    if steps == 1:
        assert tile == sw_kernels.tile_rows(rows, width, jnp.float32, fields)


def _fresh_interpreter(code):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = root + os.pathsep + env.get("PYTHONPATH", "")
    done = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True,
        text=True, timeout=300)
    assert done.returncode == 0, done.stderr[-2000:]
    return done.stdout.strip()


def test_pallas_is_imported_only_by_who_runs_the_kernel():
    """In a fresh interpreter the package, the model and a step built
    and run on CPU devices leave ``jax.experimental.pallas`` out:
    its import (0.4 s from bytecode, 1.2 s from source, on the chip's
    machine) is paid by a step built for TPU devices and by nothing
    else (``ops/flash.py`` has its own, for who imports that)."""
    out = _fresh_interpreter("""
import sys
import jax
import mpi4jax_tpu as m
from mpi4jax_tpu.models import shallow_water as sw
mesh = jax.make_mesh((1, 1), ("y", "x"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
comm = m.MeshComm.from_mesh(mesh)
cfg = sw.SWConfig(ny=16, nx=24, ghost=2)
state = sw.make_first_step(cfg, comm)(sw.make_init(cfg, comm)())
jax.block_until_ready(sw.make_multistep(cfg, comm, 2)(state))
loaded = sorted(k for k in sys.modules if "pallas" in k)
assert not loaded, loaded
print("no pallas")
""")
    assert out.endswith("no pallas")


GPU_INTERPRETER = "jax._src.pallas.mosaic_gpu.interpret.interpret_pallas_call"


@pytest.mark.parametrize("first", ["ours", "theirs"])
def test_pallas_declines_the_gpu_interpreter_on_a_first_import_only(first):
    """``sw_kernels.pallas()`` leaves out the Mosaic GPU interpreter that
    jax's ``pallas_call`` module would import (half the import's time,
    for code a TPU kernel cannot reach) when it is this process's first
    import of Pallas, and leaves no trace in ``sys.modules``: a later
    import of the GPU package gets the real modules.  Where Pallas was
    imported before, nothing is touched."""
    out = _fresh_interpreter(f"""
import sys
import jax
if {first == "theirs"!r}:
    from jax.experimental import pallas
from mpi4jax_tpu.models import sw_kernels
pl, pltpu = sw_kernels.pallas()
assert pl.pallas_call and pltpu.roll and pltpu.VMEM
print("interpreter", {GPU_INTERPRETER!r} in sys.modules,
      "gpu", "jax.experimental.mosaic.gpu" in sys.modules)
assert sys.modules.get({GPU_INTERPRETER!r}, "absent") is not None
from jax.experimental.pallas import mosaic_gpu
import {GPU_INTERPRETER}
print("later", "jax.experimental.mosaic.gpu" in sys.modules)
""")
    loaded = first == "theirs"
    assert out.splitlines() == [
        f"interpreter {loaded} gpu {loaded}", "later True"]


@pytest.mark.parametrize("platform,ghost,nu,expected", [
    ("tpu", 2, 1, 1), ("cpu", 2, 1, 0), ("tpu", 1, 1, 0), ("tpu", 4, 1, 0),
    ("tpu", 2, 0, 1),  # without friction the step is a kernel still
])
def test_a_step_built_for_tpu_devices_imports_pallas_before_it_is_traced(
        platform, ghost, nu, expected, monkeypatch):
    comm = _comm_on(platform)
    imports = []
    monkeypatch.setattr(sw_kernels, "pallas", lambda: imports.append(1))
    sw._kernels_ahead(
        sw.SWConfig(ny=64, nx=128, ghost=ghost, coriolis_f=2e-4 * nu), comm)
    assert len(imports) == expected
