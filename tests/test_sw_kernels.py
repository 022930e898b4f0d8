"""The solver's Pallas kernels against the array code they replace.

Everything here runs on the CPU backend, a kernel in Pallas's
interpret mode: it shows that each kernel computes what the array code
of the same round computes, at block shapes that exercise the tiling's
edges, and that the step picks the kernels only where they can run.  That
the kernel compiles for the chip is ``tests/test_tpu_compile.py``'s to
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
from mpi4jax_tpu.models import shallow_water as sw
from mpi4jax_tpu.models import sw_kernels

G = 2
# friction strong enough to see: dt * nu / dx**2 is 0.02, not the 1e-6
# of the published coefficients, so an error in a stencil or a mask is
# five orders of magnitude over float32's roundoff
STRONG = dict(dx=5e3, dy=4e3, coriolis_f=1.0, ghost=G)

# rows x width of one device's padded block, and the VMEM budget the
# tiling is given (None: its own): 52 rows leave a last tile of 4 under
# tiles of 48; 184 x 364 is the demo grid's block, one tile; 21 rows are
# no multiple of 8; the small budgets cut 100 rows into tiles of 8, 24
SHAPES = {
    "ragged-52x100": (52, 100, None),
    "demo-184x364": (184, 364, None),
    "odd-21x40": (21, 40, None),
    "tiles-of-8-100x140": (100, 140, 8 * 10 * 1024),
    "tiles-of-24-100x140": (100, 140, 24 * 10 * 1024),
}
WALLS = {"south": (True, False), "north": (False, True),
         "both": (True, True), "neither": (False, False)}
# round 1 in units of its own, so that every term of every tendency is
# of order one and float32's roundoff of order 1e-7: a rotation that
# changes by half from the first row to the last of the tallest block
UNIT = dict(dx=1.0, dy=0.8, gravity=1.0, depth=1.0, coriolis_f=1.0,
            coriolis_beta=4e-3, ghost=G)


def _budget(monkeypatch, shape, fields):
    """``SHAPES[shape]`` with its VMEM budget in place, scaled to a call
    of ``fields`` fields so that the tiles are the name's."""
    rows, width, budget = SHAPES[shape]
    if budget is not None:
        monkeypatch.setattr(
            sw_kernels, "_VMEM_BLOCK_BUDGET", budget * fields // 2)
        tile = sw_kernels.tile_rows(rows, width, jnp.float32, fields)
        assert tile == int(shape.split("-")[2]) and rows > 3 * tile
    return rows, width


def _ring(shape):
    ring = np.ones(shape, bool)
    ring[G:-G, G:-G] = False
    return ring


def _fields(rows, width, seed=0):
    ku, kv = jax.random.split(jax.random.PRNGKey(seed))
    return (jax.random.normal(ku, (rows, width), jnp.float32),
            jax.random.normal(kv, (rows, width), jnp.float32))


@pytest.mark.parametrize("walls", sorted(WALLS))
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_viscosity_kernel_matches_the_array_code(shape, walls, monkeypatch):
    rows, width = _budget(monkeypatch, shape, fields=2)
    cfg = sw.SWConfig(ny=rows - 2 * G, nx=width - 2 * G, **STRONG)
    u, v = _fields(rows, width)
    south, north = (jnp.bool_(w) for w in WALLS[walls])
    want = sw._viscosity_round(u, v, cfg, south, north)
    got = sw_kernels.viscosity_round(
        u, v, south, north, nu=cfg.lateral_viscosity, dx=cfg.dx, dy=cfg.dy,
        dt=cfg.dt, interpret=True)
    for name, before, a, b in zip("uv", (u, v), got, want):
        a, b, before = (np.asarray(x) for x in (a, b, before))
        # the round did something, and the kernel did the same
        assert np.abs(b - before)[G:-G, G:-G].max() > 0.1, name
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-6, err_msg=name)
        # the ghost ring goes through untouched, bit for bit (the wall
        # condition zeroes its row from end to end, as the array code's)
        ring = _ring(a.shape)
        np.testing.assert_array_equal(a[ring], b[ring], err_msg=name)
        ring[-(G + 1)] = False
        np.testing.assert_array_equal(a[ring], before[ring], err_msg=name)
    # v = 0 on the northern wall row, and only under a northern wall
    wall_row = np.asarray(got[1])[-(G + 1)]
    assert (wall_row == 0).all() == WALLS[walls][1]


@functools.lru_cache
def _tendency_definition(cfg, first_step):
    """``sw._tendency_round`` on one device's block, which it asks its
    mesh the place of: jitted, the walls traced, so that the wall cases
    of a shape share one build."""
    mesh = jax.make_mesh(
        (1, 1), ("y", "x"), axis_types=(jax.sharding.AxisType.Auto,) * 2)
    comm = m.MeshComm.from_mesh(mesh)
    block, flag = jax.P("y", "x"), jax.P()
    return jax.jit(jax.shard_map(
        lambda *args: sw._tendency_round(
            *args[:6], cfg, comm, *args[6:], first_step),
        mesh=mesh, in_specs=(block,) * 6 + (flag,) * 2, out_specs=(block,) * 6))


@pytest.mark.parametrize("first_step", [False, True], ids=["ab2", "euler"])
@pytest.mark.parametrize("walls", sorted(WALLS))
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_tendency_kernel_matches_the_array_code(
        shape, walls, first_step, monkeypatch):
    rows, width = _budget(monkeypatch, shape, fields=6)
    cfg = sw.SWConfig(ny=rows - 2 * G, nx=width - 2 * G, **UNIT)
    keys = jax.random.split(jax.random.PRNGKey(1), 6)
    h, u, v = (
        mean + spread * jax.random.normal(key, (rows, width), jnp.float32)
        for key, mean, spread in zip(keys, (1.0, 0.0, 0.0), (0.1, 0.5, 0.5)))
    old = [0.5 * jax.random.normal(key, (cfg.ny, cfg.nx), jnp.float32)
           for key in keys[3:]]
    south, north = (jnp.bool_(w) for w in WALLS[walls])
    want = _tendency_definition(cfg, first_step)(h, u, v, *old, south, north)
    if first_step:
        a, b, old = 1.0, 0.0, [jnp.zeros_like(x) for x in old]
    else:
        a, b = cfg.ab_a, cfg.ab_b
    got = sw_kernels.tendency_round(
        h, u, v, *(jnp.pad(x, G) for x in old), south, north, 0, a, b, dx=cfg.dx, dy=cfg.dy, dt=cfg.dt,
        gravity=cfg.gravity, coriolis_f=cfg.coriolis_f,
        coriolis_beta=cfg.coriolis_beta, interpret=True)
    got, want = ([np.asarray(x) for x in xs] for xs in (got, want))
    ring = _ring((rows, width))
    for name, before, a, b in zip("huv", (h, u, v), got, want):
        before = np.asarray(before)
        # the round did something, and the kernel did the same
        assert np.abs(b - before)[G:-G, G:-G].max() > 0.1, name
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-6, err_msg=name)
        np.testing.assert_array_equal(a[ring], b[ring], err_msg=name)
        if name == "v":
            ring = ring.copy()
            ring[-(G + 1)] = False
        np.testing.assert_array_equal(a[ring], before[ring], err_msg=name)
    # the new tendencies at the fields' shape, zero on the ghost ring
    for name, a, b in zip(("dh", "du", "dv"), got[3:], want[3:]):
        assert np.abs(b).max() > 0.5, name
        np.testing.assert_allclose(
            a[G:-G, G:-G], b, rtol=0, atol=1e-6, err_msg=name)
        assert not a[_ring(a.shape)].any(), name
    wall_row = got[2][-(G + 1)]
    assert (wall_row == 0).all() == WALLS[walls][1]


@pytest.mark.parametrize("mesh_shape", [(1, 1), (2, 2)])
def test_multistep_through_the_kernels_matches_the_array_path(
        mesh_shape, monkeypatch):
    """``make_init``, ``make_first_step`` and ``make_multistep`` with
    both rounds forced through the kernels (interpreted) against the
    array path, after 1 + 10 steps: walls and the Coriolis parameter's
    rows on the right devices, halos between the rounds, the first step
    and the rest through one kernel."""
    mesh = jax.make_mesh(
        mesh_shape, ("y", "x"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2)
    comm = m.MeshComm.from_mesh(mesh)
    py, px = mesh_shape
    # a fast rotation and a deep layer: friction moves u by 1e-2 m/s in
    # these steps (the published coefficients: 1e-4), and h stays
    # positive; a beta plane on which the rotation doubles from wall to
    # wall, so that a device that took another's rows would show
    cfg = sw.SWConfig(ny=40, nx=48, ghost=G, coriolis_f=2e-2, depth=1e3,
                      coriolis_beta=1e-7)
    block = (40 // py + 2 * G, 48 // px + 2 * G)

    def run():
        state = sw.make_init(cfg, comm)()
        state = sw.make_first_step(cfg, comm)(state)
        return jax.tree.map(
            np.asarray, sw.make_multistep(cfg, comm, 10)(state))

    def interiors(x):
        """A global array of padded blocks without their ghost rings."""
        blocks = x.reshape(py, block[0], px, block[1])
        return blocks[:, G:-G, :, G:-G].reshape(40, 48)

    want = run()
    assert want.dh.shape == (40, 48)
    calls = []

    def interpreted(kernel):
        def call(*args, **kwargs):
            calls.append((kernel.__name__, args[0].shape))
            return kernel(*args, interpret=True, **kwargs)

        return call

    rounds = ("tendency_round", "viscosity_round")
    for name in rounds:
        monkeypatch.setattr(
            sw_kernels, name, interpreted(getattr(sw_kernels, name)))
    monkeypatch.setattr(sw, "_runs_as_kernels", lambda cfg, comm: True)
    # Pallas's interpreter slices blocks at indices that vary over no
    # mesh axis, which shard_map's checker refuses; the compiled kernels
    # are checked (tests/test_tpu_compile.py)
    monkeypatch.setattr(
        jax, "shard_map", functools.partial(jax.shard_map, check_vma=False))
    imports = []
    pallas = sw_kernels.pallas
    monkeypatch.setattr(
        sw_kernels, "pallas", lambda: imports.append(1) or pallas())
    got = run()
    # each round is built once in each of the two programs, on one
    # device's block; Pallas is asked for where each program is built,
    # and once more where each kernel is traced: the second program
    # reuses the first's traces
    assert calls == [(name, block) for name in rounds] * 2
    assert len(imports) == 4
    # where the rounds are kernels the state carries padded tendencies,
    # from make_init on; a first step takes them interior-shaped too, as
    # who builds a state of their own hands them in (the benchmark)
    assert got.dh.shape == got.h.shape
    bare = sw.SWState(
        *sw.make_init(cfg, comm)()[:3], *(jnp.zeros((40, 48)),) * 3)
    np.testing.assert_array_equal(
        sw.make_first_step(cfg, comm)(bare).dv,
        sw.make_first_step(cfg, comm)(sw.make_init(cfg, comm)()).dv)
    with pytest.raises(ValueError, match="carries them padded"):
        sw.make_multistep(cfg, comm, 1)(bare)
    # what a broken round would leave: a kernel that took every block
    # for the mesh's first, a friction that did nothing
    tendency_round = sw_kernels.tendency_round
    monkeypatch.setattr(
        sw_kernels, "tendency_round",
        lambda *args, **kwargs: tendency_round(
            *args[:8], 0, *args[9:], **kwargs))
    misplaced = run()
    monkeypatch.setattr(sw_kernels, "tendency_round", tendency_round)
    monkeypatch.setattr(
        sw_kernels, "viscosity_round", lambda u, v, *args, **kwargs: (u, v))
    smooth = run()
    for name, a, b, c, d in zip(
            sw.SWState._fields, got, want, misplaced, smooth):
        assert np.isfinite(b).all()
        tolerance = 2e-5 * max(1.0, np.abs(b).max())
        if name.startswith("d"):
            assert not (a != 0)[np.tile(_ring(block), mesh_shape)].any(), name
            a, c, d = interiors(a), interiors(c), interiors(d)
        np.testing.assert_allclose(a, b, rtol=0, atol=tolerance, err_msg=name)
        if name in "uv":
            assert np.abs(d - b).max() > 20 * tolerance, name
            assert (np.abs(c - b).max() > 20 * tolerance) == (py > 1), name


def _comm_on(platform):
    devices = np.array([[types.SimpleNamespace(platform=platform)]])
    return types.SimpleNamespace(
        mesh=types.SimpleNamespace(devices=devices), axis_sizes=(1, 1))


@pytest.mark.parametrize("platform,dtype,rows,width,expected", [
    ("tpu", "float32", 7204, 14404, True),
    ("tpu", "float32", 184, 364, True),
    ("cpu", "float32", 7204, 14404, False),   # a Mosaic kernel cannot run
    ("gpu", "float32", 7204, 14404, False),
    ("tpu", "float64", 7204, 14404, False),   # the strips are float32's
    ("tpu", "bfloat16", 7204, 14404, False),
    ("tpu", "float32", 7, 364, False),        # not one strip of 8 rows
    ("tpu", "float32", 7204, 300_000, False),  # a strip over the budget
    # round 1's six fields decide for both rounds: a strip of two
    # fields this wide would fit, one of six does not
    ("tpu", "float32", 7204, 40_000, True),
    ("tpu", "float32", 7204, 100_000, False),
], ids=lambda x: str(x))
def test_the_step_picks_the_kernels_from_platform_dtype_and_shape(
        platform, dtype, rows, width, expected):
    cfg = sw.SWConfig(ny=rows - 2 * G, nx=width - 2 * G, dtype=dtype, ghost=G)
    assert sw._runs_as_kernels(cfg, _comm_on(platform)) is expected
    # the other two schedules are array code everywhere
    assert not sw._runs_as_kernels(replace(cfg, ghost=4), _comm_on(platform))


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


@pytest.mark.parametrize("rows,width,fields,expected", [
    (7204, 14404, 2, 72),   # the benchmark's block: 40 MiB / (10 x 57856 B)
    (1804, 3604, 2, 280),
    (184, 364, 2, 184),     # the whole block when it fits
    (52, 100, 2, 48),       # whole strips only
    (7, 100, 2, 0),
    (7204, 14404, 6, 24),   # round 1's six fields get shorter tiles
    (1804, 3604, 6, 88),
    (184, 364, 6, 184),
    (52, 100, 6, 48),
    (7204, 100_000, 6, 0),  # 8 rows x 30 blocks of 400 KB: over the budget
])
def test_tile_rows(rows, width, fields, expected):
    tile = sw_kernels.tile_rows(rows, width, jnp.float32, fields)
    assert tile == expected and tile % sw_kernels.STRIP == 0


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
    ("tpu", 2, 0, 1),  # without friction round 1 is a kernel still
])
def test_a_step_built_for_tpu_devices_imports_pallas_before_it_is_traced(
        platform, ghost, nu, expected, monkeypatch):
    comm = _comm_on(platform)
    imports = []
    monkeypatch.setattr(sw_kernels, "pallas", lambda: imports.append(1))
    sw._kernels_ahead(
        sw.SWConfig(ny=64, nx=128, ghost=ghost, coriolis_f=2e-4 * nu), comm)
    assert len(imports) == expected
