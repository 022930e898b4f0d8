"""The solver's Pallas kernels against the array code they replace.

Everything here runs on the CPU backend, the kernel in Pallas's
interpret mode: it shows that the kernel computes what the array code
of the same round computes, at block shapes that exercise the tiling's
edges, and that the step picks the kernel only where it can run.  That
the kernel compiles for the chip is ``tests/test_tpu_compile.py``'s to
show; how fast it is, only a chip run's (``PERF.md``).
"""

import functools
import os
import subprocess
import sys
import types

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


def _fields(rows, width, seed=0):
    ku, kv = jax.random.split(jax.random.PRNGKey(seed))
    return (jax.random.normal(ku, (rows, width), jnp.float32),
            jax.random.normal(kv, (rows, width), jnp.float32))


@pytest.mark.parametrize("walls", sorted(WALLS))
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_viscosity_kernel_matches_the_array_code(shape, walls, monkeypatch):
    rows, width, budget = SHAPES[shape]
    if budget is not None:
        monkeypatch.setattr(sw_kernels, "_VMEM_BLOCK_BUDGET", budget)
        tile = sw_kernels.tile_rows(rows, width, jnp.float32, fields=2)
        assert tile == int(shape.split("-")[2]) and rows > 3 * tile
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
        ring = np.ones(a.shape, bool)
        ring[G:-G, G:-G] = False
        np.testing.assert_array_equal(a[ring], b[ring], err_msg=name)
        ring[-(G + 1)] = False
        np.testing.assert_array_equal(a[ring], before[ring], err_msg=name)
    # v = 0 on the northern wall row, and only under a northern wall
    wall_row = np.asarray(got[1])[-(G + 1)]
    assert (wall_row == 0).all() == WALLS[walls][1]


@pytest.mark.parametrize("mesh_shape", [(1, 1), (2, 2)])
def test_multistep_through_the_kernel_matches_the_array_path(
        mesh_shape, monkeypatch):
    """``make_first_step`` and ``make_multistep`` with round 2 forced
    through the kernel (interpreted) against the array path, after
    1 + 10 steps: walls on the right devices, halos between the rounds."""
    mesh = jax.make_mesh(
        mesh_shape, ("y", "x"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2)
    comm = m.MeshComm.from_mesh(mesh)
    # a fast rotation and a deep layer: friction moves u by 1e-2 m/s in
    # these steps (the published coefficients: 1e-4), and h stays positive
    cfg = sw.SWConfig(ny=40, nx=48, ghost=G, coriolis_f=2e-2, depth=1e3)

    def run():
        state = sw.make_init(cfg, comm)()
        state = sw.make_first_step(cfg, comm)(state)
        return jax.tree.map(
            np.asarray, sw.make_multistep(cfg, comm, 10)(state))

    want = run()
    calls = []

    def interpreted(*args, **kwargs):
        calls.append(args[0].shape)
        return kernel(*args, interpret=True, **kwargs)

    kernel = sw_kernels.viscosity_round
    monkeypatch.setattr(sw, "_viscosity_runs_as_kernel", lambda comm, u: True)
    monkeypatch.setattr(sw_kernels, "viscosity_round", interpreted)
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
    py, px = mesh_shape
    # the round is built once in each of the two programs, on one
    # device's block; Pallas is asked for where each program is built,
    # and once more where the kernel is traced: the second program
    # reuses the first's trace
    assert calls == [(40 // py + 2 * G, 48 // px + 2 * G)] * 2
    assert len(imports) == 3
    monkeypatch.setattr(
        sw_kernels, "viscosity_round", lambda u, v, *args, **kwargs: (u, v))
    without = run()
    for name, a, b, c in zip(sw.SWState._fields, got, want, without):
        assert np.isfinite(b).all()
        tolerance = 2e-5 * max(1.0, np.abs(b).max())
        np.testing.assert_allclose(a, b, rtol=0, atol=tolerance, err_msg=name)
        if name in "uv":  # a round that did nothing would show
            assert np.abs(c - b).max() > 20 * tolerance, name


def _comm_on(platform):
    devices = np.array([[types.SimpleNamespace(platform=platform)]])
    return types.SimpleNamespace(mesh=types.SimpleNamespace(devices=devices))


@pytest.mark.parametrize("platform,dtype,rows,width,expected", [
    ("tpu", "float32", 7204, 14404, True),
    ("tpu", "float32", 184, 364, True),
    ("cpu", "float32", 7204, 14404, False),   # a Mosaic kernel cannot run
    ("gpu", "float32", 7204, 14404, False),
    ("tpu", "float64", 7204, 14404, False),   # the strips are float32's
    ("tpu", "bfloat16", 7204, 14404, False),
    ("tpu", "float32", 7, 364, False),        # not one strip of 8 rows
    ("tpu", "float32", 7204, 300_000, False),  # a strip over the budget
], ids=lambda x: str(x))
def test_the_step_picks_the_kernel_from_platform_dtype_and_shape(
        platform, dtype, rows, width, expected):
    u = jax.ShapeDtypeStruct((rows, width), jnp.dtype(dtype))
    assert sw._viscosity_runs_as_kernel(_comm_on(platform), u) is expected


def test_a_step_on_cpu_devices_is_the_array_code():
    mesh = jax.make_mesh(
        (1, 1), ("y", "x"), axis_types=(jax.sharding.AxisType.Auto,) * 2)
    comm = m.MeshComm.from_mesh(mesh)
    cfg = sw.SWConfig(ny=24, nx=48, ghost=G)
    state = jax.eval_shape(sw.make_init(cfg, comm))
    assert not sw._viscosity_runs_as_kernel(comm, state.u)
    text = sw.make_multistep(cfg, comm, 1).lower(state).as_text()
    assert "custom_call" not in text or "tpu_custom_call" not in text


@pytest.mark.parametrize("rows,width,fields,expected", [
    (7204, 14404, 2, 72),   # the benchmark's block: 40 MiB / (10 x 57856 B)
    (1804, 3604, 2, 280),
    (184, 364, 2, 184),     # the whole block when it fits
    (52, 100, 2, 48),       # whole strips only
    (7, 100, 2, 0),
    (7204, 14404, 6, 24),   # round 1's six fields would get shorter tiles
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


@pytest.mark.parametrize("platform,ghost,expected", [
    ("tpu", 2, 1), ("cpu", 2, 0), ("tpu", 1, 0), ("tpu", 4, 0)])
def test_a_step_built_for_tpu_devices_imports_pallas_before_it_is_traced(
        platform, ghost, expected, monkeypatch):
    comm = _comm_on(platform)
    comm.axis_sizes = (1, 1)
    imports = []
    monkeypatch.setattr(sw_kernels, "pallas", lambda: imports.append(1))
    sw._kernels_ahead(sw.SWConfig(ny=64, nx=128, ghost=ghost), comm)
    assert len(imports) == expected
