"""Driver of the shallow-water cells: the solver as a user drives it.

Calls the library only through what users call: ``MeshComm``,
``halo_exchange_2d`` and ``models.shallow_water``'s ``SWConfig``,
``SWState``, ``make_first_step`` and ``make_multistep`` (the objects
``make_solver`` builds).  The benchmark makes the initial fields itself
from the seed and hands the program arrays.

A batch is ``reps`` calls of the compiled multistep enqueued back to
back and one sync: the closed loop of ``make_solver``.
"""

import math
import random

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.harness import files, stats
from perfbench.harness.spans import ENQUEUE, SYNC, span

FIELDS = ("h", "u", "v")


def mode_table(seed, assumed):
    """Three height modes drawn from the seed: x and y wave numbers (x
    ones even, so the field is periodic), two phases, and amplitudes
    that sum to the configuration's total."""
    rng = random.Random(int(seed))
    lo, hi = assumed["wave_numbers"]
    n = assumed["modes"]
    weights = [rng.uniform(0.5, 1.5) for _ in range(n)]
    rows = []
    for w in weights:
        rows.append([
            2 * rng.randint((lo + 1) // 2, hi // 2),
            rng.randint(lo, hi),
            rng.uniform(0.0, 2 * math.pi),
            rng.uniform(0.0, 2 * math.pi),
            assumed["amplitude_m"] * w / sum(weights),
        ])
    return np.asarray(rows, np.float32)


def make_fields(model, ny, nx, dx, dy, sharding=None):
    """Jitted ``modes -> (h0, u0, v0)``: upstream's geostrophically
    balanced jet on the interior cells, its one fixed height mode
    replaced by the seeded ones."""
    ly, lx = ny * dy, nx * dx

    def fields(modes):
        y = (jnp.arange(ny, dtype=jnp.float32) * dy)[:, None]
        x = (jnp.arange(nx, dtype=jnp.float32) * dx)[None, :]
        u0 = 10.0 * jnp.exp(-((y - 0.5 * ly) ** 2) / (0.02 * lx) ** 2)
        coriolis = model["coriolis_f"] + y * model["coriolis_beta"]
        h_geo = jnp.cumsum(-dy * u0 * coriolis / model["gravity"], axis=0)
        bump = sum(
            modes[i, 4]
            * jnp.sin(x / lx * modes[i, 0] * jnp.pi + modes[i, 2])
            * jnp.cos(y / ly * modes[i, 1] * jnp.pi + modes[i, 3])
            for i in range(modes.shape[0])
        )
        h0 = model["depth"] + h_geo - h_geo.mean() + bump
        u0 = jnp.broadcast_to(u0, (ny, nx))
        return (h0.astype(jnp.float32), u0.astype(jnp.float32),
                jnp.zeros((ny, nx), jnp.float32))

    return jax.jit(fields, out_shardings=sharding and (sharding,) * 3)


class Session:
    def __init__(self, ctx):
        import mpi4jax_tpu as m
        from mpi4jax_tpu.models import shallow_water as sw
        from mpi4jax_tpu.parallel.halo import halo_exchange_2d

        self.ctx = ctx
        model = ctx.config["model"]
        grid = ctx.workload["grid"]
        self.ny, self.nx = grid["ny"], grid["nx"]
        py, px = ctx.workload["mesh"]
        self.chips = py * px
        # a refined cell cuts the same kilometres into more cells
        self.dx = model["dx"] / grid["refine"]
        self.dy = model["dy"] / grid["refine"]
        self.steps_per_call = model["num_multisteps"]
        self.ghost = G = model["ghost"]
        self.rows = {r["name"]: r for r in ctx.workload["rows"]}

        mesh = jax.make_mesh(
            (py, px), ("y", "x"),
            axis_types=(jax.sharding.AxisType.Auto,) * 2,
            devices=ctx.devices[: self.chips],
        )
        comm = m.MeshComm.from_mesh(mesh)
        cfg = sw.SWConfig(
            ny=self.ny, nx=self.nx, dx=self.dx, dy=self.dy,
            gravity=model["gravity"], depth=model["depth"],
            coriolis_f=model["coriolis_f"],
            coriolis_beta=model["coriolis_beta"],
            periodic_x=model["periodic_x"], ab_a=model["ab_a"],
            ab_b=model["ab_b"], dtype=model["dtype"], ghost=G,
        )
        self._SWState = sw.SWState
        self.first = sw.make_first_step(cfg, comm)
        self.multi = sw.make_multistep(
            cfg, comm, self.steps_per_call, donate=True)
        self.modes = mode_table(ctx.seed, ctx.config["assumed"]["perturbation"])
        spec = jax.P("y", "x")
        self._fields = make_fields(model, self.ny, self.nx, self.dx, self.dy,
                                   jax.NamedSharding(mesh, spec))

        def initial(*fields):
            # each chip's block with its ghost ring: walls edge-padded,
            # the rest filled by the library's own exchange; no tendencies yet
            def ghosted(a):
                return halo_exchange_2d(
                    jnp.pad(a, G, mode="edge"), comm,
                    periodic=(False, model["periodic_x"]), width=G)[0]

            return (tuple(ghosted(a) for a in fields)
                    + tuple(jnp.zeros_like(a) for a in fields))

        def interior(*fields):
            return tuple(a[G:-G, G:-G] for a in fields)

        self._initial = jax.jit(jax.shard_map(
            initial, mesh=mesh, in_specs=(spec,) * 3, out_specs=(spec,) * 6))
        self._interior = jax.jit(jax.shard_map(
            interior, mesh=mesh, in_specs=(spec,) * 3, out_specs=(spec,) * 3))
        self._text = None
        # warm up the two programs the window and the check drive
        self.state = self.multi(self.first(self._initial_state()))
        jax.block_until_ready(self.state)

    def _initial_state(self):
        return self._SWState(*self._initial(*self._fields(self.modes)))

    # -- the window ----------------------------------------------------

    def batch(self, row):
        with span(ENQUEUE):
            state = self.state
            for _ in range(self.rows[row]["reps"]):
                state = self.multi(state)
            self.state = state
        with span(SYNC):
            jax.block_until_ready(state)

    def units(self, row):
        """Steps in one batch of ``row``."""
        return self.rows[row]["reps"] * self.steps_per_call

    def end_to_end(self, samples):
        steps = [self.units(s.row) for s in samples]
        wall = samples[-1].end - samples[0].start
        cells = self.ny * self.nx
        return {
            # all the window's steps over all its time
            "solver_rate": cells * sum(steps) / wall / self.chips / 1e6,
            "solver_step_p95_us": stats.percentile(
                [s.seconds / n * 1e6 for s, n in zip(samples, steps)], 95),
        }

    def facts(self):
        return {"steps_per_call": self.steps_per_call, "cells": self.ny * self.nx}

    def compiled_text(self, key):
        """The text of the multistep, the window's one program whatever
        ``key`` a reader knows it by, as compiled for the state at hand
        (what ``harness/scopes.py attribute`` and ``signature`` read),
        compiled once however many readers ask."""
        if self._text is None:
            self._text = self.multi.lower(self.state).compile().as_text()
        return self._text

    # -- after the window ----------------------------------------------

    def check(self):
        """Drive the window's own two programs from the seeded fields
        through ``1 + calls x steps_per_call`` steps and compare with the
        plain reference; the window's last state has to be finite."""
        spec = self.ctx.config["check"]
        nonfinite = sum(
            int(jnp.sum(~jnp.isfinite(getattr(self.state, k)))) for k in FIELDS)
        self.state = None  # free the program's state before the reference
        state = self.first(self._initial_state())
        for _ in range(spec["calls"]):
            state = self.multi(state)
        got = self._interior(state.h, state.u, state.v)
        jax.block_until_ready(got)
        del state
        steps = 1 + spec["calls"] * self.steps_per_call
        return [
            {"name": "nonfinite_after_window", "value": nonfinite, "limit": 0}
        ] + self._compared(got, steps)

    def _compared(self, got, steps):
        limits = self.ctx.config["check"]["limits"]
        diffs = reference_diffs(self, got, steps)
        return [
            {"name": f"max_abs_diff_{k}", "value": diffs[k], "limit": limits[k]}
            for k in FIELDS
        ]

    def control(self):
        """The comparison with the control in the program's place: the
        plain reference carried in bfloat16, the nearest precision below
        the configuration's float32.  It has to come out not correct."""
        self.state = None
        steps = 1 + self.ctx.config["check"]["calls"] * self.steps_per_call
        bands = [b[2] for b in reference_bands(self, steps, "bfloat16")]
        got = tuple(jnp.concatenate(parts) for parts in zip(*bands))
        return self._compared(got, steps)


def reference_bands(session, steps, dtype):
    """The plain reference after ``steps`` steps in ``dtype`` on one
    device, band of rows by band of rows (so that it fits beside what
    it is compared with): yields ``(keep_lo, keep_hi, (h, u, v))``."""
    ctx = session.ctx
    ref = files.load_module("references", ctx.config["reference"], ctx.bench_dir)
    one = ctx.devices[0]
    params = ref.parameters(ctx.config["model"], session.dx, session.dy)
    start = tuple(jax.device_put(a, one) for a in session._fields(session.modes))
    bands = ref.row_blocks(session.ny, ctx.config["check"]["row_blocks"], steps)
    for lo, hi, keep_lo, keep_hi in bands:
        want = ref.run(*(a[lo:hi] for a in start), params, steps, dtype, lo)
        yield keep_lo, keep_hi, tuple(
            w[keep_lo - lo:keep_hi - lo] for w in want)


def reference_diffs(session, got, steps):
    """Largest absolute difference per field between ``got`` and the
    plain float32 reference after ``steps`` steps."""
    got = tuple(jax.device_put(g, session.ctx.devices[0]) for g in got)
    worst = dict.fromkeys(FIELDS, 0.0)
    for lo, hi, want in reference_bands(session, steps, "float32"):
        for k, g, w in zip(FIELDS, got, want):
            worst[k] = max(worst[k], float(jnp.max(jnp.abs(g[lo:hi] - w))))
    return worst


def setup(ctx):
    return Session(ctx)
