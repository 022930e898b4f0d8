"""Driver of the differentiated run: a fit of a window's initial fields
to observations, as a user drives it.

Calls the library only through what users call: ``MeshComm`` and
``models.shallow_water``'s ``SWConfig``, ``make_state``,
``make_first_step``, ``make_multistep``, ``make_snapshot`` (the truth
run that makes the observations, and the forward run the check
compares) and ``Descent`` (``make_gradient`` and ``make_descent_step``
behind it).  The seeded fields are ``drivers/shallow_water.py``'s,
loaded by name; the truth's modes are a second draw from the seed.

A batch is one iteration: the gradient of the window's misfit (two
programs: the forward sweep, which hands the backward sweep the state
each call started from) and the descent step, enqueued back to back,
and one sync.
"""

import re

import jax
import jax.numpy as jnp

from perfbench.harness import files, scopes, stats, trace
from perfbench.harness.spans import ENQUEUE, SYNC, span

FIELDS = ("h", "u", "v")
FORWARD, BACKWARD, UPDATE = "forward", "backward", "update"  # a batch's programs
TRUTH_DRAW = 1 << 33  # past any --seed: the truth's modes are another draw

# The differentiated run's scopes as an instruction's ``op_name`` carries
# them (models/shallow_water.py ADJOINT_SCOPE, parallel/halo.py TRANSPOSE).
# In a backward sweep jax wraps what it transposes, scope and all, in
# ``transpose(jvp(...))``, so a segment may end in brackets and
# ``harness/scopes.py scopes_of``, which splits at ``/`` and looks for a
# segment that starts with the prefix, finds none: these read the name
# as text, and the innermost (last) scope is the instruction's own.
PHASES = ("forward", "recompute", "step_vjp", "cost", "update")
_PHASE = re.compile(r"\bsw/adjoint/(\w+)")
_EXCHANGE = re.compile(
    r"mpi4jax_tpu\.(halo_\w+?)\)*/(transpose/)?(pack|wire|unpack)\b")


def phase_of(op_name):
    """``forward``, ``recompute``, ``step_vjp``, ``cost`` or ``update``:
    the innermost ``sw/adjoint/<phase>`` of an ``op_name``; ``None``
    where it carries none."""
    found = _PHASE.findall(op_name or "")
    return found[-1] if found else None


def exchange_of(op_name):
    """``(op, transposed, pack|wire|unpack)`` of an instruction under a
    halo exchange's scope, the innermost one's; ``None`` under none."""
    found = _EXCHANGE.findall(op_name or "")
    if not found:
        return None
    op, transposed, part = found[-1]
    return op, bool(transposed), part


class Session:
    def __init__(self, ctx):
        import mpi4jax_tpu as m
        from mpi4jax_tpu.models import shallow_water as sw

        self.ctx = ctx
        self.base = base = files.load_module(
            "drivers", "shallow_water", ctx.bench_dir)
        model, window = ctx.config["model"], ctx.config["window"]
        grid = ctx.workload["grid"]
        self.ny, self.nx = grid["ny"], grid["nx"]
        py, px = ctx.workload["mesh"]
        self.chips = py * px
        self.dx = model["dx"] / grid["refine"]
        self.dy = model["dy"] / grid["refine"]
        self.steps_per_call = model["num_multisteps"]
        self.calls = window["calls"]
        self.coarsen = grid["refine"]  # an observation a published cell
        self.window_steps = 1 + self.calls * self.steps_per_call
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
            ab_b=model["ab_b"], dtype=model["dtype"], ghost=model["ghost"],
        )
        assumed = ctx.config["assumed"]["perturbation"]
        self.modes = base.mode_table(ctx.seed, assumed)
        self.truth_modes = base.mode_table(ctx.seed + TRUTH_DRAW, assumed)
        self._fields = base.make_fields(
            model, self.ny, self.nx, self.dx, self.dy,
            jax.NamedSharding(mesh, jax.P("y", "x")))

        # (first: a tree without the differentiated run fails here, at once)
        self.fit = sw.Descent(
            cfg, comm, calls=self.calls, num_steps=self.steps_per_call,
            observe=self.coarsen)
        # the forward programs: the truth run here, the check's run later
        self._state = sw.make_state(cfg, comm)
        self._first = sw.make_first_step(cfg, comm)
        self._multi = sw.make_multistep(cfg, comm, self.steps_per_call, donate=True)
        self._observe = sw.make_snapshot(
            cfg, comm, sw.Snapshot(fields=("h",), coarsen=self.coarsen))
        self._interior = sw.make_snapshot(cfg, comm, sw.Snapshot(coarsen=1))
        self.obs = self._observations(self.truth_modes)

        self._compiled = {}
        # the configuration's: a property of the window, not of the seed
        self.rate = window["step_length"]
        print(f"perfbench: step length {self.rate!r}, the configuration's",
              flush=True)
        self.fit.start(*self._fields(self.modes), self.obs, self.rate)
        # warm up the batch as the window runs it
        self.fit.iterate()
        self.fit.wait()

    def _forward(self, modes):
        """The system's forward run from seeded fields: yields the state
        after the first step and after every call."""
        state = self._first(self._state(*self._fields(modes)))
        yield state
        for _ in range(self.calls):
            state = self._multi(state)
            yield state

    def _observations(self, modes):
        return jnp.stack([self._observe(state.h)[0]
                          for state in self._forward(modes)])

    # -- the window ----------------------------------------------------

    def batch(self, row):
        with span(ENQUEUE):
            self.fit.iterate(self.rows[row]["reps"])
        with span(SYNC):
            self.fit.wait()

    def units(self, row):
        """Model steps differentiated in one batch of ``row``."""
        return self.rows[row]["reps"] * self.window_steps

    def end_to_end(self, samples):
        steps = [self.units(s.row) for s in samples]
        wall = samples[-1].end - samples[0].start
        cells = self.ny * self.nx
        return {
            # the window's steps once a gradient: what is run again and
            # what is run backwards is a gradient's cost, not its work
            "solver_rate": cells * sum(steps) / wall / self.chips / 1e6,
            "solver_step_p95_us": stats.percentile(
                [s.seconds / n * 1e6 for s, n in zip(samples, steps)], 95),
        }

    def facts(self):
        return {"steps_per_call": self.steps_per_call,
                "cells": self.ny * self.nx,
                "window_steps": self.window_steps,
                "adjoint": self.fit.stats()}

    def traced_programs(self, traced):
        """The key of the program each execution of the traced batches
        ran, in order."""
        return [key for s in traced for _ in range(self.rows[s.row]["reps"])
                for key in (FORWARD, BACKWARD, UPDATE)]

    def compiled(self, key):
        """The compiled program ``key`` (``forward``, ``backward`` or
        ``update``) for the fields at hand, compiled once however many
        readers ask."""
        if key not in self._compiled:
            fields = self._fields(self.modes)
            gradient = self.fit.gradient
            if key == FORWARD:
                lowered = gradient.forward.lower(*fields, self.obs)
            elif key == BACKWARD:  # handed what the forward sweep hands it
                _cost, *kept = gradient.forward(*fields, self.obs)
                lowered = gradient.backward.lower(*fields, self.obs, *kept)
            else:
                lowered = self.fit.update.lower(
                    *fields, *fields, jnp.float32(0))
            self._compiled[key] = lowered.compile()
        return self._compiled[key]

    def compiled_text(self, key):
        return self.compiled(key).as_text()

    def traced_events(self, view):
        """``[(program key, event, its instruction's op_name)]`` over the
        traced batches' executions, all chips; ``None``, with the reason
        printed, where trace and programs do not belong together."""
        placed = scopes.by_execution(
            view.trace, self.traced_programs(view.traced))
        if placed is None:
            return None
        names = {key: {name: origin.op_name for name, origin in
                       scopes.origins(self.compiled_text(key)).items()}
                 for key in (FORWARD, BACKWARD, UPDATE)}
        return [(key, e, names[key].get(trace.short_name(e.name)))
                for of_chip in placed.values() for key, events in of_chip
                for e in events]

    # -- after the window ----------------------------------------------

    def _bands(self):
        spec = self.ctx.config["check"]
        ref = files.load_module(
            "references", self.ctx.config["reference"], self.ctx.bench_dir)
        return ref, ref.bands(
            self.ny, spec["band_rows"], self.coarsen, self.window_steps,
            self.ctx.seed, spec["bands"])

    def _reference_gradients(self, dtype):
        """The plain reference's cost and gradient at the first guess,
        band by band: yields ``(keep_lo, keep_hi, cost, (gh, gu, gv))``,
        the cost the band's own (wrong near a cut edge: compared with
        nothing) and the gradients cut to the rows kept."""
        ref, bands = self._bands()
        one = self.ctx.devices[0]
        params = ref.parameters(self.ctx.config["model"], self.dx, self.dy)
        start = tuple(jax.device_put(a, one) for a in self._fields(self.modes))
        obs = jax.device_put(self.obs, one)
        c = self.coarsen
        for lo, hi, keep_lo, keep_hi in bands:
            cost, *grads = ref.gradient(
                *(a[lo:hi] for a in start), obs[:, lo // c:hi // c], params,
                self.calls, self.steps_per_call, c, dtype, lo)
            yield keep_lo, keep_hi, cost, tuple(
                g[keep_lo - lo:keep_hi - lo] for g in grads)

    def _gradient_checks(self, got, bands):
        """Relative L2 distance a band and a field between the gradients
        ``got`` (whole fields) and the reference's ``bands``."""
        limits = self.ctx.config["check"]["gradient_limits"]
        checks = []
        for i, (lo, hi, _cost, want) in enumerate(bands):
            for k, g, w in zip(FIELDS, got, want):
                d = g[lo:hi] - w
                checks.append({
                    "name": f"gradient_rel_l2_{k}_band{i}",
                    "value": float(jnp.sqrt(jnp.vdot(d, d) / jnp.vdot(w, w))),
                    "limit": limits[k]})
        return checks

    def check(self):
        """The gradient the timed program gives at the first guess
        against the plain reference's, band by band; the forward programs
        through the window against the plain solver, as every solver
        cell; the fit's costs finite and falling."""
        costs = self.fit.costs()
        fitted = self.fit.fields
        nonfinite = sum(int(jnp.sum(~jnp.isfinite(a))) for a in fitted)
        nonfinite += sum(c != c or abs(c) == float("inf") for c in costs)
        stats_ = self.fit.stats()
        self.fit.fields = fitted = None  # room for the reference
        checks = [
            {"name": "nonfinite_after_window", "value": nonfinite, "limit": 0},
            # the cost before the last iteration over the cost before the
            # first: below 1 where the descent descends
            {"name": "cost_last_over_first", "value": costs[-1] / costs[0],
             "limit": 1.0},
            {"name": "gradients_not_counted",
             "value": abs(stats_["gradients"] - len(costs)), "limit": 0},
        ]
        one = self.ctx.devices[0]
        cost, *got = self.fit.gradient(*self._fields(self.modes), self.obs)
        got = tuple(jax.device_put(g, one) for g in got)
        jax.block_until_ready(got)
        checks += self._gradient_checks(got, self._reference_gradients("float32"))
        del got
        # the window's last state, by the forward programs
        *_, state = self._forward(self.modes)
        last = self._interior(state.h, state.u, state.v)
        jax.block_until_ready(last)
        del state
        limits = self.ctx.config["check"]["limits"]
        diffs = self.base.reference_diffs(self, last, self.window_steps)
        checks += [{"name": f"max_abs_diff_{k}", "value": diffs[k],
                    "limit": limits[k]} for k in FIELDS]
        return checks

    def control(self):
        """The comparison with the control in the program's place: the
        plain reference's gradient carried in bfloat16, against the
        float32 reference's.  It has to come out not correct."""
        self.fit.fields = None
        whole = [jnp.zeros((self.ny, self.nx), jnp.float32) for _ in FIELDS]
        for lo, hi, _cost, grads in self._reference_gradients("bfloat16"):
            whole = [w.at[lo:hi].set(g) for w, g in zip(whole, grads)]
        return self._gradient_checks(
            whole, self._reference_gradients("float32"))


def setup(ctx):
    return Session(ctx)
