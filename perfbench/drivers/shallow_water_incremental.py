"""Driver of the linearised run: the inner loop of incremental 4D-Var
over a window of the solver's steps, as a user drives it.

Calls the library only through what users call: ``MeshComm`` and
``models.shallow_water``'s ``SWConfig``, ``make_state``,
``make_first_step``, ``make_multistep``, ``make_snapshot`` (the truth
run that makes the observations, and the forward run the check
compares) and ``InnerLoop`` (``make_gradient``, ``make_product`` and
``make_inner_step`` behind it).  The truth run, the observations, the
reference's bands and the scopes' readers are
``drivers/shallow_water_adjoint.py``'s, whose ``Session`` this one
extends; the seeded fields are ``drivers/shallow_water.py``'s.

Set-up is the outer loop: the nonlinear window from the first guess,
its trajectory kept, the innovations.  A batch is one iteration of the
inner loop: the tangent-linear sweep, the adjoint sweep and the vector
updates of conjugate gradients, three programs enqueued back to back,
and one sync.
"""

import functools
import pathlib

import jax
import jax.numpy as jnp

from perfbench.harness import files, scopes, trace
from perfbench.harness.spans import ENQUEUE, SYNC, span

_adjoint = files.load_module(
    "drivers", "shallow_water_adjoint", pathlib.Path(__file__).resolve().parents[1])
FIELDS = _adjoint.FIELDS
TANGENT, ADJOINT, UPDATE = "tangent", "adjoint", "update"  # a batch's programs
PROGRAMS = (TANGENT, ADJOINT, UPDATE)
W_DRAW = 1 << 34  # past any --seed and the truth's draw: the adjoint test's w

# The scopes of an iteration's device time: the adjoint driver's and the
# tangent-linear sweep's (models/shallow_water.py TANGENT), which in a
# tangent sweep sits inside jax's ``jvp(...)``.
PHASES = ("tangent", "recompute", "step_vjp", "cost", "update")
phase_of = _adjoint.phase_of
exchange_of = _adjoint.exchange_of


def _rel_l2(got, want):
    d = got - want
    return float(jnp.sqrt(jnp.vdot(d, d) / jnp.vdot(want, want)))


def _dot(a, b):
    return sum(jnp.vdot(x, y) for x, y in zip(a, b))


# The loop with one fault in it, for `control`: conjugate gradients as
# `models/shallow_water.py make_inner_step` runs them, but the increment
# left where it was, or every step taken twice as long as the line
# search says, or every direction the residual (steepest descent).
FAULTS = ("increment_unchanged", "step_doubled", "no_conjugacy")


@functools.partial(jax.jit, static_argnames=("fault",), donate_argnums=(0, 1, 2))
def _faulty_update(x, r, p, g, rr, cost, weight, fault):
    q = tuple(a + weight * b for a, b in zip(g, p))
    alpha = rr / _dot(p, q)
    if fault != "increment_unchanged":
        stride = 2 * alpha if fault == "step_doubled" else alpha
        x = tuple(a + stride * b for a, b in zip(x, p))
    r = tuple(a - alpha * b for a, b in zip(r, q))
    new = _dot(r, r)
    beta = 0.0 if fault == "no_conjugacy" else new / rr
    p = tuple(a + beta * b for a, b in zip(r, p))
    return x, r, p, new, cost - 0.5 * alpha * rr


class Session(_adjoint.Session):
    def __init__(self, ctx):
        import mpi4jax_tpu as m
        from mpi4jax_tpu.models import shallow_water as sw

        self.ctx = ctx
        self.base = base = files.load_module(
            "drivers", "shallow_water", ctx.bench_dir)
        model, window = ctx.config["model"], ctx.config["window"]
        assumed = ctx.config["assumed"]
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
        self.weight = assumed["background"]["weight"]
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
        # (first: a tree without the linearised run fails here, at once)
        self.fit = sw.InnerLoop(
            cfg, comm, calls=self.calls, num_steps=self.steps_per_call,
            observe=self.coarsen, weight=self.weight,
            iterations=assumed["inner"]["iterations"])
        self.modes = base.mode_table(ctx.seed, assumed["perturbation"])
        self.truth_modes = base.mode_table(
            ctx.seed + _adjoint.TRUTH_DRAW, assumed["perturbation"])
        self._sharding = jax.NamedSharding(mesh, jax.P("y", "x"))
        self._fields = base.make_fields(
            model, self.ny, self.nx, self.dx, self.dy, self._sharding)
        # the forward programs: the truth run here, the check's run later
        self._state = sw.make_state(cfg, comm)
        self._first = sw.make_first_step(cfg, comm)
        self._multi = sw.make_multistep(cfg, comm, self.steps_per_call, donate=True)
        self._observe = sw.make_snapshot(
            cfg, comm, sw.Snapshot(fields=("h",), coarsen=self.coarsen))
        self._interior = sw.make_snapshot(cfg, comm, sw.Snapshot(coarsen=1))
        self.obs = self._observations(self.truth_modes)

        self._compiled = {}
        self.iterations = 0  # over every loop begun
        print(f"perfbench: background weight {self.weight!r}, the "
              "configuration's", flush=True)
        # the outer loop, and the batch warmed up as the window runs it
        self.fit.linearise(*self._fields(self.modes), self.obs)
        self.batch(next(iter(self.rows)))

    # -- the window ----------------------------------------------------

    def batch(self, row):
        reps = self.rows[row]["reps"]
        if self.fit.enqueued + reps > self.fit.iterations:
            self.fit.begin()  # the cap: again from a zero increment
        with span(ENQUEUE):
            self.fit.iterate(reps)
        with span(SYNC):
            self.fit.wait()
        self.iterations += reps

    def facts(self):
        return {"steps_per_call": self.steps_per_call,
                "cells": self.ny * self.nx,
                "window_steps": self.window_steps,
                "incremental": dict(self.fit.stats(), run=self.iterations)}

    def traced_programs(self, traced):
        return [key for s in traced for _ in range(self.rows[s.row]["reps"])
                for key in PROGRAMS]

    def compiled(self, key):
        """The compiled program ``key`` (``tangent``, ``adjoint`` or
        ``update``) for the arrays at hand, compiled once however many
        readers ask."""
        if key not in self._compiled:
            fit = self.fit
            x, r, p = fit.vectors
            kept = (*fit.fields, fit.starts)
            if key == TANGENT:
                lowered = fit.tangent.lower(*kept, *p)
            elif key == ADJOINT:
                lowered = fit.adjoint.lower(*kept, self.obs)
            else:
                lowered = fit.update.lower(x, r, p, p, fit.rr, fit.cost)
            self._compiled[key] = lowered.compile()
        return self._compiled[key]

    def traced_events(self, view):
        placed = scopes.by_execution(
            view.trace, self.traced_programs(view.traced))
        if placed is None:
            return None
        names = {key: {name: origin.op_name for name, origin in
                       scopes.origins(self.compiled_text(key)).items()}
                 for key in PROGRAMS}
        return [(key, e, names[key].get(trace.short_name(e.name)))
                for of_chip in placed.values() for key, events in of_chip
                for e in events]

    def held_bytes(self):
        """Bytes of the arrays held between an iteration's programs: the
        first guess, the trajectory's first level, the observations and
        the loop's vectors."""
        return sum(a.size * a.dtype.itemsize
                   for a in jax.tree.leaves(self.fit.held()))

    # -- after the window ----------------------------------------------

    def _reference_products(self, at, p, dtype, exchange_tangent=True):
        """The plain reference's ``H M p`` and ``A p`` at the first guess
        ``at`` for the direction ``p``, band by band: yields ``(keep_lo,
        keep_hi, z, (qh, qu, qv), t)``, the arrays cut to the rows kept,
        ``t`` the band's own adjoint test."""
        ref, bands = self._bands()
        one = self.ctx.devices[0]
        params = ref.parameters(self.ctx.config["model"], self.dx, self.dy)
        at = tuple(jax.device_put(a, one) for a in at)
        p = tuple(jax.device_put(a, one) for a in p)
        c = self.coarsen
        for lo, hi, keep_lo, keep_hi in bands:
            test, z, *q = ref.product(
                *(a[lo:hi] for a in at), *(a[lo:hi] for a in p), params,
                self.calls, self.steps_per_call, c, self.weight, dtype, lo,
                exchange_tangent)
            yield (keep_lo, keep_hi, z[:, (keep_lo - lo) // c:(keep_hi - lo) // c],
                   tuple(a[keep_lo - lo:keep_hi - lo] for a in q), float(test))

    def _product_checks(self, z, q, bands, suffix=""):
        """Relative L2 distance, a band and an observation time for ``H M
        p`` and a band and a field for ``A p``, between the whole fields
        ``z``, ``q`` and the reference's ``bands``."""
        spec = self.ctx.config["check"]
        c = self.coarsen
        checks = []
        for i, (lo, hi, want_z, want_q, _test) in enumerate(bands):
            for k in range(self.calls + 1):
                checks.append({
                    "name": f"tangent_rel_l2_obs{k}_band{i}{suffix}",
                    "value": _rel_l2(z[k, lo // c:hi // c], want_z[k]),
                    "limit": spec["tangent_limit"]})
            for k, got, want in zip(FIELDS, q, want_q):
                checks.append({
                    "name": f"product_rel_l2_{k}_band{i}{suffix}",
                    "value": _rel_l2(got[lo:hi], want),
                    "limit": spec["product_limits"][k]})
        return checks

    def _loop_checks(self, costs, x, b, suffix=""):
        """What holds a loop's last cost and its increment ``x`` to the
        timed sweeps, ``b`` the loop's right-hand side.  With ``z = H M
        x`` by the tangent-linear program: the quadratic cost at ``x``,
        ``weight / 2 |x|^2 + 1/2 sum |z - d|^2`` (the data term by the
        forward program, as the misfit of the first guess to the
        observations less ``z``), against the last cost of the loop's own
        recurrence; the step a line search along ``x`` would take, ``b .
        x / x . A x`` with ``x . A x = weight |x|^2 + |z|^2``, against 1
        (the loop's ``x`` is the least of the cost over all it has
        searched, its own line among them; steepest descent's is not);
        and the nonlinear misfit at the first guess plus ``x`` over that
        at the first guess."""
        fit, limits = self.fit, self.ctx.config["check"]["loop_limits"]
        one = self.ctx.devices[0]
        z = fit.tangent(*fit.fields, fit.starts, *x)
        data = float(fit.gradient.forward(*fit.fields, self.obs - z)[0][0, 0])
        after = float(fit.gradient.forward(
            *(a + d for a, d in zip(fit.fields, x)), self.obs)[0][0, 0])
        x = tuple(jax.device_put(a, one) for a in x)
        xx, zz = float(_dot(x, x)), float(jnp.vdot(z, z))
        cost = 0.5 * self.weight * xx + data
        curved = self.weight * xx + zz
        step = float(_dot(b, x)) / curved if curved > 0 else 0.0
        print(f"perfbench: the loop{suffix}: its last cost {costs[-1]!r}, the "
              f"quadratic cost at its increment by the sweeps {cost!r}, a line "
              f"search along the increment {step!r}, the nonlinear misfit "
              f"{costs[0]!r} -> {after!r}", flush=True)
        values = {"cost_off_the_increment": abs(costs[-1] - cost) / cost,
                  "step_along_the_increment_off_one": abs(step - 1.0),
                  "misfit_after_over_before": after / costs[0]}
        return [{"name": name + suffix, "value": value, "limit": limits[name]}
                for name, value in values.items()]

    def _faulty_loop(self, iterations, fault):
        """``(costs, x, b)`` of ``iterations`` iterations about the timed
        sweeps with ``fault`` in the updates (:data:`FAULTS`), ``b`` the
        right-hand side they began from."""
        fit = self.fit
        fit.begin()
        x, r, p = fit.vectors
        fit.vectors = None
        b = tuple(jnp.copy(a) for a in r)  # (the updates donate theirs)
        rr, cost = fit.rr[0, 0], fit.cost0[0, 0]
        costs = [cost]
        for _ in range(iterations):
            g = fit.adjoint(*fit.fields, fit.starts,
                            fit.tangent(*fit.fields, fit.starts, *p))
            x, r, p, rr, cost = _faulty_update(
                x, r, p, g, rr, cost, jnp.float32(self.weight), fault)
            costs.append(cost)
        return [float(c) for c in jax.device_get(costs)], x, b

    def _direction(self):
        """``(p, z, q)`` by the timed programs at the first guess: the
        loop's first direction ``p = b`` (the backward sweep's), ``z = H
        M p`` (the tangent-linear sweep's) and ``q = A p`` (the adjoint
        sweep's of ``z``, ``weight p`` added), on one device."""
        fit = self.fit
        fit.begin()
        p = fit.vectors[2]
        z = fit.tangent(*fit.fields, fit.starts, *p)
        g = fit.adjoint(*fit.fields, fit.starts, z)
        q = tuple(a + jnp.float32(self.weight) * b for a, b in zip(g, p))
        one = self.ctx.devices[0]
        return tuple(jax.device_put(x, one) for x in (p, z, q))

    def _adjoint_test(self, p, z):
        """``|<M p, w> - <p, M^T w>| / |<M p, w>|`` by the timed programs
        for a seeded ``w`` in observation space: white noise, and ``M p``
        itself scaled to the noise's norm beside it, so that ``<M p, w>``
        is of the size of ``|M p| |w|`` on every seed (noise alone leaves
        it anywhere down to nothing, and the quotient with it: 7.6e-9 to
        3.7e-5 over nineteen seeds, PERF.md, PR 59)."""
        fit = self.fit
        w = jax.random.normal(
            jax.random.key(self.ctx.seed + W_DRAW), z.shape, z.dtype)
        w = w + z * (jnp.linalg.norm(w) / jnp.linalg.norm(z))
        back = fit.adjoint(*fit.fields, fit.starts,
                           jax.device_put(w, self.obs.sharding))
        one = self.ctx.devices[0]
        there = float(jnp.vdot(z, jax.device_put(w, one)))
        home = sum(float(jnp.vdot(a, jax.device_put(b, one)))
                   for a, b in zip(p, back))
        return abs(there - home) / abs(there)

    def check(self):
        """The loop's costs and curvatures; its last cost and its
        increment held to the timed sweeps (:meth:`_loop_checks`: the
        quadratic cost at the increment, the line search along it, the
        nonlinear misfit at the first guess plus it); the tangent-linear
        sweep and the product the timed programs give
        at the first guess against the plain reference's, band by band;
        the adjoint test on the timed programs; the forward programs
        through the window against the plain solver, as every solver
        cell."""
        fit = self.fit
        stats_ = fit.stats()
        costs, curvatures = stats_["costs"], stats_["curvatures"]
        nonfinite = sum(int(jnp.sum(~jnp.isfinite(a))) for a in fit.increment())
        nonfinite += sum(c != c or abs(c) == float("inf")
                         for c in costs + curvatures)
        print(f"perfbench: {self.iterations} iterations run, "
              f"{stats_['iterations']} of them since the loop last began; the "
              f"quadratic cost {costs[0]!r} -> {costs[-1]!r}", flush=True)
        checks = [
            {"name": "nonfinite_after_window", "value": nonfinite, "limit": 0},
            # it stands still once a decrement is below float32's
            # resolution of it (a small loop converged), and is no rise
            {"name": "cost_rises", "limit": 0,
             "value": sum(b > a for a, b in zip(costs, costs[1:]))
             + (not costs[-1] < costs[0])},
            {"name": "curvatures_not_positive", "limit": 0,
             "value": sum(not c > 0 for c in curvatures)},
            {"name": "iterations_not_counted", "limit": 0,
             "value": abs(len(costs) - 1 - stats_["iterations"])
             + (self.iterations < stats_["iterations"])},
        ]
        self.checked_iterations = stats_["iterations"]
        x = fit.increment()  # (the loop begins again below; this stays)
        p, z, q = self._direction()
        checks += self._loop_checks(costs, x, p)
        del x
        checks.append({
            "name": "adjoint_test_rel", "value": self._adjoint_test(p, z),
            "limit": self.ctx.config["check"]["adjoint_test_limit"]})
        first_guess = self._fields(self.modes)
        fit.vectors = None  # room for the reference
        checks += self._product_checks(
            z, q, self._reference_products(first_guess, p, "float32"))
        del z, q
        # the window's last state, by the forward programs
        *_, state = self._forward(self.modes)
        last = self._interior(state.h, state.u, state.v)
        jax.block_until_ready(last)
        del state
        limits = self.ctx.config["check"]["limits"]
        diffs = self.base.reference_diffs(self, last, self.window_steps)
        checks += [{"name": f"max_abs_diff_{k}", "value": diffs[k],
                    "limit": limits[k]} for k in FIELDS]
        fit.begin()  # the loop as a caller finds it: begun, at a zero increment
        return checks

    def control(self):
        """The comparison with controls in the program's place.  Of the
        sweeps, each against the float32 reference's: the plain reference
        carried in bfloat16, and the float32 reference whose boundary
        code hands its ghost cells no tangent (a halo exchange without a
        forward mode rule); with them each control's own adjoint test,
        band by band (the bfloat16 one is the reading that
        `check.adjoint_test_limit` lies under; the second control's
        passes or fails it by what its tangent left out).  Of the loop,
        each held to the timed sweeps as the loop itself is
        (:meth:`_loop_checks`): as many iterations as the window's loop
        had run when it was checked, with one fault in the updates
        (:data:`FAULTS`).  Each has to come out not correct."""
        checks = []
        iterations = getattr(self, "checked_iterations", 0) or max(
            self.fit.enqueued, 1)
        for fault in FAULTS:
            costs, x, b = self._faulty_loop(iterations, fault)
            checks += self._loop_checks(costs, x, b, "_" + fault)
            del x, b
        p, _z, _q = self._direction()
        self.fit.vectors = None
        first_guess = self._fields(self.modes)
        for suffix, how in (("", ("bfloat16", True)),
                            ("_no_exchange_tangent", ("float32", False))):
            c = self.coarsen
            z = jnp.zeros((self.calls + 1, self.ny // c, self.nx // c), jnp.float32)
            q = [jnp.zeros((self.ny, self.nx), jnp.float32) for _ in FIELDS]
            for i, (lo, hi, band_z, band_q, test) in enumerate(
                    self._reference_products(first_guess, p, *how)):
                z = z.at[:, lo // c:hi // c].set(band_z)
                q = [w.at[lo:hi].set(g) for w, g in zip(q, band_q)]
                # the control's own two sweeps as each other's transpose
                checks.append({
                    "name": f"adjoint_test_rel_band{i}{suffix}", "value": test,
                    "limit": self.ctx.config["check"]["adjoint_test_limit"]})
            checks += self._product_checks(
                z, q, self._reference_products(first_guess, p, "float32"),
                suffix)
        self.fit.begin()
        return checks


def setup(ctx):
    return Session(ctx)
