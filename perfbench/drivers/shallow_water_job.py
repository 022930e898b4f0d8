"""Driver of the solver-job cells: the solver run for its output.

The program is driven through what a user of the job calls:
``models.shallow_water.make_job`` with a ``Snapshot`` and a callback,
``job.start``, ``job.advance``, ``job.drain`` and ``job.stats``: the
object ``make_solver`` and ``examples/shallow_water.py --animate`` loop
over.  The seeded modes, the initial fields and the cut of the plain
reference into bands of rows are those of ``drivers/shallow_water.py``,
loaded by name (``mode_table``, ``make_fields``, ``reference_bands``'s
way of calling the reference).

A batch is ``reps`` calls, each followed by its snapshot (whose copy to
the host the job starts when ``ahead_bytes`` lets it), and ends when
its last call's state is ready: that call's snapshot and the copies to
the host run on beside the next batch, as they do in ``make_solver``'s
loop.  The snapshots are handed, at most ``lag`` late, to a callback
that keeps the last ``kept`` and does no arithmetic.

The profiler of a traced run therefore stops while the window's last
snapshot runs, and records part of it: the readers of this cell go
through ``Session.traced_programs``, which leaves a cut one out.  A call
is the programs the job holds (``Session.programs``): a job without a
snapshot program of its own is a call of one program, and the trace's
count of modules decides nothing by itself.

A program's compiled text is asked for with what the job handed that
program (``Handed``, ``watch``, ``text_of``): a job whose last step
writes coarse sums hands its snapshot program those, not ``h, u, v``,
and a reader has to read the program that ran.
"""

import collections

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.harness import files, stats
from perfbench.harness.spans import ENQUEUE, SYNC, span
from perfbench.harness.trace import Trace

plain = files.load_module("drivers", "shallow_water")
FIELDS = plain.FIELDS
MULTI, SNAPSHOT = "multistep", "snapshot"  # the keys of a call's programs
# a program's key and the job's name for it: `job.multi`, and `job._multi`
# for what its loop calls (the same, or its executable after `job.compile()`)
PROGRAMS = {MULTI: "multi", SNAPSHOT: "snap", "stage": "stage"}


class Handed:
    """A program as the job's loop calls it, and the shapes, dtypes and
    shardings of what it was handed at its first call."""

    def __init__(self, program):
        self.program, self.handed = program, None

    def __call__(self, *args):
        if self.handed is None:
            self.handed = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding),
                args)
        return self.program(*args)

    def __getattr__(self, name):  # whatever else the job asks of its program
        return getattr(self.program, name)


def watch(job):
    """From here on, what ``job``'s loop hands each of its programs at
    the program's first call is kept for ``text_of``."""
    for name in PROGRAMS.values():
        called = getattr(job, "_" + name, None)
        if called is not None and not isinstance(called, Handed):
            setattr(job, "_" + name, Handed(called))


def text_of(job, key):
    """The compiled text of ``job``'s program ``key``, lowered with what
    the job handed it (``watch``).  A program the loop has not called
    yet, or calls through a name this file does not know, is lowered
    with the state, or the written fields, by assumption, and that is
    said."""
    program = getattr(job, PROGRAMS[key])
    if program is None:
        raise KeyError(f"the job holds no {key!r} program")
    handed = getattr(getattr(job, "_" + PROGRAMS[key], None), "handed", None)
    if handed is None:
        print(f"perfbench: the job was not seen handing its {key!r} program "
              "anything: its text is lowered with the state at hand, by "
              "assumption", flush=True)
        handed = (tuple(getattr(job.state, k) for k in FIELDS)
                  if key == SNAPSHOT else (job.state,))
    return program.lower(*handed).compile().as_text()


class Session:
    def __init__(self, ctx):
        import mpi4jax_tpu as m
        from mpi4jax_tpu.models import shallow_water as sw
        from mpi4jax_tpu.parallel.halo import halo_exchange_2d

        self.ctx = ctx
        model, output = ctx.config["model"], ctx.config["output"]
        grid = ctx.workload["grid"]
        self.ny, self.nx = grid["ny"], grid["nx"]
        py, px = ctx.workload["mesh"]
        self.chips = py * px
        self.dx = model["dx"] / grid["refine"]
        self.dy = model["dy"] / grid["refine"]
        self.steps_per_call = model["num_multisteps"]
        self.ghost = G = model["ghost"]
        self.coarsen = grid["refine"]  # a snapshot is on the published grid
        self.lag = output["lag"]
        self.rows = {r["name"]: r for r in ctx.workload["rows"]}
        self.ref = files.load_module(
            "references", ctx.config["reference"], ctx.bench_dir)

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
        # what the callback is handed: the last `kept`, and a count of
        # every snapshot that came out of order or not whole
        self.kept = collections.deque(maxlen=output["kept"])
        self.violations = 0
        self._texts = {}
        self.job = sw.make_job(
            cfg, comm, self.steps_per_call,
            sw.Snapshot(fields=tuple(output["fields"]), coarsen=self.coarsen,
                        lag=self.lag, ahead_bytes=output["ahead_bytes"]),
            self._on_chunk)
        watch(self.job)
        self.modes = plain.mode_table(
            ctx.seed, ctx.config["assumed"]["perturbation"])
        spec = jax.P("y", "x")
        self._fields = plain.make_fields(
            model, self.ny, self.nx, self.dx, self.dy,
            jax.NamedSharding(mesh, spec))

        def initial(*fields):
            # as drivers/shallow_water.py: each chip's block with its
            # ghost ring, filled by the library's own exchange
            def ghosted(a):
                return halo_exchange_2d(
                    jnp.pad(a, G, mode="edge"), comm,
                    periodic=(False, model["periodic_x"]), width=G)[0]

            return (tuple(ghosted(a) for a in fields)
                    + tuple(jnp.zeros_like(a) for a in fields))

        self._initial = jax.jit(jax.shard_map(
            initial, mesh=mesh, in_specs=(spec,) * 3, out_specs=(spec,) * 6))
        # warm up the programs the window and the check drive
        self._restart()
        self.job.advance(1)
        self.job.drain()
        self.at_setup = self.job.stats()
        self.calls = 0  # of the window (the traced batches among them)

    def _restart(self):
        """The job at step 1 of the seeded fields, nothing kept."""
        self.job.drain()
        self.job.state = None
        self.job.start(
            self._SWState(*self._initial(*self._fields(self.modes))))
        self.kept.clear()
        self._expected = self.job.step + self.steps_per_call

    def _on_chunk(self, snapshot, step):
        shape = (self.ny // self.coarsen, self.nx // self.coarsen)
        whole = (tuple(snapshot) == FIELDS and all(
            a.shape == shape and a.dtype == np.float32
            for a in snapshot.values()))
        if step != self._expected or not whole:
            self.violations += 1
        self._expected = step + self.steps_per_call
        self.kept.append((step, snapshot))

    # -- the window ----------------------------------------------------

    def batch(self, row):
        reps = self.rows[row]["reps"]
        with span(ENQUEUE):
            self.job.advance(reps)
        with span(SYNC):
            jax.block_until_ready(self.job.state)
        self.calls += reps
        if self.violations:
            raise RuntimeError(
                f"{self.violations} snapshots out of order or not whole")

    def units(self, row):
        """Steps in one batch of ``row``."""
        return self.rows[row]["reps"] * self.steps_per_call

    def end_to_end(self, samples):
        steps = [self.units(s.row) for s in samples]
        wall = samples[-1].end - samples[0].start
        cells = self.ny * self.nx
        return {
            "solver_rate": cells * sum(steps) / wall / self.chips / 1e6,
            "solver_step_p95_us": stats.percentile(
                [s.seconds / n * 1e6 for s, n in zip(samples, steps)], 95),
        }

    def facts(self):
        return {"steps_per_call": self.steps_per_call, "cells": self.ny * self.nx}

    def programs(self):
        """The keys of the programs a call runs, in order: the multistep
        and, where the job holds one, the snapshot program."""
        return (MULTI,) if self.job.snap is None else (MULTI, SNAPSHOT)

    def traced_programs(self, trace, traced):
        """``(trace, executions)`` for ``harness/scopes.py``: the
        program each device execution of the traced batches ran, in
        order (a call's ``programs()``), and the trace they are matched
        with.  Where a call ends in a snapshot the window's last one is
        running when the profiler stops: where a chip's trace holds
        fewer of its operations than of the snapshot before, or not its
        execution at all, both are returned without it, so that a
        reader takes whole executions only and divides a program's
        time by the executions it has."""
        a_call = self.programs()
        executions = [key for s in traced
                      for _ in range(self.rows[s.row]["reps"])
                      for key in a_call]
        n, per = len(executions), len(a_call)
        ordered = {plane: sorted(trace.modules.get(plane, ()),
                                 key=lambda m: m.start_ns)
                   for plane in trace.device_ops}
        # a batch ends when its last multistep's state is ready: only
        # what a call runs after that can be cut
        if (a_call[-1] == MULTI or n < 2 * per
                or any(len(modules) not in (n - 1, n)
                       for modules in ordered.values())):
            return trace, executions  # the harness says what does not match
        whole = Trace(host=trace.host)
        cut = False
        for plane, modules in ordered.items():
            events = trace.device_ops[plane]
            before, end = modules[n - 1 - per], modules[n - 2].end_ns
            of_last = sum(e.start_ns >= end for e in events)
            of_before = sum(before.start_ns <= e.start_ns < before.end_ns
                            for e in events)
            cut = cut or len(modules) < n or of_last < of_before
            whole.modules[plane] = modules[:n - 1]
            whole.device_ops[plane] = [e for e in events if e.start_ns < end]
        if not cut:
            return trace, executions
        print("perfbench: the profiler stopped inside the window's last "
              "snapshot: the readers leave that execution out", flush=True)
        return whole, executions[:-1]

    def compiled_text(self, key):
        """The text of one of the call's programs as compiled for what
        the job handed it in the window (what ``harness/scopes.py
        attribute`` and ``signature`` read), compiled once however many
        readers ask."""
        if key not in self._texts:
            self._texts[key] = text_of(self.job, key)
        return self._texts[key]

    # -- after the window ----------------------------------------------

    def check(self):
        """(c) every snapshot of the window delivered, none more than
        ``lag`` late, the window's last state finite; (b) the window's
        last snapshot against the reference's block mean of the
        window's last state; (a) the job itself from the seeded fields
        through ``calls`` calls, its snapshots against the block means
        of the plain reference at the same steps."""
        spec = self.ctx.config["check"]
        self.job.drain()
        now = self.job.stats()
        delivered = now["snapshots_delivered"] - self.at_setup["snapshots_delivered"]
        nonfinite = sum(
            int(jnp.sum(~jnp.isfinite(getattr(self.job.state, k))))
            for k in FIELDS)
        checks = [
            {"name": "snapshots_undelivered",
             "value": abs(self.calls - delivered), "limit": 0},
            {"name": "snapshots_out_of_order_or_torn",
             "value": self.violations, "limit": 0},
            {"name": "max_lag", "value": now["max_lag"], "limit": self.lag},
            {"name": "nonfinite_after_window", "value": nonfinite, "limit": 0},
        ]
        checks += self._last_snapshot("last_snapshot_diff", self.kept[-1][1])
        self._restart()  # frees the window's state before the reference
        self.job.advance(spec["calls"])
        self.job.drain()
        got = {step: snap for step, snap in self.kept}
        return checks + self._compared(got)

    def _last_snapshot(self, name, snapshot):
        """``snapshot`` against the reference's block mean of the
        job's state as it stands."""
        limits = self.ctx.config["check"]["last_snapshot_limits"]
        out = []
        for k in FIELDS:
            whole = self._interior(np.asarray(getattr(self.job.state, k)))
            want = self.ref.block_mean(whole, self.coarsen)
            out.append({"name": f"{name}_{k}", "limit": limits[k],
                        "value": float(np.max(np.abs(snapshot[k] - want)))})
        return out

    def _check_steps(self):
        """The steps of the snapshots comparison (a) makes: 11, 21, ..."""
        calls = self.ctx.config["check"]["calls"]
        return [1 + (k + 1) * self.steps_per_call for k in range(calls)]

    def _interior(self, padded):
        """The domain's cells from a field's array as the state holds
        it: each chip's block with its own ghost ring."""
        G = self.ghost
        py, px = self.ctx.workload["mesh"]
        ly, lx = padded.shape[0] // py, padded.shape[1] // px
        blocks = padded.reshape(py, ly, px, lx)[:, G:ly - G, :, G:lx - G]
        return blocks.reshape(self.ny, self.nx)

    def _compared(self, got):
        """``got[step][field]`` (host arrays, the steps of the check's
        calls) against the plain float32 reference's block means at
        those steps: the largest difference a field over all the steps."""
        spec = self.ctx.config["check"]
        steps = self._check_steps()
        worst = dict.fromkeys(FIELDS, 0.0)
        for lo, hi, means in reference_block_means(self, steps, "float32"):
            lo, hi = lo // self.coarsen, hi // self.coarsen
            for step, at_step in zip(steps, means):
                for k, want in zip(FIELDS, at_step):
                    mine = jax.device_put(got[step][k][lo:hi], self.ctx.devices[0])
                    worst[k] = max(worst[k], float(jnp.max(jnp.abs(mine - want))))
        return [
            {"name": f"max_abs_diff_{k}", "value": worst[k],
             "limit": spec["limits"][k]}
            for k in FIELDS
        ]

    def control(self):
        """Two controls, both of which have to come out not correct.
        The plain reference carried in bfloat16, block-averaged, in the
        snapshots' place.  And a snapshot one call stale: the snapshot
        of the call before the last, compared as the window's last
        snapshot is with the state as the last call left it."""
        steps = self._check_steps()
        got = {step: {k: [] for k in FIELDS} for step in steps}
        self.job.state = None
        for _lo, _hi, means in reference_block_means(self, steps, "bfloat16"):
            for step, at_step in zip(steps, means):
                for k, part in zip(FIELDS, at_step):
                    got[step][k].append(np.asarray(part))
        got = {step: {k: np.concatenate(parts) for k, parts in by.items()}
               for step, by in got.items()}
        checks = self._compared(got)
        self._restart()
        self.job.advance(2)
        self.job.drain()
        on_time = self.kept[-2][1]  # of the state one call ago
        return checks + self._last_snapshot("stale_snapshot_diff", on_time)


def reference_block_means(session, steps, dtype):
    """The plain reference walked through ``steps`` in ``dtype`` on one
    device, band of rows by band of rows as ``drivers/shallow_water.py
    reference_bands`` cuts them: yields ``(keep_lo, keep_hi, means)``,
    ``means[i]`` the block means ``(h, u, v)`` of the band after
    ``steps[i]`` steps."""
    ctx, ref = session.ctx, session.ref
    one = ctx.devices[0]
    params = ref.parameters(ctx.config["model"], session.dx, session.dy)
    start = tuple(jax.device_put(a, one) for a in session._fields(session.modes))
    bands = ref.row_blocks(session.ny, ctx.config["check"]["row_blocks"], steps[-1])
    for lo, hi, keep_lo, keep_hi in bands:
        if keep_lo % session.coarsen:
            raise ValueError(f"a band of rows from {keep_lo} cuts a block "
                             f"of {session.coarsen} rows")
        yield keep_lo, keep_hi, ref.run_block_means(
            *(a[lo:hi] for a in start), params, steps, session.coarsen,
            (keep_lo - lo, keep_hi - lo), dtype, lo)


def setup(ctx):
    return Session(ctx)
