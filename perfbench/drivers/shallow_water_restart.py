"""Driver of the restarted-job cells: the solver saved, killed, resumed.

The program is driven through what a user of the job calls:
``models.shallow_water.make_job`` with a ``Checkpoint``, ``job.start``,
``job.advance``, ``job.save``, ``job.drain``, ``job.resume`` and
``job.stats``: the object ``make_solver(checkpoint_dir=...)`` and
``examples/shallow_water.py --checkpoint-dir`` loop over.  The seeded
modes, the initial fields and the comparison with the plain reference
in bands of rows are those of ``drivers/shallow_water.py``, loaded by
name (``mode_table``, ``make_fields``, ``reference_bands``,
``reference_diffs``).

Set-up, in every run: the seeded fields, the forward-Euler step, one
call, a save, its acknowledgement; then the kill: the job, its state,
its checkpoint object and every device buffer of theirs are dropped,
and the session keeps the directory's path and nothing else; then a new
job resumes from the directory, its programs loaded, its state ready.
The window starts from the resumed job, as every job of a chain of
restarted jobs does.  A batch is ``reps`` calls enqueued back to back
and one sync on the last call's state; the job starts a save of the
whole state after every ``restart.every_calls``-th call of the
integration, and the copies to the host, the files and the commit pass
beside the next batches.

The job resumed in set-up stands after call 1, so the first save of a
window falls after its ``every_calls - 1``-th call: in the cell, 48
calls a save and ``trace_batches`` 12 (48 calls), a traced window holds
exactly one save's staging program, whole, between the third and the
fourth call of its last batch.
"""

import dataclasses
import os
import resource
import shutil
import statistics
import tempfile
import time

import jax
import jax.numpy as jnp

from perfbench.harness import files, stats
from perfbench.harness.spans import ENQUEUE, SYNC, span

plain = files.load_module("drivers", "shallow_water")
job_driver = files.load_module("drivers", "shallow_water_job")  # `watch`, `text_of`
FIELDS = plain.FIELDS
MULTI, STAGE = "multistep", "stage"  # the programs of a window, by key
STATE = ("h", "u", "v", "dh", "du", "dv")


def describe_filesystem(path):
    """``"<type> on <mount point>, <free> GB free"`` for ``path``."""
    real = os.path.realpath(path)
    best = ("", "?")
    try:
        with open("/proc/mounts") as f:
            for line in f:
                _dev, mount, kind = line.split()[:3]
                if (real == mount or real.startswith(mount.rstrip("/") + "/")) \
                        and len(mount) > len(best[0]):
                    best = (mount, kind)
    except OSError:
        pass
    free = shutil.disk_usage(real).free / 1e9
    return f"{best[1]} on {best[0] or '?'}, {free:.1f} GB free"


@jax.jit
def _differing(a, b):
    """How many elements of two arrays differ in any bit."""
    bits = jax.lax.bitcast_convert_type
    return jnp.sum(bits(a, jnp.int32) != bits(b, jnp.int32))


class Session:
    def __init__(self, ctx):
        import mpi4jax_tpu as m
        from mpi4jax_tpu.models import shallow_water as sw
        from mpi4jax_tpu.parallel.halo import halo_exchange_2d

        self.ctx, self._sw = ctx, sw
        model, restart = ctx.config["model"], ctx.config["restart"]
        grid = ctx.workload["grid"]
        self.ny, self.nx = grid["ny"], grid["nx"]
        py, px = ctx.workload["mesh"]
        self.chips = py * px
        self.dx = model["dx"] / grid["refine"]
        self.dy = model["dy"] / grid["refine"]
        self.steps_per_call = model["num_multisteps"]
        self.ghost = G = model["ghost"]
        self.every = restart["every_calls"]
        self.rows = {r["name"]: r for r in ctx.workload["rows"]}
        self.ref = files.load_module(
            "references", ctx.config["reference"], ctx.bench_dir)

        mesh = jax.make_mesh(
            (py, px), ("y", "x"),
            axis_types=(jax.sharding.AxisType.Auto,) * 2,
            devices=ctx.devices[: self.chips],
        )
        self.comm = m.MeshComm.from_mesh(mesh)
        self.cfg = sw.SWConfig(
            ny=self.ny, nx=self.nx, dx=self.dx, dy=self.dy,
            gravity=model["gravity"], depth=model["depth"],
            coriolis_f=model["coriolis_f"],
            coriolis_beta=model["coriolis_beta"],
            periodic_x=model["periodic_x"], ab_a=model["ab_a"],
            ab_b=model["ab_b"], dtype=model["dtype"], ghost=G,
        )
        self._texts = {}
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
                    jnp.pad(a, G, mode="edge"), self.comm,
                    periodic=(False, model["periodic_x"]), width=G)[0]

            return (tuple(ghosted(a) for a in fields)
                    + tuple(jnp.zeros_like(a) for a in fields))

        def interior(*fields):
            return tuple(a[G:-G, G:-G] for a in fields)

        self._initial = jax.jit(jax.shard_map(
            initial, mesh=mesh, in_specs=(spec,) * 3, out_specs=(spec,) * 6))
        self._interior = jax.jit(jax.shard_map(
            interior, mesh=mesh, in_specs=(spec,) * 3, out_specs=(spec,) * 3))

        # a fresh directory under the run's temporary directory; gone
        # with the session, or with the process
        self._tmp = tempfile.TemporaryDirectory(prefix="perfbench-restart-")
        self.directory = os.path.join(self._tmp.name, "window")
        print(f"perfbench: restart files under {self._tmp.name}: "
              f"{describe_filesystem(self._tmp.name)}", flush=True)

        # the job before this one in the chain: one call, one save
        job = self._job(self.directory)
        job.start(self._sw.SWState(*self._initial(*self._fields(self.modes))))
        job.advance(1)
        job.save()
        job.drain()
        first = job.stats()
        print(f"perfbench: set-up's save of step {job.step}: {job.saves[-1]}",
              flush=True)
        # the kill: nothing of it outlives this line but the directory
        t_kill = time.perf_counter()
        job.state = None
        del job
        self.job = self._job(self.directory)
        resumed = self.job.resume()
        jax.block_until_ready(self.job.state)
        self.resume_s = time.perf_counter() - t_kill
        job_driver.watch(self.job)
        self.at_setup = self.job.stats()
        self.calls_at_setup = self.job.calls
        print(f"perfbench: resumed from step {resumed} in {self.resume_s:.3f} s "
              f"(reading {self.at_setup['restore_read_s']:.3f} s, to the device "
              f"{self.at_setup['restore_to_device_s']:.3f} s); the save before "
              f"the kill: {first['save_bytes']} bytes", flush=True)
        if resumed != 1 + self.steps_per_call:
            raise RuntimeError(f"resumed from step {resumed}")
        self._batches = []  # (s enqueueing, s in the sync, the job's calls after it)
        self._usage_at_setup = resource.getrusage(resource.RUSAGE_SELF)

    def _job(self, directory, every=None):
        """A job that saves to ``directory`` every ``every`` calls (the
        configuration's unless given; 0: when asked)."""
        restart = self.ctx.config["restart"]
        return self._sw.make_job(
            self.cfg, self.comm, self.steps_per_call,
            checkpoint=self._sw.Checkpoint(
                directory, every_calls=self.every if every is None else every,
                keep=restart["keep"], ahead_bytes=restart["ahead_bytes"]))

    # -- the window ----------------------------------------------------

    def batch(self, row):
        reps = self.rows[row]["reps"]
        t0 = time.perf_counter()
        with span(ENQUEUE):
            self.job.advance(reps)
        t1 = time.perf_counter()
        with span(SYNC):
            jax.block_until_ready(self.job.state)
        self._batches.append((t1 - t0, time.perf_counter() - t1, self.job.calls))

    def units(self, row):
        """Steps in one batch of ``row``."""
        return self.rows[row]["reps"] * self.steps_per_call

    def end_to_end(self, samples):
        self._print_batches(samples)
        steps = [self.units(s.row) for s in samples]
        wall = samples[-1].end - samples[0].start
        cells = self.ny * self.nx
        return {
            "solver_rate": cells * sum(steps) / wall / self.chips / 1e6,
            "solver_step_p95_us": stats.percentile(
                [s.seconds / n * 1e6 for s, n in zip(samples, steps)], 95),
        }

    def _print_batches(self, samples):
        """Every batch of the window: its time, and of the batches that
        carry a save (``*``) or are over 1.01 of the median, how much of
        it the host spent enqueueing and how much in the sync; the
        process's CPU seconds since set-up (the machine's sandbox counts
        no faults and no switches).  A far-off run names its batch
        here."""
        mine = self._batches[-len(samples):]
        reps = self.rows[samples[0].row]["reps"]
        typical = statistics.median(s.seconds for s in samples)
        saving = [any(c % self.every == 0 for c in range(calls - reps + 1, calls + 1))
                  for _, _, calls in mine]
        print(f"perfbench: the window's batches from {samples[0].start:.3f} s on the "
              "process's clock, ms (* carries a save): " + " ".join(
                  f"{1e3 * s.seconds:.1f}{'*' if star else ''}"
                  for s, star in zip(samples, saving)), flush=True)
        print("perfbench: of which the host spent enqueueing, ms: " + " ".join(
            f"{1e3 * enqueue:.1f}" for enqueue, _, _ in mine), flush=True)
        for i, (s, star, (enqueue, sync, calls)) in enumerate(zip(samples, saving, mine)):
            if star or s.seconds > 1.01 * typical:
                print(f"perfbench: batch {i}{'*' if star else ''} (calls to {calls}): "
                      f"{1e3 * s.seconds:.1f} ms, {1e3 * enqueue:.1f} enqueueing, "
                      f"{1e3 * sync:.1f} in the sync, starts "
                      f"{s.start - samples[0].start:.3f} s into the window", flush=True)
        now, then = resource.getrusage(resource.RUSAGE_SELF), self._usage_at_setup
        print("perfbench: the process since set-up: "
              f"{now.ru_utime - then.ru_utime:.2f} s user, "
              f"{now.ru_stime - then.ru_stime:.2f} s system", flush=True)

    def facts(self):
        return {"steps_per_call": self.steps_per_call, "cells": self.ny * self.nx}

    def window_saves(self):
        """The records of the saves the window's job has had
        acknowledged, the one on its way waited for."""
        self.job.drain()
        return list(self.job.saves)

    def traced_programs(self, trace, traced):
        """``(trace, executions)`` for ``harness/scopes.py``: the
        program each device execution of the traced batches ran, in
        order: a call's multistep and, after every call of the
        integration whose number divides by ``every_calls``, the save's
        staging program.  The traced batches are the window's first."""
        executions, call = [], self.calls_at_setup
        for s in traced:
            for _ in range(self.rows[s.row]["reps"]):
                call += 1
                executions.append(MULTI)
                if call % self.every == 0:
                    executions.append(STAGE)
        return trace, executions

    def compiled_text(self, key):
        """The text of one of the window's programs as compiled for what
        the job handed it in the window (``drivers/shallow_water_job.py
        text_of``), compiled once however many readers ask."""
        if key not in self._texts:
            self._texts[key] = job_driver.text_of(self.job, key)
        return self._texts[key]

    # -- after the window ----------------------------------------------

    def check(self):
        """(c) the window's job's counters, the directory and the
        window's last state; (b) one more save, of the window's last
        state, read back beside it by a job that knows only the
        directory; (a) from the seeded fields, a save taken while later
        calls run, a kill, a resume, the same calls again: bit for bit
        the uninterrupted state, within the limits of the plain
        reference, which is held to its own saved-and-loaded walk."""
        spec = self.ctx.config["check"]
        job = self.job
        window = job.stats()  # (b)'s save waits for the window's last, after the window
        job.save()
        job.drain()
        now = job.stats()
        print("perfbench: the window's saves (step, s to the host, s to the "
              "commit): " + ", ".join(
                  f"({r['step']}, {r['stage_s']:.3f}, {r['commit_s']:.3f})"
                  for r in job.saves)
              + f"; inside the window the loop waited "
              f"{window['save_wait_s'] - self.at_setup['save_wait_s']:.3f} s for "
              f"acknowledgements and spent {window['save_enqueue_s']:.3f} s "
              "starting saves", flush=True)
        in_window = (job.calls // self.every - self.calls_at_setup // self.every)
        steps = [r["step"] for r in job.saves]
        series = job.series
        nonfinite = sum(int(jnp.sum(~jnp.isfinite(a))) for a in job.state)
        checks = [
            {"name": "saves_not_started",
             "value": abs(in_window + 1 - now["saves_started"]), "limit": 0},
            {"name": "saves_unacknowledged",
             "value": now["saves_started"] - now["saves_acknowledged"], "limit": 0},
            {"name": "saves_out_of_order",
             # (b)'s save may be of the step the window's last one was of
             "value": sum(a > b for a, b in zip(steps, steps[1:])), "limit": 0},
            {"name": "saves_kept_off",
             "value": abs(len(series.steps())
                          - min(self.ctx.config["restart"]["keep"],
                                1 + now["saves_started"])), "limit": 0},
            {"name": "temporaries_left", "value": len(series.leftovers()),
             "limit": 0},
            {"name": "nonfinite_after_window", "value": nonfinite, "limit": 0},
        ]
        # (b) a job that knows only the directory, beside the live state
        other = self._sw.make_job(self.cfg, self.comm, self.steps_per_call)
        named = other.resume(self.directory)
        checks.append({"name": "resaved_step_off",
                       "value": abs(named - job.step), "limit": 0})
        checks += self._bit_for_bit("resaved_differing", other.state, job.state)
        other.state = None
        del other
        # (a) on the window's own job and its compiled programs, its
        # state dropped and its directory emptied before the seeded
        # walk, and no save but the one asked for
        job.state = None
        shutil.rmtree(self.directory)
        job.checkpoint = dataclasses.replace(job.checkpoint, every_calls=0)
        self._uninterrupted = self._saved_leg(job, spec["save_after_call"])
        self.job = job = None
        resumed = self._resumed_leg(self.directory, spec["save_after_call"])
        checks += self._bit_for_bit(
            "resumed_differing", resumed.state, self._uninterrupted)
        got = self._interior(*resumed.state[:3])
        jax.block_until_ready(got)
        resumed.state = None
        del resumed
        steps = 1 + spec["calls"] * self.steps_per_call
        return checks + self._compared(got, steps) + self._reference_restarted()

    def _saved_leg(self, job, save_after):
        """``job`` from the seeded fields through the check's calls, a
        save asked for after call ``save_after`` as the window asks for
        one (the calls after it run beside it); returns the state the
        uninterrupted walk ends on."""
        job.start(self._sw.SWState(*self._initial(*self._fields(self.modes))))
        job.advance(save_after)
        job.save()
        job.advance(self.ctx.config["check"]["calls"] - save_after)
        job.drain()
        state, job.state = job.state, None
        return state

    def _resumed_leg(self, directory, saved_after, drop_tendencies=False):
        """A job that knows only ``directory``, resumed and advanced
        through the calls the save there was not yet through."""
        job = self._job(directory, every=0)
        step = job.resume()
        if step != 1 + saved_after * self.steps_per_call:
            raise RuntimeError(f"resumed from step {step}")
        if drop_tendencies:
            job.state = job.state._replace(
                **{k: jnp.zeros_like(getattr(job.state, k))
                   for k in ("dh", "du", "dv")})
        job.advance(self.ctx.config["check"]["calls"] - self.ctx.config[
            "check"]["save_after_call"])
        jax.block_until_ready(job.state)
        return job

    def _bit_for_bit(self, name, got, want):
        limit = self.ctx.config["check"]["bit_for_bit"]
        return [{"name": f"{name}_{k}", "value": int(_differing(a, b)),
                 "limit": limit} for k, a, b in zip(STATE, got, want)]

    def _compared(self, got, steps, name="max_abs_diff"):
        limits = self.ctx.config["check"]["limits"]
        diffs = plain.reference_diffs(self, got, steps)
        return [{"name": f"{name}_{k}", "value": diffs[k], "limit": limits[k]}
                for k in FIELDS]

    def _reference_restarted(self, name="reference_restart_diff", **mistake):
        """The plain reference's saved-and-loaded walk against its
        uninterrupted one, on one band of rows drawn from the seed (the
        walk is the same code on every band)."""
        spec = self.ctx.config["check"]
        before = spec["save_after_call"] * self.steps_per_call
        after = spec["calls"] * self.steps_per_call - before
        params = self.ref.parameters(self.ctx.config["model"], self.dx, self.dy)
        bands = self.ref.row_blocks(self.ny, spec["row_blocks"], 1 + before + after)
        lo, hi, keep_lo, keep_hi = bands[self.ctx.seed % len(bands)]
        band = tuple(jax.device_put(a, self.ctx.devices[0])[lo:hi]
                     for a in self._fields(self.modes))
        want = self.ref.run(*band, params, 1 + before + after, "float32", lo)
        scratch = os.path.join(self._tmp.name, "reference")
        got = self.ref.run_restarted(
            *band, params, before, after, scratch, "float32", lo, **mistake)
        shutil.rmtree(scratch, ignore_errors=True)
        keep = slice(keep_lo - lo, keep_hi - lo)
        limits = spec["reference_restart_limits"]
        return [{"name": f"{name}_{k}", "limit": limits[k],
                 "value": float(jnp.max(jnp.abs(g[keep] - w[keep])))}
                for k, g, w in zip(FIELDS, got, want)]

    def control(self):
        """Three controls, each of which has to come out not correct.
        The plain reference carried in bfloat16 in the resumed fields'
        place.  A resume that drops the tendencies (the step after it
        Adams-Bashforth on zeros): against the uninterrupted state bit
        for bit and against the reference, and the same mistake made
        by the reference's own load.  A resume from the save before the
        newest (a directory whose newest save is a call older than the
        check's): the same two comparisons."""
        spec = self.ctx.config["check"]
        steps = 1 + spec["calls"] * self.steps_per_call
        self.job = None
        bands = [b[2] for b in plain.reference_bands(self, steps, "bfloat16")]
        got = tuple(jnp.concatenate(parts) for parts in zip(*bands))
        checks = self._compared(got, steps, "bfloat16_diff")
        del got, bands
        older = spec["save_after_call"] - 1
        stale = os.path.join(self._tmp.name, "stale")
        shutil.rmtree(stale, ignore_errors=True)
        self._saved_leg(self._job(stale, every=0), older)  # for its directory
        for mistake, leg in (
                ("tendencies", (self.directory, spec["save_after_call"], True)),
                ("stale", (stale, older))):
            job = self._resumed_leg(*leg)
            checks += self._bit_for_bit(
                f"{mistake}_differing", job.state, self._uninterrupted)
            got = self._interior(*job.state[:3])
            jax.block_until_ready(got)
            job.state = None
            del job
            checks += self._compared(got, steps, f"{mistake}_diff")
            del got
        return checks + self._reference_restarted(
            "reference_dropped_diff", drop_tendencies=True)


def setup(ctx):
    return Session(ctx)
