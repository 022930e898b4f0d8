"""Driver of the cells of the job a user keeps and restarts: the solver
run for its output and saved, killed and resumed, in one job.

The program is driven through what a user of such a run calls:
``models.shallow_water.make_job`` with a ``Snapshot``, a callback and a
``Checkpoint``, ``job.start``, ``job.advance``, ``job.save``,
``job.drain``, ``job.resume`` and ``job.stats``: the object
``make_solver(on_chunk=, checkpoint_dir=)`` and ``examples/shallow_water.py
--animate --checkpoint-dir`` loop over.  The host's bound on copies that
are asked for and not yet fetched is the configuration's one figure,
``host.ahead_bytes``, given to both halves; the job keeps snapshots and
a save's pieces under it together.

This driver is two accepted ones put together, both loaded by name.
``drivers/shallow_water_restart.py``'s ``Session`` is this one's base:
its set-up (the seeded fields, one call, a save, the kill, a new job
resumed from the directory; the window starts from the resumed job),
its batch, its end-to-end arithmetic, its durability checks and its
controls, all run here on jobs that have a ``Snapshot`` too, because
``_job`` is what builds every one of them.  From
``drivers/shallow_water_job.py`` come the callback (``_on_chunk``: the
last ``kept`` snapshots, and a count of every one out of order or not
whole), ``_last_snapshot``, ``_check_steps``, ``_compared`` and
``reference_block_means``.  What neither has is here: a traced window's
executions with a staging program among the snapshots, the checks of
``host_bound`` and ``seamless_output``, and their controls.

A batch is ``reps`` calls, each followed by its snapshot, and one sync
on the last call's state; after every ``restart.every_calls``-th call
of the integration the job starts a save.  The job resumed in set-up
stands after call 1, so in the cell, 48 calls a save and
``trace_batches`` 12 (48 calls), a traced window holds 48 snapshots and
exactly one save's staging program, whole, between the third and the
fourth call of its last batch; the
window's last snapshot may be cut by the profiler's stop and is then
left out, as in the job cell.
"""

import collections
import os
import types

import jax
import numpy as np

from perfbench.harness import files
from perfbench.harness.trace import Trace

restart = files.load_module("drivers", "shallow_water_restart")
output = restart.job_driver  # drivers/shallow_water_job.py, loaded once
FIELDS = restart.FIELDS
MULTI, SNAPSHOT, STAGE = restart.MULTI, output.SNAPSHOT, restart.STAGE
PEAK = "host_in_flight_max_bytes"  # the counter `host_bound` is read from


def _words_differing(a, b):
    """How many float32 words of two host arrays differ in any bit."""
    return int(np.count_nonzero(
        np.ascontiguousarray(a).view(np.int32) != np.ascontiguousarray(b).view(np.int32)))


class Session(restart.Session):
    # the job driver's, by name: each reads of its session what this one has
    _on_chunk = output.Session._on_chunk
    _check_steps = output.Session._check_steps

    def __init__(self, ctx):
        self.coarsen = ctx.workload["grid"]["refine"]  # a snapshot is on the published grid
        self.lag = ctx.config["output"]["lag"]
        self.kept = collections.deque(maxlen=ctx.config["output"]["kept"])
        self.violations = 0
        self.host_peak = 0  # the most any job of this run had on its way to the host
        self._expected = 1 + ctx.config["model"]["num_multisteps"]
        super().__init__(ctx)

    def _job(self, directory, every=None, ahead_bytes=None, on_chunk=None):
        """A job that writes output and saves to ``directory`` every
        ``every`` calls (the configuration's unless given; 0: when
        asked), both under the host's one bound (``ahead_bytes``: the
        configuration's unless given)."""
        config, sw = self.ctx.config, self._sw
        ahead = config["host"]["ahead_bytes"] if ahead_bytes is None else ahead_bytes
        job = sw.make_job(
            self.cfg, self.comm, self.steps_per_call,
            sw.Snapshot(fields=tuple(config["output"]["fields"]), coarsen=self.coarsen,
                        lag=self.lag, ahead_bytes=ahead),
            on_chunk or self._on_chunk,
            sw.Checkpoint(directory, every_calls=self.every if every is None else every,
                          keep=config["restart"]["keep"], ahead_bytes=ahead))
        if PEAK not in job.stats():
            raise RuntimeError(
                f"the job's stats() have no {PEAK!r}: this program keeps no one "
                "bound on what is on its way to the host, and cannot give the "
                "configuration's `host_bound`")
        return job

    # -- the window ----------------------------------------------------

    def batch(self, row):
        super().batch(row)
        if self.violations:
            raise RuntimeError(
                f"{self.violations} snapshots out of order or not whole")

    def programs(self):
        """The keys of the programs every call runs, in order."""
        return (MULTI, SNAPSHOT)

    def traced_programs(self, trace, traced):
        """``(trace, executions)`` for ``harness/scopes.py``: the
        program each device execution of the traced batches ran, in
        order: a call's multistep and its snapshot and, after every
        call of the integration whose number divides by
        ``every_calls``, the save's staging program.  The window's last
        snapshot is running when the profiler stops: where a chip's
        trace holds fewer of its operations than of the snapshot before
        it, or not its execution at all, both are returned without it
        (``drivers/shallow_water_job.py traced_programs`` has why)."""
        executions, call = [], self.calls_at_setup
        for s in traced:
            for _ in range(self.rows[s.row]["reps"]):
                call += 1
                executions += [MULTI, SNAPSHOT]
                if call % self.every == 0:
                    executions.append(STAGE)
        n = len(executions)
        snapshots = [i for i, key in enumerate(executions) if key == SNAPSHOT]
        if trace is None or len(snapshots) < 2 or snapshots[-1] != n - 1:
            return trace, executions
        ordered = {plane: sorted(trace.modules.get(plane, ()),
                                 key=lambda m: m.start_ns)
                   for plane in trace.device_ops}
        if any(len(modules) not in (n - 1, n) for modules in ordered.values()):
            return trace, executions  # the harness says what does not match
        whole, cut = Trace(host=trace.host), False
        for plane, modules in ordered.items():
            events = trace.device_ops[plane]
            before, end = modules[snapshots[-2]], modules[n - 2].end_ns
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

    # -- after the window ----------------------------------------------

    def check(self):
        """The window's output (every snapshot delivered, in order,
        whole, none more than ``lag`` late; the last against the
        reference's block mean of the last state); then the restart
        driver's whole comparison, on jobs that write output; then what
        this configuration adds: the snapshots of the check's killed
        and resumed run, those before the kill and those after the
        resume, against the plain reference's block means, those after
        the resume against the uninterrupted run's bit for bit
        (``seamless_output``), the reference's own output across its
        own save and load, and the most bytes any job of the run had
        on its way to the host against the bound (``host_bound``)."""
        spec = self.ctx.config["check"]
        job = self.job
        job.drain()
        now = job.stats()
        calls = job.calls - self.calls_at_setup
        delivered = now["snapshots_delivered"] - self.at_setup["snapshots_delivered"]
        self._note(job)
        print(f"perfbench: the window's job had at most {now[PEAK]} bytes on their "
              f"way to the host (the bound {job.ahead_bytes}); copies were held back "
              f"by the other kind's for {now['transfer_wait_s']:.4f} s; the loop "
              f"waited {now['save_wait_s'] - self.at_setup['save_wait_s']:.4f} s for "
              f"saves' acknowledgements and {now['output_wait_s']:.4f} s fetching "
              "snapshots", flush=True)
        checks = [
            {"name": "snapshots_undelivered", "value": abs(calls - delivered), "limit": 0},
            {"name": "snapshots_out_of_order_or_torn", "value": self.violations,
             "limit": 0},
            {"name": "max_lag", "value": now["max_lag"], "limit": self.lag},
        ]
        checks += self._last_snapshot("last_snapshot_diff", self.kept[-1][1])
        checks += super().check()
        after = self.resumed
        wanted = [s for s in self._check_steps()
                  if s > 1 + spec["save_after_call"] * self.steps_per_call]
        checks += [
            {"name": "snapshots_after_restart_off", "limit": 0,
             "value": len(set(wanted) ^ set(after))},
            {"name": "snapshots_across_restart_differing",
             "limit": spec["snapshots_across_restart_differing"],
             "value": sum(_words_differing(after[s][k], self.whole[s][k])
                          for s in wanted if s in after for k in FIELDS)},
            {"name": PEAK, "value": self.host_peak, "limit": spec[PEAK]},
        ]
        # before the kill the saved leg's snapshots, after it the resumed leg's
        got = {s: after.get(s, self.whole[s]) for s in self._check_steps()}
        return checks + [dict(c, name="snapshot_" + c["name"])
                         for c in output.Session._compared(self, got)]

    def _note(self, job):
        self.host_peak = max(self.host_peak, job.stats()[PEAK])

    def _from(self, step):
        """The callback's memory for a leg that starts at ``step``."""
        self.kept.clear()
        self._expected = step + self.steps_per_call

    def _saved_leg(self, job, save_after):
        self._from(1)
        state = super()._saved_leg(job, save_after)
        self._note(job)
        self.whole = dict(self.kept)  # {step: snapshot} of the uninterrupted run
        return state

    def _resumed_leg(self, directory, saved_after, drop_tendencies=False):
        self._from(1 + saved_after * self.steps_per_call)
        job = super()._resumed_leg(directory, saved_after, drop_tendencies)
        job.drain()
        self._note(job)
        self.resumed = dict(self.kept)
        return job

    def _last_snapshot(self, name, snapshot):
        """The job driver's, on what it reads of a session: its
        ``_interior`` takes one field's host array where the restart
        driver's, which this session has, takes three device arrays."""
        view = types.SimpleNamespace(
            ctx=self.ctx, job=self.job, ref=self.ref, coarsen=self.coarsen,
            _interior=lambda padded: output.Session._interior(self, padded))
        return output.Session._last_snapshot(view, name, snapshot)

    def _reference_restarted(self, name="reference_restart_diff", **mistake):
        """The restart driver's (the reference's saved-and-loaded walk
        against its uninterrupted one) and the same of its output: the
        block means after every call of the second leg, on the same
        band of rows."""
        checks = super()._reference_restarted(
            name, **{k: v for k, v in mistake.items() if k != "late"})
        spec, ref, n = self.ctx.config["check"], self.ref, self.steps_per_call
        before = spec["save_after_call"] * n
        after = spec["calls"] * n - before
        params = ref.parameters(self.ctx.config["model"], self.dx, self.dy)
        bands = ref.row_blocks(self.ny, spec["row_blocks"], 1 + before + after)
        lo, hi, keep_lo, keep_hi = bands[self.ctx.seed % len(bands)]
        band = tuple(jax.device_put(a, self.ctx.devices[0])[lo:hi]
                     for a in self._fields(self.modes))
        walk = (*band, params, before, after, n, self.coarsen)
        keep = (keep_lo - lo, keep_hi - lo)
        want = ref.run_output(*walk, keep, "float32", lo)
        scratch = os.path.join(self._tmp.name, "reference-output")
        got = ref.run_output_restarted(*walk, scratch, keep, "float32", lo, **mistake)
        limits = spec["reference_restart_limits"]
        return checks + [
            {"name": f"{name}_output_{k}", "limit": limits[k],
             "value": max(float(np.max(np.abs(g[i] - w[i]))) for g, w in zip(got, want))}
            for i, k in enumerate(FIELDS)]

    def control(self):
        """The restart driver's controls (the reference in bfloat16; a
        resume that drops the tendencies, which is forward Euler's
        start after a resume; a resume from the save before the
        newest), then those of what this configuration adds, each of
        which has to come out not correct.  A snapshot of the wrong
        step after a resume: the resumed leg's snapshots a call late,
        in the uninterrupted run's places, and the reference's own
        output taken a step late.  A snapshot dropped while a save is
        waited for: a job that saves again before its first save is
        acknowledged, whose callback loses its second snapshot.  A bound overrun: a job given the two bounds of
        before PR 45 as one, the host's and the device's queue's added
        up.  A torn save: the lower half of the rows of the newest
        save's ``h.npy`` from the save before it."""
        spec, n = self.ctx.config["check"], self.steps_per_call
        saved = 1 + spec["save_after_call"] * n
        # the check's resumed leg, before the restart driver's controls run theirs
        late = {s: self.resumed[s + n] for s in self._check_steps()
                if s > saved and s + n in self.resumed}
        wrong_step = {
            "name": "late_snapshots_differing",
            "limit": spec["snapshots_across_restart_differing"],
            "value": sum(_words_differing(late[s][k], self.whole[s][k])
                         for s in late for k in FIELDS)}
        checks = super().control() + [wrong_step]
        checks += self._reference_restarted("reference_late_diff", late=True)[3:]
        # a snapshot dropped while a save is waited for
        seen, scratch = [], os.path.join(self._tmp.name, "dropped")

        def lossy(snapshot, step):
            seen.append(step)
            if len(seen) != 2:
                self._on_chunk(snapshot, step)

        before = self.violations
        job = self._job(scratch, every=spec["save_after_call"], on_chunk=lossy)
        self._from(1)
        job.start(self._sw.SWState(*self._initial(*self._fields(self.modes))))
        job.advance(spec["calls"])
        job.drain()
        checks += [
            {"name": "dropped_snapshots_undelivered", "limit": 0,
             "value": spec["calls"] - len(self.kept)},
            {"name": "dropped_snapshots_out_of_order_or_torn", "limit": 0,
             "value": self.violations - before}]
        self.violations = before
        job.state = None
        del job
        # a bound overrun: the host's bound and the device's queue's, added up
        host = self.ctx.config["host"]["ahead_bytes"]
        queue = self._sw.Checkpoint(None, ahead_bytes=host).ahead(self.chips)
        job = self._job(scratch, every=0, ahead_bytes=host + queue)
        self._saved_leg(job, spec["save_after_call"])
        checks.append({"name": f"overrun_{PEAK}", "value": job.stats()[PEAK],
                       "limit": spec[PEAK]})
        del job
        # a torn save, in the place of the check's own
        newest = os.path.join(self.directory, str(saved), "h.npy")
        older = os.path.join(self._tmp.name, "stale", str(saved - n), "h.npy")
        torn, whole = np.load(newest, mmap_mode="r+"), np.load(older, mmap_mode="r")
        torn[torn.shape[0] // 2:] = whole[torn.shape[0] // 2:]
        torn.flush()
        del torn, whole
        job = self._resumed_leg(self.directory, spec["save_after_call"])
        checks += self._bit_for_bit("torn_differing", job.state, self._uninterrupted)
        got = self._interior(*job.state[:3])
        jax.block_until_ready(got)
        job.state = None
        del job
        return checks + self._compared(got, 1 + spec["calls"] * n, "torn_diff")


def setup(ctx):
    return Session(ctx)
