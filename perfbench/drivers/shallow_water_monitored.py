"""Driver of the cells of the solver job that watches itself, on one
chip and on a mesh of four.

The program is driven through what a user of the job calls:
``models.shallow_water.make_job`` with a ``Monitor`` and a callback,
``job.start``, ``job.advance``, ``job.drain`` and ``job.stats``, and
nothing beneath them.  The seeded modes and the initial fields are
those of ``drivers/shallow_water.py``, loaded by name (``mode_table``,
``make_fields``); the session is ``drivers/shallow_water_job.py``'s,
loaded by name too, with another job in it: what a batch's steps are,
the two end-to-end metrics, what a job's programs were handed
(``watch``, ``text_of``) and the cut of a traced window's last,
unfinished execution (``traced_programs``, by ``programs()``) are that
file's and are not written again here.

A batch is ``reps`` calls, each followed by the monitor program (local
reductions of the chip's own block, then the library's ``allreduce``
over the whole mesh), and ends when its last call's state is ready:
that call's monitor program and the lines' way to the host run on
beside the next batch.  The lines are handed, at most ``lag`` calls
late, to a callback that keeps them and does no arithmetic.

The comparison holds a domain that no one chip holds (14400 x 28800
cells on the mesh: 1.66 GB a field): the seeded fields and the job's
final fields stay sharded as the job has them, the plain reference
walks a band of rows at a time (``check.row_blocks`` bands, each with
the rows either side that 41 steps can reach), band ``i`` on chip
``i mod chips``, with only that band's rows copied there
(``band_of``), and one walk of a band yields the band's part of the
line at steps 11, 21, 31 and 41 and its fields at 41 (a band's kept
rows are the domain's at every step up to the last).  ``check_bytes``
reckons what the fullest chip holds meanwhile, and ``check`` prints it.
"""

import jax
import jax.numpy as jnp

from perfbench.harness import files
from perfbench.harness.spans import ENQUEUE, SYNC, span

plain = files.load_module("drivers", "shallow_water")
job_driver = files.load_module("drivers", "shallow_water_job")
FIELDS = plain.FIELDS
MULTI, MONITOR = job_driver.MULTI, "monitor"  # the keys of a call's programs
# the job's name for its monitor program, in this file's own copy of the
# table `watch` and `text_of` go by (`load_module` makes one a loader)
job_driver.PROGRAMS[MONITOR] = "mon"
LINE = ("nonfinite", "cfl", "h_min", "mass")
# the plain reference's walk at its fullest, in bands' bytes, the seeded
# rows it was handed apart: the state `advance` is handed (6.03), the
# state it hands back (6.03) and its temporaries (10.09), by
# `references/shallow-water-restart.py _more` compiled for a described
# v5e at the four-chip cell's band of 1392 x 28800 (19.2 in all at the
# one-chip cell's 942 x 14400; `temp_size_in_bytes` overstates)
REFERENCE_WALK = 23


def band_of(field, lo, hi, device):
    """Rows ``[lo, hi)`` of ``field``, a global array sharded over a
    ``(y, x)`` mesh, put together on ``device``: each chip cuts the rows
    of its own block that the band holds, and only those leave it."""
    by_row = {}
    for shard in field.addressable_shards:
        rows, cols = shard.index
        r0, r1, _ = rows.indices(field.shape[0])
        a, b = max(lo, r0), min(hi, r1)
        if a < b:
            by_row.setdefault(a, []).append(
                (cols.indices(field.shape[1])[0],
                 jax.device_put(shard.data[a - r0:b - r0], device)))
    return jnp.concatenate(
        [jnp.concatenate([piece for _, piece in sorted(pieces, key=lambda p: p[0])],
                         axis=1)
         for _, pieces in sorted(by_row.items())], axis=0)


class Session(job_driver.Session):
    """``drivers/shallow_water_job.py``'s session round a job with a
    monitor and no snapshot: its own set-up, callback and comparison."""

    def __init__(self, ctx):  # the whole set-up: the other's makes its own job
        import mpi4jax_tpu as m
        from mpi4jax_tpu.models import shallow_water as sw
        from mpi4jax_tpu.parallel.halo import halo_exchange_2d

        self.ctx = ctx
        model, monitor = ctx.config["model"], ctx.config["monitor"]
        grid = ctx.workload["grid"]
        self.ny, self.nx = grid["ny"], grid["nx"]
        py, px = ctx.workload["mesh"]
        self.chips = py * px
        self.dx = model["dx"] / grid["refine"]
        self.dy = model["dy"] / grid["refine"]
        self.steps_per_call = model["num_multisteps"]
        self.ghost = G = model["ghost"]
        self.lag = monitor["lag"]
        self.rows = {r["name"]: r for r in ctx.workload["rows"]}
        self.ref = files.load_module(
            "references", ctx.config["reference"], ctx.bench_dir)
        self.params = self.ref.parameters(model, self.dx, self.dy)

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
        # what the callback is handed: every line since the last restart,
        # and a count of every line that came out of order or not whole
        self.lines = []
        self.violations = 0
        self._texts = {}
        self.job = sw.make_job(
            cfg, comm, self.steps_per_call,
            monitor=sw.Monitor(every_calls=monitor["every_calls"], lag=self.lag,
                               cfl_limit=monitor["cfl_limit"]),
            on_monitor=self._on_line)
        job_driver.watch(self.job)
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

        def interior(*fields):
            return tuple(a[G:-G, G:-G] for a in fields)

        self._initial = jax.jit(jax.shard_map(
            initial, mesh=mesh, in_specs=(spec,) * 3, out_specs=(spec,) * 6))
        self._interiors = jax.jit(jax.shard_map(
            interior, mesh=mesh, in_specs=(spec,) * 3, out_specs=(spec,) * 3))
        # warm up the programs the window and the check drive
        self._restart()
        self.job.advance(1)
        self.job.drain()
        self.at_setup = self.job.stats()
        self.lines.clear()  # from here on: the window's
        self.calls = 0  # of the window (the traced batches among them)

    def _restart(self):
        """The job at step 1 of the seeded fields, no line kept."""
        self.job.drain()
        self.job.state = None
        self.job.start(
            self._SWState(*self._initial(*self._fields(self.modes))))
        self.lines.clear()
        self._expected = self.job.step + self.steps_per_call

    def _on_line(self, line):
        if line["step"] != self._expected or set(line) != {"step", *LINE}:
            self.violations += 1
        self._expected = line["step"] + self.steps_per_call
        self.lines.append(line)

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
                f"{self.violations} monitor lines out of order or not whole")

    def programs(self):
        """The keys of the programs a call runs, in order.  The
        window's last monitor program is running when the profiler
        stops: ``traced_programs`` leaves a cut one out (where its
        message says "snapshot" it is this call's last program)."""
        return (MULTI, MONITOR)

    # -- after the window ----------------------------------------------

    def check_bytes(self, kept_references=0):
        """What the fullest chip holds during the comparison, by its
        parts, in bytes: its share of the seeded fields and of the
        job's final fields, and of one band on it at a time its rows'
        copies (the seeded fields' wide rows, the final fields' kept
        rows), the reference's results and its walk at its fullest
        (``REFERENCE_WALK``), with the next band's copies enqueued
        behind it; ``kept_references``: the
        references whose results are kept whole (the control's)."""
        spec = self.ctx.config["check"]
        steps = self._check_steps()[-1]
        share = 3 * self.ny * self.nx * 4 // self.chips
        widest = max(hi - lo for lo, hi, _, _ in self.ref.row_blocks(
            self.ny, spec["row_blocks"], steps))
        kept = -(-self.ny // spec["row_blocks"])
        band, rows = widest * self.nx * 4, kept * self.nx * 4
        return {
            "seeded fields, a chip's share": share,
            "final fields, a chip's share": share,
            "a band's seeded rows, two bands": 2 * 3 * band,
            "a band's final rows, two bands": 2 * 3 * rows,
            "the reference's results, two bands": 2 * 3 * rows,
            "the reference's walk, two states and its temporaries":
                REFERENCE_WALK * band,
            "references kept whole": kept_references * share,
        }

    def _say_bytes(self, kept_references=0):
        parts = self.check_bytes(kept_references)
        room = (self.ctx.devices[0].memory_stats() or {}).get("bytes_limit")
        print("perfbench: the comparison's fullest chip by reckoning: "
              + ", ".join(f"{what} {n}" for what, n in parts.items() if n)
              + f": {sum(parts.values())} bytes of the {room} a chip has",
              flush=True)

    def check(self):
        """(c) every call of the window had its line, in step order, at
        most ``lag`` calls late, none stopped the job, the mass of the
        window's last line within ``mass_drift`` of its first, and the
        window's last state finite; (a) the job itself from the seeded
        fields through ``calls`` calls: its final ``h``, ``u``, ``v``
        and each call's line against the plain reference's at the same
        steps; (b) the last of those lines against the reference's line
        of the job's own final fields, which a line of any other step
        fails."""
        spec = self.ctx.config["check"]
        self.job.drain()
        now = self.job.stats()
        read = now["monitor_lines"] - self.at_setup["monitor_lines"]
        nonfinite = sum(
            int(jnp.sum(~jnp.isfinite(getattr(self.job.state, k))))
            for k in FIELDS)
        first, last = (self.lines[i]["mass"] for i in (0, -1))
        checks = [
            {"name": "lines_unread", "value": abs(self.calls - read), "limit": 0},
            {"name": "lines_out_of_order_or_torn",
             "value": self.violations, "limit": 0},
            {"name": "max_lag_calls", "value": now["monitor_max_lag_calls"],
             "limit": self.lag},
            {"name": "monitor_stops", "value": now["monitor_stops"], "limit": 0},
            {"name": "mass_drift_window", "value": abs(last - first) / first,
             "limit": spec["mass_drift"]},
            {"name": "nonfinite_after_window", "value": nonfinite, "limit": 0},
        ]
        self._say_bytes()
        self._restart()  # frees the window's state before the reference
        self.job.advance(spec["calls"])
        self.job.drain()
        got = self._final_fields()
        lines = {line["step"]: line for line in self.lines}
        checks += self._compared(
            lambda i, lo, hi, device: tuple(band_of(g, lo, hi, device) for g in got),
            lines)
        return checks + _line_checks(
            "last_line", [(self.lines[-1], self._line_of(got))],
            spec["last_line_limits"])

    def _final_fields(self):
        """The interior ``h``, ``u``, ``v`` of the job's state, sharded
        as it is; the state itself is dropped."""
        state, self.job.state = self.job.state, None
        got = self._interiors(state.h, state.u, state.v)
        jax.block_until_ready(got)
        return got

    def _line_of(self, fields):
        """The reference's line of sharded interior ``(h, u, v)``: each
        chip's block's part where it lies, put together on the host."""
        shards = zip(*(a.addressable_shards for a in fields))
        parts = [self.ref.band_parts(*(s.data for s in of_chip))
                 for of_chip in shards]
        return self.ref.line_of(jax.device_get(parts), self.params)

    def _reference(self, dtype):
        """The plain reference walked through the check's steps in
        ``dtype``, band by band, band ``i`` on chip ``i mod chips``:
        yields ``(i, keep_lo, keep_hi, device, parts, fields)``,
        ``parts[k]`` the band's part of the line after the ``k``-th of
        the check's steps and ``fields`` its kept rows of ``(h, u, v)``
        after the last.  A chip is given its next band when the one
        before has been read."""
        ctx, ref = self.ctx, self.ref
        steps = self._check_steps()
        start = self._fields(self.modes)
        devices = ctx.devices[: self.chips]
        bands = ref.row_blocks(self.ny, ctx.config["check"]["row_blocks"], steps[-1])
        for i, (lo, hi, keep_lo, keep_hi) in enumerate(bands):
            device = devices[i % len(devices)]
            parts, fields = ref.run_lines(
                *(band_of(a, lo, hi, device) for a in start), self.params, steps,
                (keep_lo - lo, keep_hi - lo), dtype, lo)
            yield i, keep_lo, keep_hi, device, parts, fields

    def _compared(self, got_band, lines):
        """``got_band(i, lo, hi, device)`` (the rows ``[lo, hi)`` of
        ``h``, ``u``, ``v`` on ``device``) and ``lines[step]`` against
        the plain float32 reference: the largest difference a field,
        and the largest of each of a line's numbers over the check's
        steps."""
        spec = self.ctx.config["check"]
        steps = self._check_steps()
        pending, worst = [], dict.fromkeys(FIELDS, 0.0)
        at_step = [[] for _ in steps]

        def read(upto):
            # the oldest bands' numbers to the host: what waits on a chip
            while len(pending) > upto:
                diffs, parts = jax.device_get(pending.pop(0))
                for k, d in zip(FIELDS, diffs):
                    worst[k] = max(worst[k], float(d))
                for into, part in zip(at_step, parts):
                    into.append(part)

        for i, lo, hi, device, parts, want in self._reference("float32"):
            mine = got_band(i, lo, hi, device)
            pending.append((
                tuple(jnp.max(jnp.abs(g - w)) for g, w in zip(mine, want)),
                parts))
            read(self.chips)
        read(0)
        checks = [
            {"name": f"max_abs_diff_{k}", "value": worst[k],
             "limit": spec["limits"][k]}
            for k in FIELDS
        ]
        want = [self.ref.line_of(parts, self.params) for parts in at_step]
        return checks + _line_checks(
            "line", [(lines[step], w) for step, w in zip(steps, want)],
            spec["line_limits"])

    def control(self):
        """Two controls, both of which have to come out not correct.
        The plain reference carried in bfloat16, the nearest precision
        below the configuration's float32, in the program's place: its
        final fields and its four lines.  And a line one call stale:
        the line of the call before the last, compared as the last line
        is with the reference's line of the job's own final fields."""
        steps = self._check_steps()
        self._say_bytes(kept_references=1)
        self._restart()
        self.job.advance(len(steps))
        self.job.drain()
        stale = _line_checks(
            "stale_line", [(self.lines[-2], self._line_of(self._final_fields()))],
            self.ctx.config["check"]["last_line_limits"])
        kept, at_step = {}, [[] for _ in steps]
        for i, _lo, _hi, _device, parts, fields in self._reference("bfloat16"):
            kept[i] = fields
            for into, part in zip(at_step, jax.device_get(parts)):
                into.append(part)
        lines = {step: dict(self.ref.line_of(parts, self.params), step=step)
                 for step, parts in zip(steps, at_step)}
        return self._compared(
            lambda i, lo, hi, device: kept[i], lines) + stale


def _line_checks(name, pairs, limits):
    """``pairs`` of ``(line, the reference's)``: the largest difference
    of each of a line's numbers, ``mass`` as a share of the
    reference's."""
    worst = dict.fromkeys(LINE, 0.0)
    for mine, want in pairs:
        for k in LINE:
            d = abs(mine[k] - want[k])
            worst[k] = max(worst[k], d / abs(want["mass"]) if k == "mass" else d)
    return [
        {"name": f"{name}_{k}" + ("_relative" if k == "mass" else ""),
         "value": worst[k],
         "limit": limits["mass_relative" if k == "mass" else k]}
        for k in LINE
    ]


def setup(ctx):
    return Session(ctx)
