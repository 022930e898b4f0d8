"""Driver of the collectives table: the library's ops as nccl-tests
would drive them.

Every row of the workload file is one jitted ``shard_map`` over the
mesh.  Inside it the op is called ``reps`` times in a ``lax.scan``; the first
element of a call's input is rewritten, in place, from the result of the
call before it (to its own value), so calls cannot overlap or be hoisted
out of the loop, and no pass over the payload is added.  (A
``lax.optimization_barrier`` does not do: compiled for a v5e the
collective is hoisted past it, PERF.md Finding 8.)  A batch is one such program and one
host sync.  The last result of every row is compared, after the window,
with the numpy reference, bit for bit.
"""

import functools
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from perfbench.harness import files, stats
from perfbench.harness.spans import ENQUEUE, SYNC, span

AXES = ("y", "x")
BLOCK = 1 << 18  # elements of one sampled block: 1 MiB
ROLE_PAIRS, TABLE_PAIRS = 5, 3  # a probe's batches of library and plain


def payload_bytes(row, n):
    """Bytes a rank sends in one call of a row: its input, or for the
    halo exchange the four slabs that leave the block."""
    shape = local_shape(row, n)
    if row["op"] == "halo":
        return 2 * row["width"] * (shape[0] + shape[1]) * 4
    return int(np.prod(shape)) * 4


def local_shape(row, n):
    """The per-chip input of a row."""
    if row["op"] == "halo":
        return tuple(row["shape"])
    elems = max(row["bytes"] // 4, 1)
    if row["op"] == "alltoall":
        return (n, elems // n)
    return (elems,)


def library_op(row, comm):
    """The per-chip function of a row, through the library."""
    import mpi4jax_tpu as m
    from mpi4jax_tpu.parallel.halo import halo_exchange_2d

    op, n = row["op"], comm.size
    if op == "allreduce":
        return lambda x: m.allreduce(x, m.SUM, comm=comm)[0]
    if op == "allgather":
        return lambda x: m.allgather(x, comm=comm)[0]
    if op == "alltoall":
        return lambda x: m.alltoall(x, comm=comm)[0]
    if op == "bcast":
        return lambda x: m.bcast(x, row["root"], comm=comm)[0]
    if op == "sendrecv":
        ring = [(r, (r + row["shift"]) % n) for r in range(n)]
        return lambda x: m.sendrecv(x, x, source=ring, dest=ring, comm=comm)[0]
    if op == "halo":
        return lambda x: halo_exchange_2d(
            x, comm, periodic=tuple(row["periodic"]), width=row["width"])[0]
    raise ValueError(f"no program for op {op!r}")


def plain_op(row, grid):
    """The same call without the library, for the tax: jax's own
    collective, as a user would write it by hand inside the same
    ``shard_map`` over the ``grid`` of chips.  Imports nothing of
    ``mpi4jax_tpu``.  ``bcast`` is the hand-written idiom, a ``psum`` of
    the payload masked to the root, which is the library's own schedule
    too; ``halo`` is :func:`plain_halo`."""
    op, n = row["op"], grid[0] * grid[1]
    if op == "allreduce":
        return lambda x: lax.psum(x, AXES)
    if op == "allgather":
        return lambda x: lax.all_gather(x, AXES)
    if op == "alltoall":
        return lambda x: lax.all_to_all(x, AXES, 0, 0)
    if op == "bcast":
        return lambda x: lax.psum(
            jnp.where(lax.axis_index(AXES) == row["root"], x, 0.0), AXES)
    if op == "sendrecv":
        ring = [(r, (r + row["shift"]) % n) for r in range(n)]
        return lambda x: lax.ppermute(x, AXES, ring)
    if op == "halo":
        return plain_halo(grid, row["width"], tuple(row["periodic"]))
    raise ValueError(f"no plain program for op {op!r}")


def plain_halo(grid, width, periodic):
    """A halo exchange written by hand on a ``(y, x)`` grid of chips: the
    two slabs of interior cells beside the ghost ring sliced with
    ``jnp``, moved one chip along the axis by ``lax.ppermute`` and
    written over the ghosts with ``lax.dynamic_update_slice``; x first
    and then y over the full rows, so the corners arrive through the
    second exchange.  A block at a wall keeps its ghost cells."""
    w = width

    def shifted(slab, kept, axis, size, disp, wraps):
        """Every chip's ``slab`` from its neighbour ``disp`` chips back
        along ``axis``; a chip with no such neighbour keeps ``kept``."""
        pairs = [(i, (i + disp) % size) for i in range(size)
                 if wraps or 0 <= i + disp < size]
        if not pairs:
            return kept
        got = lax.ppermute(slab, axis, pairs)
        if wraps:
            return got
        source = lax.axis_index(axis) - disp
        return jnp.where((source >= 0) & (source < size), got, kept)

    def exchange(x):
        (py, px), (per_y, per_x) = grid, periodic
        ny, nx = x.shape
        west = shifted(x[:, nx - 2 * w:nx - w], x[:, :w], "x", px, 1, per_x)
        east = shifted(x[:, w:2 * w], x[:, nx - w:], "x", px, -1, per_x)
        x = lax.dynamic_update_slice(x, west, (0, 0))
        x = lax.dynamic_update_slice(x, east, (0, nx - w))
        south = shifted(x[ny - 2 * w:ny - w, :], x[:w, :], "y", py, 1, per_y)
        north = shifted(x[w:2 * w, :], x[ny - w:, :], "y", py, -1, per_y)
        x = lax.dynamic_update_slice(x, south, (0, 0))
        return lax.dynamic_update_slice(x, north, (ny - w, 0))

    return exchange


def chained(op, reps, mesh, spec):
    """``x -> (x, the result of the last of reps chained calls)``,
    jitted, ``x`` donated and handed back unchanged."""

    def local(x):
        origin = (0,) * x.ndim

        def body(carry, _):
            x, _ = carry
            y = op(x)
            # x's first element, through y: exactly itself (y is finite,
            # so y * 0 is a zero), written in place
            tied = x[origin] + y[(0,) * y.ndim] * 0.0
            return (x.at[origin].set(tied), y), None

        (x, y), _ = lax.scan(body, (x, op(x)), None, length=reps - 1)
        return x, y

    return jax.jit(
        jax.shard_map(local, mesh=mesh, in_specs=spec, out_specs=(spec, spec)),
        donate_argnums=0)


class Session:
    def __init__(self, ctx):
        import mpi4jax_tpu as m

        self.ctx = ctx
        py, px = ctx.workload["mesh"]
        self.grid = (py, px)
        self.n = n = py * px
        self.mesh = jax.make_mesh(
            (py, px), AXES, axis_types=(jax.sharding.AxisType.Auto,) * 2,
            devices=ctx.devices[:n])
        self.comm = m.MeshComm.from_mesh(self.mesh)
        self.rows = {r["name"]: r for r in ctx.workload["rows"]}
        t0 = time.perf_counter()
        self.inputs = self._make_inputs(ctx.seed)
        jax.block_until_ready(self.inputs)
        t1 = time.perf_counter()
        self.programs = {
            name: self._program(row, library_op(row, self.comm))
            for name, row in self.rows.items()
        }
        self.last = {}
        self.plain_checks = []  # a probe's comparisons, for check()
        for name in self.rows:  # warm up every program the window drives
            self.batch(name)
        print(f"perfbench: set-up: payloads {t1 - t0:.3f} s, compiling and "
              f"warming {len(self.rows)} programs {time.perf_counter() - t1:.3f} s",
              flush=True)

    def _program(self, row, op):
        return chained(op, row["reps"], self.mesh, self._global_spec(row))

    def _global_spec(self, row):
        return jax.P(*AXES) if row["op"] == "halo" else jax.P(AXES)

    def _make_inputs(self, seed):
        """Every row's payload in one jitted call, made on the chips:
        integers of the configuration's range, held in float32."""
        lo, hi = self.ctx.config["model"]["payload_values"]
        rows = list(self.rows.values())
        key_words = np.array(
            [(int(seed) >> 32) & 0xFFFFFFFF, int(seed) & 0xFFFFFFFF], np.uint32)

        def local(words):
            rank = lax.axis_index(AXES)
            key = jax.random.fold_in(jax.random.wrap_key_data(words), rank)
            out = []
            for i, row in enumerate(rows):
                shape = local_shape(row, self.n)
                ints = jax.random.randint(
                    jax.random.fold_in(key, i), shape, lo, hi + 1, jnp.int32)
                out.append(ints.astype(jnp.float32))
            return tuple(out)

        specs = tuple(self._global_spec(r) for r in rows)
        make = jax.jit(jax.shard_map(
            local, mesh=self.mesh, in_specs=jax.P(), out_specs=specs))
        return dict(zip(self.rows, make(key_words)))

    # -- the window ----------------------------------------------------

    def batch(self, name, program=None):
        """One batch of ``name``; through ``program`` (a probe's plain
        one) the result is handed back and not kept as the row's last."""
        if program is None:
            self.last.pop(name, None)  # one result buffer a row, not two
        with span(ENQUEUE):
            x, y = (program or self.programs[name])(self.inputs.pop(name))
            self.inputs[name] = x
        with span(SYNC):
            jax.block_until_ready(y)
        if program is None:
            self.last[name] = y
        return y

    def units(self, name):
        """Calls in one batch of ``name``."""
        return self.rows[name]["reps"]

    def payload_bytes(self, name):
        return payload_bytes(self.rows[name], self.n)

    def busbw(self, name, seconds_per_call):
        return stats.busbw_gbps(self.rows[name]["op"], self.payload_bytes(name),
                                self.n, seconds_per_call)

    def per_call(self, samples, name):
        """Seconds per call of a row over all its batches in ``samples``."""
        mine = [s.seconds for s in samples if s.row == name]
        if not mine:
            return None
        return sum(mine) / (len(mine) * self.units(name))

    def end_to_end(self, samples):
        roles = self.ctx.workload["roles"]
        out = {}
        big = self.per_call(samples, roles["busbw"])
        if big:
            out["coll_busbw"] = self.busbw(roles["busbw"], big)
        small = [s.seconds / self.units(s.row) * 1e6
                 for s in samples if s.row == roles["latency"]]
        if small:
            out["coll_lat_p95_us"] = stats.percentile(small, 95)
        table = [self.per_call(samples, name) for name in roles.get("table", ())]
        if table and all(table):  # never a mean over fewer rows
            out["coll_table_geomean_us"] = statistics.geometric_mean(table) * 1e6
        for name in self.rows:
            t = self.per_call(samples, name)
            if t:
                print(f"perfbench: row {name}: {t * 1e6!r} us a call, "
                      f"busbw {self.busbw(name, t)!r} GB/s", flush=True)
        return out

    def facts(self):
        return {"ranks": self.n, "roles": self.ctx.workload["roles"]}

    def layer_probe(self):
        """After a traced window: for the rows whose tax is asked for,
        the library's program and jax's plain collective in the same
        chained harness, in turn: five batches each for a role's one
        row, under the role's name, and three for every row of a role
        that lists rows, under ``rows``.  A plain program whose result
        is not the reference's gives no times, and fails the run."""
        out = {"rows": {}}
        for role, names in self.ctx.workload["roles"].items():
            if isinstance(names, str):
                out[role] = self._tax(names, ROLE_PAIRS)
            else:
                out["rows"].update(
                    (name, self._tax(name, TABLE_PAIRS)) for name in names)
        return out

    def _tax(self, name, pairs):
        """Seconds a call of the row ``name``, ``library`` and ``plain``:
        the medians of ``pairs`` batches of each, taken in turn."""
        row = self.rows[name]
        plain = self._program(row, plain_op(row, self.grid))
        y = self.batch(name, plain)  # compiles: outside the window
        wrong = self._mismatches(name, row, y)
        del y
        limit = self.ctx.config["check"]["limits"]["mismatches"]
        self.plain_checks.append(
            {"name": f"plain_mismatches_{name}", "value": wrong, "limit": limit})
        if wrong > limit:
            return None
        times = {"library": [], "plain": []}
        for _ in range(pairs):
            for kind, program in (("library", None), ("plain", plain)):
                t0 = time.perf_counter()
                self.batch(name, program)
                times[kind].append(time.perf_counter() - t0)
        pair = {k: statistics.median(v) / row["reps"] for k, v in times.items()}
        print(f"perfbench: tax {name}: {pair}", flush=True)
        return pair

    # -- after the window ----------------------------------------------

    def check(self, carry=None):
        """Every row's last result against the numpy reference, bit for
        bit, and after a probe every plain program's too.  With
        ``carry``, the reference carried in that precision stands in the
        library's place: the control."""
        limit = self.ctx.config["check"]["limits"]["mismatches"]
        out = [{"name": f"mismatches_{name}", "limit": limit,
                "value": self._mismatches(name, row, self.last[name], carry)}
               for name, row in self.rows.items()]
        return out if carry else out + self.plain_checks

    @functools.cached_property
    def ref(self):
        return files.load_module(
            "references", self.ctx.config["reference"], self.ctx.bench_dir)

    def _mismatches(self, name, row, y, carry=None):
        """Elements of ``y``, a result of the row ``name``, that differ
        from the reference's for the row's input."""
        x, y = self._to_host(name, row, y)
        if carry:
            y = self.ref.expected(row, x, self.grid, carry)
        return self.ref.mismatches(y, self.ref.expected(row, x, self.grid))

    def control(self):
        """The nearest precision below the configuration's float32:
        payloads carried and reduced in bfloat16.  Has to come out not
        correct."""
        return self.check(carry="bfloat16")

    def _to_host(self, name, row, y):
        """Every rank's input and the result ``y`` of a row on the host; for
        a row with ``sample_blocks``, that many 1 MiB blocks of each,
        at places drawn from the seed (an elementwise op only)."""
        x = self.inputs[name]
        blocks = row.get("sample_blocks")
        if blocks:
            if row["op"] != "allreduce":
                raise ValueError("only an elementwise row can be sampled")
            per_chip = x.shape[0] // self.n
            rng = np.random.default_rng(self.ctx.seed)
            starts = np.sort(rng.choice(
                per_chip // BLOCK, size=blocks, replace=False)) * BLOCK

            def pick(a, starts):
                return jax.vmap(
                    lambda s: lax.dynamic_slice(a, (s,), (BLOCK,)))(starts)

            take = jax.jit(jax.shard_map(
                pick, mesh=self.mesh, in_specs=(jax.P(AXES), jax.P()),
                out_specs=jax.P(AXES)))
            starts = jnp.asarray(starts, jnp.int32)
            x, y = take(x, starts), take(y, starts)
        return self._per_rank(x, row), self._per_rank(y, row)

    def _per_rank(self, a, row):
        """``(ranks, ...)`` from a global array, ranks row-major."""
        a = np.asarray(a)
        py, px = self.grid
        if row["op"] == "halo":
            ly, lx = a.shape[0] // py, a.shape[1] // px
            return (a.reshape(py, ly, px, lx).transpose(0, 2, 1, 3)
                    .reshape(self.n, ly, lx))
        return a.reshape((self.n, a.shape[0] // self.n) + a.shape[1:])


def setup(ctx):
    return Session(ctx)
