"""Driver of the as-written cells: upstream's step as a user who ports
it drives it.

The program is what such a user calls: ``MeshComm``, ``SWConfig`` at
its default ``ghost`` of 1, ``make_state`` for fields of their own,
``make_first_step`` and ``make_multistep``, whose step at ``ghost`` 1
is upstream's array code with one ``halo_exchange_2d`` after each of
twelve fields.  No code of this file stands in for any of it.

The seeded modes, the initial fields, the cut of the plain reference
into bands of rows, the closed loop (a batch is ``reps`` donated calls
enqueued back to back and one sync) and the end-to-end arithmetic are
those of ``drivers/shallow_water.py``, loaded by name: its ``Session``
is this one's base.  Two things differ.  The state is built by the
library (``make_state``: the accepted driver pads, exchanges and adds
zero tendencies by hand, shaped like the interior, which ``ghost`` 1
refuses).  And the comparison keeps the ghost columns: the state's
arrays are upstream's, ``(ny + 2, nx + 2)`` a block, and the two ghost
columns of ``h``, ``u``, ``v`` are held to the reference's own beside
the interior (``references/shallow-water-as-written.py`` says what
upstream's program leaves there).
"""

import jax
import jax.numpy as jnp

from perfbench.harness import files

plain = files.load_module("drivers", "shallow_water")
FIELDS = plain.FIELDS


class Session(plain.Session):
    def __init__(self, ctx):
        import mpi4jax_tpu as m
        from mpi4jax_tpu.models import shallow_water as sw

        self.ctx = ctx
        model = ctx.config["model"]
        grid = ctx.workload["grid"]
        self.ny, self.nx = grid["ny"], grid["nx"]
        py, px = ctx.workload["mesh"]
        self.chips = py * px
        self.dx = model["dx"] / grid["refine"]
        self.dy = model["dy"] / grid["refine"]
        self.steps_per_call = model["num_multisteps"]
        self.ghost = G = model["ghost"]
        if (G, model["schedule"]) != (1, "as_written"):
            raise ValueError(
                f"ghost {G}, schedule {model['schedule']!r}: this driver "
                "runs the as-written step, ghost 1")
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
        self._make_state = sw.make_state(cfg, comm)
        self.first = sw.make_first_step(cfg, comm)
        self.multi = sw.make_multistep(
            cfg, comm, self.steps_per_call, donate=True)
        self.modes = plain.mode_table(
            ctx.seed, ctx.config["assumed"]["perturbation"])
        self._fields = plain.make_fields(
            model, self.ny, self.nx, self.dx, self.dy,
            jax.NamedSharding(mesh, jax.P("y", "x")))
        ly, lx = self.ny // py, self.nx // px

        def columns(*padded):
            """The domain's rows of each of the state's arrays (every
            chip's block with its own ghost ring) at all ``nx + 2``
            columns: the cells, and the domain's two ghost columns."""
            out = []
            for a in padded:
                rows = a.reshape(py, ly + 2 * G, px, lx + 2 * G)[:, G:-G]
                out.append(jnp.concatenate([
                    rows[:, :, 0, :G],
                    rows[:, :, :, G:-G].reshape(py, ly, self.nx),
                    rows[:, :, -1, -G:],
                ], axis=2).reshape(self.ny, self.nx + 2 * G))
            return tuple(out)

        # what `check` compares: the accepted driver's name for it
        self._interior = jax.jit(columns)
        self._text = None
        # warm up the two programs the window and the check drive
        self.state = self.multi(self.first(self._initial_state()))
        jax.block_until_ready(self.state)

    def _initial_state(self):
        return self._make_state(*self._fields(self.modes))

    def _compared(self, got, steps):
        """``got``, the domain's rows of ``h``, ``u``, ``v`` at all
        their columns, against the plain float32 reference after
        ``steps`` steps: the largest absolute difference a field over
        the cells, and over the two ghost columns, each beside the
        field's limit."""
        limits = self.ctx.config["check"]["limits"]
        got = tuple(jax.device_put(g, self.ctx.devices[0]) for g in got)
        G = self.ghost
        cells, ghosts = dict.fromkeys(FIELDS, 0.0), dict.fromkeys(FIELDS, 0.0)
        for lo, hi, want in plain.reference_bands(self, steps, "float32"):
            for k, g, w in zip(FIELDS, got, want):
                off = jnp.abs(g[lo:hi] - w)
                cells[k] = max(cells[k], float(jnp.max(off[:, G:-G])))
                ghosts[k] = max(ghosts[k], float(jnp.max(off[:, :G])),
                                float(jnp.max(off[:, -G:])))
        return [
            {"name": f"{name}_{k}", "value": worst[k], "limit": limits[k]}
            for name, worst in (("max_abs_diff", cells),
                                ("ghost_columns_diff", ghosts))
            for k in FIELDS
        ]


def setup(ctx):
    return Session(ctx)
