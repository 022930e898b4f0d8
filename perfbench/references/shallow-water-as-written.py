"""Plain reference of upstream's step as written: the plain solver of
``references/shallow-water.py`` (loaded by path, beside this file) with
what the configuration ``shallow-water-as-written`` adds to the
comparison, the two ghost columns.  Imports nothing of mpi4jax_tpu.

The plain solver carries upstream's one ghost cell itself, and a band of
rows holds every column, so the ghost columns of ``h``, ``u`` and ``v``
can be compared beside the interior: ``run`` here returns the band's
rows at all ``nx + 2`` columns.  (The two ghost *rows* are a wall's: no
step writes them, they hold the initial edge row for ever, and only the
bands at a wall have them; they are not compared.)

One line of the plain solver is not upstream's: after the friction
update it refreshes the ghosts of ``u`` and ``v`` once more
(``_boundaries(diffuse(u))``), where upstream's program
(``examples/shallow_water.py:384-412``) returns them as the exchange
before friction left them, and the next step's stencils read them so.
``_step`` here puts that back: the ghost columns of ``u`` and ``v`` are
those of the same step without friction, which is what that exchange
carried.  The difference is friction's increment on two columns, some
1e-10 of ``u``: far under any limit of the comparison, so the interior
this file gives is the plain solver's to rounding, but the columns are
stale or fresh as upstream's program leaves them, not as a tidier one
would.

``dtype`` is the precision the solver is carried in, as in the solver's
file: ``bfloat16`` is the control.
"""

import functools
import importlib.util
import pathlib

import jax
import jax.numpy as jnp
from jax import lax

_spec = importlib.util.spec_from_file_location(
    "perfbench_references_shallow_water",
    pathlib.Path(__file__).with_name("shallow-water.py"))
solver = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(solver)

parameters, row_blocks = solver.parameters, solver.row_blocks


def _step(state, p, coriolis, first):
    """Upstream's step: ``solver._step``, its ghost columns of ``u`` and
    ``v`` left as the exchange before friction wrote them."""
    fresh = solver._step(state, p, coriolis, first)
    # the same step with no friction is the fields that exchange carried
    # (XLA computes what the two have in common once)
    before = solver._step(state, dict(p, nu=0.0), coriolis, first)

    def stale(after, carried):
        return after.at[:, 0].set(carried[:, 0]).at[:, -1].set(carried[:, -1])

    return (fresh[0], stale(fresh[1], before[1]), stale(fresh[2], before[2]),
            *fresh[3:])


@functools.partial(jax.jit, static_argnames=("steps", "dtype", "p_items"))
def _run(h0, u0, v0, first_row, *, steps, dtype, p_items):
    """``solver._run`` through ``_step`` above, the ghost columns kept."""
    p = dict(p_items)
    ny, nx = h0.shape
    rows = jnp.arange(-1, ny + 1, dtype=jnp.float32) + first_row
    coriolis = jnp.broadcast_to(
        (p["coriolis_f"] + rows * jnp.float32(p["dy"]) * p["coriolis_beta"])[:, None],
        (ny + 2, nx + 2),
    ).astype(dtype)

    def ghosted(a, kind):
        return solver._boundaries(jnp.pad(a.astype(dtype), 1, mode="edge"), kind)

    zeros = jnp.zeros((ny, nx), dtype)
    state = (ghosted(h0, "h"), ghosted(u0, "u"), ghosted(v0, "v"),
             zeros, zeros, zeros)
    state = _step(state, p, coriolis, first=True)
    state = lax.fori_loop(
        0, steps - 1, lambda _, s: _step(s, p, coriolis, first=False), state)
    return tuple(a[1:-1].astype(jnp.float32) for a in state[:3])


def run(h0, u0, v0, params, steps, dtype="float32", first_row=0):
    """``(h, u, v)`` after ``steps`` steps (one Euler step, then AB2)
    from the interior fields ``h0, u0, v0``, carried in ``dtype``: the
    fields' rows at all ``nx + 2`` columns, the first and the last the
    ghost columns as upstream's program leaves them.  The fields may be
    a band of rows that starts at the domain's row ``first_row``, as
    ``solver.run`` takes one."""
    return _run(h0, u0, v0, jnp.float32(first_row), steps=int(steps),
                dtype=jnp.dtype(dtype).name,
                p_items=tuple(sorted(params.items())))
