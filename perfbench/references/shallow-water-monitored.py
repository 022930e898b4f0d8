"""Plain reference of the solver job that watches itself: the plain
solver as ``references/shallow-water-restart.py`` (loaded by path,
beside this file) offers it to be walked from stop to stop
(``first_step``, ``advance``, ``fields``), and the monitor's line.
Imports nothing of mpi4jax_tpu.

A line is four numbers of the interior fields ``h``, ``u``, ``v`` of the
whole domain after a given number of steps (Veros's ``sanity_check``,
a ``global_and`` of ``isfinite``, its ``cfl_monitor`` and
``tracer_monitor`` diagnostics, and the thinnest layer, which is the
library's own):

``nonfinite``  how many of the values of ``h``, ``u``, ``v`` are not finite;
``cfl``        ``max(max|u| dt/dx, max|v| dt/dy)``;
``h_min``      ``min(h)``;
``mass``       ``sum(h) dx dy``.

``monitor`` is that in numpy over the float32 fields as they are: the
counts exact, the maxima and the minimum exact and then multiplied in
float64, the sum accumulated in float64.  ``run_lines`` is the plain
solver walked once through several step counts, the line's **parts**
taken over the rows it keeps where it stands, in ``jax.numpy`` (a band
of rows a call, as ``solver.run`` takes one, so that a domain that does
not fit one device is walked band by band): the count, the two maxima
and the minimum, which no order of evaluation changes, and the sums of
``h`` along each kept row in float32 (``band_parts`` is those of
fields at hand).  ``line_of`` puts the bands' parts together on the
host: counts and row sums added in float64
(a row's 14 400 or 28 800 additions round in float32 without bias, and
some ten thousand rows' roundings cancel: 1e-9 of the total, against
the 6e-8 a float32 holds), maxima and minimum reduced.  ``dtype`` is
the precision the solver is carried in, as in the solver's file:
``bfloat16`` is the control.
"""

import importlib.util
import pathlib

import jax
import jax.numpy as jnp
import numpy as np

_spec = importlib.util.spec_from_file_location(
    "perfbench_references_shallow_water_restart",
    pathlib.Path(__file__).with_name("shallow-water-restart.py"))
solver = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(solver)

parameters, row_blocks, run = solver.parameters, solver.row_blocks, solver.run

LINE = ("nonfinite", "cfl", "h_min", "mass")


def monitor(h, u, v, params):
    """The line of the interior fields ``h``, ``u``, ``v`` (the whole
    domain's, no ghost cell), in numpy."""
    h, u, v = (np.asarray(a) for a in (h, u, v))
    return line_of([(
        sum(int(np.count_nonzero(~np.isfinite(a))) for a in (h, u, v)),
        np.max(np.abs(u)), np.max(np.abs(v)), np.min(h),
        np.sum(h, axis=1, dtype=np.float64))], params)


def line_of(parts, params):
    """The domain's line from the ``parts`` of bands of rows that cover
    it once: ``(nonfinite, max|u|, max|v|, min h, sums of h a row)`` a
    band."""
    bad, umax, vmax, hmin, rows = zip(*parts)
    dt = params["dt"]
    return {
        "nonfinite": int(sum(int(n) for n in bad)),
        "cfl": max(float(max(umax)) * dt / params["dx"],
                   float(max(vmax)) * dt / params["dy"]),
        "h_min": float(min(hmin)),
        "mass": float(sum(np.asarray(r, np.float64).sum() for r in rows)
                      * params["dx"] * params["dy"]),
    }


def _parts(h, u, v):
    return (sum(jnp.sum(~jnp.isfinite(a), dtype=jnp.int32) for a in (h, u, v)),
            jnp.max(jnp.abs(u)), jnp.max(jnp.abs(v)), jnp.min(h),
            jnp.sum(h, axis=1))


# ``(nonfinite, max|u|, max|v|, min h, sums of h a row)`` of float32
# fields where they lie (a band of rows, a chip's block): ``line_of``'s
# parts
band_parts = jax.jit(_parts)


def run_lines(h0, u0, v0, params, steps, keep, dtype="float32", first_row=0):
    """For each of the ascending step counts ``steps``: the parts
    ``(nonfinite, max|u|, max|v|, min h, sums of h a row)`` of the
    line over the rows ``[keep[0], keep[1])`` of the fields (a band of
    rows, as ``solver.run`` takes one) after that many steps from ``h0,
    u0, v0``, carried in ``dtype``; and those rows of ``(h, u, v)``
    after the last.  ``line_of`` makes the domain's line of the bands'
    parts at one step count."""
    steps = tuple(int(n) for n in steps)
    if list(steps) != sorted(set(steps)) or steps[0] < 1:
        raise ValueError(f"step counts {steps} are not ascending from 1")
    lo, hi = (int(k) for k in keep)
    state, done, parts = solver.first_step(h0, u0, v0, params, dtype, first_row), 1, []
    for n in steps:
        state = solver.advance(state, params, n - done, dtype, first_row)
        done = n
        kept = tuple(a[lo:hi] for a in solver.fields(state))
        parts.append(band_parts(*kept))
    return tuple(parts), kept
