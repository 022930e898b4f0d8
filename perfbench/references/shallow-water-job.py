"""Plain reference of the solver job's output: the plain solver of
``references/shallow-water.py`` (loaded by path, beside this file) and a
block mean.  Imports nothing of mpi4jax_tpu.

A snapshot is, for each of ``h``, ``u``, ``v``, the mean over ``coarsen
x coarsen`` blocks of interior cells of the state after a given number
of steps.  ``block_mean`` is that in numpy, accumulated in float64 and
cast to float32: the value a snapshot of a given state is held to.
``run_block_means`` is the plain solver walked once through several step
counts, the block means of its float32 fields taken where it stands, in
``jax.numpy`` (rows first, then columns, so that no array is cut across
its lanes four ways at once): what a job's snapshots after 11, 21, 31,
41 steps are compared with.  ``dtype`` is the precision the solver is
carried in, as in the solver's file: ``bfloat16`` is the control.
"""

import functools
import importlib.util
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

_spec = importlib.util.spec_from_file_location(
    "perfbench_references_shallow_water",
    pathlib.Path(__file__).with_name("shallow-water.py"))
solver = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(solver)

parameters, row_blocks, run = solver.parameters, solver.row_blocks, solver.run


def block_mean(field, coarsen):
    """``(ny, nx) -> (ny / coarsen, nx / coarsen)`` in numpy: the mean of
    each block in float64, cast to float32."""
    a = np.asarray(field)
    ny, nx = a.shape
    c = int(coarsen)
    if ny % c or nx % c:
        raise ValueError(f"coarsen {c} does not divide {ny}x{nx}")
    return a.reshape(ny // c, c, nx // c, c).mean(
        axis=(1, 3), dtype=np.float64).astype(np.float32)


def _block_mean(a, c):
    """The same in ``jax.numpy`` and float32, rows before columns."""
    ny, nx = a.shape
    rows = a.reshape(ny // c, c, nx).sum(axis=1)
    return rows.reshape(ny // c, nx // c, c).sum(axis=2) / (c * c)


@functools.partial(
    jax.jit, static_argnames=("steps", "coarsen", "keep", "dtype", "p_items"))
def _walk(h0, u0, v0, first_row, *, steps, coarsen, keep, dtype, p_items):
    """``solver._run`` with a stop at each of ``steps``."""
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
    state = solver._step(state, p, coriolis, first=True)
    done, out = 1, []
    lo, hi = keep
    for n in steps:
        state = lax.fori_loop(
            0, n - done,
            lambda _, s: solver._step(s, p, coriolis, first=False), state)
        done = n
        out.append(tuple(
            _block_mean(a[1 + lo:1 + hi, 1:-1].astype(jnp.float32), coarsen)
            for a in state[:3]))
    return tuple(out)


def run_block_means(h0, u0, v0, params, steps, coarsen, keep,
                    dtype="float32", first_row=0):
    """For each of the ascending step counts ``steps``: the block means
    ``(h, u, v)`` of the rows ``[keep[0], keep[1])`` of the fields (a
    band of rows, as ``solver.run`` takes one) after that many steps
    from ``h0, u0, v0``, carried in ``dtype``."""
    steps = tuple(int(n) for n in steps)
    if list(steps) != sorted(set(steps)) or steps[0] < 1:
        raise ValueError(f"step counts {steps} are not ascending from 1")
    lo, hi = (int(k) for k in keep)
    if (hi - lo) % coarsen or h0.shape[1] % coarsen:
        raise ValueError(f"coarsen {coarsen} does not divide the {hi - lo} "
                         f"rows or the {h0.shape[1]} columns kept")
    return _walk(h0, u0, v0, jnp.float32(first_row), steps=steps,
                 coarsen=int(coarsen), keep=(lo, hi),
                 dtype=jnp.dtype(dtype).name,
                 p_items=tuple(sorted(params.items())))
