"""Plain reference of the restarted solver job: the plain solver of
``references/shallow-water.py`` (loaded by path, beside this file) walked
in two legs with a plain save and load of its own between them.  Imports
nothing of mpi4jax_tpu, and nothing of ``utils/checkpoint.py``'s kind:
a save is ``numpy.save`` of the six arrays and of the step count, a load
``numpy.load`` into fresh arrays.

``run`` is the solver's own: 1 + n steps uninterrupted, what a resumed
job's fields are held to.  ``run_restarted`` walks 1 + ``before`` steps,
saves, forgets, loads, and walks ``after`` more: what ``run`` itself is
held to, so that a reference that cannot be stopped and started is not
what a restarted job is compared with.  ``drop_tendencies`` is the
mistake a restart can make and a comparison has to see: the state read
back without ``dh``, ``du``, ``dv``, so that the step after it is
Adams-Bashforth on zeros.  ``dtype`` is the precision the solver is
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

NAMES = ("h", "u", "v", "dh", "du", "dv")


def _coriolis(p, shape, first_row, dtype):
    ny, nx = shape
    rows = jnp.arange(-1, ny + 1, dtype=jnp.float32) + first_row
    return jnp.broadcast_to(
        (p["coriolis_f"] + rows * jnp.float32(p["dy"]) * p["coriolis_beta"])[:, None],
        (ny + 2, nx + 2),
    ).astype(dtype)


@functools.partial(jax.jit, static_argnames=("dtype", "p_items"))
def _first(h0, u0, v0, first_row, *, dtype, p_items):
    p = dict(p_items)

    def ghosted(a, kind):
        return solver._boundaries(jnp.pad(a.astype(dtype), 1, mode="edge"), kind)

    zeros = jnp.zeros(h0.shape, dtype)
    state = (ghosted(h0, "h"), ghosted(u0, "u"), ghosted(v0, "v"),
             zeros, zeros, zeros)
    return solver._step(
        state, p, _coriolis(p, h0.shape, first_row, dtype), first=True)


@functools.partial(jax.jit, static_argnames=("steps", "dtype", "p_items"))
def _more(state, first_row, *, steps, dtype, p_items):
    p = dict(p_items)
    coriolis = _coriolis(p, state[3].shape, first_row, dtype)
    return lax.fori_loop(
        0, steps, lambda _, s: solver._step(s, p, coriolis, first=False), state)


def first_step(h0, u0, v0, params, dtype="float32", first_row=0):
    """The six arrays ``(h, u, v, dh, du, dv)`` after the forward-Euler
    step from the interior fields (a band of rows, as ``run`` takes
    one): the fields with their ghost cell, the tendencies without."""
    return _first(h0, u0, v0, jnp.float32(first_row),
                  dtype=jnp.dtype(dtype).name,
                  p_items=tuple(sorted(params.items())))


def advance(state, params, steps, dtype="float32", first_row=0):
    """``state`` after ``steps`` Adams-Bashforth steps more."""
    return _more(tuple(state), jnp.float32(first_row), steps=int(steps),
                 dtype=jnp.dtype(dtype).name,
                 p_items=tuple(sorted(params.items())))


def fields(state):
    """Interior ``(h, u, v)`` in float32, as ``run`` returns them."""
    return tuple(a[1:-1, 1:-1].astype(jnp.float32) for a in state[:3])


def save(directory, state, step):
    """The six arrays and the step count, a ``.npy`` file each."""
    directory = pathlib.Path(directory)
    directory.mkdir(parents=True, exist_ok=True)
    for name, a in zip(NAMES, state):
        np.save(directory / f"{name}.npy", np.asarray(a))
    np.save(directory / "step.npy", np.int64(step))


def load(directory, drop_tendencies=False):
    """``(state, step)`` from fresh arrays; ``drop_tendencies``: the
    mistake, zeros in the place of ``dh``, ``du``, ``dv``."""
    directory = pathlib.Path(directory)
    state = [np.load(directory / f"{name}.npy") for name in NAMES]
    if drop_tendencies:
        state[3:] = [np.zeros_like(a) for a in state[3:]]
    return tuple(jnp.asarray(a) for a in state), int(np.load(directory / "step.npy"))


def run_restarted(h0, u0, v0, params, before, after, directory,
                  dtype="float32", first_row=0, drop_tendencies=False):
    """Interior ``(h, u, v)`` after 1 + ``before`` steps, a save to
    ``directory``, a load from it and ``after`` steps more."""
    state = advance(first_step(h0, u0, v0, params, dtype, first_row),
                    params, before, dtype, first_row)
    save(directory, state, 1 + before)
    del state
    state, step = load(directory, drop_tendencies)
    if step != 1 + before:
        raise AssertionError(f"saved at step {1 + before}, loaded step {step}")
    return fields(advance(state, params, after, dtype, first_row))
