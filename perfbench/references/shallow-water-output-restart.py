"""Plain reference of the solver job that writes output and is saved at
once: the two plain references beside this file, loaded by path
(``shallow-water-restart.py``: the plain solver walked in legs with a
``numpy.save`` and a ``numpy.load`` between them; ``shallow-water-job.py``:
the block mean, in numpy with a float64 accumulator and in ``jax.numpy``),
and the one walk neither has: output across a restart.  Imports nothing
of mpi4jax_tpu, and nothing of ``SolverJob``, ``Snapshot``, ``Checkpoint``
or ``utils/checkpoint.py``'s kind.

``run_output`` walks 1 + ``before`` + ``after`` steps and takes a block
mean of ``h``, ``u``, ``v`` after every ``every`` of the last ``after``.
``run_output_restarted`` walks 1 + ``before``, saves, forgets, loads into
fresh arrays, and walks ``after`` more with the same block means: what
``run_output`` itself is held to, step for step and bit for bit, so that
a reference that cannot be stopped and started is not what a restarted
job's output is compared with.  The mistakes a comparison has to see:
``drop_tendencies`` (the load without ``dh``, ``du``, ``dv``: forward
Euler's start after a resume) and ``late`` (a snapshot taken one step
after the step it names).  ``dtype`` is the precision the solver is
carried in, as in the solver's file: ``bfloat16`` is the control.
"""

import importlib.util
import pathlib


def _beside(name):
    spec = importlib.util.spec_from_file_location(
        "perfbench_references_" + name.replace("-", "_"),
        pathlib.Path(__file__).with_name(f"{name}.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


restart = _beside("shallow-water-restart")
output = _beside("shallow-water-job")

# what the two accepted drivers ask of their reference, under their names
parameters, row_blocks, run = restart.parameters, restart.row_blocks, restart.run
first_step, advance, fields = restart.first_step, restart.advance, restart.fields
save, load, run_restarted = restart.save, restart.load, restart.run_restarted
block_mean, run_block_means = output.block_mean, output.run_block_means


def _snapshots(state, params, after, every, coarsen, keep, dtype, first_row, late):
    """``[(h, u, v), ...]``: the block means (numpy, float64 accumulate)
    of the rows ``[keep[0], keep[1])`` after every ``every`` of ``after``
    steps more from ``state``."""
    if after % every:
        raise ValueError(f"{after} steps are no whole number of {every}")
    lo, hi = keep or (0, None)
    out = []
    for _ in range(after // every):
        state = advance(state, params, every, dtype, first_row)
        seen = advance(state, params, 1, dtype, first_row) if late else state
        out.append(tuple(block_mean(a[lo:hi], coarsen) for a in fields(seen)))
    return out


def run_output(h0, u0, v0, params, before, after, every, coarsen, keep=None,
               dtype="float32", first_row=0):
    """The block means of the rows ``keep`` (all of them unless given) of
    the interior ``(h, u, v)`` (a band of rows, as ``run`` takes one)
    after 1 + ``before`` + ``every``, + 2 ``every``, ... steps,
    uninterrupted."""
    state = advance(first_step(h0, u0, v0, params, dtype, first_row),
                    params, before, dtype, first_row)
    return _snapshots(
        state, params, after, every, coarsen, keep, dtype, first_row, False)


def run_output_restarted(h0, u0, v0, params, before, after, every, coarsen,
                         directory, keep=None, dtype="float32", first_row=0,
                         drop_tendencies=False, late=False):
    """The same block means from a walk that was saved to ``directory``
    after 1 + ``before`` steps, forgotten, and loaded from it."""
    state = advance(first_step(h0, u0, v0, params, dtype, first_row),
                    params, before, dtype, first_row)
    save(directory, state, 1 + before)
    del state
    state, step = load(directory, drop_tendencies)
    if step != 1 + before:
        raise AssertionError(f"saved at step {1 + before}, loaded step {step}")
    return _snapshots(
        state, params, after, every, coarsen, keep, dtype, first_row, late)
