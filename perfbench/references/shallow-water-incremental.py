"""Plain reference of the linearised run: the tangent-linear model of a
window of the plain shallow-water solver, its transpose and the
Gauss-Newton product of the two, in ``jax.numpy`` on one device.
Imports nothing of mpi4jax_tpu.

The solver is the accepted plain reference, ``shallow-water.py``, and
the window and the observation operator ``H`` (the mean of ``h`` over
``coarsen x coarsen`` cells, after the first step and after every call)
are the accepted ``shallow-water-adjoint.py``'s, both beside this file
and loaded by path: one ghost cell, no halo code, no kernel, no
``custom_jvp``.  ``H M_k p`` is ``jax.jvp`` of the window's observed
means, jax's own forward mode through every line of that solver; the
transpose is ``jax.vjp`` of the same function; ``A p = weight p + sum_k
M_k^T H^T H M_k p`` is the one after the other (Courtier, Thepaut and
Hollingsworth 1994: the Hessian of incremental 4D-Var's quadratic
cost).  Independent of the exchange's tangent and transpose and of the
step's written-out derivative in the program under test.

On bands of rows, the adjoint reference's (``bands``): a perturbation of
the rows a band keeps reaches the observations of rows ``3 x steps``
away and their cotangents come as far back, which is the reach that
``bands`` widens a band by; ``H M_k p`` on the kept rows needs the half
of it.  Both cut edges are walls, and what is wrong near them never
reaches the rows kept.

``dtype`` is the precision the state, the tangent and the arithmetic are
carried in: ``float32`` is the reference, ``bfloat16`` the control that
the comparison has to refuse.  ``exchange_tangent=False`` is a second
control: the same window whose boundary code writes its ghost cells'
values and no tangent there (the periodic wrap of a perturbation left
out: what a program computes whose exchange has no forward mode rule and
hands zeros on), which the comparison has to refuse too.
"""

import functools
import importlib.util
import pathlib

import jax
import jax.numpy as jnp


def _load(name):
    path = pathlib.Path(__file__).with_name(name + ".py")
    spec = importlib.util.spec_from_file_location(
        "plain_" + name.replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


adjoint = _load("shallow-water-adjoint")
solver = adjoint.solver  # the module whose `_step` the window runs
parameters = solver.parameters
run = solver.run  # the forward run, for the window's last state
row_blocks = solver.row_blocks
bands = adjoint.bands
observe = adjoint.observe


def _values_alone(boundaries):
    """``boundaries`` with no tangent on the cells it writes."""
    @functools.partial(jax.custom_jvp, nondiff_argnums=(1,))
    def written(a, kind):
        return boundaries(a, kind)

    @written.defjvp
    def rule(kind, primals, tangents):
        (a,), (t,) = primals, tangents
        t = t.at[:, 0].set(0.0).at[:, -1].set(0.0)
        return written(a, kind), t.at[-2, :].set(0.0) if kind == "v" else t

    return written


@functools.partial(jax.jit, static_argnames=(
    "calls", "steps_per_call", "coarsen", "dtype", "p_items", "weight",
    "exchange_tangent"))
def _product(h0, u0, v0, ph, pu, pv, first_row, *, calls, steps_per_call,
             coarsen, dtype, p_items, weight, exchange_tangent):
    p = dict(p_items)

    def seen(h0, u0, v0):
        return jnp.stack(adjoint._window(
            h0, u0, v0, first_row, calls=calls, steps_per_call=steps_per_call,
            dtype=dtype, p=p,
            each=lambda state, k: observe(state[0], coarsen).astype(jnp.float32)))

    at, push = (h0, u0, v0), (ph, pu, pv)
    plain = solver._boundaries
    if not exchange_tangent:
        solver._boundaries = _values_alone(plain)
    try:
        with jax.default_matmul_precision("highest"):
            _, pushed = jax.jvp(seen, at, push)
            pulled = jax.vjp(seen, *at)[1](pushed)
    finally:
        solver._boundaries = plain
    # <M p, M p> against <p, M^T M p>: the adjoint test with w = H M p
    there = jnp.vdot(pushed, pushed)
    home = sum(jnp.vdot(x.astype(jnp.float32), g.astype(jnp.float32))
               for x, g in zip(push, pulled))
    return (jnp.abs(there - home) / there, pushed,
            *(jnp.float32(weight) * x + g.astype(jnp.float32)
              for x, g in zip(push, pulled)))


def product(h0, u0, v0, ph, pu, pv, params, calls, steps_per_call, coarsen,
            weight, dtype="float32", first_row=0, exchange_tangent=True):
    """``(t, H M p, qh, qu, qv)`` at the interior fields ``h0, u0, v0``
    for the perturbation ``ph, pu, pv`` of them: the tangent-linear
    model's observed means, ``(calls + 1, ny / coarsen, nx / coarsen)``,
    and the Gauss-Newton product ``A p = weight p + M^T H^T H M p``,
    carried in ``dtype``; ``t`` is the adjoint test of the two sweeps on
    these very fields, ``|<M p, w> - <p, M^T w>| / |<M p, w>|`` with
    ``w = H M p`` (of a band: the band's own, walls and all), summed in
    float32.  The fields may be a band of rows of the domain that
    starts at the domain's row ``first_row`` (``bands``): then both its
    edges are walls, and only the rows far enough from a made-up edge
    hold the domain's values."""
    return _product(
        h0, u0, v0, ph, pu, pv, jnp.float32(first_row), calls=int(calls),
        steps_per_call=int(steps_per_call), coarsen=int(coarsen),
        dtype=jnp.dtype(dtype).name, p_items=tuple(sorted(params.items())),
        weight=float(weight), exchange_tangent=bool(exchange_tangent))
