"""Plain reference of the differentiated run: the misfit of a window of
the plain shallow-water solver to observations, in ``jax.numpy`` on one
device, and ``jax.grad`` of it.  Imports nothing of mpi4jax_tpu.

The solver is the accepted plain reference, ``shallow-water.py`` beside
this file, loaded by path: one ghost cell, no halo code, no kernel, no
``custom_vjp``.  The cost is the twin experiment's (Courtier and
Talagrand 1990): ``J = 1/2 sum_k sum_blocks (H(h_k) - obs_k)^2`` over
the state after the first step and after every call of
``steps_per_call`` steps, ``H`` the mean of ``h`` over ``coarsen x
coarsen`` cells.  The gradient is jax's own reverse mode through every
line of that solver: independent of the adjoint exchange and of the
step's ``custom_vjp`` in the program under test.

On bands of rows (``bands``): the gradient with respect to the rows
``[keep_lo, keep_hi)`` of the initial fields is exact when taken on the
rows ``[lo, hi)``, both cut edges made walls, if the band is widened by
what the window can carry a value forwards and a cotangent back: a
step's stencils reach three rows (``shallow-water.py REACH_PER_STEP``
counts them double, which here is exactly there and back), so
``6 x steps`` rows and a step's more for room.  The misfits near a cut
edge are wrong and their cotangents never reach the kept rows.

One departure, which changes no value: a call and each step of it are
wrapped in ``jax.checkpoint`` (jax then keeps the states and runs a
step again for its derivative), because 41 steps' residuals of a band
do not fit a chip beside nothing.

``dtype`` is the precision the state and the arithmetic are carried in:
``float32`` is the reference, ``bfloat16`` the control that the
comparison has to refuse.  The misfit is summed in float32 either way.
"""

import functools
import importlib.util
import pathlib

import jax
import jax.numpy as jnp
from jax import lax


def _load_solver():
    path = pathlib.Path(__file__).with_name("shallow-water.py")
    spec = importlib.util.spec_from_file_location("plain_shallow_water", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


solver = _load_solver()
parameters = solver.parameters
run = solver.run  # the forward run, for the window's last state
row_blocks = solver.row_blocks


def bands(ny, own, coarsen, steps, seed, count=4):
    """``(lo, hi, keep_lo, keep_hi)`` of ``count`` bands of ``own`` rows:
    at the southern wall, at the northern wall, astride the jet in the
    middle of the domain, and the rest where the seed puts them.  Every
    edge is a multiple of ``coarsen``, so that an observation's cells
    lie in one band."""
    reach = -(-(solver.REACH_PER_STEP * (steps + 1)) // coarsen) * coarsen
    own = own // coarsen * coarsen
    starts = [0, ny - own, (ny - own) // 2 // coarsen * coarsen]
    room = (ny - own) // coarsen
    k = int(seed)
    while len(starts) < count:
        k = (k * 6364136223846793005 + 1442695040888963407) % (1 << 64)
        starts.append((k >> 33) % (room + 1) * coarsen)
    return [(max(a - reach, 0), min(a + own + reach, ny), a, a + own)
            for a in starts[:count]]


def observe(h, coarsen):
    """``H``: the mean over ``coarsen x coarsen`` cells of the interior
    of a field with its one ghost cell."""
    inner = h[1:-1, 1:-1]
    ny, nx = inner.shape
    c = coarsen
    return inner.reshape(ny // c, c, nx // c, c).mean(axis=(1, 3))


def _window(h0, u0, v0, first_row, *, calls, steps_per_call, dtype, p, each):
    """The window's states after the first step and after every call, as
    ``each(state, k)`` sees them; returns the list of what it returned."""
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
    seen = [each(state, 0)]

    step = jax.checkpoint(
        lambda s: solver._step(s, p, coriolis, first=False))

    @jax.checkpoint
    def call(state):
        return lax.scan(lambda s, _: (step(s), None), state, None,
                        length=steps_per_call)[0]

    for k in range(calls):
        state = call(state)
        seen.append(each(state, k + 1))
    return seen


@functools.partial(jax.jit, static_argnames=(
    "calls", "steps_per_call", "coarsen", "dtype", "p_items"))
def _gradient(h0, u0, v0, obs, first_row, *, calls, steps_per_call, coarsen,
              dtype, p_items):
    p = dict(p_items)

    def cost(h0, u0, v0):
        def misfit(state, k):
            d = observe(state[0], coarsen).astype(jnp.float32) - obs[k]
            return 0.5 * jnp.sum(d * d)

        return sum(_window(
            h0, u0, v0, first_row, calls=calls, steps_per_call=steps_per_call,
            dtype=dtype, p=p, each=misfit))

    value, grads = jax.value_and_grad(cost, argnums=(0, 1, 2))(h0, u0, v0)
    return (value, *(g.astype(jnp.float32) for g in grads))


def gradient(h0, u0, v0, obs, params, calls, steps_per_call, coarsen,
             dtype="float32", first_row=0):
    """``(J, dJ/dh0, dJ/du0, dJ/dv0)`` of the window from the interior
    fields ``h0, u0, v0`` against ``obs`` (``calls + 1`` coarse fields),
    carried in ``dtype``.  The fields may be a band of rows of the
    domain that starts at the domain's row ``first_row`` (``bands``),
    ``obs`` the same band's: then both its edges are walls, and only the
    rows far enough from a made-up edge hold the domain's gradient."""
    return _gradient(
        h0, u0, v0, obs, jnp.float32(first_row), calls=int(calls),
        steps_per_call=int(steps_per_call), coarsen=int(coarsen),
        dtype=jnp.dtype(dtype).name, p_items=tuple(sorted(params.items())))
