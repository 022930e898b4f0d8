"""Plain reference of the shallow-water step: upstream's scheme written
out in ``jax.numpy`` on one device.  Imports nothing of mpi4jax_tpu.

mpi4jax ``examples/shallow_water.py`` (from dionhaefner/shallow-water):
C-grid nonlinear shallow water, Sadourny's energy-conserving potential
vorticity flux, forward Euler for the first step and Adams-Bashforth-2
(1.6, -0.6) after it, lateral viscosity 1e-3 f dx^2, periodic in x,
solid walls in y.  One ghost cell round the global field; no halos, no
decomposition: ``_boundaries`` is upstream's serial ``enforce_boundaries``.

Two departures from upstream, both the documented behaviour of the
program under test (``models/shallow_water.py``): the viscosity of ``v``
reads ``v`` (upstream reads ``u`` in two of its stencils), and the
viscous fluxes are not zeroed on the northern wall row.

``dtype`` is the precision the state and the arithmetic are carried in:
``float32`` is the reference, ``bfloat16`` is the control that the
benchmark's comparison has to refuse.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax


def _boundaries(a, kind):
    a = a.at[:, 0].set(a[:, -2])
    a = a.at[:, -1].set(a[:, 1])
    if kind == "v":
        a = a.at[-2, :].set(0.0)
    return a


def _inner(a, value):
    return jnp.zeros_like(a).at[1:-1, 1:-1].set(value)


def _step(state, p, coriolis, first):
    h, u, v, dh, du, dv = state
    dx, dy, g, dt, nu = p["dx"], p["dy"], p["gravity"], p["dt"], p["nu"]
    c = slice(1, -1)  # centre
    lo = slice(None, -2)  # west / south neighbour
    hi = slice(2, None)  # east / north neighbour

    hc = _boundaries(jnp.pad(h[c, c], 1, mode="edge"), "h")
    fe = _boundaries(_inner(u, 0.5 * (hc[c, c] + hc[c, hi]) * u[c, c]), "u")
    fn = _boundaries(_inner(v, 0.5 * (hc[c, c] + hc[hi, c]) * v[c, c]), "v")
    dh_new = -(fe[c, c] - fe[c, lo]) / dx - (fn[c, c] - fn[lo, c]) / dy

    vorticity = (v[c, hi] - v[c, c]) / dx - (u[hi, c] - u[c, c]) / dy
    thickness = 0.25 * (hc[c, c] + hc[c, hi] + hc[hi, c] + hc[hi, hi])
    q = _boundaries(_inner(h, (coriolis[c, c] + vorticity) / thickness), "h")

    du_new = -g * (h[c, hi] - h[c, c]) / dx + 0.5 * (
        q[c, c] * 0.5 * (fn[c, c] + fn[c, hi])
        + q[lo, c] * 0.5 * (fn[lo, c] + fn[lo, hi])
    )
    dv_new = -g * (h[hi, c] - h[c, c]) / dy - 0.5 * (
        q[c, c] * 0.5 * (fe[c, c] + fe[hi, c])
        + q[c, lo] * 0.5 * (fe[c, lo] + fe[hi, lo])
    )
    ke = _boundaries(_inner(h, 0.5 * (
        0.5 * (u[c, c] ** 2 + u[c, lo] ** 2)
        + 0.5 * (v[c, c] ** 2 + v[lo, c] ** 2)
    )), "h")
    du_new = du_new - (ke[c, hi] - ke[c, c]) / dx
    dv_new = dv_new - (ke[hi, c] - ke[c, c]) / dy

    if first:
        inc_h, inc_u, inc_v = dh_new, du_new, dv_new
    else:
        a, b = p["ab_a"], p["ab_b"]
        inc_h = a * dh_new + b * dh
        inc_u = a * du_new + b * du
        inc_v = a * dv_new + b * dv
    h = _boundaries(h.at[c, c].add(dt * inc_h), "h")
    u = _boundaries(u.at[c, c].add(dt * inc_u), "u")
    v = _boundaries(v.at[c, c].add(dt * inc_v), "v")

    def diffuse(w):
        gx = _boundaries(_inner(w, nu * (w[c, hi] - w[c, c]) / dx), "h")
        gy = _boundaries(_inner(w, nu * (w[hi, c] - w[c, c]) / dy), "h")
        return w.at[c, c].add(dt * (
            (gx[c, c] - gx[c, lo]) / dx + (gy[c, c] - gy[lo, c]) / dy))

    u = _boundaries(diffuse(u), "u")
    v = _boundaries(diffuse(v), "v")
    return h, u, v, dh_new, du_new, dv_new


def parameters(model, dx, dy):
    """The step's constants from the configuration's ``model`` group."""
    f = model["coriolis_f"]
    return {
        "dx": dx, "dy": dy, "gravity": model["gravity"],
        "dt": 0.125 * min(dx, dy) / math.sqrt(model["gravity"] * model["depth"]),
        "nu": 1e-3 * f * dx ** 2,
        "ab_a": model["ab_a"], "ab_b": model["ab_b"],
        "coriolis_f": f, "coriolis_beta": model["coriolis_beta"],
    }


# rows a step can carry a value: three, by the stencils above; counted
# double, so that a block's made-up edge never reaches the rows it keeps
REACH_PER_STEP = 6


def row_blocks(ny, blocks, steps):
    """``(lo, hi, keep_lo, keep_hi)`` for each of ``blocks`` bands of
    rows: the band ``[keep_lo, keep_hi)`` is computed from the rows
    ``[lo, hi)``, wide enough that ``steps`` steps cannot carry the
    band's made-up edges into what is kept.  Bands at a wall end there."""
    halo = REACH_PER_STEP * steps
    edges = [round(i * ny / blocks) for i in range(blocks + 1)]
    return [
        (max(a - halo, 0), min(b + halo, ny), a, b)
        for a, b in zip(edges, edges[1:])
    ]


@functools.partial(jax.jit, static_argnames=("steps", "dtype", "p_items"))
def _run(h0, u0, v0, first_row, *, steps, dtype, p_items):
    p = dict(p_items)
    ny, nx = h0.shape
    # coordinates in float32 whatever the state's precision: the grid is
    # an input, not part of the arithmetic under test
    rows = jnp.arange(-1, ny + 1, dtype=jnp.float32) + first_row
    coriolis = jnp.broadcast_to(
        (p["coriolis_f"] + rows * jnp.float32(p["dy"]) * p["coriolis_beta"])[:, None],
        (ny + 2, nx + 2),
    ).astype(dtype)

    def ghosted(a, kind):
        return _boundaries(jnp.pad(a.astype(dtype), 1, mode="edge"), kind)

    zeros = jnp.zeros((ny, nx), dtype)
    state = (ghosted(h0, "h"), ghosted(u0, "u"), ghosted(v0, "v"),
             zeros, zeros, zeros)
    state = _step(state, p, coriolis, first=True)
    state = lax.fori_loop(
        0, steps - 1, lambda _, s: _step(s, p, coriolis, first=False), state)
    h, u, v = state[:3]
    return tuple(a[1:-1, 1:-1].astype(jnp.float32) for a in (h, u, v))


def run(h0, u0, v0, params, steps, dtype="float32", first_row=0):
    """Interior ``(h, u, v)`` after ``steps`` steps (one Euler step, then
    AB2) from the interior fields ``h0, u0, v0``, carried in ``dtype``.
    The fields may be a band of rows of the domain that starts at the
    domain's row ``first_row`` (see ``row_blocks``): then both of its
    edges are treated as walls, and only the rows far enough from a
    made-up edge are the domain's."""
    return _run(h0, u0, v0, jnp.float32(first_row), steps=int(steps),
                dtype=jnp.dtype(dtype).name,
                p_items=tuple(sorted(params.items())))
