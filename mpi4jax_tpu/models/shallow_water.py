"""Flagship workload: nonlinear shallow-water solver, SPMD over a TPU mesh.

Behavioural parity target: the reference's demo application
(examples/shallow_water.py, adapted there from dionhaefner/shallow-water)
— a C-grid nonlinear shallow-water model with the Sadourny (1975)
energy-conserving potential-vorticity scheme, Adams–Bashforth-2 stepping
with coefficients (1.6, −0.6) (shallow_water.py:126-127), periodic-x /
solid-wall-y boundaries, lateral viscosity, and a 1-cell ghost ring
exchanged ~12× per step (shallow_water.py:277-412).  The published
benchmark numbers (BASELINE.md) come from this workload on a 100×
enlarged domain (3600×1800).

TPU-first redesign (not a port):

* **One SPMD program** over a ``("y", "x")`` device mesh via
  ``jax.shard_map`` instead of one MPI process per rank: rank-dependent
  behaviour (wall masks, coordinate offsets) uses ``lax.axis_index``
  instead of Python branching, so a single compiled executable serves
  every device — and a 1×1 mesh runs the identical program on one chip.
* **Halo exchange = ppermute** (parallel/halo.py): each direction is one
  ICI nearest-neighbour transfer fused into the step, replacing ~4
  blocking host MPI calls per field (SURVEY §3.4: the reference crosses
  the process boundary ~5000× per outer tick; here the entire multistep
  loop is one XLA executable that never leaves HBM).
* **Distributed initial conditions**: each device evaluates the analytic
  jet on its own coordinate slab, and the geostrophic cumulative
  integral — a *global* cumsum in the reference
  (shallow_water.py:147-149) — becomes an mpi4jax_tpu ``scan`` (prefix
  sum) over the y axis plus an ``allreduce`` for the mean, so no device
  ever materialises the global grid.
* Everything is float32 (TPU-native; matches JAX-default behaviour of
  the reference) and the hot loop sits in ``lax.fori_loop`` inside one
  ``jit`` (shallow_water.py:415-420 does the same).
"""

import collections
import math
import time
from dataclasses import dataclass, replace
from functools import partial
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from mpi4jax_tpu.models import sw_kernels
from mpi4jax_tpu.ops import reductions
from mpi4jax_tpu.ops._core import SCOPE_PREFIX, as_token, both_modes
from mpi4jax_tpu.ops.allreduce import allreduce
from mpi4jax_tpu.ops.collectives import allgather, scan
from mpi4jax_tpu.parallel.halo import (
    halo_exchange_2d,
    halo_exchange_2d_batch,
    halo_slabs_2d,
)
from mpi4jax_tpu.utils import checkpoint as ckpt, spans

__all__ = [
    "SWConfig",
    "SWState",
    "initial_state",
    "shallow_water_step",
    "make_multistep",
    "make_state",
    "make_gradient",
    "make_descent_step",
    "Descent",
    "make_tangent",
    "make_adjoint",
    "make_product",
    "make_inner_step",
    "InnerLoop",
    "Snapshot",
    "Checkpoint",
    "Monitor",
    "MonitorStop",
    "SolverJob",
    "make_snapshot",
    "make_monitor",
    "make_job",
    "make_solver",
    "gather_global",
]

DAY_IN_SECONDS = 86_400.0


@dataclass(frozen=True)
class SWConfig:
    """Static model configuration (hashable: used as a jit-static arg)."""

    ny: int = 180  # global interior cells, y
    nx: int = 360  # global interior cells, x
    dx: float = 5e3  # metres
    dy: float = 5e3
    gravity: float = 9.81
    depth: float = 100.0
    coriolis_f: float = 2e-4
    coriolis_beta: float = 2e-11
    periodic_x: bool = True
    ab_a: float = 1.6  # Adams–Bashforth coefficients (reference :126-127)
    ab_b: float = -0.6
    dtype: str = "float32"
    # Ghost-ring width. 1 = the reference's layout and its step as
    # written (shallow_water.py:277-412 there): array code on every
    # backend, one exchange after each of 12 fields a step, the state
    # upstream's (ny+2, nx+2) arrays; what a caller's own stencil with
    # the library's exchange looks like, and on a TPU some 8 times
    # slower than ghost=2 (PERF.md, PR 43). 2 = wide-halo schedule: all
    # intermediate fields (fluxes, vorticity, kinetic energy, viscosity
    # gradients) are recomputed locally inside the ghost region, so a
    # step needs only 2 exchange rounds of the prognostic fields: 5
    # exchanges as array code and, on TPU devices in float32, 3
    # halo_slabs_2d and one Pallas kernel call (_step_wide; PR 31).
    # 4 = single-exchange schedule: one batched exchange of
    # (h, u, v) per step; the post-update viscosity operates on locally
    # recomputed ring-2 values and tendencies are never communicated
    # (they stay valid on ring-2 inductively). Identical numerics for
    # all widths (tested equal to the narrow path).
    ghost: int = 1

    def __post_init__(self):
        if self.ghost not in (1, 2, 4):
            raise ValueError(
                f"ghost={self.ghost!r}: the ghost ring is 1 cell wide (the "
                "reference's step as written, twelve exchanges a step), 2 "
                "(the wide-halo schedule: the step's kernel on TPU devices "
                "in float32, array code with five exchanges elsewhere) or 4 "
                "(the single-exchange schedule, array code)")

    @property
    def lateral_viscosity(self):
        return 1e-3 * self.coriolis_f * self.dx**2

    @property
    def dt(self):
        # CFL-limited gravity-wave time step (reference :137)
        return 0.125 * min(self.dx, self.dy) / math.sqrt(self.gravity * self.depth)

    @property
    def length_x(self):
        return self.nx * self.dx

    @property
    def length_y(self):
        return self.ny * self.dy

    def local_interior(self, comm):
        py, px = comm.axis_sizes
        if self.ny % py or self.nx % px:
            raise ValueError(
                f"grid {self.ny}x{self.nx} not divisible by mesh {py}x{px}"
            )
        return self.ny // py, self.nx // px

    def bench_size(self):
        """The published-benchmark domain: 100× the demo cell count
        (docs/shallow-water.rst:49-51 → 3600×1800), at ``ghost=2``, the
        schedule whose step is one kernel on a TPU.  The benchmark does
        not call this: ``perfbench/configs/shallow-water.json`` states
        the domain and the schedule itself."""
        return replace(self, ny=1800, nx=3600, ghost=2)


class SWState(NamedTuple):
    h: jax.Array
    u: jax.Array
    v: jax.Array
    dh: jax.Array
    du: jax.Array
    dv: jax.Array


def _device_coords(comm):
    """(iy, ix) coordinates of this device on the ("y","x") comm."""
    iy = lax.axis_index((comm.axes[0],))
    ix = lax.axis_index((comm.axes[1],))
    return iy, ix


def _local_mesh_coords(cfg, comm):
    """Per-device physical coordinates of the local block incl. ghosts."""
    G = cfg.ghost
    ny_l, nx_l = cfg.local_interior(comm)
    iy, ix = _device_coords(comm)
    # interior cell j of this device has global index iy*ny_l + j; the
    # ghost ring shifts indices by -G
    jy = jnp.arange(-G, ny_l + G, dtype=cfg.dtype) + (iy * ny_l).astype(cfg.dtype)
    jx = jnp.arange(-G, nx_l + G, dtype=cfg.dtype) + (ix * nx_l).astype(cfg.dtype)
    y = jy * cfg.dy
    x = jx * cfg.dx
    return jnp.meshgrid(y, x, indexing="ij")


def _coriolis(cfg, yy):
    return (cfg.coriolis_f + yy * cfg.coriolis_beta).astype(cfg.dtype)


def _wall_masks(comm):
    """(is_north_edge, is_south_edge) row masks for solid-wall BCs."""
    py, _ = comm.axis_sizes
    iy, _ = _device_coords(comm)
    return iy == py - 1, iy == 0


def initial_state(cfg, comm, *, token=None):
    """Geostrophically balanced zonal jet + perturbation, built
    device-locally (reference builds it globally then slices,
    shallow_water.py:138-170).

    Must be called inside the model's shard_map.
    """
    token = as_token(token)
    G = cfg.ghost
    yy, xx = _local_mesh_coords(cfg, comm)
    ly, lx = cfg.length_y, cfg.length_x

    u0 = 10.0 * jnp.exp(-((yy - 0.5 * ly) ** 2) / (0.02 * lx) ** 2)
    v0 = jnp.zeros_like(u0)

    # geostrophic balance h_y = -(f/g) u, integrated along global y.
    # Local trapezoid-free cumsum + exclusive cross-device prefix via the
    # scan collective over the y sub-communicator.
    integrand = (-cfg.dy * u0 * _coriolis(cfg, yy) / cfg.gravity).astype(cfg.dtype)
    interior = integrand[G:-G, :]
    local_cum = jnp.cumsum(interior, axis=0)
    local_total = local_cum[-1, :]
    ycomm = comm.sub(comm.axes[0])
    incl, token = scan(local_total, reductions.SUM, comm=ycomm, token=token)
    offset = incl - local_total  # exclusive prefix of previous y-blocks
    h_geo = jnp.pad(local_cum + offset[None, :], ((G, G), (0, 0)), mode="edge")

    # centre around the mean depth: global mean via allreduce
    local_sum = h_geo[G:-G, G:-G].sum()
    total, token = allreduce(local_sum, reductions.SUM, comm=comm, token=token)
    n_cells = float(cfg.ny * cfg.nx)
    h_mean = total / n_cells

    h0 = (
        cfg.depth
        + h_geo
        - h_mean
        + 0.2
        * jnp.sin(xx / lx * 10.0 * jnp.pi)
        * jnp.cos(yy / ly * 8.0 * jnp.pi)
    ).astype(cfg.dtype)
    return _ghosted_state(h0, u0, v0, cfg, comm, token)


def _ghosted_state(h0, u0, v0, cfg, comm, token):
    """A device's padded ``h0``, ``u0``, ``v0`` as the state a step
    takes: ghosts filled by the exchange (a wall's keep what they
    hold), zero tendencies in the form the schedule carries.  The one
    place that knows that form, for :func:`initial_state`'s jet and for
    a caller's own fields (:func:`make_state`)."""
    G = cfg.ghost
    ny_l, nx_l = cfg.local_interior(comm)
    per = (False, cfg.periodic_x)
    h0, token = halo_exchange_2d(h0, comm, periodic=per, token=token, width=G)
    u0, token = halo_exchange_2d(
        u0.astype(cfg.dtype), comm, periodic=per, token=token, width=G
    )
    v0, token = halo_exchange_2d(
        v0.astype(cfg.dtype), comm, periodic=per, token=token, width=G
    )

    if G == 2 and not _runs_as_kernels(cfg, comm):
        zeros = jnp.zeros((ny_l, nx_l), h0.dtype)  # wide: interior-only
    else:
        # narrow, single-exchange, and wide where the step is a kernel
        # (sw_kernels.wide_step says why): full-shape
        zeros = jnp.zeros_like(h0)
    return SWState(h0, u0, v0, zeros, zeros, zeros), token


# -- finite-difference helpers on padded blocks ---------------------------
# Neighbours as slices, interior-shaped: [1:-1, 1:-1] of the block, e/w
# shifted in x, n/s in y.  What the ghost=2 array code computes with.


def _i(a):
    return a[1:-1, 1:-1]


def _e(a):
    return a[1:-1, 2:]


def _w(a):
    return a[1:-1, :-2]


def _n(a):
    return a[2:, 1:-1]


def _s(a):
    return a[:-2, 1:-1]


# The same neighbours as views of the whole padded block, at its shape.
# What the ghost=1 step computes with: a result then has the block's own
# shape, the unshifted operand lies under it tile for tile, and there is
# no interior to place (shallow_water_step says what that is worth).


def _shifted(a, dy, dx):
    """``a`` read ``(dy, dx)`` away, at ``a``'s own shape: element
    ``[i, j]`` is ``a[i + dy, j + dx]``, and zero where that lies
    outside ``a``, which only a cell of the ghost ring reads."""
    return lax.pad(a, jnp.zeros((), a.dtype), ((-dy, dy, 0), (-dx, dx, 0)))


def _E(a):
    return _shifted(a, 0, 1)


def _W(a):
    return _shifted(a, 0, -1)


def _N(a):
    return _shifted(a, 1, 0)


def _S(a):
    return _shifted(a, -1, 0)


# The as-written step's phases and the fields it exchanges, as
# jax.named_scope segments ``sw/<phase>`` and ``sw/exchange.<field>``
# (the latter round the exchange's own ``mpi4jax_tpu.halo_exchange_2d``
# scope and its ``pack``, ``wire``, ``unpack``): every instruction of
# the ``ghost=1`` step carries one or the other in its ``op_name``, so a
# device profile splits a step by phase and by exchange.  Metadata only.
# None starts with SCOPE_PREFIX: that marks a communication op, for the
# contract analyzer (analysis/jaxpr_walk.py) and for whoever gives
# device time to the op surface, and a phase's array code is neither.
STEP_SCOPE = "sw"
STEP_PHASES = ("height", "mass_flux", "vorticity", "tendencies", "kinetic",
               "ab2", "friction")
STEP_EXCHANGES = ("hc", "fe", "fn", "q", "ke", "h", "u", "v",
                  "gx_u", "gy_u", "gx_v", "gy_v")


def _phase(name):
    return jax.named_scope(f"{STEP_SCOPE}/{name}")


def shallow_water_step(state, cfg, comm, *, first_step=False, token=None):
    """One model step (reference: shallow_water.py:277-412, same scheme).

    ``cfg.ghost == 1``: the reference's schedule as written, array code
    with one ``halo_exchange_2d`` after each of twelve fields
    (``STEP_EXCHANGES``), on every backend.  Every field it makes is
    made **at the padded shape**, ``(ny + 2, nx + 2)`` a block: a
    neighbour is a view of the whole padded array read one cell away
    (:func:`_shifted`: what lies outside is read only by a cell of the
    ghost ring), and a result goes where it belongs by a selection,
    ``lax.select(inside, value, ring)``, with ``inside`` from two iotas:
    zeros on the ring for the six fields upstream rings with zeros, the
    old ring for the tendencies and for ``h``, ``u``, ``v``, so that the
    state's ring holds what upstream's program leaves there.  An
    interior-shaped result placed into a padded array
    (``a.at[1:-1, 1:-1].set(value)``, or ``.add``) is the same field
    and, on a TPU, three passes over it where this is one: the
    interior is written, then read and written again a row and a lane
    off the tiles, and a zero ring for it is a ``pad`` more (PERF.md,
    PR 43: 87 passes over a field a step became 35 and the step half as
    long).  Only what is one strip of a field is written where it lies:
    the wall's row, and the ring of ``hc``, which is ``h`` but for its
    ring.  A stencil of your own beside ``halo_exchange_2d`` wants the
    same form.  ``cfg.ghost == 2``:
    wide-halo schedule, 5 exchanges per step as array code and, where
    the step runs as the kernel (TPU devices, float32), 3
    ``halo_slabs_2d`` and one kernel call (see :func:`_step_wide`).
    ``cfg.ghost == 4``: single-exchange schedule, one batched exchange
    per step (see :func:`_step_wide4`).  All numerically identical.
    """
    if cfg.ghost == 2:
        (state, _), token = _step_wide(
            state, cfg, comm, first_step=first_step, token=token)
        return state, token
    if cfg.ghost == 4:
        return _step_wide4(state, cfg, comm, first_step=first_step, token=token)
    token = as_token(token)
    per = (False, cfg.periodic_x)
    is_north, _is_south = _wall_masks(comm)
    dx, dy, g = cfg.dx, cfg.dy, cfg.gravity

    h, u, v, dh, du, dv = state
    # the cells of a padded block, from two iotas: fused, no bytes
    rows = lax.broadcasted_iota(jnp.int32, h.shape, 0)
    cols = lax.broadcasted_iota(jnp.int32, h.shape, 1)
    inside = ((rows > 0) & (rows < h.shape[0] - 1)
              & (cols > 0) & (cols < h.shape[1] - 1))
    zeros = jnp.zeros_like(h)

    def exchange(a, name, token):
        with jax.named_scope(f"{STEP_SCOPE}/exchange.{name}"):
            return halo_exchange_2d(a, comm=comm, periodic=per, token=token)

    def wall_v(a):
        """v = 0 on the northern wall row (reference :401-402): one row
        written where it lies."""
        return a.at[-2, :].set(jnp.where(is_north, 0.0, a[-2, :]))

    # cell-centred height with edge-padded ghosts, then exchanged: h but
    # for its ring, so a copy of h with the ring's four strips written
    # where they lie, rows first (the corners are the corner cells')
    with _phase("height"):
        hc = h.at[0, :].set(h[1, :]).at[-1, :].set(h[-2, :])
        hc = hc.at[:, 0].set(hc[:, 1]).at[:, -1].set(hc[:, -2])
    hc, token = exchange(hc, "hc", token)

    # mass fluxes on cell faces
    with _phase("mass_flux"):
        fe = lax.select(inside, 0.5 * (hc + _E(hc)) * u, zeros)
        fn = lax.select(inside, 0.5 * (hc + _N(hc)) * v, zeros)
    fe, token = exchange(fe, "fe", token)
    fn, token = exchange(fn, "fn", token)
    with _phase("mass_flux"):
        fn = wall_v(fn)

    with _phase("tendencies"):
        dh_new = lax.select(
            inside, -(fe - _W(fe)) / dx - (fn - _S(fn)) / dy, dh)

    # potential vorticity (planetary + relative, over face-mean depth)
    with _phase("vorticity"):
        yy, _xx = _local_mesh_coords(cfg, comm)
        rel_vort = (_E(v) - v) / dx - (_N(u) - u) / dy
        q = lax.select(
            inside,
            (_coriolis(cfg, yy) + rel_vort)
            / (0.25 * (hc + _E(hc) + _N(hc) + _shifted(hc, 1, 1))),
            zeros,
        )
    q, token = exchange(q, "q", token)

    # momentum tendencies: pressure gradient + PV flux (Sadourny 1975)
    with _phase("tendencies"):
        du_new = lax.select(
            inside,
            -g * (_E(h) - h) / dx
            + 0.5
            * (
                q * 0.5 * (fn + _E(fn))
                + _S(q) * 0.5 * (_S(fn) + _shifted(fn, -1, 1))
            ),
            du,
        )
        dv_new = lax.select(
            inside,
            -g * (_N(h) - h) / dy
            - 0.5
            * (
                q * 0.5 * (fe + _N(fe))
                + _W(q) * 0.5 * (_W(fe) + _shifted(fe, 1, -1))
            ),
            dv,
        )

    # kinetic energy gradient
    with _phase("kinetic"):
        ke = lax.select(
            inside,
            0.5 * (0.5 * (u ** 2 + _W(u) ** 2) + 0.5 * (v ** 2 + _S(v) ** 2)),
            zeros,
        )
    ke, token = exchange(ke, "ke", token)
    with _phase("kinetic"):
        du_new = lax.select(inside, du_new - (_E(ke) - ke) / dx, du_new)
        dv_new = lax.select(inside, dv_new - (_N(ke) - ke) / dy, dv_new)

    # time step: forward Euler bootstrap, then AB2 (reference :345-371)
    dt = jnp.asarray(cfg.dt, h.dtype)
    with _phase("ab2"):
        if first_step:
            u = lax.select(inside, u + dt * du_new, u)
            v = lax.select(inside, v + dt * dv_new, v)
            h = lax.select(inside, h + dt * dh_new, h)
        else:
            a, b = cfg.ab_a, cfg.ab_b
            u = lax.select(inside, u + dt * (a * du_new + b * du), u)
            v = lax.select(inside, v + dt * (a * dv_new + b * dv), v)
            h = lax.select(inside, h + dt * (a * dh_new + b * dh), h)

    h, token = exchange(h, "h", token)
    u, token = exchange(u, "u", token)
    v, token = exchange(v, "v", token)
    with _phase("ab2"):
        v = wall_v(v)

    # lateral friction (the reference's v-branch reads u in two stencils,
    # shallow_water.py:395-400 — reproduced here as v for correct physics;
    # flop/communication profile is identical).  As in the reference, no
    # exchange follows it: the state's ghosts of u and v are the exchange's
    # above, of the fields before friction
    nu = cfg.lateral_viscosity
    if nu > 0:

        def friction(w, name, token):
            with _phase("friction"):
                gx = lax.select(inside, nu * (_E(w) - w) / dx, zeros)
                gy = lax.select(inside, nu * (_N(w) - w) / dy, zeros)
            gx, token = exchange(gx, f"gx_{name}", token)
            gy, token = exchange(gy, f"gy_{name}", token)
            with _phase("friction"):
                w = lax.select(
                    inside,
                    w + dt * ((gx - _W(gx)) / dx + (gy - _S(gy)) / dy),
                    w,
                )
            return w, token

        u, token = friction(u, "u", token)
        v, token = friction(v, "v", token)
        with _phase("friction"):
            v = wall_v(v)

    return SWState(h, u, v, dh_new, du_new, dv_new), token


def _ring_view(a, r, dy=0, dx=0, *, G=2):
    """Ring-``r`` view of a ``(n + 2G)``-shaped block, shifted ``(dy, dx)``.

    Rows/cols within ``r`` rings of the interior, read at offset
    ``(dy, dx)`` — the wide-halo generalisation of the ``_i/_e/_w/_n/_s``
    helpers (those are the ``G=1, r=0`` cases).  Pure slicing: fuses into
    whatever consumes it.
    """
    y0 = G - r + dy
    x0 = G - r + dx
    return a[y0 : y0 + a.shape[0] - 2 * (G - r), x0 : x0 + a.shape[1] - 2 * (G - r)]


def _zero_wall_rows(a_r1, is_south, is_north, *, extra_north_interior=False):
    """Zero a ring-1 field's ghost rows on wall devices.

    Reproduces the narrow schedule exactly: intermediate fields are
    built on a zeros template and their wall-side ghost rows are never
    written by the (non-periodic) y exchange, so they are 0 there.
    ``extra_north_interior`` additionally zeroes the last interior row
    (the reference's ``wall_v`` on the northern flux, :401-402 there).
    """
    n = a_r1.shape[0]
    rows = lax.broadcasted_iota(jnp.int32, a_r1.shape, 0)
    kill = (is_south & (rows == 0)) | (is_north & (rows == n - 1))
    if extra_north_interior:
        kill = kill | (is_north & (rows == n - 2))
    return jnp.where(kill, jnp.zeros((), a_r1.dtype), a_r1)


def _wall_v_wide(v, is_north):
    """v = 0 on the northern wall row (the last interior row, ghost 2)."""
    return jnp.where(is_north, v.at[-3, :].set(0.0), v)


def _scatter_inside(a, inner, *, G=2):
    return a.at[G:-G, G:-G].add(inner)


def _add_inside(a, inner, *, G=2):
    """:func:`_scatter_inside` as a slice, a sum and a write in place,
    which is what XLA makes of that scatter, for the array code that is
    pushed forwards (:func:`_walk_as_arrays`): the scatter's own ``add``
    is an instruction of a nested computation, and inside a loop's body
    jax 0.9 names such an instruction without the scopes around it
    (``jvp()/add`` in a tangent sweep: ``tests/test_sw_tangent.py``), so
    a trace could not say whose it is.  The step's own array code keeps
    the scatter: its programs are what they were."""
    return lax.dynamic_update_slice(a, a[G:-G, G:-G] + inner, (G, G))


def _tendency_round(h, u, v, dh, du, dv, cfg, comm, is_south, is_north,
                    first_step, add=_scatter_inside):
    """Round 1 of :func:`_step_wide` as array code: the tendencies of
    ``h``, ``u`` and ``v`` (ghosts fresh) from fluxes, potential
    vorticity and kinetic energy recomputed one ring into the ghost
    region, the Adams-Bashforth update of the interior (forward Euler on
    the ``first_step``, which reads no old tendency), then ``v = 0`` on
    the northern wall row.  Tendencies are interior-shaped here.  The
    definition: what every backend but the TPU runs, and what
    :func:`sw_kernels.wide_step` is tested against.
    """
    G = 2
    V = _ring_view
    ny_l = h.shape[0] - 2 * G
    dx, dy, g = cfg.dx, cfg.dy, cfg.gravity
    dt = jnp.asarray(cfg.dt, h.dtype)

    # cell-centred height: narrow builds it by edge-padding the interior
    # and exchanging; here it is h with wall ghost rows clamped to the
    # adjacent interior row (interior + internal/periodic ghosts equal h)
    rows = lax.broadcasted_iota(jnp.int32, h.shape, 0)
    hc = jnp.where(is_south & (rows < G), h[G : G + 1, :], h)
    hc = jnp.where(
        is_north & (rows >= ny_l + G), h[ny_l + G - 1 : ny_l + G, :], hc
    )

    # --- ring-1 intermediates, all local ---
    fe = 0.5 * (V(hc, 1) + V(hc, 1, 0, 1)) * V(u, 1)
    fn = 0.5 * (V(hc, 1) + V(hc, 1, 1, 0)) * V(v, 1)
    fe = _zero_wall_rows(fe, is_south, is_north)
    fn = _zero_wall_rows(fn, is_south, is_north, extra_north_interior=True)

    dh_new = -(_i(fe) - _w(fe)) / dx - (_i(fn) - _s(fn)) / dy

    yy, _xx = _local_mesh_coords(cfg, comm)
    rel_vort = (V(v, 1, 0, 1) - V(v, 1)) / dx - (V(u, 1, 1, 0) - V(u, 1)) / dy
    q = (_coriolis(cfg, V(yy, 1)) + rel_vort) / (
        0.25 * (V(hc, 1) + V(hc, 1, 0, 1) + V(hc, 1, 1, 0) + V(hc, 1, 1, 1))
    )
    q = _zero_wall_rows(q, is_south, is_north)

    du_new = -g * (V(h, 0, 0, 1) - V(h, 0)) / dx + 0.5 * (
        _i(q) * 0.5 * (_i(fn) + _e(fn))
        + _s(q) * 0.5 * (_s(fn) + fn[:-2, 2:])
    )
    dv_new = -g * (V(h, 0, 1, 0) - V(h, 0)) / dy - 0.5 * (
        _i(q) * 0.5 * (_i(fe) + _n(fe))
        + _w(q) * 0.5 * (_w(fe) + fe[2:, :-2])
    )

    ke = 0.5 * (
        0.5 * (V(u, 1) ** 2 + V(u, 1, 0, -1) ** 2)
        + 0.5 * (V(v, 1) ** 2 + V(v, 1, -1, 0) ** 2)
    )
    ke = _zero_wall_rows(ke, is_south, is_north)
    du_new = du_new - (_e(ke) - _i(ke)) / dx
    dv_new = dv_new - (_n(ke) - _i(ke)) / dy

    # --- AB2 update (interior) ---
    if first_step:
        h = add(h, dt * dh_new)
        u = add(u, dt * du_new)
        v = add(v, dt * dv_new)
    else:
        a, b = cfg.ab_a, cfg.ab_b
        h = add(h, dt * (a * dh_new + b * dh))
        u = add(u, dt * (a * du_new + b * du))
        v = add(v, dt * (a * dv_new + b * dv))
    v = _wall_v_wide(v, is_north)
    return h, u, v, dh_new, du_new, dv_new


def _viscosity_round(u, v, cfg, is_south, is_north, add=_scatter_inside):
    """Round 2 of :func:`_step_wide` as array code: lateral friction of
    ``u`` and ``v`` (ghosts fresh) on the interior, then ``v = 0`` on the
    northern wall row.  The definition: what every backend but the TPU
    runs, and what :func:`sw_kernels.wide_step` is tested against.
    """
    G = 2
    V = _ring_view
    nu, dx, dy = cfg.lateral_viscosity, cfg.dx, cfg.dy
    dt = jnp.asarray(cfg.dt, u.dtype)

    def friction(a):
        gx = nu * (V(a, 1, 0, 1) - V(a, 1)) / dx
        gy = nu * (V(a, 1, 1, 0) - V(a, 1)) / dy
        gx = _zero_wall_rows(gx, is_south, is_north)
        gy = _zero_wall_rows(gy, is_south, is_north)
        return add(
            a, dt * ((_i(gx) - _w(gx)) / dx + (_i(gy) - _s(gy)) / dy))

    return friction(u), _wall_v_wide(friction(v), is_north)


def _runs_as_kernels(cfg, comm):
    """Whether :func:`_step_wide` after its first exchange is the Pallas
    kernel of :mod:`sw_kernels`: on TPU devices (a Mosaic kernel runs
    nowhere else), in float32 (the tiling's 8-row strips are float32's),
    on a block (one device's padded field) with at least one such strip
    whose tiles fit VMEM beside those of the step's other five arrays.
    (Such a block has four interior rows or more: the kernel counts on
    two, so that a neighbour's edge row is not its wall row as well.)
    Decided from what a step is built on, by who builds it, traces it or
    makes the state it will carry; anything else runs the array code,
    five exchanges and both rounds of it."""
    if cfg.ghost != 2:
        return False
    ny_l, nx_l = cfg.local_interior(comm)
    dtype = jnp.dtype(cfg.dtype)
    return (
        {d.platform for d in comm.mesh.devices.flat} == {"tpu"}
        and dtype == jnp.float32
        and sw_kernels.tile_rows(ny_l + 4, nx_l + 4, dtype, fields=6) > 0
    )


def _reaches_further(comm):
    """``(rows, columns)`` beyond its block's ring that a device needs
    of its neighbours for a walk of two steps: the ring's width again on
    an axis where it has a neighbour, nothing on an axis of one device,
    whose ghosts are the block's own other end (x) or a wall's (y)."""
    return tuple(sw_kernels.G * (n > 1) for n in comm.axis_sizes)


def _walks_two_steps(cfg, comm):
    """Whether a walk of the step's kernel advances two time steps
    (:func:`_step_wide`): where the step is the kernel, the second
    step's rings fit VMEM beside the first's blocks, and, on an axis
    with a neighbour, the block has room in VMEM for the two rings more
    that the walk then computes on (``sw_kernels.holds_further``: lanes
    past a row's last column, rows past the field's last in its last
    tile).  The second step's ghosts are the first step's results next
    to the block, and the kernel makes them itself: on an axis of one
    device from the block's own other end (periodic x) or not at all
    (walls), on an axis with a neighbour from slabs cut four deep of the
    fields and the tendencies, by the code that the neighbour runs on
    the same cells.  A block without that room walks one step."""
    ny_l, nx_l = cfg.local_interior(comm)
    return _runs_as_kernels(cfg, comm) and sw_kernels.holds_further(
        ny_l + 4, nx_l + 4, jnp.dtype(cfg.dtype), _reaches_further(comm))


def _kernels_ahead(cfg, comm):
    """Import Pallas before a step is traced, if the step will run the
    kernel (:func:`sw_kernels.pallas` says what that saves)."""
    if _runs_as_kernels(cfg, comm):
        sw_kernels.pallas()


def _step_wide(state, cfg, comm, *, first_step=False, token=None, steps=1,
               sums=(), coarsen=0, summing=True, read_whole=True):
    """Wide-halo (ghost=2) step: communicate prognostic fields only.

    The narrow schedule exchanges every intermediate field because a
    1-cell ghost ring can't support compound stencils (~12 exchanges per
    step — the reference's structure, shallow_water.py:277-412). With a
    2-cell ring, the fluxes, potential vorticity, kinetic energy, and
    viscosity gradients are all *recomputed locally* one ring into the
    ghost region from the exchanged ``h``/``u``/``v``, so a step is:

        round 1: exchange h, u, v   → all tendencies, AB2 update
        round 2: exchange u, v      → viscosity, wall condition

    5 thin exchanges instead of 12 (and 2 ordering rounds instead of
    12, which is what matters at scale: SURVEY §3.4 — per-exchange
    dispatch/launch latency dominates the reference's scaling).
    Numerically identical to the narrow path up to FMA/fusion roundoff
    (asserted at ~ulp tolerance by
    tests/test_shallow_water.py::test_wide_equals_narrow): the ~1%
    redundant ghost-ring flops ride along with already-loaded data.
    Tendencies are stored interior-shaped here (the ghost region of a
    tendency is never read).

    Where the step runs as the kernel of :mod:`sw_kernels`
    (:func:`_runs_as_kernels`) it is shorter still, on every mesh:

        slabs of h, u, v   → one kernel: the ghost writes, round 1 on
                             the interior and on ring 1 of u and v,
                             then round 2

    3 exchanges and 12 passes over a field a walk of the kernel, and no
    ghost written outside the kernel.  Where :func:`_walks_two_steps`
    holds, ``steps=2`` makes the one walk advance two time steps, so
    two steps are 12 passes and not 24 and the first step's results
    never reach HBM.  The second step has no exchange of its own: on a
    mesh of one device what it would bring is the block's own (periodic
    in x, a row's ghost columns are its other end, which the kernel
    sets between the steps; walls in y, nothing comes), and the walk
    starts from the same three ``halo_slabs_2d`` as a single one.  On
    an axis with a neighbour it would bring that chip's first step's
    results, which the kernel computes itself, two rings further out
    than its block, from **one exchange four cells deep of the fields
    and of the tendencies** (``halo_slabs_2d(depth=)`` of all six
    arrays, 24 permutes every second step where single walks send 12 a
    step; :mod:`sw_kernels`, "Two steps a walk").  The state that comes
    back is the one single steps return, bit for bit on the interior of
    all six arrays (on one device on the ghosts too; beside a neighbour
    the ghost cells of ``h`` and ring 2 of ``u``, ``v`` hold the kernel's
    own first step there, which the next exchange overwrites).  A first
    step is the same kernel with the first of its two steps passed over
    (``wide_step(lone=True)``; beside neighbours from the same deep
    slabs of ``h``, ``u``, ``v``, and zeros in place of the tendencies'),
    so that a process builds one kernel for ``make_first_step`` and
    ``make_multistep``; only an odd count's last step is a walk of one.
    ``halo_slabs_2d`` is the exchange without its last phase, and the
    kernel, which reads and writes every tile that holds
    a ghost cell anyway, stores the received slabs over the rows it
    takes in.  Round 1 reads one ring round
    a cell and the exchange brings two, so the kernel computes on ring 1
    the very ``u``, ``v`` a second exchange would bring, and the two
    rounds are one walk over the rows.  There the state carries its
    tendencies at the fields' padded shape, with ring 1 of ``du`` and
    ``dv`` holding the neighbours' (round 1 on ring 1 steps from them):
    hand a later step the tendencies a step returned.  A first step
    reads none, and takes either shape.

    ``read_whole`` says what a caller that differentiates may read of
    the state that comes back: every cell of it (what a user's
    ``jax.grad`` has to be given), or, where it is not set, nothing of
    the ghost cells that the next step's exchange overwrites
    (:func:`_window`'s steps: the next step and the misfit read a state).
    Their cotangents are then zeros by construction, and the backward
    step does not send them home (:func:`_step_backwards`).

    Returns ``((state, sums), token)``, ``sums`` empty but here:
    ``coarsen`` with ``sums`` (where the step is the kernel,
    :func:`_sums_in_step`; the room :func:`make_sums_room` makes, as a
    step before left it): the step runs the kernel that can also write
    the sums over ``coarsen`` rows of the new ``h``, ``u``, ``v`` into
    ``sums``, does so where ``summing`` (traced) is set, and hands the
    room back either way; where it is not set the sums are nobody's and
    cost nothing.  A job's programs ask for the one kernel in every
    step, so that a process builds one.
    """
    if not cfg.periodic_x:
        raise NotImplementedError(
            "wide-halo schedule currently requires periodic_x=True "
            "(x-boundary clamps are not implemented); use ghost=1"
        )
    token = as_token(token)
    h, dh = state[0], state[3]

    if _runs_as_kernels(cfg, comm):
        if not first_step and dh.shape != h.shape:
            raise ValueError(
                f"tendencies of shape {dh.shape} beside fields of shape "
                f"{h.shape}: where the step runs as a kernel its state "
                "carries them padded, as make_init and make_first_step "
                "return them")
        walk = partial(_kernel_walk, cfg=cfg, comm=comm, first_step=first_step,
                       steps=steps, coarsen=coarsen)
        if coarsen:  # a job's walk, with its sums: nobody's derivative
            return walk(state, token, tuple(sums), summing)

        def forward(state, token):
            (state, _), token = walk(state, token, (), True)
            return state, token

        # a run's first step reads no tendency: the caller's are no
        # operand of the derivative, and a program nobody differentiates
        # drops them as unused, as it did
        operands = SWState(
            *state[:3], *((None,) * 3 if first_step else state[3:])), token
        scope = f"{ADJOINT_SCOPE}/{STEP_VJP}"
        how = dict(cfg=cfg, comm=comm, first_step=first_step, steps=steps)
        if _derives_as_kernels(cfg, comm):
            # the derivative of a walk is a kernel's in either mode, a
            # step at a time, at the fields the step started from
            how = dict(how, read_whole=read_whole)
            state, token = _with_derivative(
                forward,
                partial(_kept_for_the_kernel, first_step=first_step, steps=steps),
                partial(_walk_forwards, **how), partial(_walk_backwards, **how),
                scope)(*operands)
        else:
            # no room for those kernels' blocks: the derivative is that
            # of the array code of the walk's steps, at the state the
            # walk started from
            state, token = _kept_at_its_start(
                forward, partial(_walk_as_arrays, **how), scope)(*operands)
        return (state, ()), token

    return _step_wide_arrays(state, cfg, comm, first_step, token)


def _step_wide_arrays(state, cfg, comm, first_step, token,
                      add=_scatter_inside):
    """:func:`_step_wide` as array code: five exchanges and both rounds
    (plain jax, which differentiates it by its own rules).  Returns
    ``((state, ()), token)``.  ``add`` adds a round's update into a
    block's interior (:func:`_add_inside` where the code is pushed
    forwards)."""
    G = 2
    per = (False, True)
    is_north, is_south = _wall_masks(comm)
    h, u, v, dh, du, dv = state

    # --- round 1: refresh prognostic ghosts (2-deep, corners valid) ---
    h, token = halo_exchange_2d(h, comm, periodic=per, token=token, width=G)
    u, token = halo_exchange_2d(u, comm, periodic=per, token=token, width=G)
    v, token = halo_exchange_2d(v, comm, periodic=per, token=token, width=G)

    h, u, v, dh, du, dv = _tendency_round(
        h, u, v, dh, du, dv, cfg, comm, is_south, is_north, first_step, add)

    # --- round 2: refresh u/v ghosts for the viscosity stencils ---
    if cfg.lateral_viscosity > 0:
        u, token = halo_exchange_2d(u, comm, periodic=per, token=token, width=G)
        v, token = halo_exchange_2d(v, comm, periodic=per, token=token, width=G)
        u, v = _viscosity_round(u, v, cfg, is_south, is_north, add)

    return (SWState(h, u, v, dh, du, dv), ()), token


def _kernel_walk(state, token, sums, summing, *, cfg, comm, first_step, steps,
                 coarsen, in_place=True):
    """:func:`_step_wide` where the step is the kernel: the exchange
    without its ghost writes, then the kernel, which reads and writes
    every tile that holds a ghost cell anyway and places the received
    slabs itself.  Returns ``((state, sums), token)``.  ``in_place``:
    ``sw_kernels.wide_step``'s, not set where ``state`` is read again."""
    G = 2
    per = (False, True)
    ny_l, _nx_l = cfg.local_interior(comm)
    is_north, is_south = _wall_masks(comm)
    h, u, v, dh, du, dv = state
    # where the rest of a run walks two steps at a time, its first
    # step goes through that kernel too, the first of the two passed
    # over: one kernel a process, traced once
    lone = first_step and _walks_two_steps(cfg, comm)
    further = _reaches_further(comm) if lone or steps == 2 else (0, 0)
    if any(further):
        # a walk of two steps beside neighbours: their four columns
        # and rows next to the block, of the fields and (the first
        # step's update out there reads them) of the tendencies,
        # of which a run's first step has none to send
        slabs, token = halo_slabs_2d(
            [h, u, v] if first_step else list(state), comm, periodic=per,
            token=token, width=G, depth=tuple(G + n for n in further))
        if first_step:
            slabs += [jax.tree.map(jnp.zeros_like, slabs[0])] * 3
        slabs = tuple(slabs)
    else:
        for_h, token = halo_slabs_2d(h, comm, periodic=per, token=token, width=G)
        for_u, token = halo_slabs_2d(u, comm, periodic=per, token=token, width=G)
        for_v, token = halo_slabs_2d(v, comm, periodic=per, token=token, width=G)
        slabs = for_h, for_u, for_v
    if first_step:
        # forward Euler is AB2 with (1, 0) on zero tendencies, and the
        # caller's, which this step does not read, may be of either shape
        a, b = 1.0, 0.0
        dh = du = dv = jnp.zeros_like(h)
    else:
        a, b = cfg.ab_a, cfg.ab_b
    iy, _ix = _device_coords(comm)
    *state, = sw_kernels.wide_step(
        h, u, v, dh, du, dv, slabs, is_south, is_north,
        iy * ny_l, a, b, lone, *([summing, tuple(sums)] if coarsen else []),
        nu=cfg.lateral_viscosity, dx=cfg.dx, dy=cfg.dy, dt=cfg.dt,
        gravity=cfg.gravity, coriolis_f=cfg.coriolis_f,
        coriolis_beta=cfg.coriolis_beta, steps=2 if lone else steps,
        coarsen=coarsen, in_place=in_place)
    return (SWState(*state[:6]), tuple(state[6:])), token


# The differentiated run's phases, jax.named_scope segments
# ``sw/adjoint/<phase>`` (docs/observability.md): the window run forwards
# (`forward`), a call's steps run again with every state kept
# (`recompute`), one step's derivative (`step_vjp`: the array code of
# the step run at a kept state and then backwards, its exchanges the
# adjoint exchange of parallel/halo.py), the misfit (`cost`), the
# descent step or a conjugate-gradient iteration's vector updates
# (`update`) and the tangent-linear sweep (`tangent`: forward mode
# through the window's steps and every exchange in them).  In a backward
# sweep jax wraps what it transposes in ``transpose(jvp(...))``, in a
# tangent sweep what it pushes forwards in ``jvp(...)``: the innermost
# ``sw/adjoint/<phase>`` of an ``op_name`` says whose an instruction is.
ADJOINT_SCOPE = f"{STEP_SCOPE}/adjoint"
FORWARD, RECOMPUTE, STEP_VJP, COST, UPDATE, TANGENT = (
    "forward", "recompute", "step_vjp", "cost", "update", "tangent")


def _adjoint_scope(phase):
    return jax.named_scope(f"{ADJOINT_SCOPE}/{phase}")


def _with_derivative(forward, keep, tangent, backward, scope):
    """``forward`` (operands -> results) as a function whose derivative
    is written out in both modes (``ops/_core.py both_modes``):
    backwards ``backward(keep(*operands), cotangents)``, run under
    ``scope``; forwards ``tangent(keep(*operands), *tangents)``.  What
    ``keep`` returns is all that is held for either."""
    def under_scope(held, cotangents):
        with jax.named_scope(scope):
            return backward(held, cotangents)

    return both_modes(forward, keep, tangent, under_scope)


def _kept_at_its_start(forward, twin, scope):
    """``forward`` (operands -> results) as a function whose derivative
    is ``twin``'s, taken where the call started: the one thing kept is
    the call's operands, the backward pass runs ``twin`` there forwards
    and backwards, under ``scope``, and a tangent is ``jax.jvp`` of
    ``twin`` there.  With ``twin`` the function itself this is
    ``jax.checkpoint`` with a name on its backward half; with another
    program of the same function (a kernel's array code; a call's steps
    one by one where ``forward`` walks two at a time) it is how that
    function gets a derivative or a cheaper one to keep."""
    def tangent(operands, *tangents):
        return jax.jvp(twin, operands, tangents)[1]

    def backward(operands, cotangents):
        _, vjp = jax.vjp(twin, *operands)
        return vjp(cotangents)

    return _with_derivative(
        forward, lambda *operands: operands, tangent, backward, scope)


def _derives_as_kernels(cfg, comm):
    """Whether the derivative of a step that runs as the kernel is a
    kernel's too, in both modes (``sw_kernels.wide_step_vjp`` backwards,
    ``sw_kernels.wide_step_jvp`` forwards): where their blocks, nine
    arrays in and six out either way, fit VMEM.  From the block's shape
    and dtype, as :func:`_runs_as_kernels` decides; a block without that
    room is differentiated as its array code (:func:`_walk_as_arrays`)."""
    ny_l, nx_l = cfg.local_interior(comm)
    return _runs_as_kernels(cfg, comm) and sw_kernels.adjoint_tile_rows(
        ny_l + 4, nx_l + 4, jnp.dtype(cfg.dtype)) > 0


def _kept_for_the_kernel(state, token, *, first_step, steps):
    """What a walk keeps for :func:`_walk_backwards` and
    :func:`_walk_forwards`: the fields it started from, which is all
    either kernel reads of a state (the old tendencies enter a step
    linearly); a walk of two steps keeps its tendencies too, for the
    state between the two."""
    if steps == 2 and not first_step:
        return state, token
    return SWState(*state[:3], None, None, None), token


def _walk_forwards(kept, state, token, *, cfg, comm, first_step, steps,
                   read_whole):
    """The tangent of :func:`_walk_as_arrays` where the step's
    derivative is a kernel (:func:`_derives_as_kernels`), the mirror of
    :func:`_walk_backwards`: for each of the walk's steps, first to
    last, :func:`_step_forwards` at the fields that step started from,
    on the tangents ``state`` and ``token`` of what the walk read.
    Those of a walk's second step are not kept: the forward kernel makes
    them again, one walk of one step.  ``read_whole``:
    :func:`_step_wide`'s, of the walk's last step; the state between two
    steps of a walk nobody reads."""
    at, stamp = kept
    push = partial(_step_forwards, cfg=cfg, comm=comm)
    if first_step:
        return push(at[:3], stamp, state[:3], token, first_step=True,
                    read_whole=read_whole)
    if steps == 2:
        state, token = push(at[:3], stamp, state, token, read_whole=False)
        # (not in place: the walk of two, whose tangent this is, reads
        # the same state)
        (at, _), stamp = _kernel_walk(
            at, stamp, (), True, cfg=cfg, comm=comm, first_step=False,
            steps=1, coarsen=0, in_place=False)
    return push(at[:3], stamp, state, token, read_whole=read_whole)


def _walk_backwards(kept, cotangents, *, cfg, comm, first_step, steps,
                    read_whole):
    """The transpose of :func:`_walk_as_arrays` where the step's
    derivative is a kernel (:func:`_derives_as_kernels`): for each of
    the walk's steps, last to first, :func:`_step_backwards` at the
    fields that step started from.  Those of a walk's second step are
    not kept: the forward kernel makes them again, one walk of one
    step.  ``read_whole``: :func:`_step_wide`'s, of the walk's last
    step; the state between two steps of a walk nobody reads."""
    state, token = kept
    ct, ct_token = cotangents
    back = partial(_step_backwards, ct_token=ct_token, cfg=cfg, comm=comm)
    if first_step:
        ct = back(state[:3], token, ct, first_step=True, read_whole=read_whole)
        return SWState(*ct[:3], None, None, None), _no_stamp(token)
    if steps == 2:
        (between, _), after = _kernel_walk(
            state, token, (), True, cfg=cfg, comm=comm, first_step=False,
            steps=1, coarsen=0)
        ct = back(between[:3], after, ct, read_whole=read_whole)
        read_whole = False
    return SWState(*back(state[:3], token, ct, read_whole=read_whole)), _no_stamp(token)


def _outermost_ring(x, ct, scale, is_south, is_north, v_is_zero=False,
                    added=False):
    """``x`` with its ghost frame set for the exchange's transpose to
    carry ring 2 of ``ct`` home, ``scale`` times: ring 2 of ``x`` is
    that, ring 1 zero, a wall's ghost rows zero (``v_is_zero``: and the
    northern wall's own row, where ``v`` is set to zero and its
    cotangent says nothing).  Four slabs written, the columns' before
    the rows', as an exchange writes them.  ``added``: the same cells
    of ``ct``, ``scale`` times, added to ``x``'s and its ghost frame
    otherwise as it is, which is this map's transpose (``ct`` then a
    block an exchange has just filled: :func:`_step_forwards`)."""
    G = 2
    rows, width = ct.shape
    as_it_came = x
    for region, at in ((np.s_[:, :G], (0, 0)), (np.s_[:, -G:], (0, width - G)),
                       (np.s_[:G, :], (0, 0)), (np.s_[-G:, :], (rows - G, 0))):
        slab = ct[region]
        r = lax.broadcasted_iota(jnp.int32, slab.shape, 0) + at[0]
        c = lax.broadcasted_iota(jnp.int32, slab.shape, 1) + at[1]
        outermost = (r == 0) | (r == rows - 1) | (c == 0) | (c == width - 1)
        walled = (is_south & (r < G)) | (
            is_north & (r >= rows - G - int(v_is_zero)))
        slab = jnp.where(outermost & ~walled, slab * jnp.asarray(scale, slab.dtype),
                         jnp.zeros((), slab.dtype))
        if added:
            slab = as_it_came[region] + slab
        x = lax.dynamic_update_slice(x, slab, at)
    return x


def _no_stamp(token):
    """A token's cotangent: a stamp says nothing (``parallel/halo.py
    _transposable``)."""
    return jax.tree.map(jnp.zeros_like, token)


def _step_backwards(fields, token, cotangents, *, ct_token, cfg, comm,
                    read_whole, first_step=False):
    """One step of the kernel's walk transposed: the six cotangents of
    a step's results to the six of the state it read, ``fields`` being
    the ``h``, ``u``, ``v`` it started from.

    The step is its first exchange and then the kernel's schedule
    (round 1 on ring 1 of ``u``, ``v`` too, round 2 after it with no
    exchange between): the same function of the mesh's interiors as the
    array code's five exchanges and two rounds, so the same derivative.
    Backwards: ``sw_kernels.wide_step_vjp`` at the fields with fresh
    ghosts hands back the cotangents of those padded fields, ghost cells
    included, and **the exchange's own transpose** (``jax.vjp`` of
    ``halo_exchange_2d``, which is linear: ``parallel/halo.py
    _adjoint``) carries the ghost cells' home to the edges they were
    copied from: one adjoint exchange a field.  Two more go the same
    way: ring 1 of the old ``du``, ``dv`` is the neighbours'
    (``sw_kernels``, "Schedule"), and what the kernel hands back there
    is theirs.  The old ``dh``'s is the interior's alone.

    The results' ghost cells.  The kernel takes the cotangents of the
    interior and of every ghost cell that the step passed through as
    they are: all of ``h``'s, a wall's ghost rows of ``u``, ``v``,
    without friction all of theirs (they go home with the fields').
    With friction the ghost cells of the results ``u``, ``v`` are what
    the array code's second exchange brought, the neighbours' round 1:
    ring 1 is this walk's own round 1 there and the kernel transposes it
    in place; ring 2 only a neighbour computed, so its cotangent goes
    home before the kernel, ``a dt`` of it on the outermost ring of the
    new ``du``, ``dv``'s cotangents (:func:`_outermost_ring`; the
    kernel's own pass-through carries the rest, to the fields and to
    the old tendencies).  Only where ``read_whole``: a state nobody
    reads whole hands zeros there (:func:`_step_wide`)."""
    G = 2
    ny_l, _nx_l = cfg.local_interior(comm)
    is_north, is_south = _wall_masks(comm)
    iy, _ix = _device_coords(comm)

    def exchange(x, token):
        return halo_exchange_2d(
            x, comm, periodic=(False, True), token=token, width=G)

    fresh, home = [], []
    for x in fields:
        (x, token), transposed = jax.vjp(exchange, x, token)
        fresh.append(x)
        home.append(lambda ct, transposed=transposed: transposed((ct, ct_token))[0])
    _, to_u, to_v = home
    ch, cu, cv, cdh, cdu, cdv = cotangents
    a, b = (1.0, 0.0) if first_step else (cfg.ab_a, cfg.ab_b)
    if cfg.lateral_viscosity > 0 and read_whole:
        rest = (a * cfg.dt, is_south, is_north)
        cdu = to_u(_outermost_ring(cdu, cu, *rest))
        cdv = to_v(_outermost_ring(cdv, cv, *rest, v_is_zero=True))
    ch, cu, cv, cdh, cdu, cdv = sw_kernels.wide_step_vjp(
        *fresh, (ch, cu, cv, cdh, cdu, cdv), is_south, is_north, iy * ny_l, a, b,
        nu=cfg.lateral_viscosity, dx=cfg.dx, dy=cfg.dy, dt=cfg.dt,
        gravity=cfg.gravity, coriolis_f=cfg.coriolis_f,
        coriolis_beta=cfg.coriolis_beta)
    ch, cu, cv = (send(ct) for send, ct in zip(home, (ch, cu, cv)))
    return ch, cu, cv, cdh, to_u(cdu), to_v(cdv)


def _step_forwards(fields, token, tangents, t_token, *, cfg, comm,
                   read_whole, first_step=False):
    """One step of the kernel's walk pushed forwards, the mirror of
    :func:`_step_backwards`: the six tangents of the state a step read
    (a first step's: the three fields') to ``(the six of its results,
    the token's)``, ``fields`` being the ``h``, ``u``, ``v`` it started
    from.

    The exchange's tangent is the exchange of the tangents
    (``jax.linearize`` of ``halo_exchange_2d``, which is linear:
    ``parallel/halo.py _transposable``): the kept fields get fresh
    ghosts and the three field tangents theirs, one exchange each, and
    so do the old ``du``, ``dv``'s, whose ring 1 the kernel's round 1
    reads (the neighbours'; the old ``dh``'s is the interior's alone).
    Five exchanges of tangents, as :func:`_step_backwards` transposes
    five, then ``sw_kernels.wide_step_jvp``.  Where the state is read
    whole (:func:`_step_wide`) and there is friction, the results ``u``,
    ``v`` on ring 2 are the neighbours' round 1, of which ``a dt`` of
    the new tendency's tangent is missing after the kernel: one exchange
    more of the new ``du``, ``dv``'s tangents each, whose ring 2 is
    added there (:func:`_outermost_ring`)."""
    G = 2
    ny_l, _nx_l = cfg.local_interior(comm)
    is_north, is_south = _wall_masks(comm)
    iy, _ix = _device_coords(comm)

    def exchange(x, token):
        return halo_exchange_2d(
            x, comm, periodic=(False, True), token=token, width=G)

    fresh, there = [], []
    for x in fields:
        (x, token), pushed = jax.linearize(exchange, x, token)
        fresh.append(x)
        there.append(pushed)

    def exchanged(pushes, arrays, t_token):
        out = []
        for pushed, x in zip(pushes, arrays):
            x, t_token = pushed(x, t_token)
            out.append(x)
        return out, t_token

    (th, tu, tv), t_token = exchanged(there, tangents[:3], t_token)
    if first_step:
        a, b = 1.0, 0.0
        tdh = tdu = tdv = jnp.zeros_like(th)
    else:
        a, b = cfg.ab_a, cfg.ab_b
        tdh = tangents[3]
        (tdu, tdv), t_token = exchanged(there[1:], tangents[4:], t_token)
    th, tu, tv, tdh, tdu, tdv = sw_kernels.wide_step_jvp(
        *fresh, (th, tu, tv, tdh, tdu, tdv), is_south, is_north, iy * ny_l, a, b,
        nu=cfg.lateral_viscosity, dx=cfg.dx, dy=cfg.dy, dt=cfg.dt,
        gravity=cfg.gravity, coriolis_f=cfg.coriolis_f,
        coriolis_beta=cfg.coriolis_beta)
    if cfg.lateral_viscosity > 0 and read_whole:
        rest = (a * cfg.dt, is_south, is_north)
        (theirs_u, theirs_v), t_token = exchanged(there[1:], (tdu, tdv), t_token)
        tu = _outermost_ring(tu, theirs_u, *rest, added=True)
        tv = _outermost_ring(tv, theirs_v, *rest, v_is_zero=True, added=True)
    return SWState(th, tu, tv, tdh, tdu, tdv), t_token


def _walk_as_arrays(state, token, *, cfg, comm, first_step, steps):
    """What a walk of the step's kernel computes (:func:`_kernel_walk`:
    ``steps`` steps, a first step always one) as the array code of
    :func:`_step_wide_arrays` on the kernel's state, whose tendencies
    are padded: the interior of each goes in, and comes back with a ring
    of zeros.  The same function of a block's interior and of a wall's
    ghost rows as the kernel's, to roundoff
    (``tests/test_sw_kernels*.py``), which is what a derivative is
    taken of; the ghost cells a kernel leaves behind (the neighbours'
    tendencies on ring 1 of ``du``, ``dv``, the first step's results
    beyond the block) are its own means to that end and carry none."""
    G = 2
    h = state[0]
    state = SWState(*state[:3], *(
        a if a is None or a.shape != h.shape else a[G:-G, G:-G]
        for a in state[3:]))
    for _ in range(1 if first_step else steps):
        (state, _), token = _step_wide_arrays(
            state, cfg, comm, first_step, token, _add_inside)
    return SWState(*state[:3], *(jnp.pad(a, G) for a in state[3:])), token


def _step_wide4(state, cfg, comm, *, first_step=False, token=None):
    """Single-exchange (ghost=4) step: one batched halo round per step.

    Extends the wide-halo recompute (:func:`_step_wide`) so the whole
    step — including the post-update viscosity, which in the reference
    reads *updated* velocities with refreshed ghosts
    (shallow_water.py:384-400 there) — is local after a single 4-deep
    batched exchange of ``(h, u, v)``:

        exchange h,u,v (width 4, one ppermute per direction for all 3)
        ring-3: fluxes, potential vorticity, kinetic energy
        ring-2: tendencies, AB2 update of h/u/v
        ring-1: viscosity gradients of the *locally updated* u/v
        interior: viscosity divergence

    Tendencies are stored full-shape, valid on ring-2, and are never
    communicated: each step recomputes them on ring-2 from the freshly
    exchanged prognostics, so validity is maintained inductively.
    On dispatch-latency-bound runtimes this schedule's win is op count:
    4 permutes + 1 round per step vs the narrow schedule's ~48 permutes
    in 12 rounds.  Numerically identical to the other schedules
    (tests/test_shallow_water.py::test_wide4_equals_narrow).
    """
    G = 4
    if not cfg.periodic_x:
        raise NotImplementedError(
            "single-exchange schedule requires periodic_x=True; use ghost=1"
        )
    ny_l, nx_l = cfg.local_interior(comm)
    if ny_l < G or nx_l < G:
        raise ValueError(
            f"ghost=4 needs local blocks >= 4x4, got {ny_l}x{nx_l}"
        )
    token = as_token(token)
    is_north, is_south = _wall_masks(comm)
    dx, dy, g = cfg.dx, cfg.dy, cfg.gravity

    h, u, v, dh, du, dv = state
    dt = jnp.asarray(cfg.dt, h.dtype)

    # --- the step's only exchange round ---
    (h, u, v), token = halo_exchange_2d_batch(
        [h, u, v], comm, periodic=(False, True), token=token, width=G
    )

    rows = lax.broadcasted_iota(jnp.int32, h.shape, 0)
    # cell-centred height: wall ghost rows clamped (edge-pad semantics)
    hc = jnp.where(is_south & (rows < G), h[G : G + 1, :], h)
    hc = jnp.where(
        is_north & (rows >= ny_l + G), h[ny_l + G - 1 : ny_l + G, :], hc
    )

    V = _ring_view

    def grow(shape, ring):
        """Global array-row index of each element of a ring-r field."""
        return (G - ring) + lax.broadcasted_iota(jnp.int32, shape, 0)

    def zero_wall(a, ring, extra_north_interior=False):
        gr = grow(a.shape, ring)
        kill = (is_south & (gr < G)) | (is_north & (gr >= ny_l + G))
        if extra_north_interior:
            kill = kill | (is_north & (gr == ny_l + G - 1))
        return jnp.where(kill, jnp.zeros((), a.dtype), a)

    # --- ring-3 intermediates, all local ---
    fe = 0.5 * (V(hc, 3, G=G) + V(hc, 3, 0, 1, G=G)) * V(u, 3, G=G)
    fn = 0.5 * (V(hc, 3, G=G) + V(hc, 3, 1, 0, G=G)) * V(v, 3, G=G)
    fe = zero_wall(fe, 3)
    fn = zero_wall(fn, 3, extra_north_interior=True)

    yy, _xx = _local_mesh_coords(cfg, comm)
    rel_vort = (V(v, 3, 0, 1, G=G) - V(v, 3, G=G)) / dx - (
        V(u, 3, 1, 0, G=G) - V(u, 3, G=G)
    ) / dy
    q = (_coriolis(cfg, V(yy, 3, G=G)) + rel_vort) / (
        0.25
        * (
            V(hc, 3, G=G)
            + V(hc, 3, 0, 1, G=G)
            + V(hc, 3, 1, 0, G=G)
            + V(hc, 3, 1, 1, G=G)
        )
    )
    q = zero_wall(q, 3)

    ke = 0.5 * (
        0.5 * (V(u, 3, G=G) ** 2 + V(u, 3, 0, -1, G=G) ** 2)
        + 0.5 * (V(v, 3, G=G) ** 2 + V(v, 3, -1, 0, G=G) ** 2)
    )
    ke = zero_wall(ke, 3)

    # --- ring-2 tendencies (ring-2 views of the ring-3 fields) ---
    def R2(a, dyr=0, dxr=0):
        return _ring_view(a, 2, dyr, dxr, G=3)

    dh_new = -(R2(fe) - R2(fe, 0, -1)) / dx - (R2(fn) - R2(fn, -1, 0)) / dy
    du_new = -g * (V(h, 2, 0, 1, G=G) - V(h, 2, G=G)) / dx + 0.5 * (
        R2(q) * 0.5 * (R2(fn) + R2(fn, 0, 1))
        + R2(q, -1, 0) * 0.5 * (R2(fn, -1, 0) + R2(fn, -1, 1))
    )
    dv_new = -g * (V(h, 2, 1, 0, G=G) - V(h, 2, G=G)) / dy - 0.5 * (
        R2(q) * 0.5 * (R2(fe) + R2(fe, 1, 0))
        + R2(q, 0, -1) * 0.5 * (R2(fe, 0, -1) + R2(fe, 1, -1))
    )
    du_new = du_new - (R2(ke, 0, 1) - R2(ke)) / dx
    dv_new = dv_new - (R2(ke, 1, 0) - R2(ke)) / dy

    # --- AB2 update on ring-2 (wall devices freeze beyond-wall rows) ---
    def R2full(a):
        return _ring_view(a, 2, G=G)

    if first_step:
        h2 = R2full(h) + dt * dh_new
        u2 = R2full(u) + dt * du_new
        v2 = R2full(v) + dt * dv_new
    else:
        a_, b_ = cfg.ab_a, cfg.ab_b
        h2 = R2full(h) + dt * (a_ * dh_new + b_ * R2full(dh))
        u2 = R2full(u) + dt * (a_ * du_new + b_ * R2full(du))
        v2 = R2full(v) + dt * (a_ * dv_new + b_ * R2full(dv))

    gr2 = grow(h2.shape, 2)
    frozen = (is_south & (gr2 < G)) | (is_north & (gr2 >= ny_l + G))
    h2 = jnp.where(frozen, R2full(h), h2)
    u2 = jnp.where(frozen, R2full(u), u2)
    v2 = jnp.where(frozen, R2full(v), v2)
    # v = 0 on the northern wall row (last interior row)
    wall_row = is_north & (gr2 == ny_l + G - 1)
    v2 = jnp.where(wall_row, jnp.zeros((), v2.dtype), v2)

    # --- viscosity on the locally recomputed ring-2 velocities ---
    nu = cfg.lateral_viscosity
    if nu > 0:

        def visc_div(w2):
            gx = nu * (V(w2, 1, 0, 1, G=2) - V(w2, 1, G=2)) / dx
            gy = nu * (V(w2, 1, 1, 0, G=2) - V(w2, 1, G=2)) / dy
            gx = zero_wall(gx, 1)
            gy = zero_wall(gy, 1)
            return (V(gx, 0, G=1) - V(gx, 0, 0, -1, G=1)) / dx + (
                V(gy, 0, G=1) - V(gy, 0, -1, 0, G=1)
            ) / dy

        u2 = u2 + jnp.pad(dt * visc_div(u2), 2)
        v2 = v2 + jnp.pad(dt * visc_div(v2), 2)
        v2 = jnp.where(wall_row, jnp.zeros((), v2.dtype), v2)

    # --- one store per field ---
    h = h.at[2:-2, 2:-2].set(h2)
    u = u.at[2:-2, 2:-2].set(u2)
    v = v.at[2:-2, 2:-2].set(v2)
    dh = dh.at[2:-2, 2:-2].set(dh_new)
    du = du.at[2:-2, 2:-2].set(du_new)
    dv = dv.at[2:-2, 2:-2].set(dv_new)

    return SWState(h, u, v, dh, du, dv), token


def _mesh_specs(comm):
    spec = jax.P(*comm.axes)
    return SWState(*([spec] * 6))


def _call_of_steps(state, sums=(), *, cfg, comm, num_steps, coarsen=0,
                   read_whole=True):
    """One device's part of :func:`make_multistep`: ``num_steps`` steps
    in one loop, two a walk where the kernel can
    (:func:`_walks_two_steps`), an odd count's last a single step's
    walk after the loop.  With ``coarsen`` (a job's, where the step
    writes the snapshot's sums) the call's last walk stands apart and
    writes them into ``sums``, and ``(state, sums)`` comes back; without
    it the state does.  :func:`make_gradient`'s forward sweep runs its
    calls through this too: what is differentiated is what is timed.
    ``read_whole``: :func:`_step_wide`'s, of the walks of two in the
    loop (a derivative's business alone: the steps are the same)."""
    # steps a walk of the kernel (``_step_wide``)
    stride = 2 if _walks_two_steps(cfg, comm) else 1
    # the walks of a call, the last one apart where it writes the sums
    # (an odd count's is a single step's walk)
    last = num_steps % stride or stride
    looped = (num_steps - last * bool(coarsen)) // stride

    def body(_, carry):
        s, sums = carry
        if coarsen:  # the kernel the last walk runs, its sums off
            (s, sums), _tok = _step_wide(
                s, cfg, comm, steps=stride, sums=sums, coarsen=coarsen,
                summing=False)
        elif stride == 1:
            s, _tok = shallow_water_step(s, cfg, comm)
        else:
            (s, _), _tok = _step_wide(
                s, cfg, comm, steps=stride, read_whole=read_whole)
        return s, sums

    if looped:
        state, sums = lax.fori_loop(0, looped, body, (state, sums))
    if coarsen:
        return _step_wide(
            state, cfg, comm, steps=last, sums=sums, coarsen=coarsen)[0]
    if num_steps % stride:
        state, _tok = shallow_water_step(state, cfg, comm)
    return state


def make_multistep(cfg, comm, num_steps, *, donate=False, snapshot=None):
    """Jitted global function advancing the model ``num_steps`` steps —
    the reference's ``do_multistep`` (shallow_water.py:415-420): the whole
    loop is one XLA executable.

    ``donate=True`` donates the input state's buffers (in-place update;
    the passed-in state is consumed).  Saves one full state copy per
    call — use it for ``state = multi(state)``-style driver loops.

    ``snapshot`` (a :class:`Snapshot`; a job's): where the step is the
    kernel and the snapshot's blocks are made of its strips' rows
    (:func:`_sums_in_step`), the function is ``(state, sums) ->
    (state, sums)``: the call's last walk is taken out of the loop and
    writes, beside the state, the sums over ``coarsen`` rows of ``h``,
    ``u`` and ``v``, in that order, which ``make_snapshot(from_sums=
    True)`` finishes, **into the room it is handed**
    (:func:`make_sums_room`'s, or what the call before returned;
    ``donate`` donates it with the state, so that a call allocates
    nothing).  The walks in the loop run the same kernel with its sums
    switched off by a scalar (they write none and do none of that
    work), so that the program holds one kernel text and a process that
    has built ``make_first_step(snapshot=)`` builds nothing new.
    Anywhere else the function is ``state -> state``, whatever
    ``snapshot`` is.
    """

    coarsen = snapshot.coarsen if _sums_in_step(cfg, comm, snapshot) else 0

    def local_fn(state, sums=()):
        return _call_of_steps(state, sums, cfg=cfg, comm=comm,
                              num_steps=num_steps, coarsen=coarsen)

    _kernels_ahead(cfg, comm)
    specs = _mesh_specs(comm)
    if coarsen:
        specs = (specs, (jax.P(*comm.axes),) * 3)
    return jax.jit(
        jax.shard_map(
            local_fn, mesh=comm.mesh,
            in_specs=specs if coarsen else (specs,), out_specs=specs
        ),
        # the state, and with it the room for the sums
        donate_argnums=((0, 1) if coarsen else (0,)) if donate else (),
    )


def make_init(cfg, comm):
    """Jitted global initial-condition builder (returns sharded SWState)."""

    def local_fn():
        state, _tok = initial_state(cfg, comm)
        return state

    specs = _mesh_specs(comm)
    return jax.jit(
        jax.shard_map(local_fn, mesh=comm.mesh, in_specs=(), out_specs=specs)
    )


def make_state(cfg, comm):
    """Jitted global function ``(h, u, v) -> SWState``: a caller's own
    fields as the state :func:`make_first_step` takes, where
    :func:`make_init` builds the demo's jet.

    ``h``, ``u``, ``v`` are the domain's interior cells, ``(cfg.ny,
    cfg.nx)`` each, sharded over the mesh or not.  Each device pads its
    block with ``cfg.ghost`` cells, a wall's ghost rows holding its edge
    row, fills the rest of the ring by the library's own exchange, and
    adds zero tendencies in the form the schedule carries: at the
    fields' padded shape for ``ghost`` 1 and 4 and where the ``ghost`` 2
    step runs as the kernel, at the interior's where it runs as array
    code.  A caller need not
    know which: :meth:`SolverJob.form` says, and ``make_init`` and this
    share the code that decides.  The state comes back in the layout
    the steps return: at ``ghost`` 1 upstream's arrays, ``(ny + 2,
    nx + 2)`` a device.
    """

    spec = jax.P(*comm.axes)
    return jax.jit(
        jax.shard_map(partial(_state_of_fields, cfg=cfg, comm=comm),
                      mesh=comm.mesh, in_specs=(spec,) * 3,
                      out_specs=_mesh_specs(comm))
    )


def _state_of_fields(h, u, v, *, cfg, comm):
    """One device's part of :func:`make_state`: its block of each field
    padded, a wall's ghost rows its edge row's, the ring exchanged."""
    G = cfg.ghost
    interior = cfg.local_interior(comm)
    for a in (h, u, v):
        if a.shape != interior:
            raise ValueError(
                f"a field of {a.shape} a device: the interior of a "
                f"{cfg.ny}x{cfg.nx} grid on a mesh of {comm.axis_sizes} "
                f"is {interior} a device, without ghost cells")
    padded = (jnp.pad(a.astype(cfg.dtype), G, mode="edge")
              for a in (h, u, v))
    state, _tok = _ghosted_state(*padded, cfg, comm, as_token(None))
    return state


def make_first_step(cfg, comm, snapshot=None):
    """Jitted global function: the forward-Euler step that starts a
    run.  ``snapshot`` (a job's): where :func:`make_multistep` would
    run the kernel that writes row sums, the step runs that kernel too,
    its sums off, so that a process builds one kernel for both, and
    returns ``(state, sums)``: the room for the sums that
    ``make_multistep(snapshot=)`` takes, made here so that a run's
    set-up holds no program of its own for it."""
    coarsen = snapshot.coarsen if _sums_in_step(cfg, comm, snapshot) else 0

    def local_fn(state):
        if coarsen:
            return _step_wide(
                state, cfg, comm, first_step=True, coarsen=coarsen, summing=False,
                sums=_zero_sums(state.h, coarsen))[0]
        state, _tok = shallow_water_step(state, cfg, comm, first_step=True)
        return state

    _kernels_ahead(cfg, comm)
    specs = _mesh_specs(comm)
    return jax.jit(jax.shard_map(
        local_fn, mesh=comm.mesh, in_specs=(specs,),
        out_specs=(specs, (jax.P(*comm.axes),) * 3) if coarsen else specs))


class _Window(NamedTuple):
    """One device's parts of a differentiated window
    (:func:`make_gradient`), to be run and differentiated inside the
    model's ``shard_map``, where the adjoint exchanges carry every other
    device's share of a cotangent to the cells it came from."""

    first: object  # (h0, u0, v0) -> the state after the first step
    call: object  # state -> state, as make_multistep's loop runs it
    step_by_step: object  # the same steps one by one, each state kept
    misfit: object  # (h, y) -> this device's share of one term of J


def _window(cfg, comm, num_steps, observe):
    if _runs_as_kernels(cfg, comm):
        # a kernel's walk keeps what its derivative reads by itself
        # (_step_wide).  A state of the sweep is read by the next step,
        # whose exchange overwrites its ghost cells, and by the misfit,
        # which reads its interior
        def one_step(state):
            return _step_wide(state, cfg, comm, read_whole=False)[0][0]
    else:
        # array code: a step keeps its input and is run again backwards
        def plain(state):
            return shallow_water_step(state, cfg, comm)[0]

        one_step = _kept_at_its_start(
            plain, plain, f"{ADJOINT_SCOPE}/{STEP_VJP}")

    def first(h0, u0, v0):
        state = _state_of_fields(h0, u0, v0, cfg=cfg, comm=comm)
        return shallow_water_step(state, cfg, comm, first_step=True)[0]

    def step_by_step(state):
        return SWState(*lax.scan(
            lambda s, _: (one_step(s), None), state, None, length=num_steps)[0])

    def call(state):
        # a tangent sweep's states are read as the backward sweep's are:
        # by the next step, and by the observation's mean
        return SWState(*_call_of_steps(
            state, cfg=cfg, comm=comm, num_steps=num_steps, read_whole=False))

    def misfit(h, y):
        with _adjoint_scope(COST):
            d = _observed(h, cfg.ghost, observe) - y
            return 0.5 * jnp.sum(d * d)

    return _Window(first, call, step_by_step, misfit)


def _spread(coarse, ghost, coarsen):
    """The transpose of :func:`_block_mean`: a coarse cell's cotangent,
    over ``coarsen`` squared, to each cell it is the mean of, and
    nothing to the ghost ring; ``[ny / c, nx / c]`` to the padded
    block's ``[ny + 2 G, nx + 2 G]``.

    A row's columns are copied ``c`` times **by a matrix product**: 128
    coarse columns, a vector register's, times a 0/1 matrix ``[128,
    128 c]`` at ``precision=HIGHEST``, which is exact (each element of
    the result is one product by one; a TPU's three bfloat16 parts of a
    float32 add up to it again), so no array has ``c`` for its minor
    dimension.  The rows are a broadcast along a major axis.  Written
    as the definition, ``broadcast_to(coarse[:, None, :, None], (ny, c,
    nx, c)).reshape(...)``, the TPU compiler laid ``f32[1800,2,3600,2]``
    two columns to a tile of 128 lanes: 6.64e9 bytes and 18.3 ms for a
    result of 104e6, where this takes 1.07 ms (``PERF.md``, PR 58;
    ``tests/test_sw_observed.py`` holds the two equal bit for bit).
    The one difference: a cotangent that is not finite spoils the 128
    columns of its row that it is multiplied beside."""
    c = coarsen
    if c == 1:
        return jnp.pad(coarse, ghost)
    ny, nx = coarse.shape
    lanes = sw_kernels.LANES
    registers = -(-nx // lanes)
    copies = (jnp.arange(lanes * c)[None, :] // c
              == jnp.arange(lanes)[:, None]).astype(coarse.dtype)
    rows = jnp.pad(coarse, ((0, 0), (0, registers * lanes - nx)))
    rows = lax.dot_general(
        rows.reshape(ny, registers, lanes), copies, (((2,), (0,)), ((), ())),
        precision=lax.Precision.HIGHEST)
    # the division where the cells are written, not a pass before
    rows = rows.reshape(ny, registers * lanes * c)[:, :nx * c] * jnp.asarray(
        1.0 / (c * c), rows.dtype)
    cells = jnp.broadcast_to(rows[:, None, :], (ny, c, nx * c))
    return jnp.pad(cells.reshape(ny * c, nx * c), ghost)


def _observed(block, ghost, coarsen):
    """:func:`_block_mean` as an observation operator, with its
    transpose written out (:func:`_spread`).  Not jax's own rule: for a
    window's sum that is a window's sum over the cotangent dilated,
    which the TPU compiler makes one ``reduce-window`` with a base
    dilation; on a v5e that read a gradient uncorrelated with the
    cost's slope at 3604 x 7204 and the right one at 516 x 1028
    (PERF.md, PR 54; as wrong in PR 57's stand-alone run: relative L2
    1.12 from numpy's there, equal to it at the small size)."""
    c = coarsen

    def observe(block):
        return _block_mean(block, ghost, c)

    # linear: its tangent is itself, and nothing is kept
    return both_modes(
        observe, lambda block: (), lambda _, block: observe(block),
        lambda _, coarse: (_spread(coarse, ghost, c),))(block)


def _behind(x, earlier, comm):
    """``x`` as it is, but not before ``earlier`` is there: its first
    element is read through the other's (a NaN there passes, into a
    result that holds it anyway).  What orders two parts of a program
    that share no data, where a barrier is only the compiler's: beside
    neighbours their exchanges' permutes must not run side by side (the
    CPU's runtime takes them by the data's order alone, and all share
    one channel).  A device alone has no permute and its program is
    left as it is."""
    if comm.mesh.size == 1:
        return x
    mark = earlier[0, 0]
    corner = jnp.where(jnp.isnan(mark), mark.astype(x.dtype), x[0, 0])
    return lax.dynamic_update_slice(x, corner.reshape(1, 1), (0, 0))


def _checked_window(cfg, comm, calls, observe):
    """The shape of a window's observations on one device, ``(calls + 1,
    rows, columns)``, where ``observe`` divides the device's block."""
    ny_l, nx_l = cfg.local_interior(comm)
    if observe < 1 or ny_l % observe or nx_l % observe:
        raise ValueError(
            f"observe {observe} does not divide a device's block of "
            f"{ny_l}x{nx_l} cells")
    return calls + 1, ny_l // observe, nx_l // observe


def _sweep_backwards(window, comm, fields, starts, seed):
    """A window's backward sweep, one device's part: the cotangents of
    the window's initial fields from those of the observed states.
    ``seed(k)`` is the cotangent of the ``h`` that observation ``k`` read
    (a padded block): the misfit's own derivative there
    (:func:`make_gradient`) or an observation-space vector spread over
    its cells (:func:`make_adjoint`).  The calls last to first, each run
    again from the state it started from (``starts``) with every state
    kept and then backwards, the first step last."""
    calls = len(starts)
    ct = SWState(seed(calls), *(jnp.zeros_like(a) for a in starts[-1][1:]))
    for k in reversed(range(calls)):
        # one call at a time: its second run reads nothing of the
        # call after it, so only this keeps it (its exchanges, and
        # its stack of kept states) behind that call's way back
        start = SWState(_behind(starts[k][0], ct.h, comm), *starts[k][1:])
        # the call's steps again, every state kept, then backwards
        with _adjoint_scope(RECOMPUTE):
            _, vjp = jax.vjp(window.step_by_step, start)
            ct, = vjp(ct)
        ct = SWState(ct.h + seed(k), *ct[1:])
    h0, u0, v0 = fields
    h0 = _behind(h0, ct.h, comm)
    with _adjoint_scope(RECOMPUTE):
        _, vjp = jax.vjp(window.first, h0, u0, v0)
        return vjp(ct)


def make_gradient(cfg, comm, *, calls, num_steps, observe=1):
    """Global function ``(h0, u0, v0, obs) -> (J, dJ/dh0, dJ/du0,
    dJ/dv0)``: the misfit of a window of the model to observations and
    its gradient with respect to the window's initial fields, by one
    forward and one backward sweep through the model's own steps.  The
    adjoint of a variational assimilation (Courtier and Talagrand 1990
    on these equations), of a parameter fit, of a solver inside a loss.

    The window is ``1 + calls * num_steps`` steps from rest tendencies,
    forward Euler first, as every driver's.  ``h0``, ``u0``, ``v0``:
    the domain's interior cells, ``(cfg.ny, cfg.nx)`` each, as
    :func:`make_state` takes them.  ``obs``: ``(calls + 1, cfg.ny //
    observe, cfg.nx // observe)``, sharded over the mesh on its last two
    axes: observations of ``h`` as means over ``observe x observe``
    cells (``observe`` has to divide a device's block) after the first
    step and after every call.  ``J = 1/2 sum_k sum_blocks (H(h_k) -
    obs_k)^2``, summed over the mesh by ``allreduce`` and handed back as
    every device's copy of it, one element a device (``J[0, 0]`` on the
    host); the gradients come back interior-shaped and sharded as the
    fields are.

    Every exchange of every step is under the derivative, transposed to
    the adjoint exchange (``parallel/halo.py``: the cotangents of ghost
    cells sent back along the reversed permutes and added to the edges
    they were copied from).  Where the step is the kernel
    (:func:`_runs_as_kernels`) a step's derivative is a kernel too
    (``sw_kernels.wide_step_vjp`` at the fields the step started from,
    :func:`_step_backwards`); on a block whose adjoint walk has no room
    in VMEM (:func:`_derives_as_kernels`) it is that of the step's
    array code at the step's input state.  The same window forwards, a
    perturbation of the initial fields pushed through every step and
    every exchange, is :func:`make_tangent`'s, and this function's
    backward sweep with a vector for the residuals :func:`make_adjoint`'s.

    Checkpointed at two levels, as two jitted programs a gradient (the
    function's ``forward`` and ``backward``).  The forward sweep runs
    the window's calls as :func:`make_multistep` runs them
    (:func:`_call_of_steps`) and hands back the cost
    and **the state each call starts from** (and the last state's
    ``h``, for its misfit): the first level, arrays on the mesh between
    the two programs.  The backward sweep takes the calls last to first:
    it runs a call's steps again one by one, keeps all ``num_steps``
    states of that call (the second level: where a step's derivative is
    the kernel, the fields ``h``, ``u``, ``v`` of each, which is all
    that kernel reads) while it takes the call's steps backwards (the
    adjoint kernel at each kept state; the array code run once more
    there and transposed, where it is not), then lets them go.  At any
    time ``calls`` states and ``num_steps`` kept ones are held, where
    keeping everything holds every step's residuals:
    ``Descent.stats()`` gives the bytes.
    The values are those of plain ``jax.value_and_grad`` of the window
    to rounding (``tests/test_sw_adjoint.py`` builds that program from
    the same parts: the compiler fuses, and so rounds, each program in
    its own way).

    **On a mesh of TPU chips, take it at blocks of 1800 x 3600 cells a
    chip or more** (there the 2x2 gradient is the 1x1 gradient on the
    chip: ``chip_smoke.py`` ``solver4.adjoint``).  At 900 x 1800 a chip
    the kernel path itself, forwards, has returned NaN or hung on a 2x2
    mesh in programs that do not donate their state (``ROADMAP.md``
    S28), and this function's two are such.
    """
    observed = _checked_window(cfg, comm, calls, observe)
    window = _window(cfg, comm, num_steps, observe)

    def checked(obs):
        if obs.shape != observed:
            raise ValueError(
                f"observations of {obs.shape} a device where a window of "
                f"{calls} calls observed over {observe}x{observe} cells "
                f"takes {observed}")
        return obs

    def summed(mine):
        with _adjoint_scope(COST):
            total, _tok = allreduce(mine, reductions.SUM, comm=comm)
        return total.reshape(1, 1)

    def forward(h0, u0, v0, obs):
        with _adjoint_scope(FORWARD):
            state = window.first(h0, u0, v0)
        mine, starts = window.misfit(state.h, obs[0]), []
        for k in range(calls):
            starts.append(state)
            with _adjoint_scope(FORWARD):
                state = window.call(state)
            mine = mine + window.misfit(state.h, obs[k + 1])
        return summed(mine), tuple(starts), state.h

    def backward(h0, u0, v0, obs, starts, last_h):
        seen = [start.h for start in starts] + [last_h]
        return _sweep_backwards(
            window, comm, (h0, u0, v0), starts,
            lambda k: jax.grad(window.misfit)(seen[k], obs[k]))

    _kernels_ahead(cfg, comm)
    spec = jax.P(*comm.axes)
    fields = (spec, spec, spec, jax.P(None, *comm.axes))
    kept = ((_mesh_specs(comm),) * calls, spec)
    run_forward = jax.jit(jax.shard_map(
        lambda *args: forward(*args[:3], checked(args[3])), mesh=comm.mesh,
        in_specs=fields, out_specs=(spec, *kept)))
    run_backward = jax.jit(jax.shard_map(
        backward, mesh=comm.mesh, in_specs=(*fields, *kept),
        out_specs=(spec,) * 3))

    def gradient(h0, u0, v0, obs):
        cost, starts, last_h = run_forward(h0, u0, v0, obs)
        return (cost, *run_backward(h0, u0, v0, obs, starts, last_h))

    gradient.forward, gradient.backward = run_forward, run_backward
    return gradient


def make_descent_step(cfg, comm):
    """Jitted global function ``(h0, u0, v0, gh, gu, gv, rate) -> (h0,
    u0, v0)``: a steepest-descent step of length ``rate`` (a scalar),
    the fields donated."""

    def local_fn(h0, u0, v0, gh, gu, gv, rate):
        with _adjoint_scope(UPDATE):
            return tuple(x - rate.astype(x.dtype) * g
                         for x, g in zip((h0, u0, v0), (gh, gu, gv)))

    spec = jax.P(*comm.axes)
    return jax.jit(jax.shard_map(
        local_fn, mesh=comm.mesh, in_specs=(spec,) * 6 + (jax.P(),),
        out_specs=(spec,) * 3), donate_argnums=(0, 1, 2))


class Descent:
    """A fit of a window's initial fields to observations by steepest
    descent, as a host drives it: every iteration one call of
    :func:`make_gradient`'s programs (the forward sweep, the backward
    sweep) and one of :func:`make_descent_step`'s, enqueued without a
    wait between; the costs stay on the device until :meth:`costs` asks.

        fit = Descent(cfg, comm, calls=4, num_steps=10, observe=2)
        rate, _, _ = fit.step_length(h0, u0, v0, obs)
        fit.start(h0, u0, v0, obs, rate)
        fit.iterate(5)
        fit.wait()
        fit.costs()      # J before each of the five steps

    ``trace`` (a :class:`mpi4jax_tpu.utils.spans.Recorder`) keeps the
    host's spans, ``mpi4jax_tpu.adjoint/enqueue`` an iteration and
    ``mpi4jax_tpu.adjoint/wait`` a wait.  :meth:`stats` counts what was
    run and what the two checkpoint levels hold.
    """

    def __init__(self, cfg, comm, *, calls, num_steps, observe=1):
        self.cfg, self.comm = cfg, comm
        self.calls, self.num_steps = calls, num_steps
        self.gradient = make_gradient(
            cfg, comm, calls=calls, num_steps=num_steps, observe=observe)
        self.update = make_descent_step(cfg, comm)
        self.trace = spans.Recorder(SCOPE_PREFIX)
        self.fields = self.obs = self.rate = None
        self._costs = []

    def step_length(self, h0, u0, v0, obs, *, iterations=5, seed=0,
                    nudge=1e-2):
        """A step length that a whole run of steepest descent can keep:
        ``1 / (2 L)``, ``L`` the largest curvature of the cost found by
        ``iterations`` steps of the power method on differences of
        gradients, ``(grad J(x + e v) - grad J(x)) / e``, from a seeded
        rough direction (``1 + iterations`` gradients).  A fixed step
        has to stay under ``2 / L`` or the roughest directions grow;
        the Cauchy step of the first gradient, which is smooth, is
        several times that at a fine grid (``PERF.md``, PR 54: 0.40
        against a bound of 0.15 at 7200x3600, and the cost rose
        25-fold in eight steps).  The power method comes to ``L`` from
        below; the factor of two is its room.  Returns ``(rate, the
        curvatures found, the cost at the fields)``."""
        fields = (h0, u0, v0)
        cost, *at = self.gradient(*fields, obs)
        keys = jax.random.split(jax.random.key(seed), 3)
        v = [jax.random.normal(k, a.shape, a.dtype) for k, a in zip(keys, fields)]
        v = [jax.device_put(x, a.sharding) for x, a in zip(v, fields)]
        found = []
        for _ in range(iterations):
            there = self.gradient(
                *(a + nudge * x for a, x in zip(fields, v)), obs)[1:]
            w = [(b - a) / nudge for a, b in zip(at, there)]
            found.append(
                sum(float(jnp.vdot(x, y)) for x, y in zip(v, w))
                / sum(float(jnp.vdot(x, x)) for x in v))
            size = sum(float(jnp.vdot(y, y)) for y in w) ** 0.5
            cells = sum(y.size for y in w) ** 0.5
            v = [y * (cells / size) for y in w]  # elements of order one
        return 0.5 / max(found), found, float(cost[0, 0])

    def start(self, h0, u0, v0, obs, rate):
        """The first guess (consumed by the first step), the
        observations and the step length."""
        self.fields, self.obs = (h0, u0, v0), obs
        self.rate = jnp.asarray(rate, jnp.float32)
        self._costs = []

    def iterate(self, n=1):
        """Enqueue ``n`` iterations; nothing is waited for."""
        for _ in range(n):
            with self.trace.span("adjoint/enqueue", key=len(self._costs)):
                cost, *grads = self.gradient(*self.fields, self.obs)
                self.fields = self.update(*self.fields, *grads, self.rate)
                self._costs.append(cost)

    def wait(self):
        with self.trace.span("adjoint/wait", key=len(self._costs)):
            jax.block_until_ready(self.fields)

    def costs(self):
        """The cost before each descent step so far, as floats (waits)."""
        return [float(c[0, 0]) for c in self._costs]

    def stats(self):
        """``gradients``: iterations enqueued.  ``window_steps``: the
        model steps of a window.  ``trajectory_bytes``: what the two
        checkpoint levels keep on the mesh at their fullest, from
        shapes: the state at each call's start and what is kept of the
        states of one call's steps (the fields alone, where a step's
        derivative is the kernel that reads no more).  ``costs``: the
        cost before each step.  (What a
        gradient runs again is not counted here: a device trace shows
        it, under ``sw/adjoint/recompute``.)"""
        return {
            "gradients": len(self._costs),
            "window_steps": 1 + self.calls * self.num_steps,
            "trajectory_bytes": _trajectory_bytes(
                self.cfg, self.comm, self.calls, self.num_steps),
            "costs": self.costs(),
        }


def _trajectory_bytes(cfg, comm, calls, num_steps):
    """What a window's two checkpoint levels keep on the mesh at their
    fullest, from shapes: the state at each call's start and what is
    kept of the states of one call's steps (the fields alone, where a
    step's derivative is the kernel that reads no more)."""
    state = jax.eval_shape(make_init(cfg, comm))
    state_bytes = sum(a.size * a.dtype.itemsize for a in state)
    kept = state[:3] if _derives_as_kernels(cfg, comm) else state
    kept_bytes = sum(a.size * a.dtype.itemsize for a in kept)
    return calls * state_bytes + num_steps * kept_bytes


def make_tangent(cfg, comm, *, calls, num_steps, observe=1):
    """Jitted global function ``(h0, u0, v0, starts, ph, pu, pv) -> z``:
    the tangent-linear sweep of :func:`make_gradient`'s window, forward
    mode through every step and every halo exchange of it.  ``ph``,
    ``pu``, ``pv`` is a perturbation of the window's initial fields,
    interior-shaped as they are; ``z`` is ``(calls + 1, cfg.ny //
    observe, cfg.nx // observe)``, sharded as the observations: ``H M_k
    p``, what the perturbation does to the observed means of ``h`` after
    the first step and after every call, to first order.  Linearised
    about the trajectory that ``gradient.forward(h0, u0, v0, obs)``
    keeps: ``starts``, the state each call starts from (its second
    result), which the sweep reads and does not run again.

    A step's tangent is ``jax.jvp`` of the step: of its array code
    where it is array code, exchange by exchange (the exchange's tangent
    is the exchange of the tangents, ``parallel/halo.py``); where the
    step is the kernel the walk runs as the kernel and each of its
    steps' tangents is a kernel's too (``sw_kernels.wide_step_jvp``, at
    the fields the step started from: :func:`_walk_forwards`; on a block
    without room for it, ``jax.jvp`` of the walk's array code), two
    steps a walk where the call walks two.  Nothing is approximated and
    nothing is kept between calls but the tangent state, six arrays.
    Under ``sw/adjoint/tangent``."""
    window = _window(cfg, comm, num_steps, observe)
    _checked_window(cfg, comm, calls, observe)

    def tangent(h0, u0, v0, starts, ph, pu, pv):
        with _adjoint_scope(TANGENT):
            _, t = jax.jvp(window.first, (h0, u0, v0), (ph, pu, pv))
            seen = [_block_mean(t.h, cfg.ghost, observe)]
            for k in range(calls):
                _, t = jax.jvp(window.call, (starts[k],), (SWState(*t),))
                seen.append(_block_mean(t.h, cfg.ghost, observe))
            return jnp.stack(seen)

    _kernels_ahead(cfg, comm)
    spec = jax.P(*comm.axes)
    return jax.jit(jax.shard_map(
        tangent, mesh=comm.mesh,
        in_specs=(spec,) * 3 + ((_mesh_specs(comm),) * calls,) + (spec,) * 3,
        out_specs=jax.P(None, *comm.axes)))


def make_adjoint(cfg, comm, *, calls, num_steps, observe=1):
    """Jitted global function ``(h0, u0, v0, starts, w) -> (gh, gu,
    gv)``: :func:`make_gradient`'s backward sweep with an
    observation-space vector in place of the misfit's residuals.  ``w``
    is shaped and sharded as the observations, ``(calls + 1, cfg.ny //
    observe, cfg.nx // observe)``; the result is ``sum_k M_k^T H^T
    w_k``, interior-shaped: the transpose of :func:`make_tangent`, to
    rounding (``<M p, w> = <p, M^T w>``), at the same trajectory
    (``starts``).  The same sweep as the gradient's (the calls' steps
    run again and kept, the adjoint kernel or the array code's transpose
    at each, every exchange the adjoint exchange): with ``w`` the
    residuals ``H(h_k) - obs_k`` it returns the gradient."""
    window = _window(cfg, comm, num_steps, observe)
    observed = _checked_window(cfg, comm, calls, observe)

    def adjoint(h0, u0, v0, starts, w):
        if w.shape != observed:
            raise ValueError(
                f"an observation-space vector of {w.shape} a device where "
                f"the window's observations are {observed}")

        def seed(k):
            with _adjoint_scope(COST):
                return _spread(w[k], cfg.ghost, observe)

        return _sweep_backwards(window, comm, (h0, u0, v0), starts, seed)

    _kernels_ahead(cfg, comm)
    spec = jax.P(*comm.axes)
    return jax.jit(jax.shard_map(
        adjoint, mesh=comm.mesh,
        in_specs=(spec,) * 3 + ((_mesh_specs(comm),) * calls,
                                jax.P(None, *comm.axes)),
        out_specs=(spec,) * 3))


def make_product(cfg, comm, *, calls, num_steps, observe=1, weight=0.0):
    """Global function ``(h0, u0, v0, starts, ph, pu, pv) -> (qh, qu,
    qv)``: the Gauss-Newton product of the window's cost, ``A p = weight
    p + sum_k M_k^T H^T H M_k p``, by one tangent-linear sweep
    (:func:`make_tangent`) and one adjoint sweep (:func:`make_adjoint`)
    of it, the function's ``tangent`` and ``adjoint``, and one program
    that adds ``weight p``.  The Hessian of incremental 4D-Var's
    quadratic cost (Courtier, Thepaut and Hollingsworth 1994) with a
    background term ``weight / 2 |p|^2``; of a Gauss-Newton or
    Newton-CG fit through the solver."""
    how = dict(calls=calls, num_steps=num_steps, observe=observe)
    tangent = make_tangent(cfg, comm, **how)
    adjoint = make_adjoint(cfg, comm, **how)
    spec = jax.P(*comm.axes)

    def weighted(gh, gu, gv, ph, pu, pv):
        with _adjoint_scope(UPDATE):
            return tuple(g + jnp.asarray(weight, g.dtype) * p
                         for g, p in zip((gh, gu, gv), (ph, pu, pv)))

    add = jax.jit(jax.shard_map(
        weighted, mesh=comm.mesh, in_specs=(spec,) * 6, out_specs=(spec,) * 3),
        donate_argnums=(0, 1, 2))

    def product(h0, u0, v0, starts, ph, pu, pv):
        seen = tangent(h0, u0, v0, starts, ph, pu, pv)
        return add(*adjoint(h0, u0, v0, starts, seen), ph, pu, pv)

    product.tangent, product.adjoint = tangent, adjoint
    return product


def make_inner_step(cfg, comm, weight):
    """Jitted global functions ``(begin, step)`` of conjugate gradients
    on ``A x = b``, ``A`` :func:`make_product`'s: the vectors are
    triples of interior-shaped fields, the scalars every device's copy,
    one element a device, and nothing comes to the host.

    ``begin(bh, bu, bv) -> (x, r, p, rr)``: ``x = 0``, ``r = p = b``
    (``b`` donated), ``rr = r . r``.  ``step(x, r, p, g, rr, cost) ->
    (x, r, p, rr, cost, pq)`` with ``g`` the data term's product ``sum_k
    M_k^T H^T H M_k p``: ``q = g + weight p``, ``alpha = rr / p . q``,
    ``x += alpha p``, ``r -= alpha q``, ``p = r + (r' . r' / rr) p``;
    ``cost`` the quadratic cost, which falls by ``alpha rr / 2`` (a
    quadratic falls by ``alpha p . r - alpha^2 p . q / 2`` along ``p``,
    and conjugate gradients' ``p . r`` is ``rr``), and ``pq`` the
    curvature ``p . A p``.  Two dot products an iteration, ``p . q``
    and ``r' . r'``, each summed over the mesh by ``allreduce``.  ``x``,
    ``r`` and ``p`` are donated.  A curvature that is not positive takes
    no step (``alpha`` 0)."""
    def dot(a, b):
        mine = sum(jnp.sum(x * y) for x, y in zip(a, b))
        total, _tok = allreduce(mine, reductions.SUM, comm=comm)
        return total.reshape(1, 1)

    def begin(bh, bu, bv):
        with _adjoint_scope(UPDATE):
            b = (bh, bu, bv)
            return tuple(jnp.zeros_like(a) for a in b), b, b, dot(b, b)

    def step(x, r, p, g, rr, cost):
        with _adjoint_scope(UPDATE):
            q = tuple(a + jnp.asarray(weight, a.dtype) * b for a, b in zip(g, p))
            pq = dot(p, q)
            alpha = jnp.where(pq > 0, rr / jnp.where(pq > 0, pq, 1), 0)[0, 0]
            x = tuple(a + alpha * b for a, b in zip(x, p))
            r = tuple(a - alpha * b for a, b in zip(r, q))
            new = dot(r, r)
            beta = jnp.where(rr > 0, new / jnp.where(rr > 0, rr, 1), 0)[0, 0]
            p = tuple(a + beta * b for a, b in zip(r, p))
            cost = cost - 0.5 * alpha * rr
            return x, r, p, new, cost, pq

    spec = jax.P(*comm.axes)
    triple = (spec,) * 3
    return (
        jax.jit(jax.shard_map(
            begin, mesh=comm.mesh, in_specs=triple,
            out_specs=(triple, triple, triple, spec)), donate_argnums=(0, 1, 2)),
        jax.jit(jax.shard_map(
            step, mesh=comm.mesh, in_specs=(triple,) * 4 + (spec, spec),
            out_specs=(triple, triple, triple, spec, spec, spec)),
            donate_argnums=(0, 1, 2)))


def _on_the_host(scalars):
    """Every device's copy of each of ``scalars`` (one element a device)
    as a float, fetched together: no program runs for it."""
    return [float(c[0, 0]) for c in jax.device_get(list(scalars))]


class InnerLoop:
    """The inner loop of incremental 4D-Var (Courtier, Thepaut and
    Hollingsworth 1994) as a host drives it: the quadratic cost ``J(dx)
    = weight / 2 |dx|^2 + 1/2 sum_k |H M_k dx - d_k|^2`` in the
    increment ``dx`` of a window's initial fields, minimised by
    conjugate gradients, every iteration one tangent-linear sweep
    (:func:`make_tangent`), one adjoint sweep (:func:`make_adjoint`) and
    the vector updates (:func:`make_inner_step`), three programs
    enqueued without a wait between; the scalars stay on the device
    until :meth:`costs` asks.

        fit = InnerLoop(cfg, comm, calls=4, num_steps=10, observe=2,
                        weight=0.11)
        fit.linearise(h0, u0, v0, obs)   # the outer loop: once
        fit.iterate(5)
        fit.wait()
        fit.costs()        # J after each of the five iterations
        fit.increment()    # dx: add it to h0, u0, v0

    :meth:`linearise` is the outer loop: the nonlinear window from the
    first guess (``make_gradient``'s forward sweep, which keeps the
    state each call starts from: the trajectory the inner loop is
    linearised about, held for all of it), the innovations ``d_k = y_k -
    H(h_k)`` and ``b = sum_k M_k^T H^T d_k``, minus the gradient that
    ``make_gradient`` gives.  ``weight`` is the background term's: ``B``
    a multiple of the identity.  ``iterations`` caps the loop:
    :meth:`iterate` past it raises, and :meth:`begin` starts the loop
    again from ``dx = 0`` at the same linearisation.

    ``trace`` (a :class:`mpi4jax_tpu.utils.spans.Recorder`) keeps the
    host's spans, ``mpi4jax_tpu.incremental/linearise``,
    ``incremental/enqueue`` an iteration and ``incremental/wait`` a
    wait.  :meth:`stats` counts what was run and what is held;
    :attr:`tangent_walks` says what the tangent sweep's steps are.
    """

    def __init__(self, cfg, comm, *, calls, num_steps, weight, observe=1,
                 iterations=50):
        self.cfg, self.comm = cfg, comm
        self.calls, self.num_steps = calls, num_steps
        self.weight, self.iterations = weight, iterations
        how = dict(calls=calls, num_steps=num_steps, observe=observe)
        self.gradient = make_gradient(cfg, comm, **how)
        self.product = make_product(cfg, comm, weight=weight, **how)
        self.tangent, self.adjoint = self.product.tangent, self.product.adjoint
        self._begin, self.update = make_inner_step(cfg, comm, weight)
        self.trace = spans.Recorder(SCOPE_PREFIX)
        self.fields = self.obs = self.starts = self.last_h = None
        self.vectors = None  # x, r, p: the increment, the residual, the direction
        # r . r, the quadratic cost and the cost at dx = 0: every device's
        # copy, one element a device, on the device
        self.rr = self.cost = self.cost0 = None
        self._costs, self._curvatures = [], []

    def linearise(self, h0, u0, v0, obs):
        """The outer loop: the trajectory from the first guess, kept, and
        the loop begun at ``dx = 0``."""
        with self.trace.span("incremental/linearise"):
            self.fields, self.obs = (h0, u0, v0), obs
            self.cost0, self.starts, self.last_h = self.gradient.forward(
                h0, u0, v0, obs)
            self.begin()

    def begin(self):
        """The loop from ``dx = 0`` at the trajectory kept: ``r = p = b``,
        by one backward sweep."""
        grads = self.gradient.backward(
            *self.fields, self.obs, self.starts, self.last_h)
        *self.vectors, self.rr = self._begin(*(-g for g in grads))
        self.cost = self.cost0
        self._costs, self._curvatures = [], []

    @property
    def enqueued(self):
        """Iterations enqueued since the loop began (nothing is read)."""
        return len(self._costs)

    def iterate(self, n=1):
        """Enqueue ``n`` iterations; nothing is waited for."""
        for _ in range(n):
            if self.enqueued >= self.iterations:
                raise ValueError(
                    f"the inner loop is capped at {self.iterations} "
                    "iterations: linearise again, or begin()")
            with self.trace.span("incremental/enqueue", key=self.enqueued):
                x, r, p = self.vectors
                seen = self.tangent(*self.fields, self.starts, *p)
                g = self.adjoint(*self.fields, self.starts, seen)
                *self.vectors, self.rr, self.cost, pq = self.update(
                    x, r, p, g, self.rr, self.cost)
                self._costs.append(self.cost)
                self._curvatures.append(pq)

    def wait(self):
        with self.trace.span("incremental/wait", key=self.enqueued):
            jax.block_until_ready(self.vectors)

    def costs(self):
        """The quadratic cost at ``dx = 0`` and after each iteration so
        far, as floats (waits)."""
        return _on_the_host((self.cost0, *self._costs))

    def curvatures(self):
        """``p . A p`` of each iteration so far, as floats (waits)."""
        return _on_the_host(self._curvatures)

    def increment(self):
        """``(dh0, du0, dv0)`` as the loop has it."""
        return self.vectors[0]

    @property
    def tangent_walks(self):
        """What pushes a walk's tangent forwards in the tangent-linear
        sweep: ``"kernel"`` (``sw_kernels.wide_step_jvp``) or
        ``"arrays"`` (``jax.jvp`` of the step's array code: the step is
        array code, or its block has no room for the kernel).
        :func:`_derives_as_kernels`' say, on the host: no program runs.
        (Not a key of :meth:`stats`, whose keys a test of the benchmark
        holds to a fixed set.)"""
        return "kernel" if _derives_as_kernels(self.cfg, self.comm) else "arrays"

    def held(self):
        """The arrays held on the mesh between an iteration's programs:
        the first guess, the trajectory's first level (the state each
        call starts from, and the last ``h``), the observations and the
        loop's vectors."""
        return self.fields, self.starts, self.last_h, self.obs, self.vectors

    def stats(self):
        """``iterations``: enqueued since the loop began.
        ``window_steps``: the model steps of a window, each linearised
        once and transposed once an iteration.  ``trajectory_bytes``:
        what the two checkpoint levels keep on the mesh at their
        fullest, as :meth:`Descent.stats` counts them; the first level
        is held across the loop.  ``vector_bytes``: the loop's vectors,
        ``x``, ``r``, ``p`` and a product, of three interior fields
        each.  ``costs``, ``curvatures``: :meth:`costs`,
        :meth:`curvatures`."""
        field = self.cfg.ny * self.cfg.nx * jnp.dtype(self.cfg.dtype).itemsize
        return {
            "iterations": self.enqueued,
            "window_steps": 1 + self.calls * self.num_steps,
            "trajectory_bytes": _trajectory_bytes(
                self.cfg, self.comm, self.calls, self.num_steps),
            "vector_bytes": 4 * 3 * field,
            "costs": self.costs(),
            "curvatures": self.curvatures(),
        }


@dataclass(frozen=True)
class Snapshot:
    """What a job writes after every call, and how late it may deliver it.

    ``fields``: the state's arrays a snapshot holds.  ``coarsen``: a
    snapshot is the interior's mean over ``coarsen × coarsen`` blocks
    of cells (1: the interior as it is); it has to divide both sides of
    a device's block.  ``lag``: a snapshot is handed to the callback at
    most this many snapshots after the newest one produced, so at most
    ``lag + 1`` wait on the device at once.  ``ahead_bytes``: the most
    bytes of snapshots whose copies to the host are asked for and not
    yet fetched, the oldest's always; ``None``: every snapshot's copy is
    asked for as it is produced.  A host that stages transfers through
    a pinned buffer of a fixed size (a TPU's:
    ``TPU_PREMAPPED_BUFFER_SIZE``, 4 GiB unless set) moves what is asked
    for past it at a tenth of the speed: a job whose ``lag + 1``
    snapshots do not fit there holds its copies under it with this.  In
    a job that is saved too it is the host's bound on all the job's
    copies, a save's pieces among them (:class:`SolverJob`).

    Where the step is the kernel of :mod:`sw_kernels` and the blocks
    are made of its strips' rows (``h``, ``u`` and ``v`` at ``coarsen``
    2, 4 or 8: :func:`_sums_in_step`) a snapshot starts in the call's
    last walk, which writes the fields' sums over ``coarsen`` rows, and
    the snapshot program finishes a ``coarsen``-th of a field where it
    would read three fields again; the same means to roundoff.  Nothing
    to ask for: it follows from the configuration, the devices and
    these fields."""

    fields: tuple = ("h", "u", "v")
    coarsen: int = 1
    lag: int = 4
    ahead_bytes: int = None


# The snapshot's phase, a jax.named_scope segment inside its own
# ``mpi4jax_tpu.snapshot`` scope (as parallel/halo.py names the
# exchange's): what a device profile books the coarse-graining under.
COARSEN = "coarsen"


def _sums_in_step(cfg, comm, snapshot):
    """Whether a call's last walk writes the sums over ``coarsen`` rows
    of what ``snapshot`` holds, for :func:`make_snapshot` to finish:
    where the step is the kernel (:func:`_runs_as_kernels`), which has
    every row of the final ``h``, ``u``, ``v`` in VMEM a strip at a
    time, the snapshot holds those three, and its blocks are made of a
    strip's rows (``coarsen`` > 1 divides ``sw_kernels.STRIP`` and a
    device's block).  From what the job is built on, like the kernel
    itself: the array code, ``coarsen`` 1 and blocks of 3 or 16 rows
    read the fields, as every backend did before."""
    if snapshot is None or not _runs_as_kernels(cfg, comm):
        return False
    c = snapshot.coarsen
    return (c > 1 and sw_kernels.STRIP % c == 0
            and not any(n % c for n in cfg.local_interior(comm))
            and sorted(snapshot.fields) == ["h", "u", "v"])


def _block_mean(block, ghost, coarsen):
    """The mean over ``coarsen × coarsen`` blocks of cells of the
    interior of one device's padded ``block``."""
    c = coarsen
    if c == 1:
        return block[ghost:-ghost, ghost:-ghost]
    # the windows are laid over the padded block as it is, the first
    # `lead` of them before the interior's first: a slice of the
    # interior first would be a pass over the field of its own
    lead = -(-ghost // c)
    pads = [(lead * c - ghost, -(n + lead * c - ghost) % c) for n in block.shape]
    sums = lax.reduce_window(
        block, jnp.zeros((), block.dtype), lax.add, (c, c), (c, c), pads)
    ny, nx = (n - 2 * ghost for n in block.shape)
    means = sums[lead:lead + ny // c, lead:lead + nx // c]
    return means * jnp.asarray(1.0 / (c * c), block.dtype)


def _row_sums_mean(sums, ghost, coarsen, shape):
    """:func:`_block_mean` of a block of ``shape`` interior cells from
    the sums over ``coarsen`` rows that the step's kernel wrote
    (:mod:`sw_kernels`, "Output in the last walk": whole width, the
    interior's first group in row ``lead``): the sums along the rows
    and the division."""
    c = coarsen
    lead = -(-ghost // c)
    ny, nx = shape
    width = sums.shape[1]
    # as `_block_mean` lays its windows: over the rows as they lie
    sums = lax.reduce_window(
        sums, jnp.zeros((), sums.dtype), lax.add, (1, c), (1, c),
        [(0, 0), (lead * c - ghost, -(width + lead * c - ghost) % c)])
    means = sums[lead:lead + ny // c, lead:lead + nx // c]
    return means * jnp.asarray(1.0 / (c * c), sums.dtype)


def _zero_sums(field, coarsen):
    """Room for the sums over ``coarsen`` rows of ``h``, ``u``, ``v``,
    one device's, beside its padded ``field`` and varying over the mesh
    as it does (so that the kernel that takes it is traced once)."""
    shape = sw_kernels.row_sums_shape(field.shape, field.dtype, coarsen)
    return tuple(jnp.zeros_like(field, shape=shape) for _ in range(3))


def make_sums_room(cfg, comm, snapshot):
    """Jitted global function ``() -> (sums, sums, sums)``: the room for
    the sums over ``coarsen`` rows of ``h``, ``u``, ``v`` that
    ``make_multistep(snapshot=)`` takes beside the state, writes and
    hands back (zeros, sharded as the state is), for a run that starts
    from a later step than ``make_first_step(snapshot=)``'s, which
    returns one; ``None`` where the step makes no sums
    (:func:`_sums_in_step`)."""
    if not _sums_in_step(cfg, comm, snapshot):
        return None
    padded = tuple(n + 2 * cfg.ghost for n in cfg.local_interior(comm))
    shape = sw_kernels.row_sums_shape(padded, cfg.dtype, snapshot.coarsen)
    spec = jax.P(*comm.axes)
    return jax.jit(jax.shard_map(
        lambda: tuple(jnp.zeros(shape, cfg.dtype) for _ in range(3)),
        mesh=comm.mesh, in_specs=(), out_specs=(spec,) * 3))


def make_snapshot(cfg, comm, snapshot, from_sums=False):
    """Jitted global function ``(field, ...) -> (coarse field, ...)``:
    each device coarse-grains the interior of its own block of each of
    the state's arrays it is handed, and the results stay sharded as the
    state is, so that each device sends the host its own share once.
    Reads its arguments and donates nothing: a job enqueues it between
    the call that made the state and the call that consumes it.

    ``from_sums``: the function is handed, in each field's place, the
    field's sums over ``coarsen`` rows as the call's last walk wrote
    them (``make_multistep(snapshot=)`` returns them beside the state;
    only where :func:`_sums_in_step` holds), a ``coarsen``-th of a
    field each, and finishes them: the sums along a row and the
    division, the same means to roundoff (the rows are added first).
    Either form refuses an operand of the other's shape."""
    c = snapshot.coarsen
    interior = ny_l, nx_l = cfg.local_interior(comm)
    if c < 1 or ny_l % c or nx_l % c:
        raise ValueError(
            f"coarsen {c} does not divide a device's block of "
            f"{ny_l}x{nx_l} cells")
    if from_sums and not _sums_in_step(cfg, comm, snapshot):
        raise ValueError(
            f"no step of this configuration on these devices makes {snapshot}'s "
            "row sums: make_snapshot(from_sums=True) has nothing to finish")
    expected = tuple(n + 2 * cfg.ghost for n in interior)
    if from_sums:
        expected = sw_kernels.row_sums_shape(expected, cfg.dtype, c)

    def local_fn(*fields):
        for a in fields:
            if a.shape != expected:
                raise ValueError(
                    f"an operand of {a.shape} a device where "
                    f"make_snapshot(from_sums={from_sums}) takes "
                    + ("a field's row sums" if from_sums else "a padded field")
                    + f" of {expected}")
        with jax.named_scope(SCOPE_PREFIX + "snapshot"), jax.named_scope(COARSEN):
            if from_sums:
                return tuple(_row_sums_mean(a, cfg.ghost, c, interior)
                             for a in fields)
            return tuple(_block_mean(a, cfg.ghost, c) for a in fields)

    spec = jax.P(*comm.axes)
    n = len(snapshot.fields)
    return jax.jit(jax.shard_map(
        local_fn, mesh=comm.mesh, in_specs=(spec,) * n, out_specs=(spec,) * n))


@dataclass(frozen=True)
class Monitor:
    """What a job watches of its own solution as it goes, and when a
    line of it stops the job: Veros's ``cfl_monitor`` and
    ``tracer_monitor`` diagnostics and its ``sanity_check`` (a
    ``global_and`` of ``isfinite``), each a reduction on the chip that
    holds a block and the library's ``allreduce`` over the job's
    communicator (:func:`make_monitor`).

    ``every_calls``: a line after every call whose number, counted from
    the integration's first, divides by this: upstream's
    ``output_frequency`` in calls, and kept for that alone (every use
    in this repository is 1, a line a call).  ``lag``: a line is read
    on the host, and handed to ``on_monitor``, at most this many calls
    after the newest call enqueued, so the loop waits for none that is
    younger, and a bad line stops the job before ``lag + 1`` more calls
    are enqueued.  ``cfl_limit``: the largest advective CFL number,
    ``max(|u| dt/dx, |v| dt/dy)`` over the domain, that is not a stop.
    A line also stops the job where a value of ``h``, ``u``, ``v`` is
    not finite, or where the thinnest layer is not above zero
    (``thickness`` is the potential vorticity's denominator, and a
    layer that dries is how this solver dies; the library's own, no
    diagnostic of Veros's).

    A state is vetted before it is saved: :meth:`SolverJob.save` first
    reads every line made so far (the host waits there for the newest
    call's, once a save), so no save is started of a state at or after
    a line that stops the job, and :meth:`SolverJob.resume` takes up
    none the monitor called bad.  With ``every_calls`` above 1 a save
    is vetted as far as the newest line."""

    every_calls: int = 1
    lag: int = 4
    cfl_limit: float = 0.5

    def why_bad(self, line):
        """What of ``line`` stops a job, in words; ``None`` for a line
        that stops none."""
        if line["nonfinite"] > 0:
            return f"{line['nonfinite']} values of h, u, v are not finite"
        if not line["h_min"] > 0:
            return f"the thinnest layer is {line['h_min']!r} m"
        if not line["cfl"] <= self.cfl_limit:
            return f"the CFL number {line['cfl']!r} is over {self.cfl_limit!r}"
        return None


class MonitorStop(RuntimeError):
    """A line of the job's :class:`Monitor` stopped the job: ``line``
    is that line (``line["step"]`` the step it is of), and no call was
    enqueued after it was read."""

    def __init__(self, line, why, calls_since):
        super().__init__(
            f"the monitor's line of step {line['step']} stops the job: {why} "
            f"(read {calls_since} calls after the call that made it; no "
            f"further call is enqueued): {line}")
        self.line = line


# The monitor's phase (``sw/monitor``, beside the as-written step's): its
# local reductions are the program's array code, and the three
# ``allreduce``s carry the op surface's own scope inside it.
MONITOR = "monitor"
# a line's numbers as the monitor program hands them back, in order
MONITOR_LINE = ("nonfinite", "cfl", "h_min", "mass")


def make_monitor(cfg, comm):
    """Jitted global function ``(h, u, v) -> line``: each device reduces
    the **interior** of its own block (a ghost cell is a neighbour's
    interior cell, or a wall's copy of one, and is counted by nobody) to
    four numbers, and three ``allreduce``s over ``comm``, one a kind of
    reduction and each over all of the mesh at once, ordered by their
    token, make them the domain's:

    ``nonfinite``  how many values of ``h``, ``u``, ``v`` are not finite
                   (``SUM``; counted in float32 beside ``mass``, so
                   that a field's count and its other reductions are
                   one pass over it: exact to 2**24 a device, and never
                   0 for a count that is not);
    ``cfl``        ``max(|u| dt/dx, |v| dt/dy)`` (``MAX``);
    ``h_min``      the thinnest layer (``MIN``);
    ``mass``       ``sum(h) dx dy`` (``SUM``), which the flux form
                   conserves between walls and a periodic x; a float32
                   sum, in the order the compiler adds a block up.

    ``line`` is float32 ``(py, px, 4)``, every device's own copy of the
    one line in :data:`MONITOR_LINE`'s order.  Reads its arguments once
    and donates nothing: a job enqueues it between the call that made
    the state and the call that consumes it, as it does the snapshot
    program.  On a mesh of one device the ``allreduce`` is the identity
    and is still the call that is made."""
    G = cfg.ghost
    cfl_x, cfl_y = cfg.dt / cfg.dx, cfg.dt / cfg.dy

    def local_fn(h, u, v):
        with _phase(MONITOR):
            h, u, v = (a[G:-G, G:-G] for a in (h, u, v))
            # counted in the fields' own type, so that a field's count
            # and its other reductions are one pass over it
            bad = sum(jnp.sum((~jnp.isfinite(a)).astype(a.dtype))
                      for a in (h, u, v))
            mass = jnp.sum(h) * jnp.asarray(cfg.dx * cfg.dy, h.dtype)
            sums = jnp.stack([bad, mass])
            cfl = jnp.maximum(jnp.max(jnp.abs(u)) * jnp.asarray(cfl_x, h.dtype),
                              jnp.max(jnp.abs(v)) * jnp.asarray(cfl_y, h.dtype))
            sums, token = allreduce(sums, reductions.SUM, comm=comm)
            cfl, token = allreduce(cfl, reductions.MAX, comm=comm, token=token)
            h_min, token = allreduce(
                jnp.min(h), reductions.MIN, comm=comm, token=token)
            return jnp.stack([sums[0], cfl, h_min, sums[1]]).reshape(1, 1, 4)

    spec = jax.P(*comm.axes)
    return jax.jit(jax.shard_map(
        local_fn, mesh=comm.mesh, in_specs=(spec,) * 3,
        out_specs=jax.P(*comm.axes, None)))


@dataclass(frozen=True)
class Checkpoint:
    """Where a job saves its whole state, how often, and how.

    ``directory``: a :class:`mpi4jax_tpu.utils.checkpoint.Series`, one
    directory a save, named by the step it holds.  ``every_calls``: a
    save after every call whose number, counted from the integration's
    first, divides by this (0: only when :meth:`SolverJob.save` is
    called).  ``keep``: the newest saves left in the directory; an
    older one goes only once a new one is committed, so ``keep`` stand
    committed at every moment from the ``keep``-th on.
    ``ahead_bytes``: the most bytes of a save's pieces whose copies to
    the host are asked for and not yet fetched, and of a restore's
    whose copies to the device are not yet done, that the caller's host
    takes (what :class:`Snapshot` says of a staging buffer holds here, a
    state being larger than any snapshot); ``None``: the host sets no
    bound.  The library holds every job under ``checkpoint.AHEAD_BYTES``
    a device besides, whatever more the host would take: a device runs
    copies and programs through one queue, so what is asked for ahead of
    a call delays it (``AHEAD_BYTES`` has the measurement: 16e6 in
    flight cost a 76 ms call nothing that shows, 160e6 cost the call
    after a save 30 ms).  A piece is at most ``checkpoint.PIECE_BYTES`` a device and
    at most half the bound, so that one copy runs while the next waits.
    A job that has snapshots and saves keeps both under the host's bound
    together, one figure (:class:`SolverJob`)."""

    directory: object
    every_calls: int = 1
    keep: int = 2
    ahead_bytes: int = None

    def ahead(self, devices=1):
        """The bound a save and a restore of ``devices`` devices run
        under: the host's, and the devices' queues'."""
        queues = ckpt.AHEAD_BYTES * devices
        return queues if self.ahead_bytes is None else min(self.ahead_bytes, queues)

    def piece_bytes(self, devices=1):
        """The most of a piece that one of ``devices`` devices holds."""
        return min(ckpt.PIECE_BYTES, self.ahead(devices) // 2 // devices)


# The phases of a save and of a restore on the device, jax.named_scope
# segments inside ``mpi4jax_tpu.checkpoint``.
STAGE, UNSTAGE = "stage", "unstage"
# How a state carries its tendencies (``_step_wide`` says why there are
# two): at the fields' padded shape, or at the interior's.
PADDED, INTERIOR = "padded", "interior"


def make_stage(comm, plan):
    """Jitted ``state -> ((piece, ...), ...)``: each device cuts each of
    the state's arrays into the bands of rows of its own block that
    ``plan`` names (``[(lo, hi), ...]`` an array).  The pieces are
    copies: enqueued after the call that made the state and before the
    call that consumes it, they hold the step they were cut at whatever
    runs next, and each is small enough for a host to take."""

    def local_fn(state):
        with jax.named_scope(SCOPE_PREFIX + "checkpoint"), jax.named_scope(STAGE):
            return tuple(tuple(a[lo:hi] for lo, hi in rows)
                         for a, rows in zip(state, plan))

    spec = jax.P(*comm.axes)
    return jax.jit(jax.shard_map(
        local_fn, mesh=comm.mesh, in_specs=(_mesh_specs(comm),),
        out_specs=tuple((spec,) * len(rows) for rows in plan)))


def make_unstage(comm):
    """Jitted ``(piece, ...) -> array``: each device puts its bands of
    rows together again."""

    def local_fn(*pieces):
        with jax.named_scope(SCOPE_PREFIX + "checkpoint"), jax.named_scope(UNSTAGE):
            return jnp.concatenate(pieces, axis=0)

    spec = jax.P(*comm.axes)
    return jax.jit(lambda *pieces: jax.shard_map(
        local_fn, mesh=comm.mesh, in_specs=(spec,) * len(pieces),
        out_specs=spec)(*pieces))


def _reshape_tendencies(cfg, comm, to):
    """Jitted ``(dh, du, dv) -> (dh, du, dv)`` between the two forms a
    ``ghost=2`` state carries its tendencies in.  To ``INTERIOR``: the
    interior of each padded block.  To ``PADDED``: zeros round each
    block and, for ``du`` and ``dv``, the neighbours' edge cells in ring
    1 (periodic in x, nothing beyond a wall), which is what the kernel
    leaves there and steps from."""
    G = cfg.ghost

    def local_fn(dh, du, dv):
        if to == INTERIOR:
            return tuple(a[G:-G, G:-G] for a in (dh, du, dv))

        def ringed(a):
            a, _ = halo_exchange_2d(
                jnp.pad(a, 1), comm, periodic=(False, cfg.periodic_x), width=1)
            return jnp.pad(a, G - 1)

        return jnp.pad(dh, G), ringed(du), ringed(dv)

    spec = (jax.P(*comm.axes),) * 3
    return jax.jit(jax.shard_map(
        local_fn, mesh=comm.mesh, in_specs=spec, out_specs=spec))


class SolverJob:
    """The solver as a job that writes output and is saved, killed and
    resumed: the loop ``state = multistep(state)`` with, after every
    call, a snapshot of the state on its way to the host while the next
    calls run, and, after every ``checkpoint.every_calls`` calls, the
    whole state on its way to disk beside them.

    Every call donates its input, output or not, saved or not.  The
    snapshot program and a save's staging program read the state after
    call ``k`` and are enqueued before call ``k + 1``, which consumes
    that state: the device runs them in the order they were enqueued,
    so a snapshot and a save hold the state of the step they name and
    of no other.  A snapshot's copy to the host is asked for as it is
    produced, or as soon as ``snapshot.ahead_bytes`` lets it, and
    ``on_chunk(snapshot, step)`` is handed ``{name: numpy array}`` and
    the number of steps the state had made, in step order, every one,
    at most ``snapshot.lag`` snapshots after the newest produced; the
    live state is never handed out.

    A save (:meth:`save`; :class:`Checkpoint`) cuts the state into
    pieces on the device, which is all the loop waits for; background
    threads fetch the pieces under ``checkpoint.ahead()``, write them,
    and commit the save by a rename, which acknowledges it.  A save does not
    start before the one before it is acknowledged, so saves are
    acknowledged in step order, and the time the loop waits for that is
    counted.  :meth:`resume` takes the newest acknowledged save of a
    directory as the job's state; what an interrupted save left there
    is removed, never read.  A resumed job has no snapshot pending: the
    first it delivers is of the resumed step plus one call, and from
    there on its output is, step for step and bit for bit where the form
    is the same, what the job that was never stopped delivers.

    **One bound on what is on its way to the host.**  A host takes so
    many bytes of copies that are asked for and not yet fetched (its
    staging buffer), whatever they are copies of, so a job has one
    figure, ``ahead_bytes``: the least of ``snapshot.ahead_bytes`` and
    ``checkpoint.ahead_bytes`` (``None``: neither sets one).  Snapshots'
    copies, asked for by the loop, and a save's pieces', asked for by
    the save's thread, count against it together
    (:class:`checkpoint.HostBound`); the save's pieces stay under
    ``checkpoint.AHEAD_BYTES`` a device besides, the device's queue's
    bound.  First come, first served: the loop asks for a snapshot's
    copy as soon as it fits and a save takes the room that is left
    (PERF.md, PR 45, has the other form, a window set aside for a
    streaming save, measured beside this one).  A snapshot that is due
    and a save's oldest piece are waited for, under ``job/ask_wait`` on
    the loop's thread and ``checkpoint/fetch_wait`` on the save's, and
    neither kind takes room ahead of the other's wait.  A copy that
    alone is over the bound goes alone, with nothing else in flight:
    only then, and by that copy's size, does
    ``stats()["host_in_flight_max_bytes"]`` read over the bound.
    Snapshots block the loop when late, a save only when the next comes
    due; while the loop waits for a save whose pieces wait for room
    that snapshots hold, it delivers those snapshots early (so wait for
    a save through :meth:`drain` or the next :meth:`save`).  A job with
    only one half never waits for room, and is what it was.

    **A job that watches itself** (``monitor``, a :class:`Monitor`).
    After every ``monitor.every_calls`` calls the monitor program
    (:func:`make_monitor`; ``mon``) reads ``h``, ``u``, ``v`` once,
    enqueued after the call that made them and before the call that
    consumes them, as the snapshot program is, so a line is of the
    state of the step it names and of no other.  Its line starts for
    the host at once, is read there at most ``monitor.lag`` calls after
    the newest call enqueued (the loop waits, under ``job/monitor_wait``,
    only for a line that old) and is handed to ``on_monitor(line)`` as
    ``{"step", "nonfinite", "cfl", "h_min", "mass"}``, in step order,
    every one.  A line that :meth:`Monitor.why_bad` names stops the
    job: :meth:`advance` (or :meth:`drain`) raises :class:`MonitorStop`
    with the line, ``stopped`` keeps it, no call is enqueued after it
    is read, and every later :meth:`advance` raises again until the job
    is given a state anew (:meth:`start`, :meth:`resume`); the lines of
    the calls enqueued before the stop are still handed out, by
    :meth:`drain`.  A resumed job has no line pending: its first is of
    the resumed step plus ``every_calls`` calls.

    ``first``, ``multi``, ``snap`` and ``stage`` are the jitted
    programs.  Where the step is the kernel and the snapshot's blocks
    are made of its strips' rows (:func:`_sums_in_step`), ``first`` and
    ``multi`` run the one kernel that can sum rows (its sums on in a
    call's last walk alone), ``multi`` is ``(state, row sums) ->
    (state, row sums)`` and ``snap`` is handed **those sums**, not
    ``h``, ``u``, ``v``.  The sums' room (a ``coarsen``-th of three
    fields) is made when the job starts (``first`` returns it beside
    the state), written by each call's last walk and donated to the
    next call with the state, which the device runs after the ``snap``
    that reads it, as it does for the fields; it is no part of
    ``state`` and is not saved: a resumed job makes its own
    (:func:`make_sums_room`)
    (``stats()["snapshots_summed_in_step"]`` counts such snapshots, and
    the ``snap`` program's ``job/enqueue`` span says ``handed``
    ``row_sums`` or ``fields``).  ``state``, ``step`` and ``calls`` are
    the model as the last
    enqueued call leaves it; ``series`` the checkpoint's directory (a
    :class:`checkpoint.Series`) and ``saves`` the acknowledged saves'
    records, in order.

    ``trace`` (a :class:`mpi4jax_tpu.utils.spans.Recorder`) keeps what
    the job's host code was doing, on ``time.perf_counter_ns()``:
    ``job/advance`` a call of :meth:`advance`; ``job/enqueue`` a call of
    a program (``program`` says which); ``job/ask`` a snapshot's copy
    started; ``job/fetch`` and ``job/callback`` a delivery;
    ``job/save`` with ``job/save_wait`` and ``job/save_start`` inside
    it; ``job/drain``; ``job/resume`` with ``checkpoint/read`` and
    ``checkpoint/to_device`` a piece and ``job/compile``; and, on a
    save's threads, ``checkpoint/save``, ``/fetch``, ``/write``,
    ``/commit`` and ``/prune``; ``job/ask_wait`` and
    ``checkpoint/fetch_wait`` a copy held back by the other kind's
    bytes (``held_by``, ``bytes``); ``job/monitor_wait`` a monitor's
    line read on the host and ``job/monitor_callback`` ``on_monitor``.
    A span's ``key`` is the model step it is about.  :meth:`spans`
    returns them; every time in :meth:`stats`
    is the sum of its spans'.
    """

    def __init__(self, cfg, comm, num_multisteps, snapshot, on_chunk,
                 checkpoint=None, monitor=None, on_monitor=None):
        self.cfg, self.comm = cfg, comm
        self.num_multisteps = num_multisteps
        self.snapshot, self.on_chunk = snapshot, on_chunk
        self.monitor, self.on_monitor = monitor, on_monitor
        self.mon = monitor and make_monitor(cfg, comm)
        self._mon = self.mon
        self._lines = collections.deque()  # (step, call, device array), oldest first
        self._stop = None  # the MonitorStop that stopped the job
        self.ahead_bytes = min(
            (half.ahead_bytes for half in (snapshot, checkpoint)
             if half is not None and half.ahead_bytes is not None), default=None)
        if checkpoint is not None:  # its pieces are cut for the job's bound
            checkpoint = replace(checkpoint, ahead_bytes=self.ahead_bytes)
        self.checkpoint = checkpoint
        self.first = make_first_step(cfg, comm, snapshot)
        self.multi = make_multistep(
            cfg, comm, num_multisteps, donate=True, snapshot=snapshot)
        # the room for the snapshot's row sums, which `multi` takes and
        # returns beside the state where the step makes them: `first`
        # returns it too, and this makes it for a run that starts later
        self._room = make_sums_room(cfg, comm, snapshot)
        self._summed, self._sums = self._room is not None, ()
        self.snap = snapshot and make_snapshot(
            cfg, comm, snapshot, from_sums=self._summed)
        self._multi, self._snap = self.multi, self.snap
        self.state, self.step, self.calls = None, 0, 0
        self._pending = collections.deque()  # (step, device arrays), oldest first
        self._asked = 0  # of them, from the oldest: their copies are on their way
        self.series = checkpoint and ckpt.Series(
            checkpoint.directory, keep=checkpoint.keep)
        self.stage, self._plan, self._save = None, None, None
        if checkpoint is not None:
            self._plan = self._piece_plan()
            self.stage = make_stage(comm, self._plan)
        self._stage = self.stage
        self.saves = []
        self.trace = spans.Recorder(SCOPE_PREFIX)
        self._host = ckpt.HostBound(self.ahead_bytes)
        self._copies = ckpt.Side(self._host, ckpt.SNAPSHOT, partial(
            self.trace.span, "job/ask_wait"))
        self._stats = dict(
            steps_per_walk=2 if _walks_two_steps(cfg, comm) else 1,
            snapshots_produced=0, snapshots_summed_in_step=0,
            snapshots_delivered=0, max_lag=0,
            bytes_to_host=0, output_wait_s=0.0, callback_s=0.0,
            saves_started=0, save_bytes=0, save_wait_s=0.0, save_enqueue_s=0.0,
            restore_read_s=0.0, restore_to_device_s=0.0,
            monitor_lines=0, monitor_max_lag_calls=0, monitor_wait_s=0.0,
            monitor_stops=0)

    def start(self, state, step=0):
        """Take ``state`` as the model after ``step`` steps.  A state at
        step 0 (``make_init``'s, or the caller's own fields) is put
        through the forward-Euler step, which does not donate it; a
        later one (a checkpoint's) is taken as it is.  What the run
        before still had on its way is finished first; a line of its
        that stops a job is handed out and counted, and not raised: the
        job is given a state anew."""
        self._drain(raises=False)
        self._stop = None
        self._host.peak = self._host.in_flight
        if step == 0:
            out, step = self.first(state), 1
            state, self._sums = out if self._summed else (out, ())
        elif self._summed:
            self._sums = self._room()
        self.state, self.step = state, step
        self.calls = (step - 1) // self.num_multisteps

    def compile(self):
        """Compile the call's programs for the state at hand without
        running them: a resumed run has no warm-up call to spend."""
        with self.trace.span("job/compile", key=self.step):
            self._multi = self.multi.lower(
                self.state, *[self._sums] * self._summed).compile()
            if self.snap is not None:
                self._snap = self.snap.lower(*self._handed()).compile()
            if self.stage is not None:
                self._stage = self.stage.lower(self.state).compile()
            if self.mon is not None:
                self._mon = self.mon.lower(*self.state[:3]).compile()

    def advance(self, calls=1):
        """Enqueue ``calls`` multistep calls, after each the snapshot,
        where ``monitor.every_calls`` says so the monitor program and,
        where ``checkpoint.every_calls`` says so, a save; ask for
        the snapshots' copies to the host that are next in line, and
        deliver every snapshot and read every monitor's line that is
        due.  Raises :class:`MonitorStop` where a line read stops the
        job, before the next call is enqueued, and from then on."""
        every = self.checkpoint.every_calls if self.checkpoint else 0
        span = self.trace.span
        if self._stop is not None:
            raise self._stop
        with span("job/advance", key=self.step + self.num_multisteps, calls=calls):
            for _ in range(calls):
                with span("job/enqueue", key=self.step + self.num_multisteps,
                          program="multi"):
                    out = self._multi(self.state, *[self._sums] * self._summed)
                self.state, self._sums = out if self._summed else (out, ())
                self.step += self.num_multisteps
                self.calls += 1
                if self.snap is not None:
                    with span("job/enqueue", key=self.step, program="snap",
                              handed="row_sums" if self._summed else "fields"):
                        parts = self._snap(*self._handed())
                    self._pending.append((self.step, parts))
                    self._stats["snapshots_produced"] += 1
                    self._stats["snapshots_summed_in_step"] += self._summed
                    self._ask()
                    self._deliver(self.snapshot.lag)
                if self.mon is not None:
                    if self.calls % self.monitor.every_calls == 0:
                        with span("job/enqueue", key=self.step, program="mon"):
                            line = self._mon(*self.state[:3])
                        # every device holds the one line: the first's goes
                        line = line.addressable_shards[0].data
                        line.copy_to_host_async()
                        self._lines.append((self.step, self.calls, line))
                    self._read_lines(self.monitor.lag)
                if every and self.calls % every == 0:
                    self.save()
        return self.state

    def drain(self):
        """Deliver every snapshot and read every monitor's line still
        on its way, wait for the save on its way to be acknowledged,
        and leave the directory with its committed saves and nothing
        else (the files a series keeps for its next save go).  Raises
        :class:`MonitorStop` where a line read now stops the job, with
        all of that done."""
        self._drain()

    def _drain(self, raises=True):
        # the lines last: one that stops the job leaves no save
        # unacknowledged and no spare file behind
        with self.trace.span("job/drain", key=self.step):
            self._deliver(0)
            self._settle()
            if self.series is not None:
                self.series.clean()
            self._read_lines(0, raises)

    def stats(self):
        """The job's counters: ``steps_per_walk``, the time steps that
        a pass over the state advances in the calls' loop (2 where the
        step is the kernel and :func:`_walks_two_steps` holds, on one
        device and beside neighbours; 1 where it is array code or the
        block has no room); ``snapshots_produced``,
        ``snapshots_summed_in_step`` (of them, those whose sums over
        ``coarsen`` rows the call's own last walk made: all where the
        step is the kernel and :func:`_sums_in_step` holds, none
        elsewhere) and
        ``snapshots_delivered``; ``max_lag``, the most snapshots one was
        delivered behind the newest; ``bytes_to_host``;
        ``output_wait_s``, host seconds spent fetching snapshots (blocked
        on a copy that was not ready, or putting shards together);
        ``callback_s``, host seconds inside ``on_chunk``;
        ``saves_started``, ``saves_acknowledged`` and ``save_bytes`` (of
        the saves started); ``save_wait_s``, host seconds the loop was
        blocked because of a save (waiting for the save before to be
        acknowledged); ``save_enqueue_s``, host seconds starting saves
        (enqueueing the staging program and handing its pieces on: host
        time beside whatever the device has queued); ``save_stage_s`` and
        ``save_commit_s``, summed over the acknowledged saves: from a
        save's start to its last piece on the host, and to its rename
        (both pass beside the loop; ``saves`` has them save by save);
        ``restore_read_s`` and ``restore_to_device_s``, host seconds a
        resume spent reading files and handing them to the device;
        ``host_in_flight_max_bytes``, the most bytes of copies to the
        host asked for and not yet fetched at any moment since
        :meth:`start` or :meth:`resume`, snapshots' and pieces'
        together; ``transfer_wait_s``, seconds a copy was held back by
        the other kind's bytes under the job's one bound, the loop's
        (``job/ask_wait``) and the acknowledged saves'
        (``checkpoint/fetch_wait``); ``monitor_lines``, the monitor's
        lines read and handed to ``on_monitor``;
        ``monitor_max_lag_calls``, the most calls one was read after
        the call that made it; ``monitor_wait_s``, host seconds reading
        lines (blocked where one was not ready: ``job/monitor_wait``);
        ``monitor_stops``, the lines that stopped the job (one a state
        the job was given).
        Every time is the sum of its spans' (:meth:`spans`)."""
        saves = list(self.saves)
        return dict(
            self._stats, saves_acknowledged=len(saves),
            save_stage_s=sum(r["stage_s"] for r in saves),
            save_commit_s=sum(r["commit_s"] for r in saves),
            host_in_flight_max_bytes=self._host.peak,
            transfer_wait_s=self._copies.waited_s + sum(
                r["fetch_wait_s"] for r in saves))

    def spans(self):
        """The finished spans of the job's host code and of its saves'
        threads (``utils.spans.Span``), in the order they ended."""
        return self.trace.spans()

    # -- saving and resuming ---------------------------------------------

    def form(self):
        """What a save of this job holds besides numbers: the global
        grid, the mesh, the ghost width and the form of the tendencies.
        A state restores bit for bit into the form it was saved in."""
        interior = self.cfg.ghost == 2 and not _runs_as_kernels(self.cfg, self.comm)
        return {
            "grid": [self.cfg.ny, self.cfg.nx],
            "mesh": list(self.comm.axis_sizes), "ghost": self.cfg.ghost,
            "dtype": jnp.dtype(self.cfg.dtype).name,
            "tendencies": INTERIOR if interior else PADDED,
        }

    def save(self):
        """Start a save of the state at hand: wait for the save before
        it to be acknowledged, enqueue the staging program, and hand its
        pieces to the threads that fetch and write them.  Returns the
        :class:`checkpoint.Save`; ``drain()`` waits for it.  A job that
        watches itself reads every line made so far first, and a
        stopped one saves nothing (:class:`MonitorStop`): the state at
        hand is at or after the line that stopped it."""
        if self.checkpoint is None:
            raise ValueError("the job was made without a `checkpoint`")
        self._read_lines(0)
        if self._stop is not None:
            raise self._stop
        span, step = self.trace.span, self.step
        with span("job/save", key=step,
                  bytes=sum(a.nbytes for a in self.state)) as whole:
            if self._save is not None and not self._save.committed:
                with span("job/save_wait", key=self._save.step) as waited:
                    self._settle()
                self._stats["save_wait_s"] += waited.seconds
            self._settle()  # a committed one's pruning is no wait for a save
            with span("job/save_start", key=step) as started:
                self._save = self._start_save(whole.id)
            self._stats["saves_started"] += 1
            self._stats["save_bytes"] += self._save.bytes
            self._stats["save_enqueue_s"] += started.seconds
        return self._save

    def _start_save(self, cause):
        """Enqueue the staging program and hand its pieces on."""
        if not all(a.is_fully_addressable for a in self.state):
            raise NotImplementedError(
                "a job's save goes through the process that enqueued it; a "
                "mesh over several processes is saved with "
                "utils.checkpoint.Manager")
        # a piece is one band of rows of every device's block: in its
        # file an array's pieces lie one after the other, rows by device
        py = self.comm.axis_sizes[0]
        files = {f"{name}.npy": (a.shape, a.dtype)
                 for name, a in zip(SWState._fields, self.state)}
        with self.trace.span("job/enqueue", key=self.step, program="stage"):
            staged = self._stage(self.state)
        pieces = [((file, py * lo), piece)
                  for file, plan, of_array in zip(files, self._plan, staged)
                  for (lo, _), piece in zip(plan, of_array)]
        manifest = {
            "format": 1, "step": self.step, "form": self.form(),
            "arrays": {name: {"file": file, "shape": list(a.shape), "bands": plan}
                       for name, a, file, plan in zip(
                           SWState._fields, self.state, files, self._plan)}}
        return ckpt.Save(
            self.series, self.step, manifest, files, pieces,
            ahead_bytes=self.checkpoint.ahead(self.comm.size),
            on_commit=self.saves.append, trace=self.trace, cause=cause,
            bound=self._host)

    def resume(self, directory=None):
        """Take the newest acknowledged save of ``directory`` (the
        ``checkpoint``'s unless given) as the job's state, and compile
        the call's programs for it.  What an interrupted save left in
        the directory is removed.  The state is read and sent to the
        device in the pieces it was saved in, under
        ``checkpoint.ahead()``.  A save of another grid, mesh or
        ghost width is refused with both named; one that carries its
        tendencies in the other form (made where the step is array code
        and resumed where it is the kernel, or the reverse) is
        converted.  Returns the step resumed from, or ``None`` where the
        directory holds no save (the job is then as it was)."""
        series = self.series if directory is None else ckpt.Series(directory)
        if series is None:
            raise ValueError("no directory: the job has no `checkpoint`")
        with self.trace.span("job/resume") as whole:
            self._drain(raises=False)  # as `start` does
            series.clean()
            step = whole.key = series.latest()
            if step is not None:
                whole.counts["bytes"] = self._restore(series, step)
        return step

    def _restore(self, series, step):
        """The save of ``step`` as the job's state, its programs
        compiled; returns the bytes read."""
        manifest = series.manifest(step)
        mine, saved = self.form(), manifest["form"]
        for key in ("grid", "mesh", "ghost", "dtype"):
            if mine[key] != saved[key]:
                raise ValueError(
                    f"{series.path(step)} holds a state of {key} {saved[key]} "
                    f"(its form: {saved}), this job runs {key} {mine[key]} "
                    f"(its form: {mine}): a save is read back by a job of "
                    "the same grid, mesh, ghost width and dtype")
        ahead = (self.checkpoint or Checkpoint(None)).ahead(self.comm.size)
        sharding = jax.NamedSharding(self.comm.mesh, jax.P(*self.comm.axes))
        unstage = make_unstage(self.comm)
        arrays, py = [], self.comm.axis_sizes[0]
        for name in SWState._fields:
            held = manifest["arrays"][name]
            pieces, read_s, to_device_s = ckpt.read_pieces(
                series.path(step) / held["file"],
                [(py * lo, py * hi) for lo, hi in held["bands"]], sharding, ahead,
                trace=self.trace, key=step)
            arrays.append(unstage(*pieces))
            self._stats["restore_read_s"] += read_s
            self._stats["restore_to_device_s"] += to_device_s
        if saved["tendencies"] != mine["tendencies"]:
            arrays[3:] = _reshape_tendencies(
                self.cfg, self.comm, mine["tendencies"])(*arrays[3:])
        self.start(SWState(*arrays), step=manifest["step"])
        self.compile()
        return sum(a.nbytes for a in arrays)

    def _piece_plan(self):
        """The bands of rows of a device's block that a save cuts each
        of the state's arrays into (``Checkpoint.piece_bytes``)."""
        G = self.cfg.ghost
        ny_l, nx_l = self.cfg.local_interior(self.comm)
        padded, devices = (ny_l + 2 * G, nx_l + 2 * G), self.comm.size
        tendency = (ny_l, nx_l) if self.form()["tendencies"] == INTERIOR else padded
        itemsize = jnp.dtype(self.cfg.dtype).itemsize
        return tuple(
            ckpt.piece_rows(rows, width * itemsize,
                            self.checkpoint.piece_bytes(devices))
            for rows, width in (padded,) * 3 + (tendency,) * 3)

    def _settle(self):
        """Wait for the save on its way, if any, to be acknowledged.
        Where its pieces wait for room that snapshots' copies hold, the
        oldest of those is delivered now: only this thread fetches them."""
        if self._save is not None:
            save, self._save = self._save, None
            while not self._host.until(lambda: save.done, ckpt.SNAPSHOT):
                self._deliver(len(self._pending) - 1)
            save.wait()

    # -- the monitor -------------------------------------------------------

    @property
    def stopped(self):
        """The line that stopped the job; ``None`` while none has."""
        return self._stop and self._stop.line

    def _read_lines(self, lag, raises=True):
        """Read, oldest first, every line made ``lag`` calls or more
        before the newest call enqueued, hand each to ``on_monitor``,
        and raise :class:`MonitorStop` at the first that stops the job
        (once a state: a stopped job's later lines are handed out and
        decide nothing).  ``raises`` false: the stop is kept and
        counted, and every line due is handed out."""
        stats, span = self._stats, self.trace.span
        while self._lines and self.calls - self._lines[0][1] >= lag:
            step, call, on_device = self._lines.popleft()
            with span("job/monitor_wait", key=step) as waited:
                numbers = np.asarray(on_device).ravel()
            stats["monitor_wait_s"] += waited.seconds
            line = dict(zip(MONITOR_LINE, numbers.tolist()), step=step)
            line["nonfinite"] = int(round(line["nonfinite"]))
            stats["monitor_lines"] += 1
            stats["monitor_max_lag_calls"] = max(
                stats["monitor_max_lag_calls"], self.calls - call)
            if self.on_monitor is not None:
                with span("job/monitor_callback", key=step):
                    self.on_monitor(line)
            why = self.monitor.why_bad(line)
            if why is not None and self._stop is None:
                self._stop = MonitorStop(line, why, self.calls - call)
                stats["monitor_stops"] += 1
                if raises:
                    raise self._stop

    # -- output ------------------------------------------------------------

    def _written(self):
        return tuple(getattr(self.state, k) for k in self.snapshot.fields)

    def _handed(self):
        """What ``snap`` reads: the fields' row sums the call's last
        walk wrote, or the fields."""
        if not self._summed:
            return self._written()
        return tuple(self._sums[SWState._fields.index(k)]
                     for k in self.snapshot.fields)

    def _ask(self, cause=None, wait=False):
        """Start the copies to the host of the oldest snapshots not yet
        asked for, as far as the job's bound goes (``wait``: the oldest
        is due, and is waited for where a save's pieces hold its room);
        returns the seconds that took.  ``cause``: the fetch that made
        the room."""
        seconds = 0.0
        while self._asked < len(self._pending):
            step, parts = self._pending[self._asked]
            size = sum(part.nbytes for part in parts)
            if not self._copies.take(size, wait and not self._asked, key=step):
                break
            with self.trace.span("job/ask", key=step, cause=cause, bytes=size) as asked:
                for part in parts:
                    part.copy_to_host_async()
            self._asked += 1
            seconds += asked.seconds
        return seconds

    def _deliver(self, keep):
        stats, span = self._stats, self.trace.span
        while len(self._pending) > keep:
            if not self._asked:  # its room was a save's pieces'
                self._ask(wait=True)
            step, parts = self._pending.popleft()
            size = sum(part.nbytes for part in parts)
            with span("job/fetch", key=step, bytes=size) as fetched:
                arrays = {k: np.asarray(part)
                          for k, part in zip(self.snapshot.fields, parts)}
            self._asked -= 1
            self._copies.give(size)
            stats["output_wait_s"] += fetched.seconds + self._ask(fetched.id)
            if self.on_chunk is not None:
                with span("job/callback", key=step) as called:
                    self.on_chunk(arrays, step)
                stats["callback_s"] += called.seconds
            stats["bytes_to_host"] += sum(a.nbytes for a in arrays.values())
            stats["max_lag"] = max(stats["max_lag"], len(self._pending))
            stats["snapshots_delivered"] += 1


def make_job(cfg, comm, num_multisteps=10, snapshot=None, on_chunk=None,
             checkpoint=None, monitor=None, on_monitor=None):
    """The solver's loop as an object (:class:`SolverJob`):
    ``job.start(state)`` or ``job.resume()``, ``job.advance(calls)``,
    ``job.drain()``, ``job.stats()``.  ``snapshot``: a :class:`Snapshot`,
    or ``None`` for a job that writes nothing (``on_chunk`` alone asks
    for whole fields of ``h``, ``u`` and ``v``).  ``checkpoint``: a
    :class:`Checkpoint`, or ``None`` for a job that is never saved.
    ``monitor``: a :class:`Monitor`, or ``None`` for a job that does not
    watch its solution; ``on_monitor(line)`` is handed every line of a
    job that has one."""
    if snapshot is None and on_chunk is not None:
        snapshot = Snapshot()
    if monitor is None and on_monitor is not None:
        raise ValueError("`on_monitor` without a `monitor`: nothing makes a line")
    return SolverJob(cfg, comm, num_multisteps, snapshot, on_chunk, checkpoint,
                     monitor, on_monitor)


def make_solver(
    cfg,
    comm,
    num_multisteps=10,
    on_chunk=None,
    checkpoint_dir=None,
    checkpoint_every=1,
    snapshot=None,
    monitor=None,
    on_monitor=None,
):
    """Full driver: init → bootstrap step → repeated jitted multisteps,
    a loop over one :class:`SolverJob`.

    Returns ``solve(t1_seconds) -> (state, wall_seconds, n_steps)`` where
    wall time covers only the post-compile hot loop, matching the
    reference's benchmark methodology (shallow_water.py:450-470).

    ``on_chunk(snapshot, step)``, if given, is handed the output of
    every multistep chunk (including the warm-up one): host arrays by
    name, ``snapshot`` saying which fields and how coarse (a
    :class:`Snapshot`; whole ``h``, ``u``, ``v`` if left out), and the
    step they belong to, in step order and at most ``snapshot.lag``
    chunks late; the rest arrive before ``solve`` returns.  E.g. to
    collect animation frames, as the reference's plotting loop does
    (shallow_water.py:586-599 there).  The snapshots' copies to the
    host run beside the next chunks and every chunk donates its input,
    output or not; callback time is included in the wall clock.

    ``checkpoint_dir`` enables resumable runs (SURVEY §5.4 — absent in
    the reference): after every ``checkpoint_every`` chunks (counted
    from the run's first, the warm-up one) the job saves its whole state
    there (:class:`Checkpoint`, :meth:`SolverJob.save`), and a ``solve``
    in a directory that holds a save resumes from the newest instead of
    re-initialising, its programs compiled ahead of the clock.  A save
    costs the loop the staging program's enqueue; the copies to the
    host, the files and the commit pass beside the next chunks, unless
    the next save comes due before the last is acknowledged: then the
    loop waits, inside the wall clock (``stats()['save_wait_s']``).
    Every save is acknowledged before ``solve`` returns.

    With both (``on_chunk`` and ``checkpoint_dir``) the job keeps the
    snapshots' copies and a save's pieces under one bound on what is on
    its way to the host, ``snapshot.ahead_bytes`` (:class:`SolverJob`),
    and a restarted run's output is the uninterrupted run's: a ``solve``
    that resumes hands ``on_chunk`` the chunks after the newest save,
    step for step and bit for bit what the run that was never stopped
    hands out (chunks that the stopped run had delivered after that save
    are delivered again, the same).

    ``monitor`` (a :class:`Monitor`) and ``on_monitor(line)``: the job
    watches its solution as it goes, and ``solve`` raises
    :class:`MonitorStop` at most ``monitor.lag`` chunks after the one
    that went bad, where a run without would be paid for to its end.
    """
    init = make_init(cfg, comm)
    checkpoint = None
    if checkpoint_dir is not None:
        checkpoint = Checkpoint(checkpoint_dir, every_calls=checkpoint_every or 0)
    job = make_job(cfg, comm, num_multisteps, snapshot, on_chunk, checkpoint,
                   monitor, on_monitor)
    chunk_s = cfg.dt * num_multisteps

    def solve(t1):
        resumed = checkpoint is not None and job.resume() is not None
        if not resumed:
            job.start(init())
            # warm-up compile (excluded from timing, as in the reference)
            job.advance()
        # model time summed chunk by chunk, as the loop below sums it,
        # so that a resumed run stops where an uninterrupted one does
        t = cfg.dt + chunk_s
        for _ in range(job.calls - 1):
            t += chunk_s
        jax.block_until_ready(job.state)
        steps = 0
        start = time.perf_counter()
        # always time at least one multistep on a FRESH run, even if
        # the warm-up call already advanced past t1 (short runs /
        # large chunks).  A resumed run must not: rerunning a
        # completed run in the same directory would otherwise push
        # the trajectory past t1 and save checkpoints beyond it.
        while t < t1 or (steps == 0 and not resumed):
            job.advance()
            t += chunk_s
            steps += num_multisteps
        job.drain()
        jax.block_until_ready(job.state)
        wall = time.perf_counter() - start
        return job.state, wall, steps

    return solve


def gather_global(local_field, comm, *, ghost=1):
    """Reassemble a global interior field from per-device blocks (the
    reference gathers to rank 0 for plotting, shallow_water.py:586-593).

    Must be called inside shard_map; returns the (ny, nx) global array
    (replicated logical value, device-varying layout).
    """
    G = ghost
    blocks, _ = allgather(local_field[G:-G, G:-G], comm=comm)
    py, px = comm.axis_sizes
    ny_l, nx_l = local_field.shape[0] - 2 * G, local_field.shape[1] - 2 * G
    grid = blocks.reshape(py, px, ny_l, nx_l)
    return grid.transpose(0, 2, 1, 3).reshape(py * ny_l, px * nx_l)


# -- t4j-lint entries: the model's own communication schedule, one per
# ghost-width schedule variant (1 = reference layout, 2 = wide-halo,
# 4 = single-exchange) — the three schedules differ in exchange
# structure and each must stay contract-clean.


def _lint_step(ghost):
    def thunk():
        import jax

        from mpi4jax_tpu.parallel.comm import MeshComm

        mesh = jax.make_mesh(
            (2, 4), ("y", "x"),
            axis_types=(jax.sharding.AxisType.Auto,) * 2,
        )
        comm = MeshComm.from_mesh(mesh)
        cfg = SWConfig(ny=8, nx=16, ghost=ghost)
        return make_multistep(cfg, comm, num_steps=1)(
            make_init(cfg, comm)()
        )

    thunk.__name__ = f"step_ghost{ghost}"
    return thunk


T4J_LINT_ENTRIES = [_lint_step(g) for g in (1, 2, 4)]
