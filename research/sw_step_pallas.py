"""Fused Pallas TPU kernels for the shallow-water wide-halo step.

.. admonition:: Research appendix, not a production path
   (round 4; moved out of the package into ``research/`` in round 5 —
   its equivalence suite is the opt-in ``pytest research/``)

   Nothing in the package selects these kernels.  The verdict that
   retired them, "shuffle-bound, measurably slower (5.8 ms vs 3.3 ms per
   step)", is an older chip allotment's (the builders' before PR 1, on a
   chip that granted 80-180 GB/s) and does not hold on the v5e: there a
   row-tiled stencil kernel with the same lane rotations and sublane
   shifts runs at what a plain copy reaches, 614 GB/s in PR 25 (ledger
   and ``PERF.md``) and 632 GB/s for the viscosity round that
   ``mpi4jax_tpu/models/sw_kernels.py`` now puts on the main path
   (``PERF.md``, PR 27).  That module is the production kernel and
   differs from this file where the chip's compiler forced it: the
   field is updated in place through a VMEM window one tile behind its
   input (the 8-row neighbour blocks below make XLA copy a field that a
   custom call both overwrites and reads through a second operand), and
   a tile is walked in strips of 8 rows.  This file stays as the
   equivalence-tested scaffold of round 1 (``_main_kernel``), which is
   the next kernel on that tiling (``ROADMAP.md`` S8, D9), and its
   viscosity kernel as the record of the scheme it started from.

The XLA form of :func:`mpi4jax_tpu.models.shallow_water._step_wide`
materialises ~10 intermediate full-size fields per step (hc, fluxes,
vorticity, kinetic energy, viscosity gradients), each a full HBM
round-trip — ~3.2 GB accessed per step on the published benchmark
domain, ~8x the ideal.  These kernels compute the whole step in two
``pallas_call``s (main tendencies + AB2 update, then viscosity) that
stream row tiles through VMEM: every intermediate lives on-chip, so the
per-step HBM traffic drops to the state fields themselves (read h/u/v
and the previous tendencies once, write the six outputs once).

Numerics are identical to the ``_step_wide`` schedule (asserted to
float32 roundoff by tests/test_shallow_water_pallas.py), which is in
turn equal to the reference's narrow schedule
(examples/shallow_water.py:277-412).

Tiling scheme
-------------
The stencil has radius 2 (ring-1 intermediates recomputed locally from
prognostics, wide-halo invariant).  Arrays keep full width ``W`` (x is
never tiled; the ghost columns exchanged by ``halo_exchange_2d`` are in
range, so x-shifts are lane-rolls whose wrap pollution lands only in
ring positions no consumer reads).  Rows are tiled by ``R`` (a multiple
of 8); each tile additionally reads two 8-row neighbour blocks (block
indices clamped at the edges) and assembles an ``(R+4, W)`` working
buffer by sublane concatenation — the 2-deep row halo.  Outputs are
written through an interior mask: ghost rows/columns pass the input
through (the next halo exchange refreshes them), exactly like the XLA
path's interior-only updates.

Wall conditions are pure masks in the kernel (`is_south`/`is_north`
device flags arrive via SMEM); the one value-gather — clamping ``h``'s
wall ghost rows so ``hc == h`` — happens outside in
:func:`clamp_wall_ghost_rows` (a 2-row dynamic-update-slice per edge
device, applied right after each exchange of ``h``).

State layout: all six fields full-shape ``(ny_l+4, nx_l+4)`` (the XLA
wide path stores tendencies interior-only; here they ride the same
specs as the prognostics — see :func:`pad_state`).
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mpi4jax_tpu.models import shallow_water as sw
from mpi4jax_tpu.ops._core import as_token
from mpi4jax_tpu.parallel.halo import halo_exchange_2d

__all__ = [
    "make_multistep_pallas",
    "make_first_step_pallas",
    "pad_state",
    "pallas_supported",
]

G = 2  # ghost width; kernels implement the wide-halo schedule only


def _roll(a, dx):
    """Lane-roll so element [., c] reads [., c + dx] (the x-shift of
    ``_ring_view``; wrap wraps, but no consumer reads wrapped lanes)."""
    if dx == 0:
        return a
    return jnp.roll(a, -dx, axis=1)


def _choose_block_rows(rows, target):
    r = min(target, rows)
    r -= r % 8
    return max(r, 8)


def _main_kernel(
    scal_ref,
    h_ref, u_ref, v_ref,
    htop, hbot, utop, ubot, vtop, vbot,
    dh_ref, du_ref, dv_ref,
    h_out, u_out, v_out, dh_out, du_out, dv_out,
    *, cfg, ny_l, nx_l, R, W, first_step,
):
    i = pl.program_id(0)
    row0 = i * R  # global (array) row of this tile's first output row
    is_s = scal_ref[0, 0] == 1
    is_n = scal_ref[0, 1] == 1
    iy = scal_ref[0, 2]
    dx, dy, grav = cfg.dx, cfg.dy, cfg.gravity
    f32 = jnp.float32

    # (R+4, W) working buffers: rows row0-2 .. row0+R+2
    hw = jnp.concatenate([htop[6:8], h_ref[...], hbot[0:2]], axis=0)
    uw = jnp.concatenate([utop[6:8], u_ref[...], ubot[0:2]], axis=0)
    vw = jnp.concatenate([vtop[6:8], v_ref[...], vbot[0:2]], axis=0)

    def V(a, r, dyr=0, dxr=0):
        """Ring-r view (rows only; x stays full-width via rolls)."""
        s = 2 - r + dyr
        return _roll(a, dxr)[s : s + R + 2 * r, :]

    def ring1_rows(shape):
        """Global array-row index of each element of a ring-1 field."""
        return row0 - 1 + lax.broadcasted_iota(jnp.int32, shape, 0)

    def zero_wall(a1, extra_north=False):
        g = ring1_rows(a1.shape)
        kill = (is_s & (g == 1)) | (is_n & (g == ny_l + 2))
        if extra_north:
            kill = kill | (is_n & (g == ny_l + 1))
        return jnp.where(kill, jnp.zeros((), a1.dtype), a1)

    # ring-1 helpers on (R+2, W) fields
    def ti(a):
        return a[1:-1, :]

    def te(a):
        return _roll(a, 1)[1:-1, :]

    def tw(a):
        return _roll(a, -1)[1:-1, :]

    def tn(a):
        return a[2:, :]

    def ts(a):
        return a[:-2, :]

    # hc == hw: wall ghost rows are pre-clamped by clamp_wall_ghost_rows
    fe = 0.5 * (V(hw, 1) + V(hw, 1, 0, 1)) * V(uw, 1)
    fn = 0.5 * (V(hw, 1) + V(hw, 1, 1, 0)) * V(vw, 1)
    fe = zero_wall(fe)
    fn = zero_wall(fn, extra_north=True)

    dh_new = -(ti(fe) - tw(fe)) / dx - (ti(fn) - ts(fn)) / dy

    # coriolis on the ring-1 rows (shallow_water._local_mesh_coords)
    g1 = ring1_rows((R + 2, W)).astype(f32)
    yy1 = (g1 - 2.0 + (iy * ny_l).astype(f32)) * dy
    cor = (cfg.coriolis_f + yy1 * cfg.coriolis_beta).astype(f32)

    rel_vort = (V(vw, 1, 0, 1) - V(vw, 1)) / dx - (V(uw, 1, 1, 0) - V(uw, 1)) / dy
    q = (cor + rel_vort) / (
        0.25 * (V(hw, 1) + V(hw, 1, 0, 1) + V(hw, 1, 1, 0) + V(hw, 1, 1, 1))
    )
    q = zero_wall(q)

    du_new = -grav * (V(hw, 0, 0, 1) - V(hw, 0)) / dx + 0.5 * (
        ti(q) * 0.5 * (ti(fn) + te(fn))
        + ts(q) * 0.5 * (ts(fn) + ts(_roll(fn, 1)))
    )
    dv_new = -grav * (V(hw, 0, 1, 0) - V(hw, 0)) / dy - 0.5 * (
        ti(q) * 0.5 * (ti(fe) + tn(fe))
        + tw(q) * 0.5 * (tw(fe) + tn(_roll(fe, -1)))
    )

    ke = 0.5 * (
        0.5 * (V(uw, 1) ** 2 + V(uw, 1, 0, -1) ** 2)
        + 0.5 * (V(vw, 1) ** 2 + V(vw, 1, -1, 0) ** 2)
    )
    ke = zero_wall(ke)
    du_new = du_new - (te(ke) - ti(ke)) / dx
    dv_new = dv_new - (tn(ke) - ti(ke)) / dy

    # interior mask over the (R, W) output tile
    g0 = row0 + lax.broadcasted_iota(jnp.int32, (R, W), 0)
    c0 = lax.broadcasted_iota(jnp.int32, (R, W), 1)
    interior = (g0 >= G) & (g0 < ny_l + G) & (c0 >= G) & (c0 < nx_l + G)

    def masked(x):
        return jnp.where(interior, x, jnp.zeros((), x.dtype))

    dt = jnp.asarray(cfg.dt, f32)
    if first_step:
        h_inc = dt * dh_new
        u_inc = dt * du_new
        v_inc = dt * dv_new
    else:
        a, b = cfg.ab_a, cfg.ab_b
        h_inc = dt * (a * dh_new + b * dh_ref[...])
        u_inc = dt * (a * du_new + b * du_ref[...])
        v_inc = dt * (a * dv_new + b * dv_ref[...])

    h_out[...] = h_ref[...] + masked(h_inc)
    u_out[...] = u_ref[...] + masked(u_inc)
    v_new = v_ref[...] + masked(v_inc)
    # v = 0 on the northern wall row (last interior row)
    v_new = jnp.where(is_n & (g0 == ny_l + 1), jnp.zeros((), v_new.dtype), v_new)
    v_out[...] = v_new
    dh_out[...] = masked(dh_new)
    du_out[...] = masked(du_new)
    dv_out[...] = masked(dv_new)


def _visc_kernel(
    scal_ref,
    u_ref, v_ref,
    utop, ubot, vtop, vbot,
    u_out, v_out,
    *, cfg, ny_l, nx_l, R, W,
):
    i = pl.program_id(0)
    row0 = i * R
    is_s = scal_ref[0, 0] == 1
    is_n = scal_ref[0, 1] == 1
    dx, dy = cfg.dx, cfg.dy
    nu = cfg.lateral_viscosity

    uw = jnp.concatenate([utop[6:8], u_ref[...], ubot[0:2]], axis=0)
    vw = jnp.concatenate([vtop[6:8], v_ref[...], vbot[0:2]], axis=0)

    def V(a, r, dyr=0, dxr=0):
        s = 2 - r + dyr
        return _roll(a, dxr)[s : s + R + 2 * r, :]

    def zero_wall(a1):
        g = row0 - 1 + lax.broadcasted_iota(jnp.int32, a1.shape, 0)
        kill = (is_s & (g == 1)) | (is_n & (g == ny_l + 2))
        return jnp.where(kill, jnp.zeros((), a1.dtype), a1)

    def ti(a):
        return a[1:-1, :]

    def tw(a):
        return _roll(a, -1)[1:-1, :]

    def ts(a):
        return a[:-2, :]

    def lap_update(w):
        gx = nu * (V(w, 1, 0, 1) - V(w, 1)) / dx
        gy = nu * (V(w, 1, 1, 0) - V(w, 1)) / dy
        gx = zero_wall(gx)
        gy = zero_wall(gy)
        return (ti(gx) - tw(gx)) / dx + (ti(gy) - ts(gy)) / dy

    g0 = row0 + lax.broadcasted_iota(jnp.int32, (R, W), 0)
    c0 = lax.broadcasted_iota(jnp.int32, (R, W), 1)
    interior = (g0 >= G) & (g0 < ny_l + G) & (c0 >= G) & (c0 < nx_l + G)
    dt = jnp.asarray(cfg.dt, jnp.float32)

    u_out[...] = u_ref[...] + jnp.where(interior, dt * lap_update(uw), 0.0)
    v_new = v_ref[...] + jnp.where(interior, dt * lap_update(vw), 0.0)
    v_new = jnp.where(is_n & (g0 == ny_l + 1), jnp.zeros((), v_new.dtype), v_new)
    v_out[...] = v_new


def _specs(rows, W, R):
    """(in_specs builder) center blocks + 8-row halo blocks per field."""
    nblk8 = max((rows + 7) // 8 - 1, 0)  # last valid 8-row block index

    center = pl.BlockSpec((R, W), lambda i: (i, 0))
    top = pl.BlockSpec(
        (8, W), lambda i: (jnp.clip(i * (R // 8) - 1, 0, nblk8), 0)
    )
    bot = pl.BlockSpec(
        (8, W), lambda i: (jnp.clip((i + 1) * (R // 8), 0, nblk8), 0)
    )
    return center, top, bot


def _out_sds(shape, dtype, like):
    """ShapeDtypeStruct carrying the input's varying-axes set (required
    by shard_map's vma checking for pallas_call outputs)."""
    try:
        vma = jax.typeof(like).vma
    except AttributeError:
        return jax.ShapeDtypeStruct(shape, dtype)
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


def _call_main(state, scal, cfg, ny_l, nx_l, *, first_step, block_rows,
               interpret):
    rows, W = state.h.shape
    R = _choose_block_rows(rows, block_rows)
    T = -(-rows // R)
    center, top, bot = _specs(rows, W, R)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    kernel = functools.partial(
        _main_kernel, cfg=cfg, ny_l=ny_l, nx_l=nx_l, R=R, W=W,
        first_step=first_step,
    )
    out_sds = _out_sds((rows, W), state.h.dtype, state.h)
    outs = pl.pallas_call(
        kernel,
        grid=(T,),
        in_specs=[smem, center, center, center, top, bot, top, bot, top,
                  bot, center, center, center],
        out_specs=[center] * 6,
        out_shape=[out_sds] * 6,
        interpret=interpret,
    )(
        scal, state.h, state.u, state.v, state.h, state.h, state.u,
        state.u, state.v, state.v, state.dh, state.du, state.dv,
    )
    return sw.SWState(*outs)


def _call_visc(u, v, scal, cfg, ny_l, nx_l, *, block_rows, interpret):
    rows, W = u.shape
    R = _choose_block_rows(rows, block_rows)
    T = -(-rows // R)
    center, top, bot = _specs(rows, W, R)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    kernel = functools.partial(
        _visc_kernel, cfg=cfg, ny_l=ny_l, nx_l=nx_l, R=R, W=W
    )
    out_sds = _out_sds((rows, W), u.dtype, u)
    return pl.pallas_call(
        kernel,
        grid=(T,),
        in_specs=[smem, center, center, top, bot, top, bot],
        out_specs=[center] * 2,
        out_shape=[out_sds] * 2,
        interpret=interpret,
    )(scal, u, v, u, u, v, v)


def clamp_wall_ghost_rows(h, comm, ny_l):
    """Clamp ``h``'s wall-side ghost rows to the adjacent interior row.

    Establishes ``hc == h`` for the kernels (the XLA path instead builds
    a separate clamped field each step).  Observationally equivalent:
    the only consumer of ``h``'s true wall ghost rows is the pressure
    gradient of the wall-row ``v``, which the wall condition zeroes.
    """
    is_north, is_south = sw._wall_masks(comm)
    south = jnp.where(is_south, jnp.broadcast_to(h[G : G + 1], (G, h.shape[1])),
                      h[:G])
    north = jnp.where(
        is_north,
        jnp.broadcast_to(h[ny_l + G - 1 : ny_l + G], (G, h.shape[1])),
        h[-G:],
    )
    return h.at[:G].set(south).at[-G:].set(north)


def _scalars(comm):
    from mpi4jax_tpu.ops._core import promote_vma

    iy, _ix = sw._device_coords(comm)
    is_north, is_south = sw._wall_masks(comm)
    scal = jnp.stack(
        [
            is_south.astype(jnp.int32),
            is_north.astype(jnp.int32),
            iy.astype(jnp.int32),
            jnp.int32(0),
        ]
    ).reshape(1, 4)
    return promote_vma(scal, comm.axes)


def _step(state, cfg, comm, *, first_step, block_rows, interpret, token):
    token = as_token(token)
    per = (False, True)
    ny_l, nx_l = cfg.local_interior(comm)
    h, u, v = state.h, state.u, state.v
    h, token = halo_exchange_2d(h, comm, periodic=per, token=token, width=G)
    u, token = halo_exchange_2d(u, comm, periodic=per, token=token, width=G)
    v, token = halo_exchange_2d(v, comm, periodic=per, token=token, width=G)
    h = clamp_wall_ghost_rows(h, comm, ny_l)
    scal = _scalars(comm)
    state = sw.SWState(h, u, v, state.dh, state.du, state.dv)
    state = _call_main(
        state, scal, cfg, ny_l, nx_l, first_step=first_step,
        block_rows=block_rows, interpret=interpret,
    )
    if cfg.lateral_viscosity > 0:
        u, token = halo_exchange_2d(
            state.u, comm, periodic=per, token=token, width=G
        )
        v, token = halo_exchange_2d(
            state.v, comm, periodic=per, token=token, width=G
        )
        u, v = _call_visc(
            u, v, scal, cfg, ny_l, nx_l, block_rows=block_rows,
            interpret=interpret,
        )
        state = sw.SWState(state.h, u, v, state.dh, state.du, state.dv)
    return state, token


def pad_state(state, cfg, comm):
    """Lift a ``_step_wide`` state (interior-shaped tendencies) to the
    kernel layout (full-shaped tendencies)."""
    if state.dh.shape == state.h.shape:
        return state
    full = jnp.zeros_like(state.h)

    def lift(t):
        return full.at[G:-G, G:-G].set(t)

    return sw.SWState(
        state.h, state.u, state.v, lift(state.dh), lift(state.du),
        lift(state.dv),
    )


def crop_state(state):
    """Inverse of :func:`pad_state` (for comparisons against the XLA
    path)."""
    return sw.SWState(
        state.h, state.u, state.v,
        state.dh[G:-G, G:-G], state.du[G:-G, G:-G], state.dv[G:-G, G:-G],
    )


def pallas_supported(cfg, comm):
    """The kernels need the wide-halo config and >= 8 local rows."""
    if cfg.ghost != 2 or not cfg.periodic_x:
        return False
    ny_l, _ = cfg.local_interior(comm)
    return ny_l + 2 * G >= 8


def make_first_step_pallas(cfg, comm, *, block_rows=64, interpret=False):
    def local_fn(state):
        state = pad_state(state, cfg, comm)
        state, _tok = _step(
            state, cfg, comm, first_step=True, block_rows=block_rows,
            interpret=interpret, token=None,
        )
        return state

    specs = sw._mesh_specs(comm)
    # interpret mode: pallas's HLO interpreter builds unvarying slice
    # indices, which trips shard_map's vma checker — fall back to the
    # legacy (unchecked) semantics there; compiled TPU runs keep checking
    return jax.jit(
        jax.shard_map(local_fn, mesh=comm.mesh, in_specs=(specs,),
                      out_specs=specs, check_vma=not interpret)
    )


def make_multistep_pallas(cfg, comm, num_steps, *, block_rows=64,
                          interpret=False):
    """Drop-in peer of :func:`shallow_water.make_multistep` running the
    fused kernels (state carries full-shaped tendencies)."""

    def local_fn(state):
        state = pad_state(state, cfg, comm)

        def body(_, s):
            s, _tok = _step(
                s, cfg, comm, first_step=False, block_rows=block_rows,
                interpret=interpret, token=None,
            )
            return s

        return lax.fori_loop(0, num_steps, body, state)

    specs = sw._mesh_specs(comm)
    return jax.jit(
        jax.shard_map(local_fn, mesh=comm.mesh, in_specs=(specs,),
                      out_specs=specs, check_vma=not interpret)
    )
