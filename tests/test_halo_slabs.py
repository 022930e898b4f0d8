"""``halo_slabs_2d`` is ``halo_exchange_2d`` without its last phase: the
four slabs a block receives, for a caller that places them itself.
Written into the ghosts in the order they come, they are the exchange's
result bit for bit, corners included, on every mesh.  And the exchange
itself, which writes no ghost between its two wires, is bit for bit the
exchange that did (the order kept here as a helper)."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mpi4jax_tpu as m
from mpi4jax_tpu.analysis.jaxpr_walk import walk_comm_jaxpr
from mpi4jax_tpu.ops._core import SCOPE_PREFIX, as_token
from mpi4jax_tpu.parallel import halo_slabs_2d
from mpi4jax_tpu.parallel.halo import (
    _shift, _shifts, halo_exchange_2d, halo_exchange_2d_batch)

NY, NX = 6, 5  # one device's interior


def _comm(mesh_shape):
    py, px = mesh_shape
    mesh = jax.make_mesh(
        mesh_shape, ("y", "x"), axis_types=(jax.sharding.AxisType.Auto,) * 2,
        devices=jax.devices()[:py * px])
    return m.MeshComm.from_mesh(mesh)


def _regions(w):
    """Where the slabs go, in the order they come: west, east, south,
    north."""
    return np.s_[:, :w], np.s_[:, -w:], np.s_[:w, :], np.s_[-w:, :]


def _program(comm, width, periodic, what):
    """A program over ``comm``'s mesh of blocks whose every cell, ghosts
    too, holds a number of its own: ``what`` of a block, stacked."""
    py, px = comm.axis_sizes
    shape = (NY + 2 * width, NX + 2 * width)

    def local(start):
        arr = start[0] + jnp.arange(
            shape[0] * shape[1], dtype=jnp.float32).reshape(shape)
        return what(arr, comm, periodic, width)[None]

    starts = 1000.0 * jnp.arange(py * px, dtype=jnp.float32)
    return jax.shard_map(
        local, mesh=comm.mesh, in_specs=jax.P(("y", "x")),
        out_specs=jax.P(("y", "x"))), starts


def _exchanged(arr, comm, periodic, width):
    return halo_exchange_2d(arr, comm, periodic=periodic, width=width)[0]


def _placed(arr, comm, periodic, width):
    slabs, _ = halo_slabs_2d(arr, comm, periodic=periodic, width=width)
    for slab, region in zip(slabs, _regions(width)):
        if slab is not None:
            assert slab.shape == arr[region].shape
            arr = arr.at[region].set(slab)
    return arr


@pytest.mark.parametrize("width", [1, 2])
@pytest.mark.parametrize(
    "periodic", [(False, True), (True, True)], ids=["walls", "torus"])
@pytest.mark.parametrize(
    "mesh_shape", [(1, 1), (2, 1), (1, 2), (2, 2), (2, 4)],
    ids=lambda s: "x".join(map(str, s)))
def test_slabs_written_in_order_are_the_exchange(mesh_shape, periodic, width):
    comm = _comm(mesh_shape)
    want, got = (
        np.asarray(jax.jit(program)(starts)) for program, starts in
        (_program(comm, width, periodic, what) for what in (_exchanged, _placed)))
    # the exchange moved something: a ghost column is a neighbour's (or,
    # on one device, the block's own far side), corners included
    before = np.arange(want[0].size, dtype=np.float32).reshape(want[0].shape)
    assert (want[0][:, :width] != before[:, :width]).all()
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize(
    "periodic", [(False, True), (True, True), (False, False)],
    ids=["walls", "torus", "box"])
@pytest.mark.parametrize(
    "mesh_shape", [(1, 1), (2, 1), (1, 2), (2, 2)],
    ids=lambda s: "x".join(map(str, s)))
def test_the_none_slabs_are_exactly_the_no_op_shifts(mesh_shape, periodic):
    comm = _comm(mesh_shape)
    found = []

    def slabs(arr, comm, periodic, width):
        out, _ = halo_slabs_2d(arr, comm, periodic=periodic, width=width)
        found.append([slab is None for slab in out])
        return arr

    program, starts = _program(comm, 2, periodic, slabs)
    jax.eval_shape(program, starts)
    py, px = mesh_shape
    per_y, per_x = periodic
    # a shift is a no-op on the whole axis where the axis is one device
    # and does not wrap; the two shifts of an axis go together
    no_x, no_y = px == 1 and not per_x, py == 1 and not per_y
    assert found == [[no_x, no_x, no_y, no_y]]


def test_the_slabs_lower_under_pack_and_wire_and_nothing_is_unpacked():
    comm = _comm((2, 2))

    def slabs(arr, comm, periodic, width):
        out, _ = halo_slabs_2d(arr, comm, periodic=periodic, width=width)
        return out[2]

    program, starts = _program(comm, 2, (False, True), slabs)
    text = jax.jit(program).lower(starts).as_text(debug_info=True)
    op = f"{SCOPE_PREFIX}halo_slabs_2d"
    scopes = {
        line.split(op + "/")[1].split("/")[0].split('"')[0]
        for line in text.splitlines() if op + "/" in line}
    # (the call that carries the slabs' derivative, PR 54, both modes
    # since PR 59, holds both: a location of the lowered text that no
    # compiled instruction keeps)
    assert scopes - {"custom_jvp_call"} == {"pack", "wire"}
    assert f"{op}/wire/{SCOPE_PREFIX}sendrecv" in text
    assert "/unpack" not in text
    # the compiled program's op_names, which the benchmark's readers
    # read, are the parent's: the op, its phase, the inner op
    compiled = jax.jit(program).lower(starts).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*' + re.escape(op) + r'[^"]*)"', compiled))
    assert names and all(
        re.search(re.escape(op) + r"/(pack|wire/" + re.escape(SCOPE_PREFIX)
                  + r"sendrecv)/", name) for name in names), names
    # and the exchange beside it still has its three
    program, starts = _program(comm, 2, (False, True), _exchanged)
    text = jax.jit(program).lower(starts).as_text(debug_info=True)
    assert f"{SCOPE_PREFIX}halo_exchange_2d/unpack" in text


def test_the_mesh_tier_holds_the_block_row_major_and_the_proc_tier_does_not(
        monkeypatch):
    """On the mesh tier the block a column slab is cut from is held
    row-major, once, under the op's ``pack`` scope, as the exchange that
    writes the ghosts holds it (left to itself the TPU compiler lays the
    carried block out to suit the slabs and transposes it back for the
    caller's kernel: PERF.md, PR 52); the multi-process tier, whose
    slabs leave through the host, is handed no layout."""
    from mpi4jax_tpu.native import runtime
    from mpi4jax_tpu.parallel import ProcGridComm, halo

    def third(arr, comm, periodic, width):
        return halo_slabs_2d(arr, comm, periodic=periodic, width=width)[0][2]

    op = f"{SCOPE_PREFIX}halo_slabs_2d"
    program, starts = _program(_comm((2, 2)), 2, (False, True), third)
    text = jax.jit(program).lower(starts).as_text(debug_info=True)
    held = [line for line in text.splitlines() if "@LayoutConstraint" in line]
    assert len(held) == 1 and "result_layouts = [dense<[1, 0]>" in held[0]
    assert f'loc("{op}/pack/layout_constraint"' in text
    # and the exchange that writes them holds its block once too
    program, starts = _program(_comm((2, 2)), 2, (False, True), _exchanged)
    text = jax.jit(program).lower(starts).as_text(debug_info=True)
    assert text.count("@LayoutConstraint") == 1
    assert f'loc("{SCOPE_PREFIX}halo_exchange_2d/pack/layout_constraint"' in text

    # the same call on a 2x2 grid of processes, traced as rank 0 with the
    # wire taken out (no native runtime here): slices and no constraint
    grid = ProcGridComm(
        ranks=(0, 1, 2, 3), context=52, axes=("y", "x"), axis_sizes=(2, 2))
    assert grid.backend == "proc"
    monkeypatch.setattr(runtime, "world_rank", lambda: 0)
    monkeypatch.setattr(
        halo, "sendrecv_multi",
        lambda slabs, templates, *, token, **_: (list(templates), token))
    arr = jnp.zeros((NY + 4, NX + 4), jnp.float32)
    text = jax.jit(lambda a: third(a, grid, (False, True), 2)).lower(
        arr).as_text(debug_info=True)
    assert f"{op}/pack/slice" in text
    assert "LayoutConstraint" not in text and "layout_constraint" not in text


def _written_between_the_shifts(arrs, comm, periodic, width, stack):
    """The exchange as it was before its ghosts were written once: every
    shift's slabs sliced from blocks that hold the shifts' before it,
    and written before the next is sliced."""
    token = as_token(None)
    for axis, disp, per, sent, received in _shifts(width, periodic):
        halo, token = _shift(
            [a[sent] for a in arrs], [a[received] for a in arrs], comm, axis,
            disp, per, token, stack=stack)
        arrs = [a if got is None else a.at[received].set(got)
                for a, got in zip(arrs, halo)]
    return arrs


def _single_then(arr, comm, periodic, width):
    return _written_between_the_shifts([arr], comm, periodic, width, False)[0]


def _pair(arr):
    return [arr, 3.0 * arr + 0.5]


def _batch_now(arr, comm, periodic, width):
    outs, _ = halo_exchange_2d_batch(
        _pair(arr), comm, periodic=periodic, width=width)
    return jnp.stack(outs)


def _batch_then(arr, comm, periodic, width):
    return jnp.stack(_written_between_the_shifts(
        _pair(arr), comm, periodic, width, True))


@pytest.mark.parametrize("form", ["single", "batch"])
@pytest.mark.parametrize("width", [1, 2, 4])
@pytest.mark.parametrize(
    "periodic", [(False, False), (False, True), (True, False), (True, True)],
    ids=["box", "walls", "channel", "torus"])
@pytest.mark.parametrize(
    "mesh_shape", [(1, 1), (1, 4), (4, 1), (2, 2), (2, 4)],
    ids=lambda s: "x".join(map(str, s)))
def test_ghosts_written_once_are_the_ghosts_written_between_the_shifts(
        mesh_shape, periodic, width, form):
    """Every cell of every block holds a number of its own, so a corner
    patched from the wrong slab, or not at all, shows."""
    comm = _comm(mesh_shape)
    now, then = {"single": (_exchanged, _single_then),
                 "batch": (_batch_now, _batch_then)}[form]
    got, want = (
        np.asarray(jax.jit(program)(starts)) for program, starts in
        (_program(comm, width, periodic, what) for what in (now, then)))
    np.testing.assert_array_equal(got, want)
    # something moved wherever a shift is not a no-op on the whole axis
    py, px = mesh_shape
    first = got[0] if form == "single" else got[0][0]
    before = np.arange(first.size, dtype=np.float32).reshape(first.shape)
    inner = np.s_[width:-width]
    if px > 1 or periodic[1]:  # the first block's west or east ghosts
        assert (first[inner, :width] != before[inner, :width]).any() or (
            first[inner, -width:] != before[inner, -width:]).any()
    if py > 1 or periodic[0]:
        assert (first[:width, inner] != before[:width, inner]).any() or (
            first[-width:, inner] != before[-width:, inner]).any()


@pytest.mark.parametrize("form,op", [
    ("single", "halo_exchange_2d"), ("batch", "halo_exchange_2d_batch")])
def test_an_exchange_is_one_op_and_four_sendrecvs(form, op):
    comm = _comm((2, 2))
    what = {"single": _exchanged, "batch": _batch_now}[form]
    program, starts = _program(comm, 2, (False, True), what)
    occurrences, findings = walk_comm_jaxpr(jax.make_jaxpr(program)(starts))
    assert not findings
    ops = [o.op for o in occurrences]
    assert set(ops) == {op, "sendrecv"} and ops.count("sendrecv") == 4


# -- slabs cut deeper than the block's ring ---------------------------------


def _deep(arr, comm, periodic, width, depth):
    """The four slabs ``depth`` deep of a block padded by ``width``."""
    return halo_slabs_2d(arr, comm, periodic=periodic, width=width, depth=depth)[0]


@pytest.mark.parametrize("width, deep", [(2, 4), (1, 2), (1, 3)])
@pytest.mark.parametrize(
    "periodic", [(False, True), (True, True)], ids=["walls", "torus"])
@pytest.mark.parametrize(
    "mesh_shape", [(2, 2), (2, 1), (1, 2), (2, 4)],
    ids=lambda s: "x".join(map(str, s)))
def test_deep_slabs_are_the_ghosts_of_an_exchange_that_deep(
        mesh_shape, periodic, width, deep):
    """A block padded by ``width`` sends its ``deep`` interior columns
    and rows next to each edge: the slabs are, bit for bit and corners
    included, what ``halo_exchange_2d(width=deep)`` writes round the
    same block padded that much, zeros where the block holds nothing; a
    device beyond whose edge nobody is keeps its own ghosts there."""
    comm = _comm(mesh_shape)
    e = deep - width

    def slabs(arr, comm, periodic, width):
        got = _deep(arr, comm, periodic, width, (deep, deep))
        want = halo_exchange_2d(
            jnp.pad(arr, e), comm, periodic=periodic, width=deep)[0]
        # (the x slabs over the interior's rows: the y slabs, which hold
        # the corners, are written over their ends)
        got = [x if x is None or k > 1 else x[width:-width]
               for k, x in enumerate(got)]
        want = (want[deep:-deep, :deep], want[deep:-deep, -deep:],
                want[:deep], want[-deep:])
        assert all(a.shape == b.shape for a, b in zip(got, want) if a is not None)
        # a slab is None exactly where the exchange moved nothing
        return jnp.stack([
            jnp.zeros(()) if a is None else jnp.abs(a - b).max()
            for a, b in zip(got, want)])

    program, starts = _program(comm, width, periodic, slabs)
    assert not np.asarray(jax.jit(program)(starts)).any()


@pytest.mark.parametrize("depth", [(4, 4), (4, 2), (2, 4)], ids=str)
@pytest.mark.parametrize(
    "mesh_shape", [(2, 2), (2, 4)], ids=lambda s: "x".join(map(str, s)))
def test_deep_slabs_of_several_arrays_are_slices_of_the_global_arrays(
        mesh_shape, depth):
    """On a torus every cell of a deep slab that is a neighbour's is a
    cell of the global array: the x slabs over the block's interior rows
    (their ghost rows are the block's own, stale), the y slabs wholly,
    with the corners that came by way of the x slabs, each axis as deep
    as it was asked for, for each array of a list."""
    comm = _comm(mesh_shape)
    py, px = mesh_shape
    w, (dy, dx) = 2, depth
    whole = [k + np.arange(py * NY * px * NX, dtype=np.float32).reshape(
        py * NY, px * NX) for k in (0.0, 0.5)]

    def local(*arrs):
        blocks = [jnp.pad(a, w, constant_values=-1.0) for a in arrs]
        got, _ = halo_slabs_2d(
            blocks, comm, periodic=(True, True), width=w, depth=depth)
        return tuple(got)

    spec = jax.P("y", "x")
    got = jax.jit(jax.shard_map(
        local, mesh=comm.mesh, in_specs=(spec,) * 2,
        out_specs=((spec,) * 4,) * 2))(*map(jnp.asarray, whole))
    def take(a, rows, cols):
        return a[np.ix_(rows % (py * NY), cols % (px * NX))]

    def of_device(x, iy, ix):
        ny, nx = x.shape[0] // py, x.shape[1] // px
        return x[iy * ny:(iy + 1) * ny, ix * nx:(ix + 1) * nx]

    for a, slabs in zip(whole, got):
        for (iy, ix), _ in np.ndenumerate(np.empty(mesh_shape)):
            west, east, south, north = (
                of_device(np.asarray(x), iy, ix) for x in slabs)
            rows = np.arange(iy * NY, (iy + 1) * NY)
            cols = np.arange(ix * NX - dx, (ix + 1) * NX + dx)
            np.testing.assert_array_equal(west[w:-w], take(a, rows, cols[:dx]))
            np.testing.assert_array_equal(east[w:-w], take(a, rows, cols[-dx:]))
            np.testing.assert_array_equal(
                south, take(a, rows[0] - dy + np.arange(dy), cols))
            np.testing.assert_array_equal(
                north, take(a, rows[-1] + 1 + np.arange(dy), cols))


@pytest.mark.parametrize(
    "mesh_shape", [(1, 1), (2, 2)], ids=lambda s: "x".join(map(str, s)))
def test_depth_equal_to_width_traces_the_exchange_as_it_was(mesh_shape):
    """``depth`` equal to ``width`` on both axes is not another program:
    equation for equation the jaxpr of the slabs without it."""
    comm = _comm(mesh_shape)
    texts = []
    for depth in (None, (2, 2)):
        def slabs(arr, comm, periodic, width, depth=depth):
            got = _deep(arr, comm, periodic, width, depth)
            return jnp.stack([x.sum() for x in got if x is not None])
        program, starts = _program(comm, 2, (False, True), slabs)
        texts.append(str(jax.make_jaxpr(program)(starts)))
    assert texts[0] == texts[1]
