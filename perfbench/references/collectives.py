"""Plain reference of the collectives table: numpy on the host.  Imports
nothing of mpi4jax_tpu and nothing of jax.

``expected(row, x, grid)`` takes every rank's input, ``x[r]``, and gives
every rank's output under MPI's semantics.  Ranks are numbered row-major
over the ``(y, x)`` grid of chips, as ``MPI_Cart_create`` numbers them.

``carry`` is the precision the payload travels and is reduced in:
``float32`` is the reference; ``bfloat16`` is the control that the
benchmark's exact comparison has to refuse.
"""

import numpy as np


def _carried(x, carry):
    if carry == "float32":
        return x
    import ml_dtypes

    return x.astype(getattr(ml_dtypes, carry))


def _halo(x, grid, width, periodic):
    """``halo_exchange_2d``: every block's ghost ring takes the
    neighbours' adjacent interior cells, x first and then y, so the
    corners arrive through the second exchange.  A block at a wall that
    is not periodic keeps its ghost cells."""
    py, px = grid
    w = width
    blocks = [[x[iy * px + ix].copy() for ix in range(px)] for iy in range(py)]
    per_y, per_x = periodic
    old = [[b.copy() for b in row] for row in blocks]
    for iy in range(py):
        for ix in range(px):
            west, east = ix - 1, ix + 1
            if per_x or west >= 0:
                blocks[iy][ix][:, :w] = old[iy][west % px][:, -2 * w:-w]
            if per_x or east < px:
                blocks[iy][ix][:, -w:] = old[iy][east % px][:, w:2 * w]
    old = [[b.copy() for b in row] for row in blocks]
    for iy in range(py):
        for ix in range(px):
            south, north = iy - 1, iy + 1
            if per_y or south >= 0:
                blocks[iy][ix][:w, :] = old[south % py][ix][-2 * w:-w, :]
            if per_y or north < py:
                blocks[iy][ix][-w:, :] = old[north % py][ix][w:2 * w, :]
    return np.stack([blocks[iy][ix] for iy in range(py) for ix in range(px)])


def expected(row, x, grid, carry="float32"):
    """Every rank's output of ``row`` (a row of the workload's table)
    for the inputs ``x`` of shape ``(ranks, ...)``."""
    n = x.shape[0]
    op = row["op"]
    x = _carried(x, carry)
    if op == "allreduce":
        total = x[0]
        for r in range(1, n):
            total = total + x[r]
        out = np.broadcast_to(total, x.shape)
    elif op == "allgather":
        out = np.broadcast_to(x[None], (n,) + x.shape)
    elif op == "alltoall":
        # x[r] is (n, block): block j goes to rank j, which files it under r
        out = np.swapaxes(x, 0, 1)
    elif op == "bcast":
        out = np.broadcast_to(x[row["root"]], x.shape)
    elif op == "sendrecv":
        # a ring: rank r sends to r+1 and receives from r-1
        out = np.roll(x, row["shift"], axis=0)
    elif op == "halo":
        out = _halo(x, grid, row["width"], row["periodic"])
    else:
        raise ValueError(f"no reference for op {op!r}")
    return np.asarray(out).astype(np.float32)


def mismatches(got, want):
    """Elements that differ bit for bit; all of them where the shapes
    differ (a gather that gathered nothing would broadcast to a match)."""
    if np.shape(got) != np.shape(want):
        return int(np.size(want))
    got = np.ascontiguousarray(got, np.float32).view(np.uint32)
    want = np.ascontiguousarray(want, np.float32).view(np.uint32)
    return int(np.count_nonzero(got != want))
