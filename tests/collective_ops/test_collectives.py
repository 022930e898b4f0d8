"""Value tests for allgather / alltoall / barrier / bcast / gather /
reduce / scan / scatter, mirroring the reference's per-op files
(tests/collective_ops/test_{allgather,alltoall,bcast,...}.py): eager,
jit, and closed-form oracles in rank/size.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mpi4jax_tpu as m

from tests.helpers import spmd, spmd_jit

SIZE = 8


def world_input():
    return jnp.arange(float(SIZE))


def _run(comm, fn, x=None, in_specs=None, out_specs=None, jit=True):
    in_specs = in_specs or jax.P(comm.axes)
    out_specs = out_specs or jax.P(comm.axes)
    f = jax.shard_map(fn, mesh=comm.mesh, in_specs=in_specs, out_specs=out_specs)
    if jit:
        f = jax.jit(f)
    return f(world_input() if x is None else x)


@pytest.mark.parametrize("jit", [True, False])
def test_allgather(comm1d, jit):
    def fn(x):
        g, _ = m.allgather(x[0], comm=comm1d)
        return g[None]  # (1, 8) per device

    out = _run(
        comm1d, fn, out_specs=jax.P(comm1d.axes, None), jit=jit
    )  # (8, 8) global
    expected = np.tile(np.arange(8.0), (8, 1))
    assert np.array_equal(np.asarray(out), expected)


@pytest.mark.parametrize("jit", [True, False])
def test_alltoall(comm1d, jit):
    # device r holds row r scaled: in[r] = r*8 + [0..7]; alltoall == transpose
    x = jnp.arange(64.0).reshape(8, 8)

    def fn(v):
        y, _ = m.alltoall(v, comm=comm1d)
        return y

    out = _run(
        comm1d,
        fn,
        x=x,
        in_specs=jax.P(None, comm1d.axes),
        out_specs=jax.P(None, comm1d.axes),
        jit=jit,
    )
    assert np.array_equal(np.asarray(out), np.arange(64.0).reshape(8, 8).T)


def test_alltoall_wrong_leading_dim(comm1d):
    with pytest.raises(ValueError, match=r"shape \(nproc, ...\)"):
        _run(comm1d, lambda v: m.alltoall(v, comm=comm1d)[0])


@pytest.mark.parametrize("root", [0, 3])
def test_bcast(comm1d, root):
    def fn(x):
        y, _ = m.bcast(x * 10, root, comm=comm1d)
        return y

    out = _run(comm1d, fn)
    assert np.array_equal(np.asarray(out), np.full(SIZE, 10.0 * root))


@pytest.mark.parametrize("schedule", ["tree", "psum"])
@pytest.mark.parametrize("root", [0, 3])
def test_bcast_takes_no_schedule_from_the_environment(
        comm1d, root, schedule, monkeypatch):
    """``MPI4JAX_TPU_BCAST`` switched the mesh tier's ``bcast`` to a
    binomial ``ppermute`` tree until the chip had it lose at 8 B and at
    4 MiB (PERF.md, PR 50): a process that still sets it gets the masked
    ``psum``, the lowered text of the call without it, and the root's
    value."""

    def fn(x):
        y, _ = m.bcast(x * 10, root, comm=comm1d)
        return y

    def text():
        # a jit of its own each time: a trace is cached by the function
        f = jax.jit(jax.shard_map(
            fn, mesh=comm1d.mesh, in_specs=jax.P(comm1d.axes),
            out_specs=jax.P(comm1d.axes)))
        return f.lower(world_input()).as_text()

    unset = text()
    monkeypatch.setenv("MPI4JAX_TPU_BCAST", schedule)
    assert text() == unset
    assert "collective_permute" not in unset and "all_reduce" in unset
    out = _run(comm1d, fn)
    assert np.array_equal(np.asarray(out), np.full(SIZE, 10.0 * root))


def test_bcast_bool(comm1d):
    def fn(x):
        y, _ = m.bcast(x[0] > 2, 5, comm=comm1d)
        return y[None].astype(jnp.float32)

    out = _run(comm1d, fn)
    assert np.array_equal(np.asarray(out), np.ones(SIZE))


@pytest.mark.parametrize("root", [0, 2])
def test_gather(comm1d, root):
    def fn(x):
        g, _ = m.gather(x[0], root, comm=comm1d)
        return g[None]

    out = _run(comm1d, fn, out_specs=jax.P(comm1d.axes, None))
    # root's row must hold every rank's value (off-root rows also valid here)
    assert np.array_equal(np.asarray(out)[root], np.arange(8.0))


@pytest.mark.parametrize("root", [0, 6])
def test_scatter(comm1d, root):
    def fn(x):
        # every rank passes (size,) template; only root's values matter
        payload = jnp.arange(8.0) * 100 if True else x
        payload = jnp.where(x[0] == root, payload, jnp.zeros(8))
        y, _ = m.scatter(payload, root, comm=comm1d)
        return y[None]

    out = _run(comm1d, fn)
    assert np.array_equal(np.asarray(out), np.arange(8.0) * 100)


@pytest.mark.parametrize("op,expected", [(m.SUM, 28.0), (m.MAX, 7.0)])
def test_reduce(comm1d, op, expected):
    def fn(x):
        y, _ = m.reduce(x, op, 0, comm=comm1d)
        return y

    out = _run(comm1d, fn)
    assert np.asarray(out)[0] == expected  # root's value


@pytest.mark.parametrize("op", [m.SUM, m.PROD, m.MAX, m.MIN])
def test_scan(comm1d, op):
    def fn(x):
        y, _ = m.scan(x + 1, op, comm=comm1d)
        return y

    out = np.asarray(_run(comm1d, fn))
    vals = np.arange(8.0) + 1
    expected = np.array(
        [
            {
                "sum": np.sum,
                "prod": np.prod,
                "max": np.max,
                "min": np.min,
            }[op.name](vals[: r + 1])
            for r in range(8)
        ]
    )
    assert np.array_equal(out, expected)


def test_scan_2d_comm(comm2d):
    def fn(x):
        y, _ = m.scan(x, m.SUM, comm=comm2d)
        return y

    out = np.asarray(_run(comm2d, fn))
    assert np.array_equal(out, np.cumsum(np.arange(8.0)))


def test_barrier(comm1d):
    def fn(x):
        tok = m.create_token()
        tok = m.barrier(comm=comm1d, token=tok)
        y, tok = m.allreduce(x, m.SUM, comm=comm1d, token=tok)
        tok = m.barrier(comm=comm1d, token=tok)
        return y

    out = _run(comm1d, fn)
    assert np.array_equal(np.asarray(out), np.full(SIZE, 28.0))


def test_chained_mixed_ops(comm1d):
    # one token chain through five different collectives
    def fn(x):
        tok = m.create_token()
        a, tok = m.allreduce(x, m.SUM, comm=comm1d, token=tok)
        b, tok = m.bcast(x * 2, 1, comm=comm1d, token=tok)
        g, tok = m.allgather(x[0], comm=comm1d, token=tok)
        s, tok = m.scan(x, m.SUM, comm=comm1d, token=tok)
        tok = m.barrier(comm=comm1d, token=tok)
        return a + b + s + g.sum()

    out = np.asarray(_run(comm1d, fn))
    ranks = np.arange(8.0)
    expected = 28.0 + 2.0 + np.cumsum(ranks) + 28.0
    assert np.array_equal(out, expected)


def test_allgather_grad(comm1d):
    # AD through allgather is a superset of the reference (which defines
    # no rules); verify it is at least consistent: d/dx sum(allgather(x))
    f = spmd_jit(comm1d, lambda x: m.allgather(x[0], comm=comm1d)[0][:1])

    def loss(x):
        return f(x).sum()

    g = jax.grad(loss)(world_input())
    assert g.shape == (8,)
