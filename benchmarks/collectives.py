"""Collective micro-benchmarks: bus bandwidth per op over the device mesh.

The second driver metric in BASELINE.md ("allreduce GB/s at 8->256
chips").  For each payload size the op runs inside one jitted shard_map
over all visible devices; reported algorithmic bandwidth uses the
standard convention (bytes * 2*(n-1)/n for allreduce, bytes * (n-1)/n
for allgather/alltoall/ppermute-ring), so numbers are comparable with
NCCL/MPI bus-bandwidth tables.  Timing also follows the NCCL-tests loop
convention: all ``reps`` iterations run inside ONE executable
(``lax.scan``) and the host syncs once, so the per-call host round trip
is amortised over the reps.  On a single device the collectives are
elided by XLA; the factor falls back to 1.0 and the number is the
residual call-site rate — mostly the amortised round-trip floor (see
docs/performance.md).

    python benchmarks/collectives.py [--sizes-mb 1 16 64] [--ops allreduce ...]

Prints one JSON line per (op, size).  ``bench_op`` is importable so
``bench.py`` and this CLI share one timing/convention implementation.
"""

import argparse
import json
import pathlib
import re
import sys
import time

# allow running straight from a checkout
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

DEFAULT_OPS = [
    "allreduce",
    "allgather",
    "alltoall",
    "sendrecv",
    "bcast",
    "scatter",
]


def busbw_factor(op, n):
    """NCCL-tests algorithmic-bandwidth factor (1.0 when collectives
    are elided on a single device)."""
    if n <= 1:
        return 1.0
    return {
        "allreduce": 2 * (n - 1) / n,
        "allgather": (n - 1) / n,
        "alltoall": (n - 1) / n,
        "sendrecv": 1.0,
        "bcast": 1.0,
        "scatter": (n - 1) / n,
    }[op]


def bench_op(comm, op, mb, reps=20):
    """Time ``op`` at ``mb`` MB per-device payload on ``comm``'s mesh.

    Returns ``(busbw_bytes_per_sec, seconds_per_call, payload_bytes)``.
    Timing is min-of-3 batches of ``reps`` chained calls, each ended
    by ``jax.block_until_ready``.
    """
    import jax
    import jax.numpy as jnp

    import mpi4jax_tpu as m

    mesh = comm.mesh
    n = comm.size
    axes = tuple(mesh.axis_names)
    per_dev = max(int(mb * 1e6 / 4), n)
    per_dev -= per_dev % n  # alltoall/scatter need a multiple of n
    ring = [(r, (r + 1) % n) for r in range(n)]

    def local(x):
        if op == "allreduce":
            return m.allreduce(x, m.SUM, comm=comm)[0]
        if op == "allgather":
            return m.allgather(x, comm=comm)[0].sum(axis=0)
        if op == "alltoall":
            blk = x.reshape(n, -1)
            return m.alltoall(blk, comm=comm)[0].reshape(x.shape)
        if op == "sendrecv":
            return m.sendrecv(x, x, source=ring, dest=ring, comm=comm)[0]
        if op == "bcast":
            return m.bcast(x, 0, comm=comm)[0]
        if op == "scatter":
            blk = x.reshape(n, -1)
            return m.scatter(blk, 0, comm=comm)[0]
        raise ValueError(op)

    def chained(c):
        # c: per-device (1,) carry.  The operand is built on-device,
        # per-shard (a global jnp.ones would transiently materialize
        # n*payload on one device) and depends on the previous call's
        # output so chained calls can't overlap.  The ``reps`` loop is
        # INSIDE the executable (lax.scan): all iterations are enqueued
        # back to back and the host syncs once — the NCCL-tests timing
        # convention; a host dispatch per call would dominate small/
        # medium payloads.
        from jax import lax

        def body(carry, _):
            x = jnp.ones((per_dev,), jnp.float32) + carry[0]
            y = local(x)
            return y.ravel()[:1].astype(jnp.float32) + 0.0 * carry, None

        out, _ = lax.scan(body, c, None, length=reps)
        return out

    fn = jax.jit(
        jax.shard_map(
            chained, mesh=mesh, in_specs=jax.P(axes), out_specs=jax.P(axes)
        )
    )
    carry = jnp.zeros((n,), jnp.float32)
    jax.block_until_ready(fn(carry))  # compile + warm
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(carry))
        best = min(best, (time.perf_counter() - t0) / reps)
    payload = per_dev * 4
    return payload * busbw_factor(op, n) / best, best, payload


def force_cpu_mesh(n):
    """Force an n-device virtual CPU mesh (must run before jax
    initialises a backend).  Pins the platform in code as well as the
    device count, so a child started by a parent that holds the chip
    never reaches for it, whatever JAX_PLATFORMS says."""
    import os

    flags = os.environ.get("XLA_FLAGS", "")
    key = "--xla_force_host_platform_device_count"
    if key in flags:
        flags = re.sub(rf"{key}=\d+", f"{key}={n}", flags)
    else:
        flags = (flags + f" {key}={n}").strip()
    os.environ["XLA_FLAGS"] = flags

    import jax

    jax.config.update("jax_platforms", "cpu")
    assert len(jax.devices()) == n, (
        f"requested {n} CPU devices, got {len(jax.devices())} "
        "(was jax imported before force_cpu_mesh?)"
    )


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--sizes-mb", nargs="*", type=float, default=[1, 4, 16, 64])
    p.add_argument("--ops", nargs="*", default=DEFAULT_OPS)
    p.add_argument("--reps", type=int, default=20)
    p.add_argument(
        "--cpu-mesh",
        type=int,
        default=0,
        metavar="N",
        help="force an N-device virtual CPU mesh",
    )
    args = p.parse_args(argv)

    if args.cpu_mesh:
        force_cpu_mesh(args.cpu_mesh)

    import jax

    import mpi4jax_tpu as m

    n = len(jax.devices())
    mesh = jax.make_mesh(
        (n,), ("i",), axis_types=(jax.sharding.AxisType.Auto,)
    )
    comm = m.MeshComm.from_mesh(mesh)

    for op in args.ops:
        for mb in args.sizes_mb:
            busbw, dt, payload = bench_op(comm, op, mb, reps=args.reps)
            print(
                json.dumps(
                    {
                        "metric": f"{op}_busbw",
                        "value": round(busbw / 1e9, 3),
                        "unit": "GB/s",
                        "devices": n,
                        "payload_mb": round(payload / 1e6, 2),
                        "time_us": round(dt * 1e6, 1),
                    }
                )
            )
            sys.stdout.flush()


if __name__ == "__main__":
    from mpi4jax_tpu.utils.runtime import enable_compile_cache

    enable_compile_cache()
    main()
