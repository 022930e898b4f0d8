"""Long-context attention via sequence parallelism.

The reference provides the primitives every sequence-parallel scheme is
assembled from (SURVEY §5.7: ring step = sendrecv, head/sequence
reshard = alltoall) but no scheme itself.  Here both named schemes run
as library calls over a 1-D device ring, each device holding 1/N of the
sequence:

* ring attention  — KV blocks rotate around the ring (``sendrecv``),
  online-softmax accumulation, supports causal masking;
* Ulysses         — ``alltoall`` reshards sequence<->heads around plain
  local attention.

Both are verified against single-device attention on the gathered
sequence.

Usage:

    python examples/long_context.py [--seq-per-device 256] [--heads 8]
"""

import argparse
import pathlib
import sys

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--seq-per-device", type=int, default=256)
    p.add_argument("--heads", type=int, default=8)
    p.add_argument(
        "--kv-heads",
        type=int,
        default=None,
        help="fewer kv heads than query heads = grouped-query attention "
        "(default: same as --heads)",
    )
    p.add_argument("--head-dim", type=int, default=64)
    p.add_argument("--causal", action="store_true")
    p.add_argument(
        "--force-cpu",
        action="store_true",
        help="run on virtual CPU devices (honours "
        "--xla_force_host_platform_device_count in XLA_FLAGS)",
    )
    args = p.parse_args(argv)

    import jax

    if args.force_cpu:
        jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import mpi4jax_tpu as m
    from mpi4jax_tpu.parallel import longseq

    n = len(jax.devices())
    mesh = jax.make_mesh(
        (n,), ("sp",), axis_types=(jax.sharding.AxisType.Auto,)
    )
    comm = m.MeshComm.from_mesh(mesh)

    B, S, H, D = 2, args.seq_per_device * n, args.heads, args.head_dim
    HK = args.kv_heads if args.kv_heads is not None else H
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(keys[0], (B, S, H, D), jnp.float32)
    k = jax.random.normal(keys[1], (B, S, HK, D), jnp.float32)
    v = jax.random.normal(keys[2], (B, S, HK, D), jnp.float32)

    def run(scheme):
        def local(ql, kl, vl):
            if scheme == "ring":
                out, _ = longseq.ring_attention(ql, kl, vl, comm, causal=args.causal)
            elif scheme == "ring-zigzag":
                # balanced-causal layout: every rank does the same
                # half-block of work per ring step
                out, _ = longseq.ring_attention(
                    ql, kl, vl, comm, causal=args.causal, layout="zigzag"
                )
            else:
                out, _ = longseq.ulysses_attention(ql, kl, vl, comm, causal=args.causal)
            return out

        arrs = (q, k, v)
        if scheme == "ring-zigzag":
            arrs = tuple(longseq.zigzag_shard(a, n) for a in arrs)
        out = jax.jit(
            jax.shard_map(
                local,
                mesh=mesh,
                in_specs=(jax.P(None, "sp"),) * 3,
                out_specs=jax.P(None, "sp"),
            )
        )(*arrs)
        if scheme == "ring-zigzag":
            out = longseq.zigzag_unshard(out, n)
        return out

    reference = longseq.local_attention(q, k, v, causal=args.causal, impl="xla")
    schemes = ["ring"]
    if S % (2 * n) == 0:
        schemes.append("ring-zigzag")
    else:
        print(
            f"ring-zigzag skipped: sequence {S} not divisible by "
            f"2*{n} devices"
        )
    if H % n == 0 and HK % n == 0:
        schemes.append("ulysses")
    else:
        print(
            f"ulysses skipped: heads {H}/{HK} not both divisible by "
            f"{n} devices"
        )
    for scheme in schemes:
        out = run(scheme)
        err = float(jnp.max(jnp.abs(out - reference)))
        print(
            f"{scheme:12s}: global seq {S} over {n} devices "
            f"({args.seq_per_device}/device), max |err| vs single-device "
            f"attention = {err:.2e}"
        )
        assert err < 2e-5, f"{scheme} diverged from the reference"


if __name__ == "__main__":
    from mpi4jax_tpu.utils.runtime import enable_compile_cache

    enable_compile_cache()
    main()
