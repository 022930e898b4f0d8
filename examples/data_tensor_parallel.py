"""DP x TP training on the communication primitives.

The reference's README headline pattern (README.rst:61-80, gradient
allreduce inside the loss) and its tensor-parallel matvec tests
(tests/collective_ops/test_allreduce_matvec.py:44-62) — composed here
into a complete training loop over a ("dp", "tp") device mesh:

* data parallel: per-shard batches, gradient ``allreduce`` over "dp"
  (differentiable — the allreduce sits *inside* the loss graph);
* tensor parallel: Megatron-style column/row-sharded MLP with the
  partial-product ``allreduce`` over "tp" and its AD-correct transpose;
* ``--zero``: ZeRO-1-style sharded optimizer — momentum state split
  1/dp per device, gradients delivered by ``reduce_scatter`` instead of
  ``allreduce`` (models/train.py:make_global_zero_train_step).

Usage:

    python examples/data_tensor_parallel.py [--dp 2] [--tp 4] [--steps 60] [--zero]
"""

import argparse
import pathlib
import sys

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--dp", type=int, default=None)
    p.add_argument("--tp", type=int, default=None)
    p.add_argument("--steps", type=int, default=60)
    p.add_argument("--hidden", type=int, default=64)
    p.add_argument(
        "--zero", action="store_true",
        help="shard the optimizer state over dp (reduce_scatter grads)",
    )
    args = p.parse_args(argv)

    import jax
    import mpi4jax_tpu as m
    from mpi4jax_tpu.models import train as tr
    from mpi4jax_tpu.utils.runtime import best_mesh_shape

    n = len(jax.devices())
    dp, tp = (args.dp, args.tp) if args.dp and args.tp else best_mesh_shape(n)
    assert dp * tp == n, f"dp*tp must equal device count {n}"

    mesh = jax.make_mesh(
        (dp, tp), ("dp", "tp"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2,
    )
    comm = m.MeshComm.from_mesh(mesh)
    dpc, tpc = comm.sub("dp"), comm.sub("tp")

    d_in, d_out = 16, 8
    params = tr.init_params(
        jax.random.PRNGKey(0), d_in, args.hidden, d_out, tp_size=tp
    )
    if args.zero:
        step, init_state = tr.make_global_zero_train_step(
            mesh, dpc, tpc, lr=5e-2, momentum=0.9
        )
        opt_state = init_state(params)
        per_dev = sum(
            v.sharding.shard_shape(v.shape)[1] for v in opt_state
        )
        # a dense optimizer would hold each device's LOCAL params: the
        # tp shard of w1/b1/w2 plus the replicated b2
        local_dense = (
            params.w1.size // tp + params.b1.size // tp
            + params.w2.size // tp + params.b2.size
        )
        print(
            f"ZeRO-1: momentum state {per_dev} floats/device "
            f"(an unsharded optimizer would hold {local_dense})"
        )
    else:
        step = tr.make_global_train_step(mesh, dpc, tpc, lr=5e-2)

    x = jax.random.normal(jax.random.PRNGKey(1), (8 * dp, d_in))
    w_true = jax.random.normal(jax.random.PRNGKey(2), (d_in, d_out))
    targets = x @ w_true

    loss0 = None
    for i in range(args.steps):
        if args.zero:
            params, opt_state, loss = step(params, opt_state, (x, targets))
        else:
            params, loss = step(params, (x, targets))
        val = float(np.asarray(loss)[0])
        if loss0 is None:
            loss0 = val
        if i % 10 == 0:
            print(f"step {i:4d}  loss {val:.5f}")
    print(
        f"mesh {dp}x{tp} ({n} devices): loss {loss0:.4f} -> {val:.4f} "
        f"({val / loss0:.3%} of start)"
    )
    assert val < loss0, "training did not reduce the loss"


if __name__ == "__main__":
    from mpi4jax_tpu.utils.runtime import enable_compile_cache

    enable_compile_cache()
    main()
