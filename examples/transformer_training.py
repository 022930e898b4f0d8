"""Transformer training across every parallelism family.

One decoder model, three sharded train steps — pick with ``--mode``:

* ``dense`` — dp×tp×sp: Megatron f/g tensor parallelism + ring-attention
  sequence parallelism (GQA) + data parallelism
  (models/transformer.py).
* ``moe``   — dp×tp×sp where sp doubles as the expert-parallel axis:
  mixture-of-experts MLP, local expert-choice routing, two ICI
  ``alltoall``s per layer (models/moe_transformer.py).
* ``pp``    — dp×pp: the same decoder's layers staged into a GPipe
  pipeline; activations hand off by ``sendrecv``, gradients ride the
  reversed ring (models/pp_transformer.py).

Every step is one jitted ``shard_map`` program; all collectives ride
the device mesh (ICI on a TPU slice).  Each variant's SGD step is
oracle-tested against unsharded math in tests/parallel/.

Usage:

    python examples/transformer_training.py --mode dense [--steps 20]
    python examples/transformer_training.py --mode moe
    python examples/transformer_training.py --mode pp [--micro 2]
    python examples/transformer_training.py --force-cpu   # 8 virtual devices
"""

import argparse
import pathlib
import sys

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--mode", choices=("dense", "moe", "pp"), default="dense")
    p.add_argument(
        "--schedule", choices=("gpipe", "1f1b"), default="gpipe",
        help="pipeline schedule for --mode pp (1f1b = interleaved "
        "fwd/bwd, bounded activation memory)",
    )
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--micro", type=int, default=2, help="pp microbatches")
    p.add_argument(
        "--routing", choices=("expert_choice", "topk"),
        default="expert_choice",
        help="moe routing scheme (topk = GShard/Switch token choice)",
    )
    p.add_argument(
        "--aux-weight", type=float, default=0.0,
        help="Switch load-balancing loss weight (topk routing)",
    )
    p.add_argument(
        "--z-weight", type=float, default=0.0,
        help="ST-MoE router z-loss weight (typical 1e-3)",
    )
    p.add_argument(
        "--checkpoint", default=None, metavar="DIR",
        help="save params every --checkpoint-every steps; a rerun with "
        "the same DIR resumes from the latest step bit-identically",
    )
    p.add_argument("--checkpoint-every", type=int, default=5)
    p.add_argument(
        "--generate", type=int, default=0, metavar="N",
        help="after training (dense mode), greedily decode N tokens "
        "from the first training sequence's prefix (TP-sharded KV "
        "cache)",
    )
    p.add_argument(
        "--kv-bucket", type=int, default=None,
        help="decode with bucketed KV growth: each step reads only the "
        "cache written so far, rounded up to this bucket — the "
        "large-batch decode lever",
    )
    p.add_argument(
        "--force-cpu", action="store_true",
        help="run on 8 virtual CPU devices regardless of platform",
    )
    p.add_argument(
        "--remat", choices=("off", "full", "dots", "names"), default="off",
        help="dense-mode activation checkpointing: full = per-layer "
        "jax.checkpoint, dots = save every matmul output, names = the "
        "q/k/attn-out/mlp-out policy the MFU bench uses",
    )
    args = p.parse_args(argv)

    if args.remat != "off" and args.mode == "pp":
        p.error(
            "--remat applies to the dense/moe layer scan; the pipeline "
            "schedules have their own built-in per-stage remat "
            "(models/pipeline.py)"
        )

    if args.force_cpu:
        import os

        flags = os.environ.get("XLA_FLAGS", "")
        if "host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8"
            ).strip()

    import jax

    if args.force_cpu:
        jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp

    import mpi4jax_tpu as m

    n = len(jax.devices())
    auto = (jax.sharding.AxisType.Auto,)

    if args.mode in ("dense", "moe"):
        if n % 8 == 0:
            shape = (n // 4, 2, 2)
        elif n == 4:
            shape = (1, 2, 2)
        elif n == 2:
            shape = (1, 2, 1)
        else:
            shape = (1, 1, 1)
        mesh = jax.make_mesh(shape, ("dp", "tp", "sp"), axis_types=auto * 3)
        world = m.MeshComm.from_mesh(mesh)
        dp, tp, sp = world.sub("dp"), world.sub("tp"), world.sub("sp")

        remat = {"off": False, "full": True}.get(args.remat, args.remat)
        if args.mode == "dense":
            from mpi4jax_tpu.models import transformer as tfm

            cfg = tfm.TransformerConfig(
                vocab=64, d_model=32, layers=2, heads=4, kv_heads=2,
                head_dim=8, d_ff=64,
            )
            params = tfm.init_params(jax.random.PRNGKey(0), cfg)
            step = tfm.make_global_train_step(
                mesh, dp, tp, sp, cfg, lr=3e-1, remat=remat
            )
        else:
            from mpi4jax_tpu.models import moe_transformer as moe

            cfg = moe.MoEConfig(
                vocab=64, d_model=32, layers=2, heads=4, kv_heads=2,
                head_dim=8, experts=4 * sp.size, d_ff=64,
                routing=args.routing, aux_weight=args.aux_weight,
                z_weight=args.z_weight,
            )
            params = moe.init_params(jax.random.PRNGKey(0), cfg)
            step = moe.make_global_train_step(
                mesh, dp, tp, sp, cfg, lr=3e-1, remat=remat
            )
        b = 2 * dp.size
        s = 16 * sp.size
        label = f"mesh {shape} (dp x tp x sp)"
    else:
        pp_n = min(n, 4) if n > 1 else 1
        dp_n = n // pp_n
        mesh = jax.make_mesh((dp_n, pp_n), ("dp", "pp"), axis_types=auto * 2)
        world = m.MeshComm.from_mesh(mesh)
        dp, pp = world.sub("dp"), world.sub("pp")

        from mpi4jax_tpu.models import pp_transformer as ppt

        cfg = ppt.TransformerConfig(
            vocab=64, d_model=32, layers=pp_n, heads=4, kv_heads=2,
            head_dim=8, d_ff=64,
        )
        params = ppt.init_params(jax.random.PRNGKey(0), cfg)
        step = ppt.make_global_train_step(
            mesh, dp, pp, cfg, n_micro=args.micro, lr=3e-1,
            schedule=args.schedule,
        )
        b = 2 * args.micro * dp_n
        s = 16
        label = (
            f"mesh ({dp_n}, {pp_n}) (dp x pp), {args.micro} microbatches, "
            f"{args.schedule} schedule"
        )

    tokens = jax.random.randint(jax.random.PRNGKey(1), (b, s), 0, cfg.vocab)
    batch = (tokens, jnp.roll(tokens, -1, axis=1))

    mgr = None
    start = 0
    if args.checkpoint:
        from mpi4jax_tpu.utils import checkpoint as ckpt

        mgr = ckpt.Manager(args.checkpoint, max_to_keep=2)
        last = mgr.latest_step()
        if last is not None:
            tree = mgr.restore(last, like={"params": params})
            # back to host arrays: restored leaves are committed to a
            # single device, which the multi-device jit would reject —
            # uncommitted inputs it re-shards automatically
            params = jax.tree.map(np.asarray, tree["params"])
            start = last
            print(f"resumed from step {start}")
            if start >= args.steps:
                print(
                    f"checkpoint already at step {start} >= --steps "
                    f"{args.steps}; nothing to train"
                )

    print(f"{args.mode}: {label}, batch {b}x{s}, {n} devices")
    loss0 = None
    val = None
    try:
        for i in range(start, args.steps):
            params, loss = step(params, batch)
            val = float(np.asarray(loss)[0])
            if loss0 is None:
                loss0 = val
            if i % 5 == 0:
                print(f"step {i:4d}  loss {val:.4f}")
            if mgr is not None:
                mgr.maybe_save(
                    i + 1, {"params": params}, every=args.checkpoint_every
                )
    finally:
        # drain any in-flight async save even on interrupt — losing the
        # newest checkpoint defeats the flag's purpose
        if mgr is not None:
            mgr.close()
    if val is not None:
        print(f"loss {loss0:.4f} -> {val:.4f}")
        assert start > 0 or val < loss0, "training did not reduce the loss"

    if args.mode != "moe" and (
        args.routing != "expert_choice" or args.aux_weight or args.z_weight
    ):
        print("--routing/--aux-weight/--z-weight apply to --mode moe only")
    if args.mode == "moe" and args.routing == "topk":
        # router-quality diagnostics on the trained weights (§5.5):
        # per-expert load, unweighted balance/z losses, dropped tokens
        rep = moe.routing_report(params, tokens, cfg, dp.size, sp.size)
        load = ", ".join(f"{v:.3f}" for v in np.asarray(rep["load"]))
        print(
            f"router: load [{load}]  balance {rep['balance_loss']:.3f}  "
            f"z {rep['z_loss']:.3f}  dropped {rep['dropped_fraction']:.3f}"
        )

    if args.kv_bucket is not None and not (
        args.generate and args.mode == "dense"
    ):
        print("--kv-bucket only applies to --generate in dense mode; ignored")
    if args.generate and args.mode != "dense":
        print("--generate is only supported with --mode dense; skipping")
    elif args.generate:
        # inference round trip on the trained weights: prefix of the
        # first training sequence -> greedy continuation
        prefix = 4
        max_len = prefix + args.generate
        decode = tfm.make_global_decode(
            mesh, dp, tp, cfg, max_len, kv_bucket=args.kv_bucket
        )
        prompt = jnp.broadcast_to(
            tokens[:1, :prefix], (dp.size, prefix)
        )
        out = np.asarray(decode(params, prompt))
        print(f"prompt  {out[0, :prefix].tolist()}")
        print(f"decoded {out[0, prefix:].tolist()}")
    return params


if __name__ == "__main__":
    from mpi4jax_tpu.utils.runtime import enable_compile_cache

    enable_compile_cache()
    main()


# -- t4j-lint entries (trace-time contract verification; no execution) --


def _lint_dense_train_step():
    import jax
    import jax.numpy as jnp
    import mpi4jax_tpu as m
    from mpi4jax_tpu.models import transformer as tfm

    mesh = jax.make_mesh(
        (2, 2, 2), ("dp", "tp", "sp"),
        axis_types=(jax.sharding.AxisType.Auto,) * 3,
    )
    world = m.MeshComm.from_mesh(mesh)
    cfg = tfm.TransformerConfig(
        vocab=32, d_model=16, layers=2, heads=4, kv_heads=2, head_dim=8,
        d_ff=32,
    )
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, cfg.vocab)
    step = tfm.make_global_train_step(
        mesh, world.sub("dp"), world.sub("tp"), world.sub("sp"), cfg,
        lr=1e-1,
    )
    return step(params, (tokens, jnp.roll(tokens, -1, axis=1)))


T4J_LINT_ENTRIES = [("dense_train_step_2x2x2", _lint_dense_train_step)]
