"""Shallow-water demo application for mpi4jax_tpu.

The counterpart of the reference's examples/shallow_water.py, redesigned
SPMD: instead of `mpirun -n N python shallow_water.py` with one process
per rank, a single process shards the domain over all visible devices
via a ("y", "x") mesh — on a TPU slice the halo exchanges ride ICI.

Usage:

    # quick correctness check on a small grid
    python examples/shallow_water.py --check

    # demo run (360x180 grid, 10 model days)
    python examples/shallow_water.py

    # published-benchmark configuration (3600x1800, 0.1 model days;
    # reference numbers in BASELINE.md)
    python examples/shallow_water.py --benchmark

    # explicit decomposition (devices = py * px)
    python examples/shallow_water.py --mesh 2 4

    # the run that watches itself: a monitor line after every chunk
    python examples/shallow_water.py --monitor

    # the run that is differentiated: fit the initial fields to
    # observations of a truth run by 5 steps of steepest descent
    python examples/shallow_water.py --assimilate 5

    # the run that is linearised: the same fit by incremental 4D-Var, 5
    # iterations of its inner loop (a tangent-linear sweep and an
    # adjoint sweep a conjugate-gradient iteration)
    python examples/shallow_water.py --incremental 5

Every mode but --benchmark builds `SWConfig()` with its default
`ghost=1`: upstream's layout, (ny+2, nx+2) arrays a device, and
upstream's step as written, array code with one halo exchange after
each of twelve fields a step.  --benchmark takes `bench_size()`'s
`ghost=2`, which on a TPU in float32 is one Pallas kernel and three
exchanges a step.  On one v5e chip at 14400x7200 cells the two read
2 202 and 18 358 Mcell/s (PERF.md, PR 43: the cells
`sw-as-written-1chip` and `sw-bench-1chip`): for a run that is timed,
pass `ghost=2`.
"""

import argparse
import pathlib
import sys

import numpy as np

# allow running straight from a checkout
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))


def twin_experiment(cfg, comm, num_steps, calls, observe):
    """``(guess, obs)``: observations of ``h`` of a truth run, and the
    first guess, the balanced jet without the demo's perturbation."""
    import jax.numpy as jnp

    from mpi4jax_tpu.models import shallow_water as sw

    interior = sw.make_snapshot(cfg, comm, sw.Snapshot(coarsen=1))
    observed = sw.make_snapshot(
        cfg, comm, sw.Snapshot(fields=("h",), coarsen=observe))
    truth = interior(*sw.make_init(cfg, comm)()[:3])
    # the truth run's h as the window's misfit sees it
    state = sw.make_first_step(cfg, comm)(sw.make_state(cfg, comm)(*truth))
    multi = sw.make_multistep(cfg, comm, num_steps)
    obs = [observed(state.h)[0]]
    for _ in range(calls):
        state = multi(state)
        obs.append(observed(state.h)[0])
    obs = jnp.stack(obs)
    # the first guess: the balanced jet without the demo's perturbation
    y = (jnp.arange(cfg.ny, dtype=jnp.float32) * cfg.dy)[:, None]
    x = (jnp.arange(cfg.nx, dtype=jnp.float32) * cfg.dx)[None, :]
    bump = 0.2 * jnp.sin(x / cfg.length_x * 10 * jnp.pi) * jnp.cos(
        y / cfg.length_y * 8 * jnp.pi)
    return (truth[0] - bump.astype(truth[0].dtype), truth[1], truth[2]), obs


def assimilate(cfg, comm, iterations, num_steps, calls=4, observe=2):
    """The twin experiment of ``--assimilate``: returns the costs."""
    import jax

    from mpi4jax_tpu.models import shallow_water as sw

    guess, obs = twin_experiment(cfg, comm, num_steps, calls, observe)
    fit = sw.Descent(cfg, comm, calls=calls, num_steps=num_steps, observe=observe)
    # one step length for the run, under what the roughest direction
    # of the cost allows
    rate, _curvatures, _cost = fit.step_length(*guess, obs)
    print(
        f"assimilate: a window of {1 + calls * num_steps} steps, h observed "
        f"over {observe}x{observe} cells {calls + 1} times; step length "
        f"{rate:.4g}",
        file=sys.stderr,
    )
    fit.start(*guess, obs, rate)
    fit.iterate(iterations)
    fit.wait()
    costs = fit.costs()
    for i, c in enumerate(costs):
        print(f"iteration {i}: cost {c:.6g}")
    final = float(fit.gradient(*fit.fields, obs)[0][0, 0])
    print(f"after {iterations} steps: cost {final:.6g}")
    jax.block_until_ready(fit.fields)
    return costs + [final]


def incremental(cfg, comm, iterations, num_steps, calls=4, observe=2,
                weight=0.11):
    """The twin experiment of ``--incremental``: the quadratic cost
    before the loop and after each iteration, then the nonlinear misfit
    at the first guess plus the increment."""
    from mpi4jax_tpu.models import shallow_water as sw

    guess, obs = twin_experiment(cfg, comm, num_steps, calls, observe)
    fit = sw.InnerLoop(cfg, comm, calls=calls, num_steps=num_steps,
                       observe=observe, weight=weight, iterations=iterations)
    print(
        f"incremental: a window of {1 + calls * num_steps} steps, h observed "
        f"over {observe}x{observe} cells {calls + 1} times; background "
        f"weight {weight:g}",
        file=sys.stderr,
    )
    fit.linearise(*guess, obs)  # the outer loop
    fit.iterate(iterations)  # the inner loop: a tangent and an adjoint sweep each
    fit.wait()
    costs = fit.costs()
    for i, c in enumerate(costs):
        print(f"iteration {i}: quadratic cost {c:.6g}")
    final = float(fit.gradient.forward(
        *(a + d for a, d in zip(guess, fit.increment())), obs)[0][0, 0])
    print(f"the misfit at the first guess plus the increment: {final:.6g}")
    return costs + [final]


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument(
        "--benchmark",
        action="store_true",
        help="the published benchmark's grid at ghost=2 (on a TPU the "
        "step's kernel); every other mode runs SWConfig()'s ghost=1, "
        "upstream's step as written: twelve exchanges a step, some 17 "
        "times slower on a v5e chip (PERF.md, PR 42)",
    )
    p.add_argument("--check", action="store_true")
    p.add_argument("--mesh", nargs=2, type=int, metavar=("PY", "PX"))
    p.add_argument("--days", type=float, default=None, help="model days")
    p.add_argument("--multistep", type=int, default=25)
    p.add_argument(
        "--force-cpu",
        action="store_true",
        help="run on virtual CPU devices (honours "
        "--xla_force_host_platform_device_count in XLA_FLAGS)",
    )
    p.add_argument(
        "--plot",
        metavar="FILE.png",
        help="save the final surface-height anomaly (the reference "
        "gathers to rank 0 and plots, shallow_water.py:586-599 there)",
    )
    p.add_argument(
        "--animate",
        metavar="FILE.gif",
        help="collect one frame per multistep chunk and save an "
        "animation (the reference's matplotlib animation output): the "
        "solver's job writes a snapshot of h after every chunk and "
        "copies it to the host beside the next chunks; with "
        "--checkpoint-dir a rerun goes on writing frames from the newest "
        "save, the same frames a run that was never stopped writes",
    )
    p.add_argument(
        "--coarsen",
        type=int,
        default=1,
        metavar="C",
        help="a frame is the mean of h over CxC blocks of cells (1: "
        "every cell); C has to divide a device's block",
    )
    p.add_argument(
        "--checkpoint-dir",
        "--checkpoint",
        dest="checkpoint",
        metavar="DIR",
        help="save the whole state every --checkpoint-every chunks, "
        "beside the chunks that follow; a rerun with the same DIR "
        "resumes from the newest save (the job's own: "
        "docs/shallow-water.md, Saving and resuming); with --animate the "
        "snapshots' copies and a save's pieces share one bound on what is "
        "on its way to the host",
    )
    p.add_argument("--checkpoint-every", type=int, default=1)
    p.add_argument(
        "--monitor",
        action="store_true",
        help="watch the solution as it goes: after every chunk the "
        "job prints a line (non-finite values, the largest CFL number, "
        "the thinnest layer, the mass: each a reduction on the chip "
        "that holds a block and the library's allreduce over the mesh) "
        "and stops the run at most four chunks after the one that went "
        "bad, where a run without it is paid for to its end",
    )
    p.add_argument(
        "--assimilate",
        type=int,
        default=0,
        metavar="N",
        help="a twin experiment (Courtier and Talagrand 1990): observe h "
        "of a truth run after the first step and after each of 4 chunks "
        "of --multistep steps, start from the jet without its "
        "perturbation, and take N steps of steepest descent on the "
        "misfit, each along the gradient that make_gradient takes back "
        "through every step and every halo exchange of the window; "
        "prints the cost before each step",
    )
    p.add_argument(
        "--incremental",
        type=int,
        default=0,
        metavar="N",
        help="the same twin experiment by incremental 4D-Var (Courtier, "
        "Thepaut and Hollingsworth 1994): the nonlinear window once from "
        "the first guess, then N iterations of conjugate gradients on the "
        "quadratic cost in the increment, each one tangent-linear sweep "
        "(forward mode through every step and every halo exchange) and "
        "one adjoint sweep; prints the quadratic cost after each",
    )
    args = p.parse_args(argv)

    import jax

    if args.force_cpu:
        jax.config.update("jax_platforms", "cpu")

    import mpi4jax_tpu as m
    from mpi4jax_tpu.models import shallow_water as sw
    from mpi4jax_tpu.utils.runtime import best_mesh_shape

    n_dev = len(jax.devices())
    shape = tuple(args.mesh) if args.mesh else best_mesh_shape(n_dev)
    mesh = jax.make_mesh(
        shape, ("y", "x"), axis_types=(jax.sharding.AxisType.Auto,) * 2
    )
    comm = m.MeshComm.from_mesh(mesh)

    if args.benchmark:
        cfg = sw.SWConfig().bench_size()
        days = args.days if args.days is not None else 0.1
    elif args.check:
        cfg = sw.SWConfig(ny=24, nx=48)
        days = args.days if args.days is not None else 0.02
    else:
        cfg = sw.SWConfig()
        days = args.days if args.days is not None else 10.0

    print(
        f"shallow_water: grid {cfg.ny}x{cfg.nx}, mesh {shape}, "
        f"devices {n_dev}, dt {cfg.dt:.1f}s, {days} model days",
        file=sys.stderr,
    )

    if args.assimilate:
        return assimilate(cfg, comm, args.assimilate, args.multistep)
    if args.incremental:
        return incremental(cfg, comm, args.incremental, args.multistep)

    gather = None
    if args.plot or args.animate:
        import matplotlib  # fail in ms, not after the whole run  # noqa: F401

    if args.plot:
        specs = sw._mesh_specs(comm)
        gather = jax.jit(
            jax.shard_map(
                lambda s: sw.gather_global(s.h, comm, ghost=cfg.ghost)[None],
                mesh=mesh,
                in_specs=(specs,),
                out_specs=jax.P(("y", "x"), None, None),
            )
        )

    frames = []
    on_chunk = snapshot = None
    if args.animate:
        # frames ride the solver's job (models/shallow_water.py
        # SolverJob): after every chunk each device coarse-grains its
        # own block of h, the copy to the host runs beside the next
        # chunks (which still donate their input), and the callback is
        # handed host arrays and the step they belong to, in order, at
        # most `lag` chunks late; the live state is not handed out
        snapshot = sw.Snapshot(fields=("h",), coarsen=args.coarsen, lag=4)

        def on_chunk(snapshot, step):
            frames.append(snapshot["h"])

    monitor = on_monitor = None
    if args.monitor:
        # the lines ride the job too (SolverJob, "A job that watches
        # itself"): read at most `lag` chunks late, and a bad one raises
        monitor = sw.Monitor(lag=4)

        def on_monitor(line):
            print(
                f"monitor: step {line['step']}: cfl {line['cfl']:.4f}, "
                f"h_min {line['h_min']:.3f} m, mass {line['mass']:.9e} m^3, "
                f"{line['nonfinite']} values not finite",
                file=sys.stderr,
            )

    solve = sw.make_solver(
        cfg,
        comm,
        num_multisteps=args.multistep,
        on_chunk=on_chunk,
        checkpoint_dir=args.checkpoint,
        checkpoint_every=args.checkpoint_every,
        snapshot=snapshot,
        monitor=monitor,
        on_monitor=on_monitor,
    )
    try:
        state, wall, steps = solve(days * sw.DAY_IN_SECONDS)
    except sw.MonitorStop as stop:
        sys.exit(f"shallow_water: {stop}")

    h_local = np.asarray(jax.device_get(state.h))
    assert np.isfinite(h_local).all(), "solution diverged"

    cells = cfg.ny * cfg.nx
    rate = cells * steps / wall if wall > 0 else float("nan")
    print(
        f"steps timed: {steps}, wall: {wall:.3f}s, "
        f"{rate:.3e} cell-updates/s ({rate / n_dev:.3e} per device)",
        file=sys.stderr,
    )
    if args.check:
        print("check passed: solution finite", file=sys.stderr)

    if args.plot or args.animate:
        import matplotlib

        matplotlib.use("Agg")
        import matplotlib.pyplot as plt

        def anomaly(h):
            return h - cfg.depth

        if args.plot:
            fig, ax = plt.subplots(figsize=(8, 4))
            hg = np.asarray(jax.device_get(gather(state)[0]))
            im = ax.imshow(anomaly(hg), origin="lower", cmap="RdBu_r")
            fig.colorbar(im, ax=ax, label="surface height anomaly [m]")
            ax.set_title(f"shallow water, {days} model days")
            fig.savefig(args.plot, dpi=120, bbox_inches="tight")
            print(f"saved {args.plot}", file=sys.stderr)
        if args.animate and not frames:
            print(
                "no frames collected (run shorter than one multistep "
                "chunk) — no animation written",
                file=sys.stderr,
            )
        if args.animate and frames:
            from matplotlib import animation

            fig, ax = plt.subplots(figsize=(8, 4))
            im = ax.imshow(
                anomaly(frames[0]), origin="lower", cmap="RdBu_r",
                animated=True,
            )
            fig.colorbar(im, ax=ax, label="surface height anomaly [m]")

            def update(i):
                im.set_array(anomaly(frames[i]))
                return (im,)

            ani = animation.FuncAnimation(
                fig, update, frames=len(frames), interval=80, blit=True
            )
            ani.save(args.animate, writer=animation.PillowWriter(fps=12))
            print(
                f"saved {args.animate} ({len(frames)} frames)",
                file=sys.stderr,
            )
    return rate


if __name__ == "__main__":
    from mpi4jax_tpu.utils.runtime import enable_compile_cache

    enable_compile_cache()
    main()


# -- t4j-lint entries (trace-time contract verification; no execution) --
#
# `t4j-lint examples/shallow_water.py` traces these thunks with
# mpi4jax_tpu.analysis.verify_comm: the full halo-exchange schedule of
# a multistep solver chunk is extracted and checked against the rule
# catalog (docs/static-analysis.md) on a small grid — the schedule is
# size-independent, so linting the 16x8 grid certifies the 3600x1800 one.


def _lint_multistep():
    import jax
    import mpi4jax_tpu as m
    from mpi4jax_tpu.models import shallow_water as sw

    mesh = jax.make_mesh(
        (2, 4), ("y", "x"), axis_types=(jax.sharding.AxisType.Auto,) * 2
    )
    comm = m.MeshComm.from_mesh(mesh)
    cfg = sw.SWConfig(ny=8, nx=16)
    return sw.make_multistep(cfg, comm, num_steps=2)(
        sw.make_init(cfg, comm)()
    )


T4J_LINT_ENTRIES = [("multistep_2x4", _lint_multistep)]
