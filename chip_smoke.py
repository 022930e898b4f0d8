#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py            # one TPU chip, every default phase
    python chip_smoke.py --chips 4  # the paths that exist only across chips

Run from the root of a copy of the tree (no git, no network needed).  It
drives the main path once through the entry points a user calls — the
shallow-water solver at the paper's size, the op surface under ``jit``
inside ``shard_map``, the ``large`` train step of
``benchmarks/transformer.py``, the native bridge's staged tier — checks
what comes out against a reference, and ends with one JSON line

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

as the LAST line of stdout.  Before it come one JSON object per phase
(wall and compile seconds, persistent-cache requests/hits/writes, what
was compared and the largest difference, peak device memory).  No line
carries a rate: the benchmark is ``python3 -m perfbench.run``.  Without
a TPU it prints one line on stderr, nothing on stdout, and exits 2; a
failed phase makes the exit code 1 and the last line ``{"ok": false,
"failed": [...]}``.

**One process for each chip.**  A chip belongs to one process at a time,
so this file is a JAX-free parent: it never imports jax.  Every phase
group runs as a child in turn (``python -c "import chip_smoke;
chip_smoke.child(<group>)"``), each child holds the chip alone, exits and
gives it back, and the parent kills a child's whole process group at its
deadline — which is how a hung host callback becomes a loud failure
instead of a hung machine.  The children share compiled code through the
persistent cache's fixed path (``utils.runtime.enable_compile_cache``).

There is no rehearsal option: tests import this module and call the
check functions at a tiny size on CPU devices (tests/test_chip_smoke.py).
"""

import argparse
import contextlib
import json
import os
import pathlib
import signal
import subprocess
import sys
import tempfile
import time

ROOT = pathlib.Path(__file__).resolve().parent

# The whole run must end inside the driver's 1200 s; children started
# late get what is left of this.
TOTAL_BUDGET_S = 1100

# float32 tolerances of the solver phase, in metres of surface height.
# At the paper's size h runs from 5 to 194 m (the geostrophic jet), so
# one float32 ulp is up to 1.5e-5 m.
#
# Schedules and decompositions run the same arithmetic on the same
# values and differ by fusion and reduction order only.  1e-3 is the
# bound tests/test_shallow_water.py holds the schedules to; its 2e-4 for
# decompositions is for a 24x48 grid and does not carry to 1800x3600,
# where the initial state subtracts a mean depth that is a float32 sum
# of 6.5 M terms, taken in another order by each decomposition: a nearly
# uniform offset (on the CPU backend 1.1e-4 mean, 2.7e-4 at the worst
# cell) that the 76 steps carry and do not grow.
TOL_SAME_ARITHMETIC = 1e-3
# TPU against the CPU backend: on top of that, exp/sin/cos and the
# division are different implementations, and the geostrophic height is
# a cumulative sum down 1800 rows whose rounding depends on the scan
# algorithm.
TOL_CPU_REFERENCE = 2e-3
# a snapshot against the block mean of the state it was taken from: a
# float32 sum of 16 depths of 100 m against the same sum in float64
TOL_BLOCK_MEAN = 1e-4

# Worker of the staged phase, shared with
# tests/proc/test_staged_backend.py: proc-backend ops on arrays that live
# on the worker's default device, eagerly and under jit.
STAGED_WORKER = """
import jax
import jax.numpy as jnp
import numpy as np
import mpi4jax_tpu as m

comm = m.get_default_comm()
assert comm.backend == "proc", comm
x = jnp.arange(4.0)  # lives on the default device
platform = next(iter(x.devices())).platform
base = np.arange(4.0)

def check(y, g, b):
    assert next(iter(y.devices())).platform == platform, y.devices()
    assert np.allclose(np.asarray(y), base * comm.size), y
    assert np.asarray(g).shape == (comm.size, 2), g
    assert np.allclose(np.asarray(b), 3 * base), b

def ops(v):
    tok = m.create_token()
    y, tok = m.allreduce(v, m.SUM, comm=comm, token=tok)
    g, tok = m.allgather(v[:2], comm=comm, token=tok)
    b, tok = m.bcast(v * 3, 0, comm=comm, token=tok)
    tok = m.barrier(comm=comm, token=tok)
    return y, g, b, tok.stamp

check(*ops(x)[:3])           # eagerly
check(*jax.jit(ops)(x)[:3])  # inside one compiled program
print(f"rank {comm.rank()} staged ok platform={platform}")
"""

# Child of the cpu_child phase: pins the CPU in code before jax starts a
# backend, so it never reaches for the chip its parent holds, and runs
# the public allreduce over eight virtual devices against numpy.
CPU_CHILD = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import mpi4jax_tpu as m

devices = jax.devices()
assert devices[0].platform == "cpu", devices
mesh = jax.make_mesh(
    (8,), ("p",), axis_types=(jax.sharding.AxisType.Auto,)
)
comm = m.MeshComm.from_mesh(mesh)
fn = jax.jit(jax.shard_map(
    lambda v: m.allreduce(v, m.SUM, comm=comm)[0],
    mesh=mesh, in_specs=jax.P("p"), out_specs=jax.P("p"),
))
x = np.outer(np.arange(1.0, 9.0), np.arange(1.0, 129.0)).astype(np.float32)
got = np.asarray(fn(x.ravel())).reshape(8, 128)
diff = float(np.abs(got - x.sum(0)).max())
print(f"cpu child devices={len(devices)} max_diff={diff}")
"""


# --------------------------------------------------------------- parent


def _say(msg):
    print(f"chip_smoke: {msg}", file=sys.stderr, flush=True)


def _run(argv, deadline, *, env=None, stderr=None):
    """Run ``argv`` from the checkout in a session of its own, with the
    checkout on PYTHONPATH; at ``deadline`` seconds the whole session is
    killed.  Returns ``(returncode or None on deadline, stdout,
    stderr)``; stderr passes through unless ``stderr=subprocess.PIPE``."""
    env = dict(os.environ, **(env or {}))
    env["PYTHONPATH"] = str(ROOT) + os.pathsep + env.get("PYTHONPATH", "")
    popen = subprocess.Popen(
        argv, stdout=subprocess.PIPE, stderr=stderr, text=True, env=env,
        cwd=str(ROOT), start_new_session=True,
    )
    try:
        out, err = popen.communicate(timeout=deadline)
        return popen.returncode, out, err
    except subprocess.TimeoutExpired:
        with contextlib.suppress(ProcessLookupError):
            os.killpg(os.getpgid(popen.pid), signal.SIGKILL)
        out, err = popen.communicate()
        return None, out, err


def _run_child(group, deadline):
    """One phase group as a child; ``(returncode or None, stdout)``."""
    rc, out, _ = _run(
        [
            sys.executable, "-c",
            f"import chip_smoke; chip_smoke.child({group!r})",
        ],
        deadline,
    )
    return rc, out


def _records(stdout, relay=True):
    recs = []
    for line in stdout.splitlines():
        if relay:  # the phase lines are the report
            print(line, flush=True)
        try:
            rec = json.loads(line)
        except ValueError:
            continue
        if isinstance(rec, dict) and "phase" in rec:
            recs.append(rec)
    return recs


def main(argv=None):
    ap = argparse.ArgumentParser(prog="chip_smoke.py")
    ap.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="4: run the cross-chip phases (2x2 solver, ops and "
        "rendezvous on 4 devices, the dp1 x tp2 x sp2 train step) and "
        "what they are compared with, and no default phase",
    )
    args = ap.parse_args(argv)
    start = time.monotonic()

    # nothing reaches stdout before a TPU is known to be there
    rc, out = _run_child("probe", 180)
    probe = next(iter(_records(out or "", relay=False)), None)
    if rc != 0 or probe is None:
        _say("no TPU: the probe child could not initialise jax "
             f"(exit {rc})")
        return 2
    device = probe["device"]
    if device["platform"] != "tpu":
        _say(f"no TPU: jax.devices()[0].platform is {device['platform']!r}")
        return 2
    if device["count"] < args.chips:
        _say(f"--chips {args.chips} needs {args.chips} devices, jax "
             f"reports {device['count']}")
        return 2

    failed = []
    for group, (deadline, phases) in GROUPS[args.chips].items():
        left = TOTAL_BUDGET_S - (time.monotonic() - start)
        rc, out = _run_child(group, max(30, min(deadline, left)))
        seen = {r["phase"]: r for r in _records(out or "")}
        for name in phases:
            if name not in seen:
                why = "deadline" if rc is None else f"exit {rc}"
                print(json.dumps({
                    "phase": name, "ok": False,
                    "error": f"no record: child {group!r} ended ({why})",
                }), flush=True)
                failed.append(name)
            elif not seen[name].get("ok"):
                failed.append(name)
    if failed:
        _say(f"FAILED phases: {', '.join(failed)}")
        print(json.dumps({"ok": False, "failed": failed}), flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


# ---------------------------------------------------------------- child


def child(group):
    """Entry of a phase-group child (internal): the only place that
    initialises a jax backend."""
    if group == "probe":
        import jax

        d = jax.devices()
        print(json.dumps({"phase": "probe", "device": {
            "platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d),
        }}), flush=True)
        return
    from mpi4jax_tpu.utils.runtime import enable_compile_cache

    enable_compile_cache()
    _deadline, phases = {**GROUPS[1], **GROUPS[4]}[group]
    # the staged group's own process never initialises a backend: its
    # launcher worker holds the chip
    ok = all([
        run_phase(name, check, holds_device=group != "staged")
        for name, check in phases.items()
    ])
    sys.stdout.flush()
    # skip interpreter teardown: a phase that failed inside a host
    # callback can leave a runtime thread that never joins
    os._exit(0 if ok else 1)


def _compiled_since(began_ns):
    """``(compile_s, cache)`` of what jax compiled or loaded since
    ``began_ns``: backend-compile seconds and the persistent cache's
    requests, hits and writes, from the library's own record of the
    process's builds (``utils/spans.py builds``, which listens to
    ``jax.monitoring``; this file registers nothing).  The recorder
    keeps its newest spans: where it has dropped some, ``cache`` says
    how many under ``dropped`` and the figures are of what is left."""
    from mpi4jax_tpu.utils import spans

    compiles = [s for s in spans.builds.spans()
                if s.name == spans.COMPILE and s.end_ns >= began_ns]
    cache = {key: sum(bool(s.counts[flag]) for s in compiles)
             for key, flag in (("requests", "asked"), ("hits", "cached"),
                               ("writes", "written"))}
    if spans.builds.dropped:
        cache["dropped"] = spans.builds.dropped
    return sum(s.seconds for s in compiles), cache


def run_phase(name, check, *, holds_device=True):
    """Run ``check`` (returns a dict of what it compared, raises where
    it is wrong), print the phase's JSON line, return whether it
    passed.  ``holds_device=False`` for a phase whose own process must
    never initialise a backend (its child holds the chip)."""
    import traceback

    import jax

    import mpi4jax_tpu.utils.spans  # noqa: F401  listens from here on

    rec = {"phase": name, "ok": False}
    began_ns = time.perf_counter_ns()
    try:
        rec.update(check())
        rec["ok"] = True
    except Exception as exc:  # noqa: BLE001 — report, then fail the run
        traceback.print_exc()
        rec["error"] = f"{type(exc).__name__}: {exc}"[:2000]
    rec["wall_s"] = round((time.perf_counter_ns() - began_ns) / 1e9, 3)
    compile_s, cache = _compiled_since(began_ns)
    rec["compile_s"], rec["cache"] = round(compile_s, 3), cache
    # memory_stats() is None on the CPU backend
    stats = jax.devices()[0].memory_stats() if holds_device else None
    if stats:
        rec["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
    print(json.dumps(rec), flush=True)
    return rec["ok"]


def _auto(n):
    import jax

    return (jax.sharding.AxisType.Auto,) * n


# ---------------------------------------------------------------- solver


def _interior(field, ghost, mesh_shape):
    """Global interior of a solver field from its sharded array (each
    device's block carries its own ghost ring)."""
    import numpy as np

    arr = np.asarray(field)
    py, px = mesh_shape
    ly, lx = arr.shape[0] // py, arr.shape[1] // px
    g = ghost
    blocks = arr.reshape(py, ly, px, lx)[:, g:ly - g, :, g:lx - g]
    return blocks.reshape(py * (ly - 2 * g), px * (lx - 2 * g))


def _solve(cfg, devices, mesh_shape, steps_per_call, timed_calls):
    """init -> first step -> warm-up multistep -> ``timed_calls`` more,
    through ``make_solver``; returns the final state's interior fields,
    the state and the comm."""
    import jax

    import mpi4jax_tpu as m
    from mpi4jax_tpu.models import shallow_water as sw

    mesh = jax.make_mesh(
        mesh_shape, ("y", "x"), axis_types=_auto(2), devices=devices
    )
    comm = m.MeshComm.from_mesh(mesh)
    solve = sw.make_solver(cfg, comm, num_multisteps=steps_per_call)
    total = 1 + steps_per_call * (1 + timed_calls)
    state, _wall, steps = solve(cfg.dt * (total - 0.5))
    if steps != steps_per_call * timed_calls:
        raise AssertionError(f"timed {steps} steps, wanted "
                             f"{steps_per_call * timed_calls}")
    fields = {
        k: _interior(getattr(state, k), cfg.ghost, mesh_shape)
        for k in ("h", "u", "v")
    }
    return fields, state, comm


def _max_diff(a, b):
    import numpy as np

    return float(max(np.abs(a[k] - b[k]).max() for k in a))


def _require_finite(fields, what):
    import numpy as np

    for k, v in fields.items():
        if not np.isfinite(v).all():
            raise AssertionError(f"{what}: field {k} is not finite")


def solver_check(cfg, devices, ref_device, *, ghosts=(1, 2, 4),
                 steps_per_call=25, timed_calls=2):
    """The three ghost schedules agree with each other on a 1x1 mesh,
    and ghost 2 agrees with the same solver on ``ref_device`` (a named
    reference, not a fallback)."""
    from dataclasses import replace

    out, runs = {}, {}
    for ghost in ghosts:
        fields, _state, _comm = _solve(
            replace(cfg, ghost=ghost), devices[:1], (1, 1),
            steps_per_call, timed_calls,
        )
        _require_finite(fields, f"ghost {ghost}")
        runs[ghost] = fields
    base = ghosts[0]
    out["schedules_max_diff"] = max(
        _max_diff(runs[base], runs[g]) for g in ghosts[1:]
    )
    ref, _state, _comm = _solve(
        replace(cfg, ghost=2), [ref_device], (1, 1),
        steps_per_call, timed_calls,
    )
    out["reference_max_diff"] = _max_diff(runs[2], ref)
    out["compared"] = (
        f"{cfg.ny}x{cfg.nx} f32 h,u,v after "
        f"{1 + steps_per_call * (1 + timed_calls)} steps: ghost "
        f"{'/'.join(map(str, ghosts))} with each other (tol "
        f"{TOL_SAME_ARITHMETIC}), ghost 2 with the solver on {ref_device} "
        f"(tol {TOL_CPU_REFERENCE})"
    )
    out["max_diff"] = max(out["schedules_max_diff"],
                          out["reference_max_diff"])
    if out["schedules_max_diff"] > TOL_SAME_ARITHMETIC:
        raise AssertionError(f"schedules disagree: {out}")
    if out["reference_max_diff"] > TOL_CPU_REFERENCE:
        raise AssertionError(f"reference disagrees: {out}")
    return out


def solver_weak_check(cfg, devices, *, steps_per_call=25, timed_calls=2):
    """2x2 at the weak-scaled size: every array on four distinct
    devices, halos as collective-permutes in the compiled program."""
    from dataclasses import replace

    from mpi4jax_tpu.models import shallow_water as sw

    # the same physical domain at twice the resolution: doubling the
    # cell counts at a fixed dx doubles the jet's width and its Coriolis
    # range, and the geostrophic height then exceeds the depth (NaN)
    big = replace(cfg, ny=2 * cfg.ny, nx=2 * cfg.nx,
                  dx=cfg.dx / 2, dy=cfg.dy / 2)
    fields, state, comm = _solve(
        big, devices[:4], (2, 2), steps_per_call, timed_calls
    )
    _require_finite(fields, "2x2 weak-scaled")
    for name, arr in zip(state._fields, state):
        on = {s.device for s in arr.addressable_shards}
        if len(on) != 4:
            raise AssertionError(
                f"state.{name} lives on {len(on)} device(s), wanted 4"
            )
    text = sw.make_multistep(big, comm, steps_per_call).lower(
        state).compile().as_text()
    if "collective-permute" not in text:
        raise AssertionError("no collective-permute in the 2x2 program")
    return {
        "compared": f"{big.ny}x{big.nx} on 2x2 ({cfg.ny}x{cfg.nx} per "
        "chip): finite, 6 arrays x 4 distinct devices, "
        "collective-permute in the compiled text",
        "max_diff": None,
    }


def solver_invariance_check(cfg, devices, *, steps_per_call=25,
                            timed_calls=2):
    """One global problem on 2x2 against the same problem on 1x1."""
    four, *_ = _solve(cfg, devices[:4], (2, 2), steps_per_call, timed_calls)
    one, *_ = _solve(cfg, devices[:1], (1, 1), steps_per_call, timed_calls)
    _require_finite(four, "2x2")
    diff = _max_diff(four, one)
    out = {
        "compared": f"{cfg.ny}x{cfg.nx} ghost {cfg.ghost} h,u,v after "
        f"{1 + steps_per_call * (1 + timed_calls)} steps: 2x2 with 1x1 "
        f"(tol {TOL_SAME_ARITHMETIC})",
        "max_diff": diff,
    }
    if diff > TOL_SAME_ARITHMETIC:
        raise AssertionError(f"decomposition changes the answer: {out}")
    return out


def _window_inputs(cfg, comm, mesh_shape, calls, observe, seed):
    """``(fields, obs, rng)`` of a differentiated window: the jet's own
    interior fields, observations a seeded way off the observed ``h``
    they start with, and the generator, for what else a check draws."""
    import numpy as np

    from mpi4jax_tpu.models import shallow_water as sw

    state = sw.make_init(cfg, comm)()
    fields = tuple(
        _interior(getattr(state, k), cfg.ghost, mesh_shape)
        for k in ("h", "u", "v"))
    coarse = fields[0].reshape(
        cfg.ny // observe, observe, cfg.nx // observe, observe
    ).mean(axis=(1, 3))
    rng = np.random.default_rng(seed)
    noise = rng.normal(size=(calls + 1, *coarse.shape))
    return fields, (coarse + 0.05 * noise).astype(np.float32), rng


def solver_adjoint_check(cfg, devices, *, calls=2, steps_per_call=5, observe=2,
                         tol=1e-4):
    """The gradient of a small window on 2x2 against the gradient of the
    same fields on 1x1 (``models/shallow_water.py make_gradient``): the
    adjoint exchange's reversed permutes cross chips on the one side and
    wrap onto the same block on the other.  The jet's own fields, and
    observations a seeded way off what the window makes of them."""
    import jax
    import numpy as np

    import mpi4jax_tpu as m
    from mpi4jax_tpu.models import shallow_water as sw

    def gradient(mesh_shape, some, fields=None, obs=None):
        mesh = jax.make_mesh(
            mesh_shape, ("y", "x"), axis_types=_auto(2), devices=some
        )
        comm = m.MeshComm.from_mesh(mesh)
        if fields is None:
            fields, obs, _ = _window_inputs(
                cfg, comm, mesh_shape, calls, observe, seed=54)
        out = sw.make_gradient(
            cfg, comm, calls=calls, num_steps=steps_per_call, observe=observe
        )(*fields, obs)
        return fields, obs, [np.asarray(a) for a in out]

    fields, obs, one = gradient((1, 1), devices[:1])
    _, _, four = gradient((2, 2), devices[:4], fields, obs)
    rel = {
        k: float(np.linalg.norm(a - b) / np.linalg.norm(b))
        for k, a, b in zip(("h", "u", "v"), four[1:], one[1:])
    }
    out = {
        "compared": f"{cfg.ny}x{cfg.nx} ghost {cfg.ghost}: dJ/dh0, dJ/du0, "
        f"dJ/dv0 of a window of {1 + calls * steps_per_call} steps, h "
        f"observed over {observe}x{observe} cells: 2x2 with 1x1, relative "
        f"L2 (tol {tol})",
        "cost": [float(four[0][0, 0]), float(one[0][0, 0])],
        "rel_l2": rel,
    }
    if not all(np.isfinite(a).all() for a in four) or max(rel.values()) > tol:
        raise AssertionError(f"decomposition changes the gradient: {out}")
    return out


def solver_tangent_check(cfg, devices, ref_devices, *, calls=2, steps_per_call=5,
                         observe=2, weight=0.11, tol=2e-4):
    """The linearised window on ``devices`` (2x2 where there are four,
    else 1x1) against the same on ``ref_devices``' first as 1x1
    (``models/shallow_water.py make_product``): ``H M p`` by the
    tangent-linear sweep and ``A p`` by the adjoint sweep of it, and the
    adjoint test ``<M p, w> = <p, M^T w>`` on ``devices``' own two
    programs.  On one chip against the CPU backend the kernel's walk and
    its written-out tangent stand against the array code and jax's own
    rules; on four chips against one the exchange's tangent crosses
    chips on the one side and wraps onto the same block on the other.
    The jet's own fields, observations a seeded way off what the window
    makes of them, a seeded direction."""
    import jax
    import numpy as np

    import mpi4jax_tpu as m
    from mpi4jax_tpu.models import shallow_water as sw

    how = dict(calls=calls, num_steps=steps_per_call, observe=observe)

    def product(mesh_shape, some, made=None):
        mesh = jax.make_mesh(
            mesh_shape, ("y", "x"), axis_types=_auto(2), devices=some
        )
        comm = m.MeshComm.from_mesh(mesh)
        if made is None:
            fields, obs, rng = _window_inputs(
                cfg, comm, mesh_shape, calls, observe, seed=59)
            made = (fields, obs,
                    tuple(rng.normal(size=a.shape).astype(np.float32)
                          for a in fields),
                    rng.normal(size=obs.shape).astype(np.float32))
        fields, obs, p, w = made
        on_mesh = jax.sharding.NamedSharding(mesh, jax.P("y", "x"))
        stacked = jax.sharding.NamedSharding(mesh, jax.P(None, "y", "x"))
        fields = tuple(jax.device_put(a, on_mesh) for a in fields)
        p = tuple(jax.device_put(a, on_mesh) for a in p)
        _, starts, _ = sw.make_gradient(cfg, comm, **how).forward(
            *fields, jax.device_put(obs, stacked))
        run = sw.make_product(cfg, comm, weight=weight, **how)
        seen = run.tangent(*fields, starts, *p)
        back = run.adjoint(*fields, starts, jax.device_put(w, stacked))
        there = float(np.vdot(np.asarray(seen), w))
        home = sum(float(np.vdot(np.asarray(a), np.asarray(b)))
                   for a, b in zip(p, back))
        q = run(*fields, starts, *p)
        return made, np.asarray(seen), [np.asarray(a) for a in q], (there, home)

    mesh_shape = (2, 2) if len(devices) >= 4 else (1, 1)
    made, seen, q, (there, home) = product(
        mesh_shape, devices[:mesh_shape[0] * mesh_shape[1]])
    _, want_seen, want_q, _ = product((1, 1), ref_devices[:1], made)

    def rel(a, b):
        return float(np.linalg.norm(a - b) / np.linalg.norm(b))

    out = {
        "compared": f"{cfg.ny}x{cfg.nx} ghost {cfg.ghost}: H M p and A p of a "
        f"window of {1 + calls * steps_per_call} steps, h observed over "
        f"{observe}x{observe} cells, weight {weight}: "
        f"{mesh_shape[0]}x{mesh_shape[1]} on {devices[0].platform} with 1x1 on "
        f"{ref_devices[0].platform}, relative L2 (tol {tol}); the adjoint "
        "test on the former's two sweeps",
        "rel_l2": {"tangent": rel(seen, want_seen),
                   **{k: rel(a, b) for k, a, b in zip("huv", q, want_q)}},
        "adjoint_test": [there, home, abs(there - home) / abs(there)],
    }
    finite = all(np.isfinite(a).all() for a in (seen, *q))
    if not finite or max(out["rel_l2"].values()) > tol or out["adjoint_test"][2] > tol:
        raise AssertionError(f"the linearised window is not its reference's: {out}")
    return out


def solver_observed_check(shapes, devices, *, ghost=2, observe=2):
    """The observation operator's transpose alone
    (``models/shallow_water.py _observed``, under ``jax.vjp``), on one
    device at blocks of each of ``shapes`` interior cells, against
    numpy's ``repeat`` of the same cotangent: equal, every cell of the
    padded block.  At the benchmark's block a fast form of it was wrong
    on the chip where the same form was right at 516 x 1028 (jax's own
    rule, PERF.md, PR 54): a CPU test cannot see that, so the size is
    this check's point."""
    import jax
    import numpy as np

    from mpi4jax_tpu.models import shallow_water as sw

    c, g, wrong = observe, ghost, {}

    def transposed(block, coarse):
        return jax.vjp(lambda b: sw._observed(b, g, c), block)[1](coarse)[0]

    for ny, nx in shapes:
        rng = np.random.default_rng(57)
        coarse = (rng.normal(size=(ny // c, nx // c)) * 10.0 ** rng.integers(
            -6, 3, size=(ny // c, nx // c))).astype(np.float32)
        block = np.zeros((ny + 2 * g, nx + 2 * g), np.float32)
        got = np.asarray(jax.jit(transposed)(
            *(jax.device_put(a, devices[0]) for a in (block, coarse))))
        want = np.pad(np.repeat(np.repeat(coarse, c, 0), c, 1)
                      * np.float32(1.0 / (c * c)), g)
        wrong[f"{ny}x{nx}"] = (
            int((got != want).sum()) if got.shape == want.shape else -1)
    out = {
        "compared": f"the transpose of h's mean over {c}x{c} cells at ghost "
        f"{g}, a padded block from a coarse cotangent, with numpy's repeat: "
        "cells that differ (limit 0)",
        "wrong_cells": wrong,
    }
    if any(wrong.values()):
        raise AssertionError(f"the observation's transpose is not numpy's: {out}")
    return out


def _job(cfg, devices, mesh_shape, snapshot, steps_per_call=10, calls=4):
    """``make_init`` -> ``job.start`` -> ``calls`` calls through
    ``make_job``; returns the job and the snapshots its callback was
    handed, as ``[(step, {name: array})]``."""
    import jax

    import mpi4jax_tpu as m
    from mpi4jax_tpu.models import shallow_water as sw

    mesh = jax.make_mesh(
        mesh_shape, ("y", "x"), axis_types=_auto(2), devices=devices
    )
    comm = m.MeshComm.from_mesh(mesh)
    got = []
    job = sw.make_job(
        cfg, comm, steps_per_call, snapshot,
        snapshot and (lambda fields, step: got.append((step, fields))),
    )
    job.start(sw.make_init(cfg, comm)())
    job.advance(calls)
    job.drain()
    return job, got


def solver_job_check(cfg, devices, *, mesh_shapes=((1, 1),), coarsen=4,
                     steps_per_call=10, calls=4):
    """The solver as a job that writes output (``make_job``): on the
    first mesh a job with output returns bit for bit the state of a job
    without (every call donates its input either way); every snapshot
    comes once, in step order; the last is the block mean of the state
    the job returns; and on every further mesh the snapshots are the
    first mesh's to the rounding of another decomposition.  On the chip
    the step is the kernel and the snapshots start in it: the record's
    ``snapshots_summed_in_step`` says for how many on each mesh (all of
    them where ``coarsen`` divides a strip; none on the CPU), and
    ``steps_per_walk`` how many time steps a walk of it advances (2 on
    every mesh, beside neighbours from deeper slabs; 1 on the CPU)."""
    import numpy as np

    from mpi4jax_tpu.models import shallow_water as sw

    snapshot = sw.Snapshot(coarsen=coarsen, lag=2)
    steps = [1 + steps_per_call * (k + 1) for k in range(calls)]
    runs = []
    for shape in mesh_shapes:
        n = shape[0] * shape[1]
        job, got = _job(cfg, devices[:n], shape, snapshot, steps_per_call, calls)
        if [step for step, _ in got] != steps:
            raise AssertionError(
                f"{shape}: snapshots of steps {[s for s, _ in got]}, "
                f"wanted {steps}")
        stats = job.stats()
        if stats["max_lag"] > snapshot.lag:
            raise AssertionError(f"{shape}: a snapshot came late: {stats}")
        runs.append((shape, job, got))
    shape, job, got = runs[0]
    quiet, _ = _job(cfg, devices[:shape[0] * shape[1]], shape, None,
                    steps_per_call, calls)
    for name, a, b in zip(job.state._fields, job.state, quiet.state):
        if not np.array_equal(np.asarray(a), np.asarray(b)):
            raise AssertionError(
                f"{shape}: state.{name} of a job with output differs from "
                "that of a job without")
    c = coarsen
    last = 0.0
    for k, mine in got[-1][1].items():
        whole = _interior(getattr(job.state, k), cfg.ghost, shape)
        ny, nx = whole.shape
        want = whole.reshape(ny // c, c, nx // c, c).mean(
            axis=(1, 3), dtype=np.float64)
        last = max(last, float(np.abs(mine - want).max()))
    across = max(
        (float(np.abs(a[k] - b[k]).max())
         for _, _, other in runs[1:]
         for (_, a), (_, b) in zip(got, other) for k in a),
        default=0.0)
    out = {
        "compared": f"{cfg.ny}x{cfg.nx} ghost {cfg.ghost} as a job, {calls} "
        f"calls of {steps_per_call} steps, h,u,v averaged {c}x{c} after "
        f"each: with output bit for bit the state without; the last "
        f"snapshot with the returned state's block mean (tol "
        f"{TOL_BLOCK_MEAN}); snapshots on "
        f"{' and '.join('x'.join(map(str, s)) for s in mesh_shapes)} "
        f"(tol {TOL_SAME_ARITHMETIC})",
        "last_snapshot_max_diff": last,
        "meshes_max_diff": across,
        "max_diff": max(last, across),
        "snapshots_summed_in_step": {
            "x".join(map(str, shape)): job.stats()["snapshots_summed_in_step"]
            for shape, job, _ in runs},
        "steps_per_walk": {
            "x".join(map(str, shape)): job.stats()["steps_per_walk"]
            for shape, job, _ in runs},
    }
    if last > TOL_BLOCK_MEAN:
        raise AssertionError(f"the last snapshot is not the state's: {out}")
    if across > TOL_SAME_ARITHMETIC:
        raise AssertionError(f"decomposition changes the snapshots: {out}")
    return out


def solver_restart_check(cfg, devices, *, mesh_shapes=((1, 1),),
                         steps_per_call=10, calls=4, every=2):
    """The solver as a job that is saved, killed and resumed
    (``make_job(checkpoint=...)``): on every mesh a job saves its whole
    state every ``every`` calls beside the calls that follow, each
    chip's share in pieces, and is dropped after ``calls``; a new job
    that knows the directory resumes from the newest save and runs
    ``every`` calls more: bit for bit the state of a job that was never
    stopped; the directory holds the newest saves and no temporary; and
    the meshes agree to the rounding of another decomposition."""
    import shutil
    import tempfile

    import numpy as np

    from mpi4jax_tpu.models import shallow_water as sw

    ends = []
    for shape in mesh_shapes:
        n = shape[0] * shape[1]
        whole, _ = _job(cfg, devices[:n], shape, None, steps_per_call,
                        calls + every)
        directory = tempfile.mkdtemp(prefix="chip-smoke-restart-")
        try:
            ck = sw.Checkpoint(directory, every_calls=every, keep=2)
            comm = whole.comm
            killed = sw.make_job(cfg, comm, steps_per_call, checkpoint=ck)
            killed.start(sw.make_init(cfg, comm)())
            killed.advance(calls)
            killed.drain()
            saved = killed.stats()
            killed.state = None
            del killed
            resumed = sw.make_job(cfg, comm, steps_per_call, checkpoint=ck)
            step = resumed.resume()
            if step != 1 + steps_per_call * (calls - calls % every):
                raise AssertionError(f"{shape}: resumed from step {step}")
            resumed.advance(every + calls % every)
            resumed.drain()
            series = resumed.series
            if series.leftovers() or len(series.steps()) != 2:
                raise AssertionError(
                    f"{shape}: the directory holds {series.steps()} and "
                    f"{series.leftovers()}")
            if saved["saves_started"] != saved["saves_acknowledged"]:
                raise AssertionError(f"{shape}: a save was lost: {saved}")
            for name, a, b in zip(whole.state._fields, resumed.state, whole.state):
                if not np.array_equal(np.asarray(a), np.asarray(b)):
                    raise AssertionError(
                        f"{shape}: state.{name} of the resumed job differs "
                        "from that of a job never stopped")
            ends.append({k: _interior(getattr(resumed.state, k), cfg.ghost, shape)
                         for k in ("h", "u", "v")})
            record = resumed.saves[-1]
        finally:
            shutil.rmtree(directory, ignore_errors=True)
    across = max((float(np.abs(ends[0][k] - other[k]).max())
                  for other in ends[1:] for k in other), default=0.0)
    out = {
        "compared": f"{cfg.ny}x{cfg.nx} ghost {cfg.ghost} saved every {every} "
        f"calls of {steps_per_call} steps, dropped after {calls} and resumed: "
        f"bit for bit the job never stopped, on "
        f"{' and '.join('x'.join(map(str, s)) for s in mesh_shapes)} "
        f"(tol {TOL_SAME_ARITHMETIC} between them)",
        "save_bytes": record["bytes"],
        "save_commit_s": record["commit_s"],
        "meshes_max_diff": across,
        "max_diff": across,
    }
    if across > TOL_SAME_ARITHMETIC:
        raise AssertionError(f"decomposition changes the resumed run: {out}")
    return out


def solver_output_restart_check(cfg, devices, *, mesh_shapes=((1, 1),), coarsen=4,
                                steps_per_call=10, calls=4, every=2):
    """The solver as the job a user keeps and restarts
    (``make_job(snapshot, on_chunk, checkpoint)``): on every mesh a job
    writes a snapshot after every call and saves its whole state every
    ``every`` calls, both under one bound on what is on its way to the
    host (two snapshots: a third, or a snapshot beside a save's window
    of pieces, has to wait), and is dropped after ``calls`` with output
    undelivered; a new job resumes from the directory and runs
    ``every`` calls more.  The most bytes in flight never pass the
    bound; the resumed job's snapshots and state are bit for bit those
    of a job with output alone that was never stopped; and the meshes'
    snapshots agree to the rounding of another decomposition."""
    import shutil
    import tempfile

    import numpy as np

    from mpi4jax_tpu.models import shallow_water as sw

    one = 3 * (cfg.ny // coarsen) * (cfg.nx // coarsen) * np.dtype(cfg.dtype).itemsize
    bound = 2 * one + one // 2
    runs, peaks, waited = [], [], 0.0
    for shape in mesh_shapes:
        n = shape[0] * shape[1]
        snapshot = sw.Snapshot(coarsen=coarsen, lag=2, ahead_bytes=bound)
        whole, whole_got = _job(cfg, devices[:n], shape, snapshot,
                                steps_per_call, calls + every)
        directory = tempfile.mkdtemp(prefix="chip-smoke-output-restart-")
        try:
            comm, got = whole.comm, []

            def job():
                return sw.make_job(
                    cfg, comm, steps_per_call, snapshot,
                    lambda fields, step: got.append((step, fields)),
                    sw.Checkpoint(directory, every_calls=every, keep=2))

            killed = job()
            killed.start(sw.make_init(cfg, comm)())
            killed.advance(calls)
            killed._settle()  # the newest save acknowledged, output still pending
            peaks.append(killed.stats()["host_in_flight_max_bytes"])
            killed.state = None
            del killed, got[:]
            resumed = job()
            step = resumed.resume()
            if step != 1 + steps_per_call * (calls - calls % every):
                raise AssertionError(f"{shape}: resumed from step {step}")
            resumed.advance(every + calls % every)
            resumed.drain()
            stats = resumed.stats()
            peaks.append(stats["host_in_flight_max_bytes"])
            waited += stats["transfer_wait_s"]
            after = [(s, f) for s, f in whole_got if s > step]
            if [s for s, _ in got] != [s for s, _ in after]:
                raise AssertionError(
                    f"{shape}: the resumed job wrote steps {[s for s, _ in got]}, "
                    f"the job never stopped {[s for s, _ in after]}")
            for (s, mine), (_, theirs) in zip(got, after):
                for k in mine:
                    if not np.array_equal(mine[k], theirs[k]):
                        raise AssertionError(
                            f"{shape}: the snapshot of {k} at step {s} of the "
                            "resumed job differs from the job never stopped")
            for name, a, b in zip(whole.state._fields, resumed.state, whole.state):
                if not np.array_equal(np.asarray(a), np.asarray(b)):
                    raise AssertionError(
                        f"{shape}: state.{name} of the resumed job differs "
                        "from that of a job never stopped")
            runs.append(got)
        finally:
            shutil.rmtree(directory, ignore_errors=True)
    across = max((float(np.abs(a[k] - b[k]).max())
                  for other in runs[1:]
                  for (_, a), (_, b) in zip(runs[0], other) for k in a), default=0.0)
    out = {
        "compared": f"{cfg.ny}x{cfg.nx} ghost {cfg.ghost} as a job with output "
        f"({coarsen}x{coarsen} means after every call of {steps_per_call} steps) "
        f"and a save every {every} calls under one bound of {bound} bytes, "
        f"dropped after {calls} and resumed: snapshots and state bit for bit "
        "the job never stopped, on "
        f"{' and '.join('x'.join(map(str, s)) for s in mesh_shapes)} "
        f"(tol {TOL_SAME_ARITHMETIC} between them)",
        "host_bound_bytes": bound,
        "host_in_flight_max_bytes": max(peaks),
        "transfer_wait_s": waited,
        "meshes_max_diff": across,
        "max_diff": across,
    }
    if max(peaks) > bound:
        raise AssertionError(f"more on its way to the host than it takes: {out}")
    if across > TOL_SAME_ARITHMETIC:
        raise AssertionError(f"decomposition changes the resumed output: {out}")
    return out


# ------------------------------------------------------------------- ops

_K = 128  # elements per device in the op checks


def solver_monitor_check(cfg, devices, *, mesh_shapes=((1, 1),), lag=2,
                         steps_per_call=10, calls=3):
    """The solver as a job that watches itself (``make_job(monitor=)``):
    on every mesh a line after every call, in step order, the mass of
    each within 1e-5 of the first's, every chip holding the same line;
    then a NaN is written into the last chip's block of ``h`` and the
    job has to stop by itself within ``lag + 1`` further calls, naming
    the first step whose state held it; and the meshes' lines agree to
    the rounding of another decomposition."""
    import jax
    import numpy as np

    import mpi4jax_tpu as m
    from mpi4jax_tpu.models import shallow_water as sw

    steps = [1 + steps_per_call * (k + 1) for k in range(calls)]
    runs, stops = [], {}
    for shape in mesh_shapes:
        n = shape[0] * shape[1]
        mesh = jax.make_mesh(
            shape, ("y", "x"), axis_types=_auto(2), devices=devices[:n])
        comm = m.MeshComm.from_mesh(mesh)
        lines = []
        job = sw.make_job(cfg, comm, steps_per_call,
                          monitor=sw.Monitor(lag=lag), on_monitor=lines.append)
        job.start(sw.make_init(cfg, comm)())
        job.advance(calls)
        job.drain()
        if [line["step"] for line in lines] != steps:
            raise AssertionError(
                f"{shape}: lines of steps {[l['step'] for l in lines]}, "
                f"wanted {steps}")
        for line in lines:
            if (line["nonfinite"] or not 0 < line["cfl"] < 0.5
                    or abs(line["mass"] / lines[0]["mass"] - 1) > 1e-5):
                raise AssertionError(f"{shape}: a line of a sound run: {line}")
        every = np.asarray(job.mon(*job.state[:3])).reshape(n, -1)
        if not (every == every[0]).all():
            raise AssertionError(f"{shape}: the chips hold different lines: {every}")
        # a NaN in the last chip's block, three cells inside its corner
        h = job.state.h
        job.state = job.state._replace(
            h=h.at[h.shape[0] - cfg.ghost - 3, h.shape[1] - cfg.ghost - 3].set(
                float("nan")))
        runs.append(list(lines))
        bad_from, before = job.step + steps_per_call, job.calls
        try:
            for _ in range(lag + 2):
                job.advance()
            job.drain()
        except sw.MonitorStop as stop:
            stops[shape] = {"step": stop.line["step"],
                            "calls_enqueued": job.calls - before,
                            "nonfinite": stop.line["nonfinite"]}
        if shape not in stops:
            raise AssertionError(f"{shape}: a NaN in h did not stop the job")
        if (stops[shape]["step"] != bad_from
                or stops[shape]["calls_enqueued"] > lag + 1
                or job.stats()["monitor_stops"] != 1):
            raise AssertionError(
                f"{shape}: {stops[shape]}; the NaN was in the state from step "
                f"{bad_from} on and the lag is {lag}; {job.stats()}")
    across = max(
        (abs(a[k] - b[k]) / (abs(a["mass"]) if k == "mass" else 1.0)
         for other in runs[1:] for a, b in zip(runs[0], other)
         for k in ("cfl", "h_min", "mass")), default=0.0)
    out = {
        "compared": f"{cfg.ny}x{cfg.nx} ghost {cfg.ghost} as a job that "
        f"watches itself, a line after each of {calls} calls of "
        f"{steps_per_call} steps, lag {lag}: in order, every chip the same "
        "line; a NaN written into the last chip's block stops the job by "
        f"the first line that can hold it, within {lag + 1} calls; lines on "
        f"{' and '.join('x'.join(map(str, s)) for s in mesh_shapes)} "
        f"(tol {TOL_SAME_ARITHMETIC}, mass as a share)",
        "stops": {"x".join(map(str, k)): v for k, v in stops.items()},
        "last_line": runs[0][-1],
        "meshes_max_diff": across,
        "max_diff": across,
    }
    if across > TOL_SAME_ARITHMETIC:
        raise AssertionError(f"decomposition changes the lines: {out}")
    return out


def ops_program(devices):
    """The twelve primitives plus reduce_scatter in one jitted
    ``shard_map`` program on one token chain; returns it with its
    ``(n, K)`` input (row r is what device r holds)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import mpi4jax_tpu as m

    n = len(devices)
    if _K % n:
        raise ValueError(f"{n} devices do not divide {_K}")
    mesh = jax.make_mesh((n,), ("p",), axis_types=_auto(1), devices=devices)
    comm = m.MeshComm.from_mesh(mesh)
    fwd = [(r, (r + 1) % n) for r in range(n)]
    bwd = [(r, (r - 1) % n) for r in range(n)]
    weights = np.arange(1.0, n + 1, dtype=np.float32)

    def local(x):
        tok = m.create_token()
        out = {}
        out["allreduce"], tok = m.allreduce(x, m.SUM, comm=comm, token=tok)
        g, tok = m.allgather(x, comm=comm, token=tok)
        out["allgather"] = g[None]
        a, tok = m.alltoall(x.reshape(n, -1), comm=comm, token=tok)
        out["alltoall"] = a[None]
        tok = m.barrier(comm=comm, token=tok)
        out["bcast"], tok = m.bcast(x, n - 1, comm=comm, token=tok)
        g, tok = m.gather(x, 0, comm=comm, token=tok)
        out["gather"] = g[None]
        out["reduce"], tok = m.reduce(x, m.MAX, 0, comm=comm, token=tok)
        out["scan"], tok = m.scan(x, m.SUM, comm=comm, token=tok)
        table = jnp.arange(n * _K, dtype=x.dtype).reshape(n, _K)
        out["scatter"], tok = m.scatter(table, 0, comm=comm, token=tok)
        tok = m.send(x, fwd, comm=comm, token=tok)
        out["send_recv"], tok = m.recv(x, fwd, comm=comm, token=tok)
        out["sendrecv"], tok = m.sendrecv(
            x, x, source=bwd, dest=bwd, comm=comm, token=tok
        )
        rows = x[None] * jnp.asarray(weights)[:, None]
        out["reduce_scatter"], tok = m.reduce_scatter(
            rows, comm=comm, token=tok
        )
        return out

    spec = jax.P("p")
    fn = jax.jit(
        jax.shard_map(local, mesh=mesh, in_specs=spec, out_specs=spec)
    )
    # device r holds (r+1) * [1..K]: integer-valued, so every sum the
    # check makes is exact in float32
    return fn, np.outer(weights, np.arange(1.0, _K + 1)).astype(np.float32)


def ops_check(devices):
    """:func:`ops_program` on ``devices``, values against numpy."""
    import numpy as np

    fn, x = ops_program(devices)
    n = len(x)
    weights = np.arange(1.0, n + 1, dtype=np.float32)
    got = fn(x.ravel())
    want = {
        "allreduce": np.tile(x.sum(0), n),
        "allgather": np.tile(x[None], (n, 1, 1)),
        "alltoall": x.reshape(n, n, -1).transpose(1, 0, 2),
        "bcast": np.tile(x[n - 1], n),
        "scan": np.cumsum(x, axis=0).ravel(),
        "scatter": np.arange(n * _K, dtype=np.float32),
        "send_recv": np.roll(x, 1, axis=0).ravel(),
        "sendrecv": np.roll(x, -1, axis=0).ravel(),
        "reduce_scatter": np.outer(weights, x.sum(0)).ravel(),
    }
    diffs = {
        k: float(np.abs(np.asarray(got[k], np.float32) - v).max())
        for k, v in want.items()
    }
    # gather and reduce define root's value only
    diffs["gather"] = float(np.abs(np.asarray(got["gather"])[0] - x).max())
    diffs["reduce"] = float(
        np.abs(np.asarray(got["reduce"])[:_K] - x.max(0)).max()
    )
    out = {
        "compared": f"13 ops x {n} device(s) on one token chain (barrier "
        "by completion) against numpy, exact",
        "max_diff": max(diffs.values()),
    }
    if out["max_diff"] != 0.0:
        raise AssertionError(f"ops differ from numpy: {diffs}")
    return out


def grad_check(devices):
    """``jax.grad`` through ``allreduce(SUM)`` (the reference's AD
    contract: the gradient of sum(allreduce(x)) is ones)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import mpi4jax_tpu as m

    n = len(devices)
    mesh = jax.make_mesh((n,), ("p",), axis_types=_auto(1), devices=devices)
    comm = m.MeshComm.from_mesh(mesh)
    f = jax.jit(jax.shard_map(
        lambda v: m.allreduce(v, m.SUM, comm=comm)[0],
        mesh=mesh, in_specs=jax.P("p"), out_specs=jax.P("p"),
    ))
    x = jnp.arange(1.0, n + 1)
    value, grad = jax.value_and_grad(lambda v: f(v).sum())(x)
    want = float(n * np.arange(1, n + 1).sum())
    diff = max(abs(float(value) - want),
               float(np.abs(np.asarray(grad) - 1.0).max()))
    if diff != 0.0:
        raise AssertionError(f"value {value} (want {want}), grad {grad}")
    return {"compared": "value and grad of sum(allreduce(x, SUM)) against "
            f"{want} and ones", "max_diff": diff}


def selfcomm_check():
    """The README's first example on the single-process world."""
    import jax.numpy as jnp

    import mpi4jax_tpu as m

    comm = m.get_default_comm()
    if comm.backend != "self":
        raise AssertionError(f"default comm is {comm!r}, wanted SelfComm")
    res, _token = m.allreduce(jnp.zeros((3, 3)) + 1, op=m.SUM)
    total = float(res.sum())
    if total != 9.0:
        raise AssertionError(f"sum {total}, wanted 9.0")
    return {"compared": "SelfComm allreduce of ones((3,3)), sum against "
            "9.0", "max_diff": abs(total - 9.0)}


def rendezvous_program(devices):
    """A ring through the host-rendezvous tier: a runtime-valued
    destination and an ``ANY_SOURCE`` receive, ``io_callback`` from
    device code.  Returns the jitted program."""
    import jax

    import mpi4jax_tpu as m

    n = len(devices)
    mesh = jax.make_mesh((n,), ("p",), axis_types=_auto(1), devices=devices)
    comm = m.MeshComm.from_mesh(mesh)

    def local(x):
        r = jax.lax.axis_index("p")
        tok = m.create_token()
        tok = m.send(x[0], (r + 1) % n, comm=comm, token=tok)
        st = m.Status()
        y, tok = m.recv(
            x[0], source=m.ANY_SOURCE, comm=comm, token=tok, status=st
        )
        return y[None], st.source[None]

    return jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=jax.P("p"),
        out_specs=(jax.P("p"), jax.P("p")),
    ))


def rendezvous_check(devices):
    """:func:`rendezvous_program` on ``devices``: payload and
    ``Status.source`` against numpy."""
    import jax
    import numpy as np

    n = len(devices)
    x = 10.0 + np.arange(n, dtype=np.float32)[:, None]
    y, src = jax.block_until_ready(rendezvous_program(devices)(x))
    want_src = np.roll(np.arange(n), 1)
    diff = float(np.abs(np.asarray(y)[:, 0] - (10.0 + want_src)).max())
    if diff != 0.0 or not np.array_equal(np.asarray(src), want_src):
        raise AssertionError(f"received {np.asarray(y)[:, 0]} from "
                             f"{np.asarray(src)}, wanted from {want_src}")
    return {"compared": f"{n}-rank ring through the rendezvous engine: "
            "payload and Status.source against numpy", "max_diff": diff}


def cpu_child_check():
    """A child that pins the CPU itself runs the public ``allreduce`` on
    eight virtual devices, exactly, while this process holds the chip
    (what a launcher worker or a test runner beside a chip holder
    does)."""
    import jax

    rc, out, err = _run(
        [sys.executable, "-c", CPU_CHILD], 240, stderr=subprocess.PIPE
    )
    if rc != 0 or "cpu child devices=8 max_diff=0.0" not in out:
        raise AssertionError(
            f"exit {rc} (None: killed at its deadline), stdout "
            f"{out[-500:]!r}, stderr {err[-1500:]!r}"
        )
    return {
        "compared": "allreduce(SUM) on 8 virtual CPU devices in a child of "
        f"the process holding {jax.devices()[0].platform}, against numpy, "
        "exact",
        "max_diff": 0.0,
    }


# ----------------------------------------------------------- transformer


def train_check(size, devices, *, steps=3, expect_kernel=True, **build_kw):
    """``steps`` train steps of ``benchmarks/transformer.build(**size)``
    on one batch: the preset's batch if the chip takes it, else the
    largest power of two below it that compiles; widths are never cut."""
    import jax
    import numpy as np

    from benchmarks import transformer as tb

    size = dict(size)
    refused = []
    while True:
        built = tb.build(devices=devices, **size, **build_kw)
        try:
            compiled = built.step.lower(built.params, built.data).compile()
            break
        except jax.errors.JaxRuntimeError as exc:
            if "RESOURCE_EXHAUSTED" not in str(exc) or size["batch"] == 1:
                raise
            refused.append(
                {"batch": size["batch"], "why": str(exc).splitlines()[0]}
            )
            del built
            size["batch"] //= 2
    has_kernel = "tpu_custom_call" in compiled.as_text()
    if expect_kernel and not has_kernel:
        raise AssertionError(
            "no tpu_custom_call in the compiled step: the dense attention "
            "path was taken quietly"
        )
    mem = compiled.memory_analysis()
    params, losses = built.params, []
    for _ in range(steps):
        params, loss = compiled(params, built.data)
        losses.append(float(np.asarray(loss, np.float32)[0]))
    if not np.isfinite(losses).all():
        raise AssertionError(f"loss not finite: {losses}")
    b, s = built.data[0].shape
    return {
        "batch": size["batch"],
        "refused": refused,
        "mesh": list(built.shape),
        "global_tokens": [b, s],
        "losses": losses,
        "tpu_custom_call": has_kernel,
        "memory_analysis_gib": {
            k: round(getattr(mem, f"{k}_size_in_bytes") / 2**30, 3)
            for k in ("argument", "output", "alias", "temp")
        },
    }


def train_falls_check(size, devices, **kw):
    """One chip: the loss is finite and falls over three steps."""
    out = train_check(size, devices, **kw)
    first, last = out["losses"][0], out["losses"][-1]
    out["compared"] = "3 steps on one batch: loss finite and falling"
    out["max_diff"] = None
    out["loss_drop"] = first - last
    if not last < first:
        raise AssertionError(f"loss does not fall: {out['losses']}")
    return out


# bf16 activations and params, the loss a float32 mean over 32k tokens:
# the sharded step sums its matmuls in another order (tp all-reduce, ring
# attention blocks) and rounds at other places than the one-device step.
# Measured on the chip at the `large` widths: 1.7e-5.
TOL_SHARDED_LOSS = 1e-3


def train_sharded_check(size, devices, *, steps=2, expect_kernel=True,
                        **kw):
    """dp1 x tp2 x sp2 against the one-device step on the same tokens.
    The ring attention of sp > 1 has its own blockwise schedule, so only
    the one-device side is held to the flash kernel."""
    import numpy as np

    four = train_check(size, devices[:4], steps=steps,
                       expect_kernel=False, **kw)
    sp = four["mesh"][2]
    same = dict(size, batch=four["global_tokens"][0],
                seq=four["global_tokens"][1])
    one = train_check(same, devices[:1], steps=steps,
                      expect_kernel=expect_kernel, **kw)
    if one["batch"] != same["batch"]:
        raise AssertionError(f"one device refused batch {same['batch']}")
    diff = float(np.abs(np.subtract(four["losses"], one["losses"])).max())
    out = {
        "compared": f"loss of {steps} steps, mesh {four['mesh']} (seq "
        f"{same['seq'] // sp} per sp shard) against one device on the "
        f"same {four['global_tokens']} tokens (tol {TOL_SHARDED_LOSS})",
        "max_diff": diff, "four_chips": four, "one_chip": one,
    }
    if diff > TOL_SHARDED_LOSS:
        raise AssertionError(f"sharded loss differs: {out}")
    return out


def flash_check(shape=(1, 2048, 4, 128), *, interpret=False, blocks=1024):
    """Flash forward and backward against ``impl="xla"`` at one causal
    shape, in bf16, the dtype the model runs: forward to the 3e-2 of
    tests/parallel/test_flash.py's bf16 case, and dq, dk, dv to the same
    3e-2 of the largest dense gradient entry (that file has no bf16
    gradient case).  The dense side runs at full matmul precision.

    Its float32 cases (2e-5) hold in interpret mode only: on the chip the
    kernel's float32 dots run at the MXU's default precision, one bf16
    pass.  The float32 forward difference is reported, not bounded."""
    import jax
    import jax.numpy as jnp

    from mpi4jax_tpu.ops.flash import flash_attention
    from mpi4jax_tpu.parallel.longseq import local_attention

    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q32, k32, v32 = (jax.random.normal(kk, shape, jnp.float32) for kk in ks)
    q, k, v = (x.astype(jnp.bfloat16) for x in (q32, k32, v32))

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=blocks,
                               block_k=blocks, interpret=interpret)

    def dense(q, k, v):
        with jax.default_matmul_precision("highest"):
            return local_attention(q, k, v, causal=True, impl="xla")

    def grads(fn):
        def loss(q, k, v):
            return (fn(q, k, v).astype(jnp.float32) ** 2).sum()

        return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)

    def gap(a, b):
        return float(jnp.abs(a.astype(jnp.float32)
                             - b.astype(jnp.float32)).max())

    out = jax.jit(flash)(q, k, v)
    if out.dtype != jnp.bfloat16:
        raise AssertionError(f"bf16 in, {out.dtype} out")
    fwd = gap(out, jax.jit(dense)(q, k, v))
    bwd = 0.0
    for a, b in zip(grads(flash), grads(dense)):
        bwd = max(bwd, gap(a, b) / float(jnp.abs(b.astype(jnp.float32)).max()))
    res = {
        "compared": f"flash vs impl='xla' at {shape} causal bf16: forward "
        "(atol 3e-2), dq,dk,dv (3e-2 of the largest entry)",
        "max_diff": fwd, "max_rel_diff_grad": bwd,
        "max_diff_f32_inputs": gap(
            jax.jit(flash)(q32, k32, v32), jax.jit(dense)(q32, k32, v32)
        ),
    }
    if fwd > 3e-2 or bwd > 3e-2:
        raise AssertionError(f"flash differs from dense: {res}")
    return res


def decode_check(cfg, devices, *, batch=4, prompt=256, max_len=288,
                 prefill_impl="flash"):
    """Flash-prefill greedy decode on a one-device mesh, token-exact
    against ``reference_greedy_decode``
    (tests/parallel/test_decode.py::test_decode_flash_prefill_matches_oracle,
    which only a TPU can run).  Both sides run at full float32 matmul
    precision: token-exactness is defined only where the cached and the
    recomputing path round alike, and the TPU's default single-pass bf16
    matmul does not."""
    import jax
    import numpy as np

    import mpi4jax_tpu as m
    from mpi4jax_tpu.models import transformer as tfm

    mesh = jax.make_mesh(
        (1, 1), ("dp", "tp"), axis_types=_auto(2), devices=devices[:1]
    )
    world = m.MeshComm.from_mesh(mesh)
    params = tfm.init_params(jax.random.PRNGKey(1), cfg)
    tokens = jax.random.randint(
        jax.random.PRNGKey(2), (batch, prompt), 0, cfg.vocab
    )
    decode = tfm.make_global_decode(
        mesh, world.sub("dp"), world.sub("tp"), cfg, max_len,
        prefill_impl=prefill_impl,
    )
    with jax.default_matmul_precision("highest"):
        got = np.asarray(decode(params, tokens))
        want = np.asarray(
            jax.jit(tfm.reference_greedy_decode, static_argnums=(2, 3))(
                params, tokens, cfg, max_len
            )
        )
    wrong = int((got != want).sum())
    if not np.array_equal(got[:, :prompt], np.asarray(tokens)) or wrong:
        raise AssertionError(f"{wrong} of {got.size} tokens differ")
    return {
        "compared": f"greedy decode, prefill_impl={prefill_impl!r}, batch "
        f"{batch}, prompt {prompt} -> {max_len}: tokens against "
        "reference_greedy_decode, exact",
        "max_diff": wrong,
    }


# ---------------------------------------------------------------- staged


def staged_check(platform, env=None):
    """The native bridge meets the chip: one launcher worker on the
    default platform, proc-backend ops eagerly and under ``jit`` through
    ``io_callback``.  The bridge is built here from
    ``mpi4jax_tpu/native/src``; a machine without a compiler fails with
    the compiler's message."""
    from mpi4jax_tpu.native import build

    built_now = build._needs_build()
    t0 = time.perf_counter()
    build.ensure_built()
    build_s = time.perf_counter() - t0
    with tempfile.TemporaryDirectory() as tmp:
        worker = pathlib.Path(tmp) / "staged_worker.py"
        worker.write_text(STAGED_WORKER)
        rc, out, err = _run(
            [
                sys.executable, "-m", "mpi4jax_tpu.launch", "-np", "1",
                "--platform", "default", str(worker),
            ],
            240, env=env, stderr=subprocess.PIPE,
        )
    if rc != 0 or f"staged ok platform={platform}" not in out:
        raise AssertionError(
            f"exit {rc} (None: killed at its deadline), stdout "
            f"{out[-500:]!r}, stderr {err[-2000:]!r}"
        )
    return {
        "compared": "allreduce, allgather, bcast, barrier on the proc "
        f"backend, arrays on {platform}, eagerly and under jit, against "
        "numpy",
        "max_diff": 0.0,
        "bridge_built_here": built_now,
        "bridge_build_s": round(build_s, 1),
    }


# ------------------------------------------------- what runs on the chip


def _one():
    import jax

    return jax.devices()[:1]


def _all():
    import jax

    return jax.devices()


def _cpu():
    import jax

    return jax.devices("cpu")


def _small_cfg():
    """A block of 256x512 of the benchmark's cells, the kernel's
    schedule."""
    from dataclasses import replace

    return replace(_bench_cfg(), ny=256, nx=512)


def _bench_cfg():
    from mpi4jax_tpu.models import shallow_water as sw

    return sw.SWConfig().bench_size()


def _refined(cfg, refine):
    """``cfg``'s domain cut into ``refine`` times the cells each way."""
    from dataclasses import replace

    return replace(cfg, ny=cfg.ny * refine, nx=cfg.nx * refine,
                   dx=cfg.dx / refine, dy=cfg.dy / refine)


def _large(check, devices):
    """``check`` on ``SIZES["large"]`` as ``run`` constructs it."""
    from benchmarks.transformer import SIZES

    size = dict(SIZES["large"])
    return check(size, devices, bf16=True, remat=size.pop("remat"),
                 attn_impl="flash")


def _decode():
    import jax

    from mpi4jax_tpu.models import transformer as tfm

    cfg = tfm.TransformerConfig(
        vocab=512, d_model=512, layers=2, heads=4, kv_heads=2,
        head_dim=128, d_ff=1024,
    )
    return decode_check(cfg, jax.devices()[:1])


def _solver():
    import jax

    return solver_check(_bench_cfg(), _one(), jax.devices("cpu")[0])


# chips -> group (one child process each) -> (deadline in seconds,
# {phase record it must produce: the check that produces it})
GROUPS = {
    1: {
        "staged": (420, {"staged": lambda: staged_check("tpu")}),
        "solver": (420, {
            "solver": _solver,
            "solver.job": lambda: solver_job_check(_bench_cfg(), _one()),
            "solver.restart": lambda: solver_restart_check(_bench_cfg(), _one()),
            "solver.monitor": lambda: solver_monitor_check(_bench_cfg(), _one()),
        }),
        # a group of its own, as solver4.adjoint is: `import chip_smoke;
        # chip_smoke.child("solver.observed")` is a one-chip call of a
        # minute.  At the block of the differentiated run's cell (3600x7200
        # cells a chip) and at the size where the wrong fast form was right
        "solver.observed": (240, {
            "solver.observed": lambda: solver_observed_check(
                [(3600, 7200), (512, 1024)], _one()),
        }),
        # a group of its own too: `chip_smoke.child("solver.tangent")` is a
        # one-chip call.  The kernel's walk and its written-out tangent and
        # transpose at a small block against the CPU backend's array code
        "solver.tangent": (300, {
            "solver.tangent": lambda: solver_tangent_check(
                _small_cfg(), _one(), _cpu()),
        }),
        "ops": (300, {
            "ops": lambda: ops_check(_one()),
            "ops.grad": lambda: grad_check(_one()),
            "ops.selfcomm": selfcomm_check,
            # last, while this process still holds the chip
            "cpu_child": cpu_child_check,
        }),
        "rendezvous": (150, {
            "rendezvous": lambda: rendezvous_check(_one()),
        }),
        "transformer": (600, {
            "transformer.train": lambda: _large(train_falls_check, _one()),
            "transformer.flash": flash_check,
            "transformer.decode": _decode,
        }),
    },
    4: {
        "solver4": (600, {
            "solver4.weak": lambda: solver_weak_check(_bench_cfg(), _all()),
            "solver4.invariance": lambda: solver_invariance_check(
                _bench_cfg(), _all()
            ),
            "solver4.job": lambda: solver_job_check(
                _bench_cfg(), _all(), mesh_shapes=((2, 2), (1, 1))
            ),
            "solver4.restart": lambda: solver_restart_check(
                _bench_cfg(), _all(), mesh_shapes=((2, 2), (1, 1))
            ),
            "solver4.output_restart": lambda: solver_output_restart_check(
                _bench_cfg(), _all(), mesh_shapes=((2, 2), (1, 1))
            ),
            "solver4.monitor": lambda: solver_monitor_check(
                _bench_cfg(), _all(), mesh_shapes=((2, 2), (1, 1))
            ),
        }),
        # a group of its own: `import chip_smoke;
        # chip_smoke.child("solver4.adjoint")` is a four-chip call of two
        # minutes where the whole of --chips 4 is six.  At the domain
        # refined once, 1800x3600 cells a chip, the block at which the
        # mesh's forward programs were held bit for bit on the chip
        # (PR 53): at `_bench_cfg()`'s 900x1800 a chip the kernel path
        # itself gives NaN or hangs in programs that do not donate
        # their state (ROADMAP.md S28), the gradient's among them
        "solver4.adjoint": (420, {
            "solver4.adjoint": lambda: solver_adjoint_check(
                _refined(_bench_cfg(), 2), _all()
            ),
        }),
        # the linearised window likewise, at the same block (S28): the
        # exchange's tangent over the wire, 2x2 against 1x1
        "solver4.tangent": (420, {
            "solver4.tangent": lambda: solver_tangent_check(
                _refined(_bench_cfg(), 2), _all(), _all()
            ),
        }),
        "ops4": (300, {"ops4": lambda: ops_check(_all()[:4])}),
        "rendezvous4": (150, {
            "rendezvous4": lambda: rendezvous_check(_all()[:4]),
        }),
        "transformer4": (900, {
            "transformer4": lambda: _large(train_sharded_check, _all()),
        }),
    },
}


if __name__ == "__main__":
    sys.exit(main())
