"""Share of an inner-loop iteration's device time that the
tangent-linear sweep takes, in per cent: the leaf events of the trace
whose instruction lies under ``sw/adjoint/tangent`` in the compiled
programs' text (forward mode through the window's steps and every
exchange in them, ``models/shallow_water.py make_tangent``), over the
device's busy time.  The rest is the adjoint sweep (``recompute``,
``step_vjp``, ``cost``) and the vector updates of conjugate gradients
(``update``).

Prints the split over the five scopes (``drivers/
shallow_water_incremental.py phase_of``: the innermost
``sw/adjoint/<phase>`` of an ``op_name``, which in a tangent sweep sits
beside jax's ``jvp(...)``), the tangent program's time under none of
them, which the reading leaves out (copies that the compiler adds and
does not name: 2.3 % of busy on a v5e, PERF.md PR 59), and the largest
instructions under none.

``None`` where the session has no such programs, or no program of them
carries the ``sw/adjoint/tangent`` scope (a program from before the
sweep)."""

from perfbench.harness import files, scopes, trace

SWEEP = "tangent"  # the scope, and the key of the program the sweep is


def split(view):
    """``({phase: seconds}, the tangent program's seconds under no scope,
    {instruction: seconds} under no scope)`` of the traced executions,
    all chips; ``None`` where nothing is read."""
    session = view.session
    if not hasattr(session, "traced_events"):
        return None
    driver = files.load_module(
        "drivers", "shallow_water_incremental", session.ctx.bench_dir)
    events = session.traced_events(view)
    if events is None:
        return None
    by_phase, unnamed, neither = dict.fromkeys(driver.PHASES, 0.0), 0.0, {}
    for key, e, op_name in events:
        phase = driver.phase_of(op_name)
        if phase in by_phase:
            by_phase[phase] += e.duration_ns / 1e9
            continue
        unnamed += e.duration_ns / 1e9 * (key == SWEEP)
        at = f"{key}: {scopes.opcode(e.name)} %{trace.short_name(e.name)}"
        neither[at] = neither.get(at, 0.0) + e.duration_ns / 1e9
    if not by_phase[SWEEP]:
        print("perfbench: the programs' text carries no "
              "sw/adjoint/tangent scope: nothing is reported", flush=True)
        return None
    return by_phase, unnamed, neither


def read(view):
    found = split(view)
    if found is None:
        return None
    by_phase, unnamed, neither = found
    chips = len(view.trace.device_ops)
    busy = trace.busy_s(view.trace) * chips
    steps = sum(view.session.units(s.row) for s in view.traced) * chips
    print("perfbench: an iteration's device time by scope: scope | us a window "
          "step | % of busy", flush=True)
    for name, seconds in by_phase.items():
        print(f"perfbench:   sw/adjoint/{name} | {seconds / steps * 1e6:.3f} | "
              f"{100 * seconds / busy:.3f}", flush=True)
    print(f"perfbench:   the tangent program under no scope | "
          f"{unnamed / steps * 1e6:.3f} | {100 * unnamed / busy:.3f}", flush=True)
    for name, seconds in sorted(neither.items(), key=lambda kv: -kv[1])[:8]:
        print(f"perfbench:   under no scope: {name} | "
              f"{seconds / steps * 1e6:.3f} | {100 * seconds / busy:.3f}",
              flush=True)
    return 100.0 * by_phase[SWEEP] / busy
