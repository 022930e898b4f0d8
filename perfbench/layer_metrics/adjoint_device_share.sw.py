"""Share of the differentiated run's device time that a derivative costs
beyond the window itself, in per cent: the leaf events of the trace
whose instruction lies under ``sw/adjoint/recompute`` (a call's steps
run again, every state kept) or ``sw/adjoint/step_vjp`` (a step's array
code run at a kept state and then backwards) in the compiled gradient
program's text, over the device's busy time.  The rest is the window
run forwards, the misfit and the descent step.

Prints the split over the five scopes (``models/shallow_water.py
ADJOINT_SCOPE``) and what carries none, by instruction.  The scopes are
read by ``drivers/shallow_water_adjoint.py phase_of``: the innermost
``sw/adjoint/<phase>`` of an ``op_name``, which in a backward sweep sits
inside jax's ``transpose(jvp(...))``.

``None`` where the session has no such programs, or their text carries
no such scope (a program from before the scopes)."""

from perfbench.harness import files, scopes, trace

COUNTED = ("recompute", "step_vjp")


def split(view):
    """``({phase: seconds}, {instruction: seconds} under no scope)`` of
    the traced executions, all chips; ``None`` where nothing is read."""
    session = view.session
    if not hasattr(session, "traced_events"):
        return None
    driver = files.load_module(
        "drivers", "shallow_water_adjoint", session.ctx.bench_dir)
    events = session.traced_events(view)
    if events is None:
        return None
    by_phase, neither = dict.fromkeys(driver.PHASES, 0.0), {}
    for _key, e, op_name in events:
        phase = driver.phase_of(op_name)
        if phase in by_phase:
            by_phase[phase] += e.duration_ns / 1e9
        else:
            at = f"{scopes.opcode(e.name)} %{trace.short_name(e.name)}"
            neither[at] = neither.get(at, 0.0) + e.duration_ns / 1e9
    if not any(by_phase.values()):
        print("perfbench: the programs' text carries no "
              "sw/adjoint/<phase> scope: nothing is reported", flush=True)
        return None
    return by_phase, neither


def read(view):
    found = split(view)
    if found is None:
        return None
    by_phase, neither = found
    chips = len(view.trace.device_ops)
    busy = trace.busy_s(view.trace) * chips
    steps = sum(view.session.units(s.row) for s in view.traced) * chips
    print("perfbench: a gradient's device time by scope: scope | us a window "
          "step | % of busy", flush=True)
    for name, seconds in by_phase.items():
        print(f"perfbench:   sw/adjoint/{name} | {seconds / steps * 1e6:.3f} | "
              f"{100 * seconds / busy:.3f}", flush=True)
    for name, seconds in sorted(neither.items(), key=lambda kv: -kv[1])[:8]:
        print(f"perfbench:   under no scope: {name} | "
              f"{seconds / steps * 1e6:.3f} | {100 * seconds / busy:.3f}",
              flush=True)
    return 100.0 * sum(by_phase[k] for k in COUNTED) / busy
