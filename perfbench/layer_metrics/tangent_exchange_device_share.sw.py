"""Share of an inner-loop iteration's device time spent in the halo
exchanges of its tangent-linear sweep, in per cent: the leaf events of
the trace whose instruction lies under ``sw/adjoint/tangent`` and under
a ``mpi4jax_tpu.halo_*`` scope in the compiled tangent program's text,
over the device's busy time.  The exchange's tangent is the exchange
itself on the tangents (``parallel/halo.py _transposable``), under the
same ``pack``, ``wire`` and ``unpack`` scopes inside jax's ``jvp(...)``,
beside the exchanges of the state it is linearised about.

Prints the split by op and by ``pack``, ``wire`` and ``unpack``, and
beside it the adjoint sweep's exchanges, transposed and not.  On one
chip the permutes are elided and ``wire`` is empty.  A fusion is one
event under its root's scope: where XLA fuses a ghost write into a
stencil's fusion the exchange's time is the stencil's.  The scopes are
read by ``drivers/shallow_water_incremental.py exchange_of``.

Goes by scope, so an exchange's instruction whose ``op_name`` carried
none would be missed: prints beside the split how much of the tangent
program's time lies under no ``sw/adjoint`` scope at all, which bounds
what it could have missed.

``None`` where the session has no such programs or the tangent sweep no
exchange's scope."""

from perfbench.harness import files, trace

SWEEP = "tangent"


def read(view):
    session = view.session
    if not hasattr(session, "traced_events"):
        return None
    driver = files.load_module(
        "drivers", "shallow_water_incremental", session.ctx.bench_dir)
    events = session.traced_events(view)
    if events is None:
        return None
    by, unplaced = {}, 0.0
    for key, e, op_name in events:
        if key == SWEEP and driver.phase_of(op_name) is None:
            unplaced += e.duration_ns / 1e9
        found = driver.exchange_of(op_name)
        if found is not None:
            at = (driver.phase_of(op_name) == SWEEP, *found)
            by[at] = by.get(at, 0.0) + e.duration_ns / 1e9
    if not any(at[0] for at in by):
        print("perfbench: the tangent sweep's text carries no "
              "mpi4jax_tpu.halo_* scope: nothing is reported", flush=True)
        return None
    chips = len(view.trace.device_ops)
    busy = trace.busy_s(view.trace) * chips
    steps = sum(session.units(s.row) for s in view.traced) * chips
    print("perfbench: the exchanges' device time: sweep | op | direction | "
          "part | us a window step | % of busy", flush=True)
    for (tangent, op, transposed, part), seconds in sorted(
            by.items(), key=lambda kv: -kv[1]):
        print(f"perfbench:   {'tangent' if tangent else 'adjoint'} | {op} | "
              f"{'transposed' if transposed else 'forward'} | {part} | "
              f"{seconds / steps * 1e6:.3f} | {100 * seconds / busy:.3f}",
              flush=True)
    print(f"perfbench:   the tangent program under no scope, where an "
          f"exchange would be missed | {unplaced / steps * 1e6:.3f} | "
          f"{100 * unplaced / busy:.3f}", flush=True)
    return 100.0 * sum(s for at, s in by.items() if at[0]) / busy
