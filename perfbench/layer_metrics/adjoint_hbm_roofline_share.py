"""Share of the HBM roofline that the backward sweep of a gradient
reaches whatever implements it, in per cent: the bytes its events moved
over the table's HBM bandwidth, divided by their device time.  The
sweep is what lies under ``sw/adjoint/recompute`` and
``sw/adjoint/step_vjp`` (``adjoint_device_share.sw`` has the split): a
call's steps run again by the kernel, and each step's array code run at
its kept state and backwards, which today is XLA's fusions.  What an
adjoint kernel is worth is what this reads under 100.  Bound: bandwidth.

Bytes by ``sw_field_passes_per_step``'s own ``moved_bytes``, loaded by
name: a kernel call its whole signature, an in-place write its update,
a fusion handed less than it hands back what it is handed, any other
instruction twice its result.  Every byte counted is a byte that moved,
so the reading cannot pass 100; what a fusion reads beyond its result's
size is not counted, so it can read under the truth.  It does not ask
what implements the sweep: a later adjoint kernel is read by its call's
signature, and the cell keeps its share.

``None`` where the session has no such programs or the sweep no events."""

from perfbench.harness import files

SWEEP = ("recompute", "step_vjp")
BACKWARD = "backward"  # the program the sweep is


def read(view):
    session = view.session
    if not hasattr(session, "traced_events"):
        return None
    bench_dir = session.ctx.bench_dir
    driver = files.load_module("drivers", "shallow_water_adjoint", bench_dir)
    passes = files.load_module(
        "layer_metrics", "sw_field_passes_per_step", bench_dir)
    events = session.traced_events(view)
    if events is None:
        return None
    sweep = [e for key, e, op_name in events
             if key == BACKWARD and driver.phase_of(op_name) in SWEEP]
    if not sweep:
        print("perfbench: no event of the trace lies under the backward "
              "sweep's scopes: nothing is reported", flush=True)
        return None
    moved = passes.moved_bytes(
        sweep, session.compiled_text(BACKWARD), bench_dir)
    if moved is None:
        return None
    steps = (sum(session.units(s.row) for s in view.traced)
             * len(view.trace.device_ops))
    seconds = sum(e.duration_ns for e in sweep) / 1e9
    least_s = moved[0] / (view.peaks["hbm_gbps"] * 1e9)
    print(f"perfbench: the backward sweep takes {seconds / steps * 1e6:.3f} us "
          f"of device time a window step, the least its {moved[0] / steps:.0f} "
          f"bytes a step could {least_s / steps * 1e6:.3f} us; "
          f"{moved[1] / steps:g} kernel calls a step", flush=True)
    return 100.0 * least_s / seconds
