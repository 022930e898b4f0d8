"""Share of the HBM roofline that the tangent-linear sweep of an
inner-loop iteration reaches whatever implements it, in per cent: the
bytes its events moved over the table's HBM bandwidth, divided by their
device time.  The sweep is what the tangent program's executions ran
(every instruction of it lies under ``sw/adjoint/tangent`` but what the
compiler adds without a name, a copy, which moves bytes and takes time
as the rest; ``tangent_device_share.sw`` has the split and prints what
lies under no scope): the window's walks run by the kernel, and
each walk's array code pushed forwards at the same state, which today
is XLA's fusions.  What a tangent kernel is worth is
what this reads under 100.  Bound: bandwidth.

Bytes by ``sw_field_passes_per_step``'s own ``moved_bytes``, loaded by
name, as ``adjoint_hbm_roofline_share`` counts the backward sweep's: a
kernel call its whole signature, an in-place write its update, a fusion
handed less than it hands back what it is handed, any other instruction
twice its result.  Every byte counted is a byte that moved, so the
reading cannot pass 100; what a fusion reads beyond its result's size is
not counted, so it can read under the truth.  It does not ask what
implements the sweep: a later tangent kernel is read by its call's
signature.

``None`` where the session has no such programs or the sweep no events."""

from perfbench.harness import files

TANGENT = "tangent"  # the program the sweep is, and its scope


def read(view):
    session = view.session
    if not hasattr(session, "traced_events"):
        return None
    bench_dir = session.ctx.bench_dir
    driver = files.load_module(
        "drivers", "shallow_water_incremental", bench_dir)
    passes = files.load_module(
        "layer_metrics", "sw_field_passes_per_step", bench_dir)
    events = session.traced_events(view)
    if events is None:
        return None
    sweep = [e for key, e, _op_name in events if key == TANGENT]
    if not any(driver.phase_of(op_name) == TANGENT
               for key, _e, op_name in events if key == TANGENT):
        print("perfbench: no event of the trace lies under the tangent "
              "sweep's scope: nothing is reported", flush=True)
        return None
    moved = passes.moved_bytes(
        sweep, session.compiled_text(TANGENT), bench_dir)
    if moved is None:
        return None
    steps = (sum(session.units(s.row) for s in view.traced)
             * len(view.trace.device_ops))
    seconds = sum(e.duration_ns for e in sweep) / 1e9
    least_s = moved[0] / (view.peaks["hbm_gbps"] * 1e9)
    print(f"perfbench: the tangent sweep takes {seconds / steps * 1e6:.3f} us "
          f"of device time a window step, the least its {moved[0] / steps:.0f} "
          f"bytes a step could {least_s / steps * 1e6:.3f} us; "
          f"{moved[1] / steps:g} kernel calls a step", flush=True)
    return 100.0 * least_s / seconds
