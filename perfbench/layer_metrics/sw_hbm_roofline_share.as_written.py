"""Share of the HBM roofline that a solver step reaches whatever
implements it, in per cent: the bytes a step of the traced multistep
executions moved (``sw_field_passes_per_step``'s own ``step_bytes``,
loaded by name: a kernel call by its signature as
``sw_hbm_roofline_share`` counts one, an in-place write by its update,
any other instruction twice its result) over the table's HBM bandwidth,
divided by the device's busy time a step from the trace.  Bound:
bandwidth.

``sw_hbm_roofline_share`` reports nothing where the step runs no kernel
call; the as-written step runs none, its fusions are XLA's, and this is
their share of the roofline.  It does not ask what implements the step:
a later program that runs a kernel call there is read by that call's
signature, and the cell keeps its share.  Every byte counted is a byte
that moved, so the reading cannot pass 100; what a fusion reads beyond
its result's size is not counted, so it can read under the truth."""

from perfbench.harness import files, trace


def read(view):
    passes = files.load_module(
        "layer_metrics", "sw_field_passes_per_step", view.session.ctx.bench_dir)
    moved = passes.step_bytes(view)
    if moved is None:
        return None
    busy_per_step = (trace.busy_s(view.trace) * len(view.trace.device_ops)
                     / passes.traced_steps(view))
    least_s = moved[0] / (view.peaks["hbm_gbps"] * 1e9)
    print(f"perfbench: a step takes {busy_per_step * 1e6:.3f} us of device "
          f"time, the least its {moved[0]:.0f} bytes could "
          f"{least_s * 1e6:.3f} us", flush=True)
    return 100.0 * least_s / busy_per_step
