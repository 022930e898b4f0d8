"""Share of the HBM roofline the solver's step reaches where it runs as
a job, in per cent: ``sw_hbm_roofline_share``'s least bytes a step (its
own function, loaded by name: the signature bytes of the kernel calls
that ran, twice the result of any other instruction), taken over the
multistep program's executions alone, over the table's HBM bandwidth,
divided by the device time a step of those executions (the union of
each one's leaf events).  ``sw_hbm_roofline_share`` divides by all the
device's busy time and would book the snapshot program's to the step.
Bound: bandwidth."""

from perfbench.harness import files, scopes, trace

MULTI = "multistep"


def read(view):
    session = view.session
    placed = scopes.by_execution(
        *session.traced_programs(view.trace, view.traced))
    if placed is None:
        return None
    mine = [events for of_chip in placed.values()
            for key, events in of_chip if key == MULTI]
    if not mine:
        return None
    steps = len(mine) * view.facts["steps_per_call"]
    per_step = sum(trace.union_ns(events) for events in mine) / steps / 1e9
    step = files.load_module(
        "layer_metrics", "sw_hbm_roofline_share", session.ctx.bench_dir)
    least_s = step.least_step_s(
        [e for events in mine for e in events], steps,
        session.compiled_text(MULTI), view.peaks["hbm_gbps"])
    if least_s is None:
        return None
    print(f"perfbench: a step of the multistep takes {per_step * 1e6:.3f} us "
          f"of device time, the least its bytes could {least_s * 1e6:.3f} us",
          flush=True)
    return 100.0 * least_s / per_step
