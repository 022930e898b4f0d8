"""Share of the HBM roofline the solver's step reaches where it runs as
a job, in per cent: ``sw_hbm_roofline_share``'s least bytes a step (its
own function, loaded by name) over the table's HBM bandwidth, divided by
the device time a step of the multistep program's executions alone (the
union of each one's leaf events).  ``sw_hbm_roofline_share`` divides by
all the device's busy time and would book the snapshot program's to the
step.  Bound: bandwidth."""

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
    per_step = (sum(trace.union_ns(events) for events in mine) / len(mine)
                / view.facts["steps_per_call"] / 1e9)
    step = files.load_module(
        "layer_metrics", "sw_hbm_roofline_share", session.ctx.bench_dir)
    least_s = (step.least_bytes_per_step(view.facts["padded_field_bytes"])
               / (view.peaks["hbm_gbps"] * 1e9))
    print(f"perfbench: a step of the multistep takes {per_step * 1e6:.3f} us "
          f"of device time, the least its bytes could {least_s * 1e6:.3f} us",
          flush=True)
    return 100.0 * least_s / per_step
