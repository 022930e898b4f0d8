"""Share of the HBM roofline the solver's step reaches, in per cent.

The least a step can move: every one of the 12 field-sized arrays it has
to touch once.  Read h, u, v and the three old tendencies (6), write h,
u, v and the three new tendencies (6); the viscosity pass on the updated
u, v could ride along in a perfectly fused step.  12 x (ny+2G)(nx+2G) x
4 bytes, over the table's HBM bandwidth, divided by the device's busy
time per step from the trace.  Bound: bandwidth (the step has about 150
flops a cell, 0.3 ms at the peak where the 12 passes take 14 ms)."""

from perfbench.harness import trace

PASSES = 12


def least_bytes_per_step(padded_field_bytes):
    return PASSES * padded_field_bytes


def read(view):
    steps = sum(view.session.units(s.row) for s in view.traced)
    if not steps:
        return None
    busy_per_step = trace.busy_s(view.trace) / steps
    least_s = (least_bytes_per_step(view.facts["padded_field_bytes"])
               / (view.peaks["hbm_gbps"] * 1e9))
    return 100.0 * least_s / busy_per_step
