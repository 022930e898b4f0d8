"""Device operations executed per solver step: the leaf events of the
trace over the steps the traced batches made.  Repeats exactly."""

from perfbench.harness import trace


def read(view):
    steps = sum(view.session.units(s.row) for s in view.traced)
    if not steps:
        return None
    return trace.op_count(view.trace) / steps
