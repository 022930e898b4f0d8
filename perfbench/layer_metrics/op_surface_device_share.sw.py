"""Share of the solver's device time spent under the library's own
scopes, in per cent: the leaf events of the trace whose instruction
carries a ``mpi4jax_tpu.<op>`` scope in the compiled multistep's text,
over the device's busy time.  On one chip that is the halo exchange's
slab slices and ghost writes (its permutes are elided).  Prints where
all the device time goes, by layer and by source line."""

import time

from perfbench.harness import scopes, trace

MULTI = "multistep"


def read(view):
    t0 = time.perf_counter()
    session = view.session
    executions = [MULTI for s in view.traced
                  for _ in range(session.rows[s.row]["reps"])]
    rows = scopes.attribute(view.trace, executions, session.compiled_text)
    if rows is None:
        return None
    busy = trace.busy_s(view.trace)
    steps = sum(session.units(s.row) for s in view.traced)
    scopes.print_layers("device time by layer", rows, busy)
    scopes.print_table("device time by origin", rows, busy, {MULTI: steps}, "step")
    mine = scopes.total(r for r in rows if r.layer == scopes.OP_SURFACE)
    print(f"perfbench: op_surface_device_share.sw: read in "
          f"{time.perf_counter() - t0:.3f} s", flush=True)
    return 100.0 * mine / busy
