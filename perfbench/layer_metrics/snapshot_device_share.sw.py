"""Share of the job's device time spent making snapshots, in per cent:
the leaf events of the trace whose instruction lies under the program's
``mpi4jax_tpu.snapshot`` scope in the text of the program that ran it,
over the device time of all leaf events.  A call of the job runs two
programs, the multistep and the snapshot; each event is read against the
text of its own, and each program's time is taken per execution the
trace holds whole (the profiler stops inside the window's last
snapshot).  Prints where all the device time goes, by layer and by
source."""

import collections

from perfbench.harness import scopes, trace

SCOPE = scopes.SCOPE_PREFIX + "snapshot"


def read(view):
    session = view.session
    whole, executions = session.traced_programs(view.trace, view.traced)
    rows = scopes.attribute(whole, executions, session.compiled_text)
    if rows is None:
        return None
    per = collections.Counter(executions)
    busy = trace.busy_s(whole)
    scopes.print_layers("device time by layer", rows, busy)
    scopes.print_table("device time by origin", rows, busy, per, "call")
    a_call = sum(r.seconds / per[r.program] for r in rows)
    mine = sum(r.seconds / per[r.program] for r in rows
               if r.scopes[:1] == (SCOPE,))
    return 100.0 * mine / a_call
