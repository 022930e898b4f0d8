"""Share of the device time of a traced window that holds exactly one
save spent on it, in per cent: the leaf events of the trace whose
instruction lies under the program's ``mpi4jax_tpu.checkpoint`` scope
in the text of the program that ran it (the staging program: the state
cut into pieces), over the device time of all leaf events.  The cell's
``trace_batches`` and the call its resumed job stands at place one save
in the window (``drivers/shallow_water_restart.py``); a window with
another number of them is not this metric's, and ``None``.  Prints
where all the device time goes, by layer and by source."""

import collections

from perfbench.harness import scopes, trace

SCOPE = scopes.SCOPE_PREFIX + "checkpoint"
STAGE = "stage"


def read(view):
    session = view.session
    whole, executions = session.traced_programs(view.trace, view.traced)
    if executions.count(STAGE) != 1:
        print(f"perfbench: the traced window holds {executions.count(STAGE)} "
              "saves, not one: nothing is reported", flush=True)
        return None
    rows = scopes.attribute(whole, executions, session.compiled_text)
    if rows is None:
        return None
    per = collections.Counter(executions)
    busy = trace.busy_s(whole)
    scopes.print_layers("device time by layer", rows, busy)
    scopes.print_table("device time by origin", rows, busy, per, "execution")
    mine = scopes.total(r for r in rows if r.scopes[:1] == (SCOPE,))
    return 100.0 * mine / scopes.total(rows)
