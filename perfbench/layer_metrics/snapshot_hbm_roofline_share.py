"""Share of the HBM roofline the snapshot program reaches, in per cent.

The least a snapshot program can move is what it is handed and what it
hands back, each once: its signature bytes, read from its compiled text
(``harness/scopes.py signature``).  As the job makes it since PR 34:
each field's padded array in and its coarse field out, fields x
((ny+2G)(nx+2G) + (ny/c)(nx/c)) x 4 bytes; a program that is handed
coarse sums has a floor of their bytes.  Over the table's HBM bandwidth,
divided by the device time of one execution of the snapshot program from
the trace (the union of its leaf events, a mean over the executions the
trace holds whole).  Bound: bandwidth (a mean of c x c cells is one
addition a cell).

Where a call has no program of its own for the snapshot, what output
costs lies in the step's programs: ``snapshot_device_share.sw`` counts
it there, and this reader says so and reports nothing."""

from perfbench.harness import scopes

SNAPSHOT = "snapshot"


def read(view):
    session = view.session
    whole, executions = session.traced_programs(view.trace, view.traced)
    if SNAPSHOT not in executions:
        print("perfbench: a call has no program of its own for the snapshot: "
              "what output costs is in the step's programs, where "
              "snapshot_device_share.sw counts it; nothing is reported",
              flush=True)
        return None
    return scopes.floor_share(
        whole, executions, SNAPSHOT, session.compiled_text,
        view.peaks["hbm_gbps"], "a snapshot")
