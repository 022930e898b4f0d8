"""Share of the HBM roofline a save's staging program reaches, in per
cent.  The least it can move is what it is handed and what it hands
back, each once: its signature bytes, read from its compiled text
(``harness/scopes.py signature``): the state in and its pieces out, 2 x
the state's bytes as the job makes it since PR 36.  Over the table's HBM
bandwidth, divided by the device time of one execution of the staging
program from the trace (the union of its leaf events, a mean over the
executions the trace holds).  Bound: bandwidth (a piece is a copy).

Where the traced window ran no staging program (no save in it, or a
save whose pieces another program writes) the reader says so and
reports nothing."""

from perfbench.harness import scopes

STAGE = "stage"


def read(view):
    session = view.session
    whole, executions = session.traced_programs(view.trace, view.traced)
    if STAGE not in executions:
        print("perfbench: the traced window ran no staging program of a "
              "save's own: nothing is reported", flush=True)
        return None
    return scopes.floor_share(
        whole, executions, STAGE, session.compiled_text,
        view.peaks["hbm_gbps"], "a save's staging")
