"""Share of the HBM roofline that the monitor program reaches, in per
cent: the least time the bytes it has to move could take at the
table's HBM bandwidth, over its device time.

Bytes: the program's signature (``harness/scopes.py signature``: what
it is handed and what it hands back, each once, read from its compiled
text): a chip's padded ``h``, ``u``, ``v`` in, 3 x (ny+2G)(nx+2G) x 4
bytes, and a line of four numbers out.  Every reduction reads all of a
field, so the signature is what the program has to read; a program that
read a field twice would read below its share, not above.

Time: one execution's device time (the union of its leaf events, a
mean over the executions the trace holds whole, all chips').  On four
chips that holds the wait in the ``allreduce``s for the slowest chip,
which is no HBM traffic: ``monitor_allreduce_us_per_call`` has it.
Bound: bandwidth (a count, two maxima, a minimum and a sum are four
operations a cell).

Where the call has no monitor program or the trace none of its
executions: ``monitor_device_share.sw``'s printed reason and nothing."""

from perfbench.harness import files, scopes

MONITOR = "monitor"


def read(view):
    session = view.session
    share = files.load_module(
        "layer_metrics", "monitor_device_share.sw", session.ctx.bench_dir)
    found = share.executions_of(view)
    if found is None:
        return None
    whole, executions, _placed, _mine = found
    return scopes.floor_share(
        whole, executions, MONITOR, session.compiled_text,
        view.peaks["hbm_gbps"], "the monitor program")
