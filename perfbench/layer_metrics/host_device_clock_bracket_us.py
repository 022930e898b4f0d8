"""Width, in us, of the bracket that the harness's own spans put round
the offset between the profiler's host clock and the device's: no
program of a traced batch starts before that batch's ``enqueue`` span
starts, and the program its ``sync`` waits for has ended when that
``sync`` ends (``harness/hostspans.py bracket``).  How far the names
under ``idle_in_sync_share.sw``, ``idle_in_job_share.sw`` and
``idle_unnamed_share.sw`` can be trusted: an idle stretch shorter than
this carries no name.  Prints the clocks, the window's idle by name and
the host's self time by span."""

from perfbench.harness import hostspans


def read(view):
    found = hostspans.split(view)
    return None if found is None else found.width_ns / 1e3
