"""Share of the traced window, in per cent, in which the chip ran
nothing while the host's main thread was inside a span of the job
(``job/enqueue``, ``job/fetch``, ``job/save_start``, ...: the innermost
one names the stretch in the printed table): the chip waiting for the
program's own host code.  ``harness/hostspans.py`` has the clocks."""

from perfbench.harness import hostspans


def read(view):
    found = hostspans.split(view)
    return None if found is None else found.share("in_job")
