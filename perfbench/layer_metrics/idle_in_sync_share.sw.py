"""Share of the traced window, in per cent, in which the chip ran
nothing while the host's main thread was inside the harness's ``sync``
and inside no span of the job: the closed loop's own idle, a batch's
last program done and the next not yet enqueued.  The job's spans reach
the device's clock through ``harness/hostspans.py``; with
``idle_in_job_share.sw`` and ``idle_unnamed_share.sw`` it sums to
``device_idle_share.sw``."""

from perfbench.harness import hostspans


def read(view):
    found = hostspans.split(view)
    return None if found is None else found.share("in_sync")
