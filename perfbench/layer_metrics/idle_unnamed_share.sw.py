"""The rest of ``device_idle_share.sw``, in per cent of the traced
window, after ``idle_in_sync_share.sw`` and ``idle_in_job_share.sw``:
idle while the host's main thread was in no span (the head and the tail
of the window, the profiler's start and stop), every stretch shorter
than ``host_device_clock_bracket_us`` (``below_resolution``), less the
device work that the trace holds outside the window, which
``device_idle_share.sw`` counts as busy (the table printed by
``harness/hostspans.py`` has each part)."""

from perfbench.harness import hostspans


def read(view):
    found = hostspans.split(view)
    return None if found is None else found.share("unnamed")
