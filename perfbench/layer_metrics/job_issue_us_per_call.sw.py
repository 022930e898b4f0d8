"""What one call costs the job's loop on the host, in us: the self time
of ``job/advance``, of the multistep's and the snapshot's
``job/enqueue`` and of ``job/ask`` on the main thread, over the calls
of the window's batches, traced or not.  Fetches, callbacks and saves
are left out (``output_wait_share.sw``, ``save_stall_share.sw`` and the
printed ``save_enqueue_s`` have them).  It passes beside a busy device
while a call is long, and sets the pace as soon as a call is short.
Also prints, for every batch of the window 3 ms or more over a batch
without a save, the spans of every thread that lay over it: the table a
stalled run is read by.  Source: the job's own spans
(``SolverJob.spans()``)."""

from perfbench.harness import hostspans


def read(view):
    hostspans.print_long_batches(view)
    return hostspans.issue_us_per_call(view)
