"""Share of the window the loop stood waiting for a monitor's line, in
per cent: the job's spans ``job/monitor_wait`` (a line read on the
host: blocked where its copy was not there yet) that start inside the
window's batches, traced or not, over the batches' time; their sum over
a job's life is ``stats()["monitor_wait_s"]``.  Prints the count, the
longest, and the job's counters ``monitor_lines``,
``monitor_max_lag_calls`` and ``monitor_stops``.  A line is read ``lag``
calls after the call that made it, so a loop that keeps the device busy
reads lines that have long arrived, and the share is the cost of the
read itself.

A program without the span or the counters (a job without a monitor,
or the parent of the PR that brought them): a printed reason and
nothing."""

from perfbench.harness import hostspans

WAIT = "job/monitor_wait"
COUNTERS = ("monitor_lines", "monitor_max_lag_calls", "monitor_wait_s",
            "monitor_stops")


def read(view):
    spans = hostspans.job_spans(view)
    batches = view.samples + view.traced
    if spans is None or not batches:
        return None
    now = view.session.job.stats()
    if (not all(k in now for k in COUNTERS)
            or getattr(view.session.job, "monitor", None) is None):
        print("perfbench: the job keeps no monitor: nothing is reported",
              flush=True)
        return None
    start = min(b.start for b in batches) * 1e9
    end = max(b.end for b in batches) * 1e9
    waits = [s.seconds for s in spans
             if s.name == WAIT and start <= s.start_ns <= end]
    print(f"perfbench: {WAIT}: {len(waits)} lines read inside the window's "
          f"batches, {sum(waits):.6f} s, the longest "
          f"{max(waits, default=0.0) * 1e6:.1f} us; the job's counters: "
          + ", ".join(f"{k} {now[k]!r}" for k in COUNTERS), flush=True)
    return 100.0 * sum(waits) / sum(b.seconds for b in batches)
