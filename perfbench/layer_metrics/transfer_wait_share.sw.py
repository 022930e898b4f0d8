"""Share of the window in which a copy to the host was held back by
the other kind's bytes under the job's one bound, in per cent: the
job's spans ``job/ask_wait`` (the loop's thread: a snapshot that is due
waits for room a save's pieces hold) and ``checkpoint/fetch_wait`` (the
save's thread: its oldest piece waits for room snapshots hold) that
start inside the window's batches, traced or not, over the batches'
time; their sum over a job's life is ``stats()["transfer_wait_s"]``.
Only the first kind blocks the loop; the second makes a commit later
(``save_commit_period_ratio``).  Prints the split by ``held_by``.  A
program without these spans (a job with one half, or the parent of the
PR that brought them): a printed reason and nothing."""

import collections

from perfbench.harness import hostspans

WAITS = ("job/ask_wait", "checkpoint/fetch_wait")


def read(view):
    spans = hostspans.job_spans(view)
    batches = view.samples + view.traced
    if spans is None or not batches:
        return None
    if "transfer_wait_s" not in view.session.job.stats():
        print("perfbench: the job keeps no one bound on its copies to the "
              "host: nothing is reported", flush=True)
        return None
    start = min(b.start for b in batches) * 1e9
    end = max(b.end for b in batches) * 1e9
    by = collections.defaultdict(lambda: [0.0, 0, 0])
    for s in spans:
        if s.name in WAITS and start <= s.start_ns <= end:
            held = by[s.name, s.counts.get("held_by")]
            held[0] += s.seconds
            held[1] += 1
            held[2] += s.counts.get("bytes", 0)
    for (name, held_by), (seconds, count, nbytes) in sorted(by.items()):
        print(f"perfbench: {name} held by {held_by}: {count} waits, "
              f"{seconds:.6f} s, {nbytes} bytes", flush=True)
    waited = sum(seconds for seconds, _, _ in by.values())
    return 100.0 * waited / sum(b.seconds for b in batches)
