"""How much of the time between two saves a commit takes: the median,
over the saves started and committed inside the window, of the length
of a save's ``checkpoint/save`` span (start to rename) over the median
time between two saves' starts.  A ratio, not a share: a save does not
start before the one before it is acknowledged, so 1.0 is where the
loop starts to wait (``job/save_wait``), and the room under it is what
a slower disk, or copies that give way to snapshots, may still take.
Fewer than two saves in the window, or a program without spans: a
printed reason and nothing.  Source: the job's own spans."""

import statistics

from perfbench.harness import hostspans


def read(view):
    spans = hostspans.job_spans(view)
    batches = view.samples + view.traced
    if spans is None or not batches:
        return None
    start = min(b.start for b in batches) * 1e9
    end = max(b.end for b in batches) * 1e9
    saves = sorted((s for s in spans if s.name == hostspans.SAVE
                    and start <= s.start_ns <= end), key=lambda s: s.start_ns)
    if len(saves) < 2:
        print(f"perfbench: {len(saves)} saves were started and committed inside "
              "the window: no period; nothing is reported", flush=True)
        return None
    period = statistics.median(
        (b.start_ns - a.start_ns) / 1e9 for a, b in zip(saves, saves[1:]))
    commit = statistics.median(s.seconds for s in saves)
    print(f"perfbench: {len(saves)} saves, committed after {commit:.3f} s at the "
          f"median ({', '.join(f'{s.seconds:.3f}' for s in saves)}), "
          f"{period:.3f} s apart", flush=True)
    return commit / period
