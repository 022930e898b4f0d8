"""Seconds from a save's start to its acknowledgement (the rename that
commits it), the median over the saves the window's job started: the
staging program, the pieces' copies to the host under ``ahead_bytes``,
the files, the manifest.  It passes beside the loop, and it bounds how
often a job can save: a save does not start before the one before it is
acknowledged.  Source: the job's own records (``SolverJob.saves``)."""

import statistics


def read(view):
    saves = view.session.window_saves()
    if not saves:
        return None
    for r in saves:
        print(f"perfbench: the save of step {r['step']}: {r['bytes']} bytes, "
              f"on the host after {r['stage_s']:.3f} s, committed after "
              f"{r['commit_s']:.3f} s", flush=True)
    return statistics.median(r["commit_s"] for r in saves)
