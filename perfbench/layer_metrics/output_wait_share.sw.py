"""Share of the window the host spent on the job's output, in per
cent: the job's own counters ``output_wait_s`` (fetching snapshots whose
copies it had started: blocked where one was not ready) and
``callback_s`` (inside the callback), since set-up, over the time of
the window's batches.  The device does not wait for either unless the
host falls a whole batch behind: ``device_idle_share.sw`` says."""


def read(view):
    batches = view.samples + view.traced
    if not batches:
        return None
    now, before = view.session.job.stats(), view.session.at_setup
    host = sum(now[k] - before[k] for k in ("output_wait_s", "callback_s"))
    return 100.0 * host / sum(s.seconds for s in batches)
