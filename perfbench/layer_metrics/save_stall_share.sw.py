"""Share of the window the loop was blocked because of a save, in per
cent: the job's own counter ``save_wait_s`` (host seconds waiting for the
save before to be acknowledged: a save does not start before that) since
set-up, over the time of the window's batches.  0 while a save is
committed before the next comes due.  What a save costs beside that:
the staging program's device time is ``checkpoint_device_share.sw``'s;
the host's time starting it (``save_enqueue_s``, printed by the driver)
passes beside what the device has queued; its copies, files and commit
pass on other threads (``save_commit_s``)."""


def read(view):
    batches = view.samples + view.traced
    if not batches:
        return None
    now, before = view.session.job.stats(), view.session.at_setup
    waited = now["save_wait_s"] - before["save_wait_s"]
    return 100.0 * waited / sum(s.seconds for s in batches)
