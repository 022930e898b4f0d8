"""Seconds from the kill to the resumed job ready, in set-up: the old
job and its buffers dropped, the directory cleaned and its newest
committed save found, the state read and sent to the device in the
pieces it was saved in, the pieces put together, and the call's
programs loaded ahead of the first call.  Part of ``setup_s``.
Source: the driver's clock round ``SolverJob.resume``."""


def read(view):
    return getattr(view.session, "resume_s", None)
