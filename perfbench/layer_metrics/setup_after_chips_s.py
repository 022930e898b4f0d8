"""Seconds of set-up after the chips are reached: from the moment before
the driver's module, and with it the program, is loaded to the window's
start.  The steady part of ``setup_s``, and the part a change to the
program can move; the rest is jax's import and the runtime's start.
Source: the harness's own clock."""


def read(view):
    print(f"perfbench: set-up: {view.setup}", flush=True)
    return view.setup["after_chips_s"]
