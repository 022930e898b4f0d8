"""Share of the traced window in which no operation ran on the chip."""

from perfbench.harness import trace


def read(view):
    return trace.idle_share(view.trace)
