"""Seconds jax spent in the backend compiler during set-up (0 when every
program came from the persistent cache).  Source: jax's own counters."""


def read(view):
    print(f"perfbench: compile: {view.compile}", flush=True)
    return view.compile["compile_s"]
