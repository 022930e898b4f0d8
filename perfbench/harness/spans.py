"""The harness's own spans: names the trace reduction looks for on the host's plane."""

import jax

ENQUEUE = "enqueue"
SYNC = "sync"
HOST_SPANS = (ENQUEUE, SYNC)


def span(name):
    return jax.profiler.TraceAnnotation(name)
