"""Seconds of set-up after the chips that jax spent tracing the cell's
jitted functions into jaxprs: the self time of the program's
``build/trace`` spans on the batches' thread before the window's first
batch, what a trace holds of other spans (the traces of the jitted
functions it calls, an import, a small compile) given to those.  The
persistent cache saves none of it: a program is traced before its key
exists.  The first of the four to run prints the set-up's table, a row
a (program, phase).  Source: the program's own spans
(``mpi4jax_tpu.utils.spans.builds``; ``README.setup-spans.md``)."""

from perfbench.harness import setupspans


def read(view):
    return setupspans.phase_seconds(view, setupspans.TRACE)
