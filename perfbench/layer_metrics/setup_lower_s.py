"""Seconds of set-up after the chips that jax spent lowering the cell's
jaxprs to MLIR modules, a kernel's Mosaic lowering included: the self
time of the program's ``build/lower`` spans on the batches' thread
before the window's first batch.  Paid in every run, by each program
that holds the kernel: the cache's key is made of the lowered module.
Source: the program's own spans (``mpi4jax_tpu.utils.spans.builds``;
``README.setup-spans.md``)."""

from perfbench.harness import setupspans


def read(view):
    return setupspans.phase_seconds(view, setupspans.LOWER)
