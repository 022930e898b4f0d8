"""Seconds of set-up after the chips that went into importing what the
program is built with: the self time of the program's ``build/import``
spans on the batches' thread before the window's first batch, the
package's own import (``module`` ``mpi4jax_tpu``) and Pallas's
(``jax.experimental.pallas``, in ``sw_kernels.pallas()``; parted from
the trace it may lie in).  Source: the program's own spans
(``mpi4jax_tpu.utils.spans.builds``; ``README.setup-spans.md``)."""

from perfbench.harness import setupspans


def read(view):
    return setupspans.phase_seconds(view, setupspans.IMPORT)
