"""Seconds of set-up after the chips under no build span: what is left
of ``setup_after_chips_s`` once the self times of ``build/trace``,
``build/lower``, ``build/compile`` and ``build/import`` are taken out.
The driver's own: its data, the warm-up batches, a save, a kill and a
resume, a truth run.  ``build/compile``'s self time is ``compile_s``'
own quantity and no metric of its own; the two are printed side by
side and must agree to milliseconds.  Source: the program's own spans
(``mpi4jax_tpu.utils.spans.builds``; ``README.setup-spans.md``) against
the harness's clock."""

from perfbench.harness import setupspans


def read(view):
    return setupspans.unnamed_seconds(view)
