"""Share of the step's device time spent under the library's own
scopes where the solver runs as a job, in per cent: of the leaf events
of the multistep program's executions, those whose instruction carries a
``mpi4jax_tpu.<op>`` scope in that program's text, over all of them.  On
one chip that is the halo exchange's slab slices, as in
``op_surface_device_share.sw``, which reads one program a call and would
count the snapshot program (all of it under ``mpi4jax_tpu.snapshot``) as
op surface; ``snapshot_device_share.sw`` has that and prints the table."""

from perfbench.harness import scopes

MULTI = "multistep"


def read(view):
    session = view.session
    rows = scopes.attribute(
        *session.traced_programs(view.trace, view.traced),
        session.compiled_text)
    if rows is None:
        return None
    step = [r for r in rows if r.program == MULTI]
    if not step:
        return None
    mine = scopes.total(r for r in step if r.layer == scopes.OP_SURFACE)
    return 100.0 * mine / scopes.total(step)
