"""What the library's allreduce costs over jax's plain ``lax.psum`` at
the cell's small size (see ``allreduce_tax_large``)."""


def read(view):
    pair = view.probe.get("latency")
    return pair["library"] / pair["plain"] if pair else None
