"""What the library's allreduce costs over jax's plain ``lax.psum`` at
the cell's large size: seconds a call over seconds a call, both in the
same chained program, batches taken in turn after the traced window."""


def read(view):
    pair = view.probe.get("busbw")
    return pair["library"] / pair["plain"] if pair else None
