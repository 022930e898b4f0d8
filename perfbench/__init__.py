"""The benchmark of mpi4jax_tpu: one command, driven by the data files beside it (see README.md)."""
