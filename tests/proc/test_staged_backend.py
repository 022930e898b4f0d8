"""The host-callback (staged) tier of the proc backend: the analog of
the reference's GPU COPY_TO_HOST path (mpi_xla_bridge_gpu.pyx:211-251).
On real accelerators jax stages HBM->host around the io_callback; here
MPI4JAX_TPU_FORCE_STAGED=1 exercises the identical code path on CPU."""

import pytest

from tests.proc.test_proc_backend import run_workers


def test_staged_ops_real_accelerator():
    """One proc-backend op set, eagerly and under jit, with arrays on the
    worker's default device: on an accelerator jax stages HBM->host
    around the io_callback.  The worker is the one ``chip_smoke.py`` runs
    on the chip; where only CPU devices are visible it still runs, on
    the CPU's FFI path.
    """
    import chip_smoke

    res = run_workers(
        chip_smoke.STAGED_WORKER,
        nprocs=1,
        timeout=300,
        launch_args=("--platform", "default"),
    )
    assert res.returncode == 0, (res.stdout, res.stderr)
    assert "rank 0 staged ok platform=" in res.stdout, (
        res.stdout, res.stderr
    )


def test_staged_ops_cuda():
    """The CUDA leg of the staged tier (reference GPU path analog,
    mpi_xla_bridge_gpu.pyx:211-251): identical op set with the workers
    pinned to ``JAX_PLATFORMS=cuda``, so the io_callback stages GPU
    HBM↔host exactly as it does TPU HBM↔host.  Skips wherever no CUDA
    jaxlib/device is present (this image is TPU-only) — the guard, not
    the hardware, is what keeps ``has_cuda_support()`` honest.
    """
    import subprocess
    import sys

    probe = subprocess.run(
        [
            sys.executable, "-c",
            "import jax; jax.config.update('jax_platforms', 'cuda'); "
            "print(len(jax.devices()))",
        ],
        capture_output=True, text=True, timeout=120,
    )
    if probe.returncode != 0 or not probe.stdout.strip().isdigit():
        pytest.skip("no CUDA backend available")

    res = run_workers(
        """
        import jax
        import jax.numpy as jnp
        import numpy as np
        import mpi4jax_tpu as m

        assert jax.default_backend() == "gpu", jax.default_backend()
        assert m.has_cuda_support()

        comm = m.get_default_comm()
        assert comm.backend == "proc", comm
        x = jnp.arange(4.0)  # lives on the GPU
        assert "cuda" in str(x.device).lower(), x.device

        tok = m.create_token()
        y, tok = m.allreduce(x, m.SUM, comm=comm, token=tok)
        g, tok = m.allgather(x[:2], comm=comm, token=tok)
        b, tok = m.bcast(x * 3, 0, comm=comm, token=tok)
        tok = m.barrier(comm=comm, token=tok)
        assert "cuda" in str(y.device).lower(), y.device
        assert np.allclose(np.asarray(y), np.arange(4.0) * comm.size), y
        assert np.asarray(g).shape == (comm.size, 2), g
        assert np.allclose(np.asarray(b), 3 * np.arange(4.0)), b
        print(f"rank {comm.rank()} cuda staged ok")
        """,
        nprocs=1,
        timeout=300,
        launch_args=("--platform", "cuda"),
    )
    assert res.returncode == 0, (res.stdout, res.stderr)
    assert "cuda staged ok" in res.stdout, (res.stdout, res.stderr)


def test_staged_ops_across_processes():
    res = run_workers(
        """
        import jax
        jax.config.update("jax_platforms", "cpu")
        import jax.numpy as jnp
        import numpy as np
        import mpi4jax_tpu as m

        comm = m.get_default_comm()
        rank, size = comm.rank(), comm.size
        assert size == 2

        @jax.jit
        def f(x):
            tok = m.create_token()
            y, tok = m.allreduce(x, m.SUM, comm=comm, token=tok)
            g, tok = m.allgather(x[:2], comm=comm, token=tok)
            s, tok = m.scan(x, m.SUM, comm=comm, token=tok)
            b, tok = m.bcast(x * 3, 0, comm=comm, token=tok)
            tok = m.barrier(comm=comm, token=tok)
            return y, g, s, b

        x = jnp.arange(4.0) + rank
        y, g, s, b = f(x)
        base = np.arange(4.0)
        assert np.allclose(np.asarray(y), 2 * base + 1), y  # sum over ranks
        assert np.allclose(np.asarray(g), np.stack([base[:2], base[:2] + 1])), g
        assert np.allclose(
            np.asarray(s), base * (rank + 1) + rank * rank
        ), s  # inclusive prefix: sum_{r<=rank}(base+r)
        assert np.allclose(np.asarray(b), 3 * base), b  # root 0's x

        # p2p + status through the staged path
        tok = m.create_token()
        status = m.Status()
        if rank == 0:
            tok = m.send(jnp.full(3, 5.0), dest=1, tag=9, comm=comm, token=tok)
        else:
            got, tok = m.recv(jnp.zeros(3), source=m.ANY_SOURCE,
                              tag=m.ANY_TAG, comm=comm, token=tok,
                              status=status)
            assert np.allclose(np.asarray(got), 5.0), got
            assert int(status.source) == 0 and int(status.tag) == 9

        # sendrecv ring
        other = 1 - rank
        y2, tok = m.sendrecv(jnp.full(2, float(rank)), jnp.zeros(2),
                             source=other, dest=other, comm=comm, token=tok)
        assert np.allclose(np.asarray(y2), float(other)), y2
        print(f"rank {rank} staged ok")
        """,
        nprocs=2,
        env={"MPI4JAX_TPU_FORCE_STAGED": "1"},
    )
    assert res.returncode == 0, (res.stdout, res.stderr)
    assert res.stdout.count("staged ok") == 2, res.stdout
