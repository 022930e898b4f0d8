"""Two-process ``jax.distributed`` integration: the pod-tier bootstrap
exercised beyond its single-host degenerate case (VERDICT r1 #7).

Two OS processes, each with 4 virtual CPU devices, join one distributed
world through a local coordinator (gloo CPU collectives); both run the
same SPMD program over ``world_comm()`` and must agree on collective
results — the TPU-native analog of the reference's ``mpirun -np 2``
CI tier (SURVEY §4.1).
"""

import os
import pathlib
import signal
import socket
import subprocess
import sys
import textwrap

REPO = pathlib.Path(__file__).resolve().parent.parent

WORKER = """
import sys
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
import numpy as np

import mpi4jax_tpu as m
from mpi4jax_tpu.parallel import distributed

coord, pid = sys.argv[1], int(sys.argv[2])
distributed.initialize(
    coordinator_address=coord, num_processes=2, process_id=pid
)
assert jax.process_count() == 2, jax.process_count()
assert len(jax.devices()) == 8, jax.devices()  # 4 local x 2 processes

comm = distributed.world_comm()
assert comm.size == 8

def fn():
    r = jax.lax.axis_index("world").astype(jnp.float32)[None]
    total, tok = m.allreduce(r, m.SUM, comm=comm)
    everyone, tok = m.allgather(r[0], comm=comm, token=tok)
    ring = [(i, (i + 1) % 8) for i in range(8)]
    shifted, tok = m.sendrecv(r, r, source=ring, dest=ring, comm=comm,
                              token=tok)
    return total, everyone[None], shifted

out_specs = (jax.P("world"), jax.P("world", None), jax.P("world"))
total, everyone, shifted = jax.jit(
    jax.shard_map(fn, mesh=comm.mesh, in_specs=(), out_specs=out_specs)
)()

# each process checks its addressable shards against the closed-form
# oracles (sum 0..7 = 28; allgather = arange; ring shift = rank-1)
for shard in total.addressable_shards:
    assert np.allclose(np.asarray(shard.data), 28.0), shard
for shard in everyone.addressable_shards:
    assert np.allclose(np.asarray(shard.data).ravel(), np.arange(8.0)), shard
for shard in shifted.addressable_shards:
    dev_rank = shard.index[0].start
    assert np.allclose(
        np.asarray(shard.data), (dev_rank - 1) % 8
    ), (shard.index, np.asarray(shard.data))

print(f"DIST_OK {pid}", flush=True)
"""


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_two_process_distributed_world(tmp_path):
    script = tmp_path / "dist_worker.py"
    script.write_text(textwrap.dedent(WORKER))
    coord = f"127.0.0.1:{_free_port()}"

    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO) + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)  # worker sets its own device count

    procs = [
        subprocess.Popen(
            [sys.executable, str(script), coord, str(pid)],
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
            env=env,
            cwd=str(tmp_path),  # the package comes from PYTHONPATH alone
            start_new_session=True,
        )
        for pid in (0, 1)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=240)
            outs.append(out)
    except subprocess.TimeoutExpired:
        for p in procs:
            try:
                os.killpg(os.getpgid(p.pid), signal.SIGKILL)
            except ProcessLookupError:
                pass
        raise AssertionError(f"distributed job hung\n{outs}")
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, (pid, out)
        assert f"DIST_OK {pid}" in out, (pid, out)
