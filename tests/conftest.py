"""Test harness configuration.

The reference's test philosophy (SURVEY §4): no mocks — run the same
suite under 1 process and under ``mpirun -np 2``.  The TPU-native
equivalent simulates an N-device slice with XLA's host-platform device
count (SURVEY §4 rebuild implication): every collective here executes
against 8 real XLA CPU devices under ``shard_map`` — the same program
XLA would run over ICI on a TPU slice — and single-process semantics are
covered by the SelfComm backend tests.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

# Tests run on the virtual CPU mesh, whatever the machine's default
# platform is.
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", False)

import pytest  # noqa: E402


N_DEVICES = 8


def pytest_report_header(config):
    devs = jax.devices()
    return [
        f"jax {jax.__version__}, {len(devs)} {devs[0].platform} devices "
        f"(virtual slice for shard_map collectives)"
    ]


def _auto(n):
    return (jax.sharding.AxisType.Auto,) * n


@pytest.fixture(scope="session")
def mesh1d():
    return jax.make_mesh((N_DEVICES,), ("i",), axis_types=_auto(1))


@pytest.fixture(scope="session")
def mesh2d():
    return jax.make_mesh((2, 4), ("y", "x"), axis_types=_auto(2))


@pytest.fixture(scope="session")
def comm1d(mesh1d):
    from mpi4jax_tpu import MeshComm

    return MeshComm.from_mesh(mesh1d)


@pytest.fixture(scope="session")
def comm2d(mesh2d):
    from mpi4jax_tpu import MeshComm

    return MeshComm.from_mesh(mesh2d)


@pytest.fixture(scope="session")
def selfcomm():
    from mpi4jax_tpu import SelfComm

    return SelfComm()
