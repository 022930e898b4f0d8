"""Support-code tests: capability probes, drain/flush, versioning —
the counterparts of the reference's tests/test_has_cuda.py and
tests/test_flush.py plus a version-shape check (versioneer analog)."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mpi4jax_tpu as m


def test_capability_probes():
    # on the CPU test platform: no TPU, and CUDA is never supported here
    assert m.has_cuda_support() is False
    assert m.has_tpu_support() is False  # conftest pins jax_platforms=cpu


def test_version_shape():
    # PEP-440-ish: starts with digits, dot-separated (git-describe local
    # parts allowed after '+')
    assert re.match(r"^\d+\.\d+", m.__version__), m.__version__


def test_drain_blocks_and_returns_scalar():
    from mpi4jax_tpu.utils.runtime import drain

    x = (jnp.arange(16.0) + 1).reshape(4, 4) * 2
    out = drain(x)
    assert np.asarray(out) == 2.0  # first element (nonzero on purpose)
    s = drain(jnp.float32(7))
    assert np.asarray(s) == 7.0


def test_drain_after_collective(comm1d):
    from mpi4jax_tpu.utils.runtime import drain
    from tests.helpers import spmd_jit

    f = spmd_jit(comm1d, lambda x: m.allreduce(x, m.SUM, comm=comm1d)[0])
    out = f(jnp.arange(8.0))
    assert drain(out) == 28.0


def test_version_prerelease_tags_are_pep440():
    """v0.1.0-rc1 must become the PEP 440 pre-release 0.1.0rc1 (which
    sorts BEFORE 0.1.0), not the local version 0.1.0+rc1 (after)."""
    from mpi4jax_tpu._version import _munge_describe as munge

    assert munge("v0.1.0-rc1") == "0.1.0rc1"
    assert munge("v0.1.0-rc1-3-gabc12") == "0.1.0rc1+3.gabc12"
    assert munge("v0.2.0-alpha.2") == "0.2.0a2"
    assert munge("v0.1.0-beta2") == "0.1.0b2"
    assert munge("v0.1.0-5-gdef00") == "0.1.0+5.gdef00"
    assert munge("v0.1.0") == "0.1.0"


def test_compile_cache_env_set_touches_nothing(monkeypatch, tmp_path):
    from mpi4jax_tpu.utils import runtime

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert runtime.enable_compile_cache() == str(tmp_path)
    # jax reads the variable itself: the code set no directory of its own
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_unset_is_one_fixed_path_in_the_checkout(monkeypatch):
    import os
    import pathlib
    import subprocess
    import sys

    from mpi4jax_tpu.utils import runtime

    repo = pathlib.Path(__file__).resolve().parent.parent
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        first = runtime.enable_compile_cache()
        assert first == runtime.enable_compile_cache()  # two calls
        assert jax.config.jax_compilation_cache_dir == first
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert pathlib.Path(first) == repo / ".jax_cache"
    code = (
        "from mpi4jax_tpu.utils.runtime import enable_compile_cache; "
        "print(enable_compile_cache())"
    )
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    others = [
        subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            cwd=cwd, env=dict(env, PYTHONPATH=str(repo)), timeout=120,
        ).stdout.strip()
        for cwd in (repo, "/")  # two processes, two working directories
    ]
    assert others == [first, first]


def _fake_native_tree(monkeypatch, tmp_path):
    """native/build.py pointed at a scratch source tree and a recording
    stand-in for the compiler."""
    from mpi4jax_tpu.native import build

    src = tmp_path / "src"
    src.mkdir()
    for name in build._SOURCES + build._HEADERS:
        (src / name).write_text(f"// {name}\n")
    out = tmp_path / "_t4j_dcn.so"
    builds = []

    def fake_build(verbose=False):
        builds.append(build._build_key())
        out.write_bytes(b"so")
        out.with_suffix(".buildinfo").write_text(builds[-1] + "\n")
        return out

    monkeypatch.setattr(build, "_SRC_DIR", src)
    monkeypatch.setattr(build, "_OUT", out)
    monkeypatch.setattr(build, "build", fake_build)
    return build, src, out, builds


def test_native_build_rebuilds_on_content_not_mtime(monkeypatch, tmp_path):
    import os

    build, src, out, builds = _fake_native_tree(monkeypatch, tmp_path)
    build.ensure_built()
    build.ensure_built()
    assert len(builds) == 1  # cached: same content, same machine, same mode

    # newer mtimes alone (what a copy of the tree does) rebuild nothing
    for f in src.iterdir():
        os.utime(f, (2e9, 2e9))
    build.ensure_built()
    assert len(builds) == 1

    # changed content under an UNCHANGED mtime rebuilds
    target = src / "dcn.cc"
    stamp = target.stat()
    target.write_text("// dcn.cc, edited\n")
    os.utime(target, ns=(stamp.st_atime_ns, stamp.st_mtime_ns))
    assert out.stat().st_mtime > 0 and build._needs_build()
    build.ensure_built()
    assert len(builds) == 2 and builds[0] != builds[1]


def test_native_build_never_loads_a_foreign_binary(monkeypatch, tmp_path):
    build, _src, out, builds = _fake_native_tree(monkeypatch, tmp_path)
    # a .so that travelled with a copy: no build record, or another's
    out.write_bytes(b"foreign")
    assert build._needs_build()
    out.with_suffix(".buildinfo").write_text("someone-elses-key\n")
    assert build._needs_build()
    build.ensure_built()
    assert len(builds) == 1 and out.read_bytes() == b"so"
    monkeypatch.setenv("T4J_SANITIZE", "address")  # the flags are in the key
    assert build._needs_build()


def test_launch_child_names_the_one_process_per_chip_rule(monkeypatch):
    from mpi4jax_tpu import launch
    from mpi4jax_tpu.native import runtime

    def no_bootstrap():
        raise AssertionError("bootstrap must not start without a device")

    def busy():
        raise RuntimeError("TPU is already in use by process 4242")

    monkeypatch.setattr(runtime, "ensure_initialized", no_bootstrap)
    monkeypatch.setattr(launch, "_acquire_devices", busy)
    monkeypatch.setenv("T4J_PLATFORM", "default")
    monkeypatch.setenv("T4J_SIZE", "2")
    monkeypatch.setenv("T4J_RANK", "1")
    with pytest.raises(SystemExit) as exc:
        launch.child_main(["prog.py"])
    msg = str(exc.value.code)
    assert "rank 1 of 2" in msg and "already in use by process 4242" in msg
    assert "one process at a time" in msg and "does not pin chips" in msg
    assert "-np 1" in msg


@pytest.mark.parametrize(
    "platform,size", [("default", "2"), ("default", "1"), ("cpu", "2")]
)
def test_launch_child_reaches_the_bootstrap_when_devices_are_there(
    monkeypatch, platform, size
):
    # a CPU-only machine keeps running `--platform default -np 2`; a
    # world of one, and CPU workers, are never asked
    from mpi4jax_tpu import launch
    from mpi4jax_tpu.native import runtime

    class Reached(Exception):
        pass

    def bootstrap():
        raise Reached

    asked = []
    monkeypatch.setattr(runtime, "ensure_initialized", bootstrap)
    monkeypatch.setattr(
        launch, "_acquire_devices", lambda: asked.append(1) or jax.devices()
    )
    monkeypatch.setenv("T4J_PLATFORM", platform)
    monkeypatch.setenv("T4J_SIZE", size)
    with pytest.raises(Reached):
        launch.child_main(["prog.py"])
    assert len(asked) == (platform == "default" and size == "2")
