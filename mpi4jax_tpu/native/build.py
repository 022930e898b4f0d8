"""Build the native DCN bridge shared library.

The reference compiles its Cython bridge with mpicc at pip-install time
(setup.py:75-86 custom_build_ext); here the C++ bridge is compiled with
g++ against the XLA FFI headers shipped inside jaxlib
(``jax.ffi.include_dir()``) on first use, and cached under a key made
of the sources' content, the build mode and the CPU's feature flags.

Also usable standalone:  python -m mpi4jax_tpu.native.build
"""

import pathlib
import subprocess
import sys

__all__ = ["lib_path", "ensure_built", "build"]

_SRC_DIR = pathlib.Path(__file__).resolve().parent / "src"
_OUT = pathlib.Path(__file__).resolve().parent / "_t4j_dcn.so"
_SOURCES = ["dcn.cc", "shm.cc", "ffi.cc"]


def lib_path():
    return _OUT


_HEADERS = ["dcn.h", "shm.h", "telemetry.h"]


def _sanitize_flags():
    """Opt-in sanitizer build: T4J_SANITIZE=address compiles the bridge
    under ASan so the fault-injection suite can double as a memory-
    safety harness locally, and T4J_SANITIZE=thread under TSan so the
    same suite exercises the bridge's progress/abort threads for data
    races (tools/ci_smoke.sh has a build leg for each).  Other values
    are passed through to -fsanitize verbatim (e.g. undefined)."""
    import os

    san = os.environ.get("T4J_SANITIZE", "").strip().lower()
    if not san:
        return []
    if san in ("address", "asan", "1"):
        san = "address"
    elif san in ("thread", "tsan"):
        san = "thread"
    return [f"-fsanitize={san}", "-fno-omit-frame-pointer", "-g"]


def _strict():
    """T4J_NATIVE_STRICT=1 promotes the bridge build to
    -Wall -Wextra -Werror and runs clang-tidy (bugprone-*,
    concurrency-*; .clang-tidy at the repo root) when the tool is
    installed.  Our sources must stay warning-clean; the jaxlib FFI
    headers are third-party and enter via -isystem so their warnings
    never gate our build."""
    import os

    from mpi4jax_tpu.utils.config import truthy

    return truthy(os.environ.get("T4J_NATIVE_STRICT"), default=False)


def _machine_key():
    """CPU-feature + build-mode fingerprint: the cached .so contains
    -march=native codegen, so a package dir shared across heterogeneous
    hosts (NFS conda env) must rebuild per machine instead of
    SIGILL-ing; toggling T4J_SANITIZE must rebuild too, or a cached
    plain .so would silently satisfy a sanitizer run."""
    import hashlib

    san = "|".join(_sanitize_flags())
    if _strict():
        san = f"{san}|strict" if san else "strict"
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith("flags"):
                    key = hashlib.sha256(line.encode()).hexdigest()[:16]
                    return f"{key}|{san}" if san else key
    except OSError:
        pass
    import platform

    key = platform.machine()
    return f"{key}|{san}" if san else key


def _build_key():
    """What a cached .so must have been built from to be loaded: the
    machine/build-mode key plus the CONTENT of every source and header.
    A copy of the tree keeps neither mtimes nor the machine, so a
    binary that travelled with it, or one older than an edit that kept
    the mtime, never matches."""
    import hashlib

    h = hashlib.sha256(_machine_key().encode())
    for name in _SOURCES + _HEADERS:
        h.update(name.encode())
        h.update((_SRC_DIR / name).read_bytes())
    return h.hexdigest()


def _needs_build():
    if not _OUT.exists():
        return True
    try:
        recorded = _OUT.with_suffix(".buildinfo").read_text().strip()
    except OSError:
        return True
    return recorded != _build_key()


def _ffi_include_dir():
    """The XLA FFI headers inside the installed jaxlib."""
    import jax.ffi

    return jax.ffi.include_dir()


def build(verbose=False):
    import os

    include = _ffi_include_dir()
    key = _build_key()  # of the sources as read now, before compiling
    tmp = _OUT.with_suffix(f".tmp{os.getpid()}.so")
    # compiler override mirrors the reference's MPI4JAX_BUILD_MPICC
    # (setup.py:78); CXX is the conventional spelling here
    cxx = os.environ.get("MPI4JAX_TPU_BUILD_CXX") or os.environ.get(
        "CXX", "g++"
    )
    strict = _strict()
    # the jaxlib FFI headers are third-party: -isystem keeps their
    # (numerous) -Wextra findings out of our warning surface, so the
    # strict gate measures only this repo's sources
    warn = ["-Wall", "-Wextra", "-Werror"] if strict else ["-Wall"]

    def cmd_for(extra):
        return [
            cxx,
            "-O3",
            *extra,
            *_sanitize_flags(),
            "-fPIC",
            "-shared",
            "-std=c++17",
            *warn,
            f"-isystem{include}",
            *[str(_SRC_DIR / s) for s in _SOURCES],
            "-o",
            str(tmp),
            "-lpthread",
            "-lrt",
        ]

    if strict:
        _run_clang_tidy(include)

    # -march=native vectorises the reduction combines (the shm arena's
    # fold is memory-bound only when SIMD keeps up); the library is
    # JIT-built per machine on first use, so native codegen is safe.
    # Fall back to portable flags if the toolchain rejects it.
    proc = None
    for extra in (["-march=native"], []):
        cmd = cmd_for(extra)
        if verbose:
            print(" ".join(cmd), file=sys.stderr)
        proc = subprocess.run(cmd, capture_output=True, text=True)
        if proc.returncode == 0:
            break
    if proc.returncode != 0:
        tmp.unlink(missing_ok=True)
        raise RuntimeError(
            f"native bridge build failed:\n{proc.stderr[-4000:]}"
        )
    os.replace(tmp, _OUT)  # atomic: concurrent loaders never see a torn .so
    _OUT.with_suffix(".buildinfo").write_text(key + "\n")
    return _OUT


def _run_clang_tidy(include):
    """clang-tidy leg of the strict build (checks from the repo-root
    .clang-tidy: bugprone-*, concurrency-*, warnings-as-errors).  Skips
    with a note when clang-tidy is not installed — the strict *compile*
    still gates; containers with the full toolchain get both."""
    import os
    import shutil

    tidy = shutil.which(os.environ.get("T4J_CLANG_TIDY", "clang-tidy"))
    if tidy is None:
        print(
            "t4j strict build: clang-tidy not found, running the "
            "-Werror compile gate only",
            file=sys.stderr,
        )
        return
    cmd = [
        tidy,
        *[str(_SRC_DIR / s) for s in _SOURCES],
        "--warnings-as-errors=*",
        "--",
        "-std=c++17",
        f"-isystem{include}",
    ]
    proc = subprocess.run(cmd, capture_output=True, text=True)
    if proc.returncode != 0:
        raise RuntimeError(
            f"clang-tidy failed (T4J_NATIVE_STRICT=1):\n"
            f"{(proc.stdout + proc.stderr)[-4000:]}"
        )


def ensure_built():
    if not _needs_build():
        return _OUT
    # N launcher children may hit a cold cache simultaneously; serialise
    # through a file lock so exactly one compiles and the rest reuse it
    import fcntl

    lock = _OUT.with_suffix(".lock")
    with open(lock, "w") as fh:
        fcntl.flock(fh, fcntl.LOCK_EX)
        try:
            if _needs_build():
                build()
        finally:
            fcntl.flock(fh, fcntl.LOCK_UN)
    return _OUT


if __name__ == "__main__":
    build(verbose=True)
    print(f"built {_OUT}")
