"""benchmarks/transformer.py's command line at a tiny size on a virtual
CPU mesh: each mode runs to its JSON record and the size presets
resolve.  chip_smoke.py, the examples and their tests build the train
step from this module; nothing printed here is a device result.
"""

import json
import pathlib
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent


def _run_cli(*args, timeout=300):
    res = subprocess.run(
        [sys.executable, str(REPO / "benchmarks" / "transformer.py"), *args],
        capture_output=True, text=True, timeout=timeout, cwd=REPO,
    )
    assert res.returncode == 0, (res.stdout, res.stderr)
    # last stdout line is the JSON record
    return json.loads(res.stdout.strip().splitlines()[-1])


TINY = (
    "--cpu-mesh", "8", "--batch", "1", "--seq", "64", "--layers", "2",
    "--d-model", "64", "--heads", "4", "--kv-heads", "4", "--d-ff",
    "128", "--vocab", "256", "--batches", "2",
)


@pytest.mark.parametrize("mode", ["dense", "moe", "pp"])
def test_transformer_bench_modes(mode):
    rec = _run_cli("--mode", mode, *TINY)
    assert rec["value"] > 0
    assert rec["devices"] == 8
    assert "model_tflops_per_sec" in rec


def test_transformer_bench_decode_mode():
    rec = _run_cli(
        "--mode", "decode", "--max-len", "32", "--prompt", "8", *TINY
    )
    assert rec["metric"] == "transformer_decode_tokens_per_sec"
    assert rec["value"] > 0


def test_size_presets_resolve():
    # presets must parse and explicit flags must override them (tiny
    # overrides keep this runnable on the CPU mesh)
    for size in ("small", "large", "long"):
        rec = _run_cli("--size", size, *TINY)
        assert rec["seq"] == 128  # 64 * sp(2): the override won
