"""Example-as-test (reference: tests/test_examples.py:20-24 runs the real
shallow-water demo in CI)."""

import pathlib
import sys

import pytest


def test_shallow_water_example_runs():
    examples = pathlib.Path(__file__).resolve().parent.parent / "examples"
    sys.path.insert(0, str(examples))
    try:
        import shallow_water as demo

        rate = demo.main(["--check", "--mesh", "2", "4"])
        assert rate > 0
    finally:
        sys.path.remove(str(examples))


def test_best_mesh_shape_is_the_most_square():
    # the examples' default mesh
    from mpi4jax_tpu.utils.runtime import best_mesh_shape

    assert best_mesh_shape(8) == (2, 4)
    assert best_mesh_shape(7) == (1, 7)


def _run_example(name, argv):
    examples = pathlib.Path(__file__).resolve().parent.parent / "examples"
    sys.path.insert(0, str(examples))
    try:
        import importlib

        mod = importlib.import_module(name)
        mod.main(argv)
    finally:
        sys.path.remove(str(examples))


def test_dp_tp_example_runs():
    _run_example("data_tensor_parallel", ["--steps", "25"])


def test_dp_tp_example_zero():
    _run_example("data_tensor_parallel", ["--steps", "25", "--zero"])


@pytest.mark.parametrize("mode", ["dense", "moe", "pp"])
def test_transformer_training_example(mode):
    _run_example(
        "transformer_training", ["--mode", mode, "--steps", "6"]
    )


def test_transformer_training_example_1f1b():
    _run_example(
        "transformer_training",
        ["--mode", "pp", "--schedule", "1f1b", "--steps", "6"],
    )


def test_transformer_training_generate():
    _run_example(
        "transformer_training",
        ["--mode", "dense", "--steps", "6", "--generate", "4"],
    )


def test_transformer_training_generate_kv_bucket():
    _run_example(
        "transformer_training",
        [
            "--mode", "dense", "--steps", "6", "--generate", "4",
            "--kv-bucket", "4",
        ],
    )


def test_transformer_training_resume_bit_identical(tmp_path):
    # interrupted-and-resumed training must land on the same bits as an
    # uninterrupted run (the solver's resume contract, applied to the
    # model trainer)
    import importlib
    import numpy as np

    examples = pathlib.Path(__file__).resolve().parent.parent / "examples"
    sys.path.insert(0, str(examples))
    try:
        demo = importlib.import_module("transformer_training")
        full = demo.main(["--steps", "8"])
        ck = str(tmp_path / "ck")
        demo.main(["--steps", "4", "--checkpoint", ck, "--checkpoint-every", "2"])
        resumed = demo.main(
            ["--steps", "8", "--checkpoint", ck, "--checkpoint-every", "2"]
        )
    finally:
        sys.path.remove(str(examples))

    import jax

    assert jax.tree.structure(full) == jax.tree.structure(resumed)
    for a, b in zip(
        jax.tree.leaves(full), jax.tree.leaves(resumed), strict=True
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("mode", ["dense", "moe", "pp"])
def test_transformer_bench_runs_tiny(mode):
    root = pathlib.Path(__file__).resolve().parent.parent
    sys.path.insert(0, str(root))
    try:
        from benchmarks import transformer as tb

        tb.main([
            "--mode", mode, "--batch", "2", "--seq", "64", "--layers", "2",
            "--d-model", "64", "--d-ff", "128", "--vocab", "256",
            "--batches", "2",
        ])
    finally:
        sys.path.remove(str(root))


def test_long_context_example_runs():
    _run_example("long_context", ["--seq-per-device", "32", "--causal"])


def test_long_context_example_gqa():
    # grouped-query attention path (kv heads < query heads); ulysses
    # self-skips when kv heads don't divide the device count
    _run_example(
        "long_context",
        ["--seq-per-device", "32", "--causal", "--kv-heads", "2"],
    )
