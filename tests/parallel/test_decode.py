"""Greedy autoregressive decoding with a TP-sharded KV cache
(models/transformer.py:make_global_decode) vs the unsharded
full-recompute oracle: generated token sequences must match exactly.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mpi4jax_tpu as m
from mpi4jax_tpu.models import transformer as tfm

CFG = tfm.TransformerConfig(
    vocab=32, d_model=16, layers=2, heads=4, kv_heads=2, head_dim=8, d_ff=32
)
B, P, MAX = 4, 5, 14


@pytest.fixture(scope="module")
def mesh2d():
    # tp=2 so the GQA kv_heads=2 divide; dp=4 batches
    return jax.make_mesh(
        (4, 2), ("dp", "tp"), axis_types=(jax.sharding.AxisType.Auto,) * 2
    )


@pytest.fixture(scope="module")
def comms(mesh2d):
    world = m.MeshComm.from_mesh(mesh2d)
    return world.sub("dp"), world.sub("tp")


@pytest.mark.parametrize("prefill", ["batched", "stepwise"])
def test_decode_matches_oracle(mesh2d, comms, prefill):
    comm_dp, comm_tp = comms
    params = tfm.init_params(jax.random.PRNGKey(1), CFG)
    prompt = jax.random.randint(jax.random.PRNGKey(2), (B, P), 0, CFG.vocab)

    decode = tfm.make_global_decode(
        mesh2d, comm_dp, comm_tp, CFG, MAX, prefill=prefill
    )
    got = decode(params, prompt)

    want = tfm.reference_greedy_decode(params, prompt, CFG, MAX)
    got, want = np.asarray(got), np.asarray(want)
    # the prompt must be echoed verbatim
    np.testing.assert_array_equal(got[:, :P], np.asarray(prompt))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("prefill", ["batched", "stepwise"])
@pytest.mark.parametrize("bucket", [4, 5, 14])
def test_decode_kv_bucket_matches_oracle(mesh2d, comms, prefill, bucket):
    # bucketed KV growth (scan carry = a cache view growing by static
    # buckets) is token-exact vs the oracle — including a bucket that
    # does not divide max_len (ragged last segment) and bucket ==
    # max_len (degenerates to the un-bucketed loop)
    comm_dp, comm_tp = comms
    params = tfm.init_params(jax.random.PRNGKey(1), CFG)
    prompt = jax.random.randint(jax.random.PRNGKey(2), (B, P), 0, CFG.vocab)
    decode = tfm.make_global_decode(
        mesh2d, comm_dp, comm_tp, CFG, MAX, prefill=prefill,
        kv_bucket=bucket,
    )
    got = decode(params, prompt)
    want = tfm.reference_greedy_decode(params, prompt, CFG, MAX)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.skipif(
    jax.default_backend() != "tpu",
    reason="flash prefill needs the compiled Pallas kernel (interpret "
    "mode inside shard_map trips jax's vma checking); on the chip "
    "chip_smoke.py's transformer.decode phase runs this comparison",
)
def test_decode_flash_prefill_matches_oracle(mesh2d, comms):
    # prefill_impl="flash" (the long-prompt prefill kernel) produces
    # the identical token sequence
    comm_dp, comm_tp = comms
    params = tfm.init_params(jax.random.PRNGKey(1), CFG)
    prompt = jax.random.randint(jax.random.PRNGKey(2), (B, P), 0, CFG.vocab)
    decode = tfm.make_global_decode(
        mesh2d, comm_dp, comm_tp, CFG, MAX, prefill_impl="flash"
    )
    got = decode(params, prompt)
    want = tfm.reference_greedy_decode(params, prompt, CFG, MAX)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("top_k", [None, 3])
@pytest.mark.parametrize("prefill", ["batched", "stepwise"])
def test_decode_sampling_matches_oracle(mesh2d, comms, prefill, top_k):
    # categorical sampling: the per-row key folds in position and
    # GLOBAL row id, so the dp/tp-sharded sampler must match the
    # unsharded oracle bitwise given the same key — with and without
    # top-k truncation
    comm_dp, comm_tp = comms
    params = tfm.init_params(jax.random.PRNGKey(1), CFG)
    prompt = jax.random.randint(jax.random.PRNGKey(2), (B, P), 0, CFG.vocab)
    key = jax.random.PRNGKey(42)
    decode = tfm.make_global_decode(
        mesh2d, comm_dp, comm_tp, CFG, MAX, prefill=prefill,
        sampler="categorical", temperature=0.8, top_k=top_k,
    )
    got = np.asarray(decode(params, prompt, key))
    want = np.asarray(
        tfm.reference_sample_decode(
            params, prompt, CFG, MAX, key, temperature=0.8, top_k=top_k
        )
    )
    np.testing.assert_array_equal(got[:, :P], np.asarray(prompt))
    np.testing.assert_array_equal(got, want)


def test_decode_sampling_key_sensitivity(mesh2d, comms):
    # different keys must (for this config) give different sequences,
    # and the same key must reproduce bitwise
    comm_dp, comm_tp = comms
    params = tfm.init_params(jax.random.PRNGKey(1), CFG)
    prompt = jax.random.randint(jax.random.PRNGKey(2), (B, P), 0, CFG.vocab)
    decode = tfm.make_global_decode(
        mesh2d, comm_dp, comm_tp, CFG, MAX, sampler="categorical",
        temperature=2.0,
    )
    a = np.asarray(decode(params, prompt, jax.random.PRNGKey(0)))
    a2 = np.asarray(decode(params, prompt, jax.random.PRNGKey(0)))
    b = np.asarray(decode(params, prompt, jax.random.PRNGKey(7)))
    np.testing.assert_array_equal(a, a2)
    assert (a != b).any(), "distinct keys produced identical sequences"


def test_decode_sampler_validation(mesh2d, comms):
    comm_dp, comm_tp = comms
    with pytest.raises(ValueError, match="sampler"):
        tfm.make_global_decode(
            mesh2d, comm_dp, comm_tp, CFG, MAX, sampler="beam"
        )
    with pytest.raises(ValueError, match="temperature"):
        tfm.make_global_decode(
            mesh2d, comm_dp, comm_tp, CFG, MAX, sampler="categorical",
            temperature=0.0,
        )
    with pytest.raises(ValueError, match="top_k"):
        tfm.make_global_decode(
            mesh2d, comm_dp, comm_tp, CFG, MAX, sampler="categorical",
            top_k=CFG.vocab + 1,
        )


def test_decode_kv_bucket_validation(mesh2d, comms):
    comm_dp, comm_tp = comms
    with pytest.raises(ValueError, match="kv_bucket"):
        tfm.make_global_decode(
            mesh2d, comm_dp, comm_tp, CFG, MAX, kv_bucket=0
        )
    with pytest.raises(ValueError, match="kv_bucket"):
        tfm.make_global_decode(
            mesh2d, comm_dp, comm_tp, CFG, MAX, kv_bucket=MAX + 1
        )


@pytest.mark.parametrize("prefill", ["batched", "stepwise"])
def test_decode_prompt_only_roundtrip(mesh2d, comms, prefill):
    # max_len == prompt length: nothing generated, prompt returned
    comm_dp, comm_tp = comms
    params = tfm.init_params(jax.random.PRNGKey(3), CFG)
    prompt = jax.random.randint(jax.random.PRNGKey(4), (B, 6), 0, CFG.vocab)
    decode = tfm.make_global_decode(
        mesh2d, comm_dp, comm_tp, CFG, 6, prefill=prefill
    )
    out = decode(params, prompt)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(prompt))


def test_decode_single_token_prompt(mesh2d, comms):
    # p_len == 1: the batched path degrades to stepwise (a 1-token
    # prefill IS one step); both must match the oracle
    comm_dp, comm_tp = comms
    params = tfm.init_params(jax.random.PRNGKey(9), CFG)
    prompt = jax.random.randint(jax.random.PRNGKey(10), (B, 1), 0, CFG.vocab)
    decode = tfm.make_global_decode(mesh2d, comm_dp, comm_tp, CFG, 8)
    want = tfm.reference_greedy_decode(params, prompt, CFG, 8)
    np.testing.assert_array_equal(
        np.asarray(decode(params, prompt)), np.asarray(want)
    )


def test_decode_prompt_longer_than_budget_errors(mesh2d, comms):
    comm_dp, comm_tp = comms
    params = tfm.init_params(jax.random.PRNGKey(7), CFG)
    prompt = jax.random.randint(jax.random.PRNGKey(8), (B, 9), 0, CFG.vocab)
    decode = tfm.make_global_decode(mesh2d, comm_dp, comm_tp, CFG, 8)
    with pytest.raises(ValueError, match="exceeds max_len"):
        decode(params, prompt)


def test_decode_deterministic_across_meshes(comms, mesh2d):
    # tp=2 (the mesh2d fixture's tp extent) vs tp=1: same greedy
    # sequence (collective roundoff must not flip the argmax at these
    # scales/seeds)
    comm_dp, comm_tp = comms
    params = tfm.init_params(jax.random.PRNGKey(5), CFG)
    prompt = jax.random.randint(jax.random.PRNGKey(6), (B, P), 0, CFG.vocab)
    d4 = tfm.make_global_decode(mesh2d, comm_dp, comm_tp, CFG, MAX)
    mesh1 = jax.make_mesh(
        (1, 1), ("dp", "tp"), axis_types=(jax.sharding.AxisType.Auto,) * 2
    )
    w1 = m.MeshComm.from_mesh(mesh1)
    d1 = tfm.make_global_decode(mesh1, w1.sub("dp"), w1.sub("tp"), CFG, MAX)
    np.testing.assert_array_equal(
        np.asarray(d4(params, prompt)), np.asarray(d1(params, prompt))
    )
