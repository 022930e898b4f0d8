"""Decoder-transformer train step over a 3-D ``(dp, tp, sp)`` mesh.

The composition showcase: every parallelism family the library ships,
in one differentiable training step —

* **TP** (Megatron f/g pair over ``tp``): qkv / mlp-up projections
  column-sharded, output / mlp-down row-sharded. The "g" collective is
  :func:`~mpi4jax_tpu.ops.allreduce.allreduce` (forward sum, identity
  backward); the "f" collective falls out of the reference's
  double-transpose convention for free — binding the allreduce
  primitive with ``transpose=True`` lowers to an identity whose
  *transpose* is a real allreduce (reference:
  mpi4jax/_src/collective_ops/allreduce.py:77-79, :182-194), i.e.
  exactly "identity forward, all-reduce backward".
* **SP/CP** (ring attention over ``sp``): the sequence axis is sharded;
  KV blocks rotate via ``sendrecv``/``ppermute`` with causal masking,
  gradients ride the ring backward (sendrecv transpose contract).
  Grouped-query attention supported (``kv_heads < heads``).
* **DP** over ``dp``: per-device micro-batches; gradients synced with
  typed ``psum`` so the updated parameters stay replicated.

Oracle-tested against an unsharded single-device implementation
(tests/parallel/test_transformer.py): forward loss and one SGD step
match to collective-roundoff.
"""

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from mpi4jax_tpu.ops import reductions
from mpi4jax_tpu.ops._core import create_token
from mpi4jax_tpu.ops.allreduce import allreduce, allreduce_p
from mpi4jax_tpu.parallel.longseq import local_attention, ring_attention

__all__ = [
    "TransformerConfig",
    "BlockParams",
    "TransformerParams",
    "init_params",
    "make_global_train_step",
    "make_global_decode",
    "reference_loss",
    "reference_greedy_decode",
    "reference_sample_decode",
    "CHECKPOINT_NAMES",
]

# checkpoint_name tags attached inside each layer (see _forward_sharded);
# remat may be given as a tuple drawn from these to pick a custom
# save-list between full remat (save nothing) and "names" (the default
# q/k/attn-out/mlp-out sweet spot)
CHECKPOINT_NAMES = ("qkv", "v_proj", "attn_out", "mlp_out")


class TransformerConfig(NamedTuple):
    vocab: int = 64
    d_model: int = 32
    layers: int = 2
    heads: int = 4
    kv_heads: int = 2  # < heads = grouped-query attention
    head_dim: int = 8
    d_ff: int = 64
    eps: float = 1e-6
    # single-device attention kernel ("auto" / "flash" / "xla", see
    # parallel.longseq.local_attention); only reaches the sp=1 shortcut
    # and the ulysses full-sequence call — the multi-rank ring path has
    # its own blockwise schedule
    attn_impl: str = "auto"
    # >0: compute the loss in token chunks of this size (must divide the
    # local sequence length) — the head matmul and logsumexp run per
    # chunk under jax.checkpoint, so the full [B, S, V] logits tensor is
    # NEVER materialised (2.1 GB bf16 at the 940M/seq-2048/b16 MFU
    # config, 4.3 GB at b32 — the allocation that OOMs the larger-batch
    # and heavier-save-list configs).  The backward recomputes each
    # chunk's logits: one extra head matmul of FLOPs in exchange for
    # the logits' round-trips.  0 = off (single streaming-CE pass).
    ce_chunk: int = 0


class BlockParams(NamedTuple):
    ln1: jax.Array  # (L, d)              replicated
    wq: jax.Array   # (L, d, Hq*dh)       column-sharded over tp
    wk: jax.Array   # (L, d, Hkv*dh)      column-sharded over tp
    wv: jax.Array   # (L, d, Hkv*dh)      column-sharded over tp
    wo: jax.Array   # (L, Hq*dh, d)       row-sharded over tp
    ln2: jax.Array  # (L, d)              replicated
    w1: jax.Array   # (L, d, F)           column-sharded over tp
    w2: jax.Array   # (L, F, d)           row-sharded over tp


class TransformerParams(NamedTuple):
    embed: jax.Array  # (V, d)  replicated
    blocks: BlockParams
    ln_f: jax.Array   # (d,)    replicated
    head: jax.Array   # (d, V)  replicated


def init_params(key, cfg, dtype=jnp.float32):
    """Global parameter arrays (shard with :func:`param_specs`)."""
    c = cfg
    ks = jax.random.split(key, 8)

    def norm(k, shape, fan_in):
        return jax.random.normal(k, shape, dtype) * (1.0 / math.sqrt(fan_in))

    L, d, dh = c.layers, c.d_model, c.head_dim
    blocks = BlockParams(
        ln1=jnp.ones((L, d), dtype),
        wq=norm(ks[0], (L, d, c.heads * dh), d),
        wk=norm(ks[1], (L, d, c.kv_heads * dh), d),
        wv=norm(ks[2], (L, d, c.kv_heads * dh), d),
        wo=norm(ks[3], (L, c.heads * dh, d), c.heads * dh),
        ln2=jnp.ones((L, d), dtype),
        w1=norm(ks[4], (L, d, c.d_ff), d),
        w2=norm(ks[5], (L, c.d_ff, d), c.d_ff),
    )
    return TransformerParams(
        embed=norm(ks[6], (c.vocab, d), d),
        blocks=blocks,
        ln_f=jnp.ones((d,), dtype),
        head=norm(ks[7], (d, c.vocab), d),
    )


def param_specs(tp_ax):
    """PartitionSpecs: TP shards live on the projections' head/ff dims."""
    blocks = BlockParams(
        ln1=jax.P(None, None),
        wq=jax.P(None, None, tp_ax),
        wk=jax.P(None, None, tp_ax),
        wv=jax.P(None, None, tp_ax),
        wo=jax.P(None, tp_ax, None),
        ln2=jax.P(None, None),
        w1=jax.P(None, None, tp_ax),
        w2=jax.P(None, tp_ax, None),
    )
    return TransformerParams(
        embed=jax.P(None, None),
        blocks=blocks,
        ln_f=jax.P(None),
        head=jax.P(None, None),
    )


def _check_tp_divisibility(cfg, tp):
    for name, heads in (("heads", cfg.heads), ("kv_heads", cfg.kv_heads)):
        if heads % tp:
            raise ValueError(
                f"cfg.{name}={heads} must be divisible by the tensor-"
                f"parallel size {tp} (each tp rank owns "
                f"{name}/tp heads; for MQA-style configs with fewer kv "
                f"heads than tp ranks, replicate kv heads to tp first)"
            )


def _rmsnorm(x, g, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _f_collective(x, comm, token):
    """Megatron "f": identity forward, all-reduce backward over tp.

    Implemented as the allreduce primitive bound with ``transpose=True``
    (lowers to identity; its AD transpose is the real allreduce — the
    reference's double-transpose contract)."""
    res, stamp = allreduce_p.bind(
        x, token.stamp, op=reductions.SUM, comm=comm, transpose=True
    )
    return res, token.with_stamp(stamp)


def _dense_mlp(h2, bp, cfg, comm_tp, comm_sp, token):
    """Megatron MLP: column-sharded up, row-sharded down, g-allreduce."""
    h2, token = _f_collective(h2, comm_tp, token)
    m_part = jax.nn.gelu(h2 @ bp.w1) @ bp.w2
    return allreduce(m_part, reductions.SUM, comm=comm_tp, token=token)


def _forward_sharded(
    params, tokens, cfg, comm_tp, comm_sp, mesh_axes, mlp=None,
    sequence="ring", remat=False, return_hidden=False,
):
    """Per-device forward; call inside shard_map over (dp, tp, sp).

    ``tokens``: local [B_local, S_local] int32.  Activations are
    replicated across tp, sequence-sharded across sp.  ``mesh_axes`` is
    the full axis set of the enclosing shard_map: activations are
    typed varying over all of it (collective outputs vary on their own
    axis, so the layer-scan carry must start that way too).

    ``mlp(h2, bp, cfg, comm_tp, comm_sp, token) -> (out, token)`` is
    the MLP sublayer (post-ln2); defaults to the dense Megatron pair —
    models/moe_transformer.py substitutes the expert-parallel MoE here.
    An mlp may instead return ``(out, token, aux)`` with ``aux`` a
    scalar auxiliary-loss contribution (e.g. MoE load-balancing / router
    z-loss); the per-layer contributions are summed and returned beside
    the logits.

    ``sequence`` picks the context-parallel attention scheme over sp:
    ``"ring"`` (KV blocks rotate, sendrecv transpose carries the
    gradient) or ``"ulysses"`` (two all-to-alls reshard heads↔sequence
    around full-sequence local attention).  Both compute exact
    attention — the same oracle covers either.
    """
    from mpi4jax_tpu.ops._core import promote_vma
    from mpi4jax_tpu.parallel.longseq import ulysses_attention

    mlp = mlp or _dense_mlp
    tp = comm_tp.size
    dh = cfg.head_dim
    hq_l, hk_l = cfg.heads // tp, cfg.kv_heads // tp
    b, s = tokens.shape
    if sequence not in ("ring", "ulysses"):
        raise ValueError(
            f"sequence must be 'ring' or 'ulysses', got {sequence!r}"
        )
    seq_attn = ring_attention if sequence == "ring" else ulysses_attention

    x = promote_vma(params.embed[tokens], mesh_axes)  # (B, S_local, d)
    aux0 = promote_vma(jnp.zeros((), jnp.float32), mesh_axes)

    from jax.ad_checkpoint import checkpoint_name

    def layer(carry, bp):
        x, aux = carry
        token = create_token()
        h = _rmsnorm(x, bp.ln1, cfg.eps)
        h, token = _f_collective(h, comm_tp, token)
        # checkpoint_name tags are inert except under remat="names",
        # whose policy saves exactly these tensors (see below)
        q = checkpoint_name((h @ bp.wq).reshape(b, s, hq_l, dh), "qkv")
        k = checkpoint_name((h @ bp.wk).reshape(b, s, hk_l, dh), "qkv")
        # v is tagged apart: the names policy recomputes it (one cheap
        # [t,d]x[d,d] matmul off the already-recomputed h) — the 128 MB
        # per layer it would pin is what lets batch 16 fit in HBM
        v = checkpoint_name((h @ bp.wv).reshape(b, s, hk_l, dh), "v_proj")
        attn, token = seq_attn(
            q, k, v, comm_sp, causal=True, token=token,
            impl=getattr(cfg, "attn_impl", "auto"),
        )
        a_part = attn.reshape(b, s, hq_l * dh) @ bp.wo
        a, token = allreduce(a_part, reductions.SUM, comm=comm_tp, token=token)
        x = x + checkpoint_name(a, "attn_out")

        h2 = _rmsnorm(x, bp.ln2, cfg.eps)
        res = mlp(h2, bp, cfg, comm_tp, comm_sp, token)
        m = checkpoint_name(res[0], "mlp_out")
        if len(res) > 2:  # (out, token, aux) — MoE auxiliary losses
            aux = aux + res[2]
        return (x + m, aux), None

    if isinstance(remat, (tuple, list)) and not remat:
        # () is falsy — it would silently skip the remat block below
        # and benchmark the non-remat path instead of erroring
        raise ValueError(
            "empty remat save-list; use remat=True for full remat or a "
            f"non-empty subset of {CHECKPOINT_NAMES}"
        )
    if remat:
        # rematerialise each layer in the backward pass: activation
        # memory drops from O(layers) to O(1) layers (plus the scan
        # carry) at ~1/3 extra FLOPs — the standard long-context lever
        # on HBM-bound chips.  The collectives re-execute under remat;
        # token ordering is per-layer-instance so replay is safe.
        # remat="dots" keeps every batched-matmul output (qkv/o/mlp
        # projections) and recomputes only the cheap rest — in practice
        # the attention internals, whose [T, T] score tensors are the
        # memory hog — recovering most of full-remat's memory saving at
        # a fraction of its ~1/3 FLOP overhead.
        # remat="names" is the builders' choice for the `large` preset
        # (not measured on the current chip): keep FOUR
        # [tokens, d]-sized tensors per layer (q, k, attn-out, mlp-out
        # — v is tagged "v_proj", deliberately outside the save list)
        # and recompute only the cheap glue (rmsnorms, residual adds,
        # gelu) plus v, the single wide w1 matmul, and the flash
        # forward — ~0.9N recompute FLOPs vs full remat's 2N, at ~1/13
        # of the activation memory the dots policy would pin (it saves
        # the [tokens, d_ff] w1 outputs; this policy's whole point is
        # NOT saving those).
        # An explicit tuple/list of tag names selects a CUSTOM save
        # list — the memory/recompute dial exposed to sweeps (e.g. at
        # seq 32k, where the standard names list OOMs, a lighter
        # ("attn_out", "mlp_out") list can still fit).
        if remat == "dots":
            layer = jax.checkpoint(
                layer,
                policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            )
        elif remat == "names":
            layer = jax.checkpoint(
                layer,
                policy=jax.checkpoint_policies.save_only_these_names(
                    "qkv", "attn_out", "mlp_out"
                ),
            )
        elif isinstance(remat, (tuple, list)):
            unknown = set(remat) - set(CHECKPOINT_NAMES)
            if unknown:
                raise ValueError(
                    f"unknown checkpoint tag(s) {sorted(unknown)}; the "
                    f"layer tags are {CHECKPOINT_NAMES}"
                )
            layer = jax.checkpoint(
                layer,
                policy=jax.checkpoint_policies.save_only_these_names(
                    *remat
                ),
            )
        elif remat is True:
            layer = jax.checkpoint(layer)
        else:
            raise ValueError(
                f"remat must be False, True, 'dots', 'names' or a "
                f"tuple of tag names, got {remat!r}"
            )
    (x, aux), _ = lax.scan(layer, (x, aux0), params.blocks)
    x = _rmsnorm(x, params.ln_f, cfg.eps)
    if return_hidden:
        # chunked-CE path: the caller applies the head per token chunk
        return x, aux  # (B, S_local, d) final hidden, aux-loss sum
    return x @ params.head, aux  # (B, S_local, V) logits, aux-loss sum


def _ce(logits, targets):
    """Streaming cross-entropy: ``mean(lse - logits[target])``.

    Mathematically identical to log_softmax + gather (the picked
    log-probability IS ``logits[target] - logsumexp``), but never
    materialises a float32 ``[B, S, V]`` tensor: the f32 conversion
    fuses into the logsumexp reductions, so XLA reads the bf16 logits
    and writes only ``[B, S]`` statistics.  The log_softmax form moves
    ~12 GB/step of f32 through HBM on the `large` preset's
    ``[16, 2048, 32768]`` logits (a count from shapes)."""
    lse = jax.scipy.special.logsumexp(logits.astype(jnp.float32), axis=-1)
    picked = jnp.take_along_axis(logits, targets[..., None], axis=-1)
    return (lse - picked[..., 0].astype(jnp.float32)).mean()


def _ce_chunked(x, head, targets, chunk, mesh_axes=()):
    """Chunked cross-entropy: the head matmul + streaming CE run per
    token chunk inside a ``lax.scan`` whose body is ``jax.checkpoint``ed
    — the full ``[B, S, V]`` logits tensor is never materialised (only
    one ``[B, chunk, V]`` block lives at a time), and the backward
    recomputes each chunk's logits instead of loading stored ones.
    Same math as :func:`_ce` (per-chunk f32 sums, one final divide), so
    results agree to f32 reduction-order roundoff.

    ``x``: [B, S_local, d] final hidden; ``head``: [d, V]."""
    b, s, d = x.shape
    if s % chunk:
        raise ValueError(
            f"ce_chunk={chunk} must divide the local sequence length "
            f"{s} (global seq / sp size)"
        )
    n = s // chunk
    xs = jnp.moveaxis(x.reshape(b, n, chunk, d), 1, 0)  # [n, B, c, d]
    ts = jnp.moveaxis(targets.reshape(b, n, chunk), 1, 0)  # [n, B, c]

    def blk(acc, inp):
        xb, tb = inp
        logits = xb @ head  # [B, c, V] — freed when the chunk ends
        lse = jax.scipy.special.logsumexp(
            logits.astype(jnp.float32), axis=-1
        )
        picked = jnp.take_along_axis(logits, tb[..., None], axis=-1)
        return acc + (lse - picked[..., 0].astype(jnp.float32)).sum(), None

    from mpi4jax_tpu.ops._core import promote_vma

    # the scan carry must match the body output's varying-axes type
    # under shard_map (same promotion as the layer scan's carry)
    acc0 = promote_vma(jnp.float32(0.0), mesh_axes)
    total, _ = lax.scan(jax.checkpoint(blk), acc0, (xs, ts))
    return total / (b * s)


def make_global_train_step(
    mesh, comm_dp, comm_tp, comm_sp, cfg, lr=1e-1, *, mlp=None, specs=None,
    sequence="ring", remat=False, donate=False,
):
    """Jitted global train step over a ``(dp, tp, sp)`` mesh.

    ``batch = (tokens, targets)``, both global ``[B, S]`` int32 sharded
    ``(dp, sp)`` (targets are the caller's shifted next tokens — the
    shift crosses sp shard boundaries, so it is done globally).
    Returns ``(new_params, loss)``.

    ``mlp`` / ``specs`` substitute the MLP sublayer and the parameter
    PartitionSpecs (see :func:`_forward_sharded`; used by the MoE
    variant, models/moe_transformer.py).  ``sequence`` picks the
    context-parallel attention scheme ("ring" or "ulysses" — the
    latter needs the per-tp-rank head counts divisible by the sp
    size).  ``remat=True`` wraps each layer in ``jax.checkpoint`` —
    activation memory O(1) layers instead of O(layers), ~1/3 extra
    FLOPs; gradients are unchanged (same math, recomputed).
    ``remat="dots"`` / ``remat="names"`` select partial policies (see
    ``_forward_sharded``); ``donate=True`` donates the params argument
    to the update (training-loop idiom).
    """
    dp_ax = comm_dp.axes[0]
    tp_ax = comm_tp.axes[0]
    sp_ax = comm_sp.axes[0]
    if sequence not in ("ring", "ulysses"):
        raise ValueError(
            f"sequence must be 'ring' or 'ulysses', got {sequence!r}"
        )
    n_data = float(comm_dp.size * comm_sp.size)
    tp = float(comm_tp.size)
    _check_tp_divisibility(cfg, comm_tp.size)
    if sequence == "ulysses" and comm_sp.size > 1:
        # checked after tp-divisibility so invalid-everywhere configs
        # get the general diagnosis, not ulysses-specific advice
        for name, heads in (("heads", cfg.heads), ("kv_heads", cfg.kv_heads)):
            if (heads // comm_tp.size) % comm_sp.size:
                raise ValueError(
                    f"sequence='ulysses' needs cfg.{name}/tp divisible by "
                    f"the sp size: {heads}//{comm_tp.size} per tp rank, "
                    f"sp={comm_sp.size} (for GQA, repeat kv heads or use "
                    f"sequence='ring')"
                )

    specs = param_specs(tp_ax) if specs is None else specs
    batch_specs = (jax.P(dp_ax, sp_ax), jax.P(dp_ax, sp_ax))

    def sync_grad(g, spec):
        # shard_map's vma-aware AD has ALREADY psum'ed each param's
        # cotangent over every axis the param is invariant on (the
        # transpose of replication is a sum) — adding explicit psums
        # here would double-count.  Only scaling remains:
        if tp_ax in tuple(spec):
            # tp-sharded: g = sum over (dp, sp) of the per-rank local
            # grads; the global loss is the mean of the local losses
            return g / n_data
        # replicated: g additionally summed over tp, but the
        # f-collectives made each rank's grad the FULL tp-sum already,
        # so the automatic tp-sum overcounts by tp.  (sp-sharded MoE
        # expert params land here too: their cross-device contributions
        # arrive through the alltoall transpose — same scaling class.)
        return g / (n_data * tp)

    def local_step(params, batch):
        tokens, targets = batch

        ce_chunk = getattr(cfg, "ce_chunk", 0)

        def loss_fn(p):
            out, aux = _forward_sharded(
                p, tokens, cfg, comm_tp, comm_sp, (dp_ax, tp_ax, sp_ax),
                mlp=mlp, sequence=sequence, remat=remat,
                return_hidden=bool(ce_chunk),
            )
            if ce_chunk:
                return _ce_chunked(
                    out, p.head, targets, ce_chunk,
                    mesh_axes=(dp_ax, tp_ax, sp_ax),
                ) + aux
            return _ce(out, targets) + aux

        loss, grads = jax.value_and_grad(loss_fn)(params)
        grads = jax.tree.map(sync_grad, grads, specs)
        params = jax.tree.map(lambda p, g: p - lr * g, params, grads)
        loss = lax.psum(loss, (dp_ax, tp_ax, sp_ax)) / (n_data * tp)
        return params, loss[None]

    return jax.jit(
        jax.shard_map(
            local_step,
            mesh=mesh,
            in_specs=(specs, batch_specs),
            out_specs=(specs, jax.P((dp_ax, tp_ax, sp_ax))),
        ),
        # donate=True releases the old params' buffers to the update
        # (the training-loop idiom `params, loss = step(params, ...)`);
        # callers that reuse params after the call keep the default
        donate_argnums=(0,) if donate else (),
    )


def _attn_residual(x, bp, cfg):
    """Unsharded attention sublayer: ln1 → QKV → causal attention → wo,
    plus the residual.  THE single copy of the dense layer's attention
    math — the oracles and the pipeline stage all call it."""
    b, s, _ = x.shape
    h = _rmsnorm(x, bp.ln1, cfg.eps)
    q = (h @ bp.wq).reshape(b, s, cfg.heads, cfg.head_dim)
    k = (h @ bp.wk).reshape(b, s, cfg.kv_heads, cfg.head_dim)
    v = (h @ bp.wv).reshape(b, s, cfg.kv_heads, cfg.head_dim)
    attn = local_attention(q, k, v, causal=True, impl="xla")
    return x + attn.reshape(b, s, -1) @ bp.wo


def dense_layer(x, bp, cfg):
    """One full unsharded decoder layer (attention + dense MLP)."""
    x = _attn_residual(x, bp, cfg)
    h2 = _rmsnorm(x, bp.ln2, cfg.eps)
    return x + jax.nn.gelu(h2 @ bp.w1) @ bp.w2


def reference_loss(params, tokens, targets, cfg):
    """Unsharded oracle: identical math on one device."""
    x = params.embed[tokens]

    def layer(x, bp):
        return dense_layer(x, bp, cfg), None

    x, _ = lax.scan(layer, x, params.blocks)
    x = _rmsnorm(x, params.ln_f, cfg.eps)
    return _ce(x @ params.head, targets)


# --------------------------- inference -----------------------------


def _choose_token(logits, pos, key, row_ids, sampler, temperature, top_k):
    """Next-token choice from ``[B, V]`` logits — THE single copy shared
    by the sharded decoder and the unsharded oracle (like
    :func:`_attn_residual` for the layer math).

    Sampling is shard-invariant by construction: each row's draw uses
    ``fold_in(fold_in(key, pos), global_row_id)``, so the randomness
    for a given (sequence, position) is identical however the batch is
    sharded over dp — the sharded decoder matches the unsharded oracle
    bitwise given the same key."""
    if sampler == "greedy":
        return jnp.argmax(logits, axis=-1)
    logits = logits.astype(jnp.float32) / temperature
    if top_k is not None:
        # keep the k highest logits per row; ties at the threshold stay
        # eligible (same rule in the oracle, so they cancel)
        thresh = jax.lax.top_k(logits, int(top_k))[0][..., -1:]
        logits = jnp.where(logits >= thresh, logits, -jnp.inf)
    step_key = jax.random.fold_in(key, pos)
    row_keys = jax.vmap(lambda r: jax.random.fold_in(step_key, r))(row_ids)
    return jax.vmap(jax.random.categorical)(row_keys, logits)


def _check_sampler(sampler, temperature, top_k, vocab):
    if sampler not in ("greedy", "categorical"):
        raise ValueError(
            f"sampler must be 'greedy' or 'categorical', got {sampler!r}"
        )
    if sampler == "greedy":
        # greedy ignores both knobs — setting one is a forgotten
        # sampler="categorical", not a request for deterministic output
        if temperature != 1.0 or top_k is not None:
            raise ValueError(
                "temperature/top_k only apply to sampler='categorical' "
                f"(got sampler='greedy' with temperature={temperature}, "
                f"top_k={top_k})"
            )
        return
    if not temperature > 0:
        raise ValueError(f"temperature must be > 0, got {temperature}")
    if top_k is not None and (
        int(top_k) != top_k or not 0 < int(top_k) <= vocab
    ):
        raise ValueError(
            f"top_k must be an integer in (0, vocab={vocab}], got {top_k!r}"
        )


def _decode_step_sharded(params, cache, last_tok, pos, cfg, comm_tp, hq_l, hk_l):
    """One decode step on the local tp shard: embed the last token,
    run the cached attention + MLP, and return the position's logits —
    the caller picks the next token (greedy or sampled).

    ``cache``: (layers, 2, B, S_max, Hkv_local, dh) — K/V per layer.
    ``last_tok``: (B,) int32; ``pos``: scalar int32 write position.
    Returns (cache, logits).
    """
    dh = cfg.head_dim
    b = last_tok.shape[0]
    x = params.embed[last_tok][:, None, :]  # (B, 1, d)
    token = create_token()

    def layer(carry, inputs):
        x, token = carry
        bp, kv = inputs
        h = _rmsnorm(x, bp.ln1, cfg.eps)
        h, token = _f_collective(h, comm_tp, token)
        q = (h @ bp.wq).reshape(b, 1, hq_l, dh)
        k_new = (h @ bp.wk).reshape(b, 1, hk_l, dh)
        v_new = (h @ bp.wv).reshape(b, 1, hk_l, dh)
        k_cache = lax.dynamic_update_slice(kv[0], k_new, (0, pos, 0, 0))
        v_cache = lax.dynamic_update_slice(kv[1], v_new, (0, pos, 0, 0))
        # attend over positions <= pos (masked full-cache attention;
        # q_offset=pos makes the causal mask pass exactly those)
        attn = local_attention(
            q, k_cache, v_cache, causal=True, q_offset=pos, impl="xla"
        )
        a_part = attn.reshape(b, 1, hq_l * dh) @ bp.wo
        a, token = allreduce(a_part, reductions.SUM, comm=comm_tp, token=token)
        x = x + a
        h2 = _rmsnorm(x, bp.ln2, cfg.eps)
        h2, token = _f_collective(h2, comm_tp, token)
        m_part = jax.nn.gelu(h2 @ bp.w1) @ bp.w2
        m, token = allreduce(m_part, reductions.SUM, comm=comm_tp, token=token)
        return (x + m, token), jnp.stack([k_cache, v_cache])

    (x, _token), cache = lax.scan(layer, (x, token), (params.blocks, cache))
    x = _rmsnorm(x, params.ln_f, cfg.eps)
    logits = (x @ params.head)[:, 0, :]  # (B, V)
    return cache, logits


def _prefill_sharded(
    params, prompt, cfg, comm_tp, hq_l, hk_l, max_len, impl="xla",
    logits_pos=None,
):
    """Batched prefill on the local tp shard: one causal forward pass
    over the whole prompt, writing every prompt position's K/V into the
    (max_len-budget) cache and returning the greedy next token after
    the last prompt position.

    Identical math to running :func:`_decode_step_sharded` position by
    position — the attention is causal and the projections are
    per-position — but the matmuls are [B, P, ·] instead of P
    sequential [B, 1, ·] calls, so the prompt costs one MXU-shaped
    forward instead of P dispatches.  Returns ``(cache, logits)`` with
    the LAST prompt position's ``[B, V]`` logits — the caller picks
    the next token (greedy or sampled).

    ``logits_pos`` (traced scalar) returns the logits of THAT position
    instead of the last one: the serving engine right-pads prompts to
    a compile-size bucket (one executable per bucket, not per length)
    and reads the logits at the true last prompt position — the padded
    tail positions are causally invisible to it, and their garbage KV
    is overwritten in order by the decode steps that follow
    (mpi4jax_tpu/serving/engine.py).
    """
    dh = cfg.head_dim
    b, p_len = prompt.shape
    x = params.embed[prompt]  # (B, P, d)
    token = create_token()
    pad = max_len - p_len

    def layer(carry, bp):
        x, token = carry
        h = _rmsnorm(x, bp.ln1, cfg.eps)
        h, token = _f_collective(h, comm_tp, token)
        q = (h @ bp.wq).reshape(b, p_len, hq_l, dh)
        k = (h @ bp.wk).reshape(b, p_len, hk_l, dh)
        v = (h @ bp.wv).reshape(b, p_len, hk_l, dh)
        attn = local_attention(q, k, v, causal=True, impl=impl)
        a_part = attn.reshape(b, p_len, hq_l * dh) @ bp.wo
        a, token = allreduce(a_part, reductions.SUM, comm=comm_tp, token=token)
        x = x + a
        h2 = _rmsnorm(x, bp.ln2, cfg.eps)
        h2, token = _f_collective(h2, comm_tp, token)
        m_part = jax.nn.gelu(h2 @ bp.w1) @ bp.w2
        m, token = allreduce(m_part, reductions.SUM, comm=comm_tp, token=token)
        kv = jnp.stack([
            jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0))),
            jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0))),
        ])
        return (x + m, token), kv

    (x, _token), cache = lax.scan(layer, (x, token), params.blocks)
    x = _rmsnorm(x, params.ln_f, cfg.eps)
    if logits_pos is None:
        last = x[:, -1, :]  # (B, d): last prompt position
    else:
        last = lax.dynamic_index_in_dim(
            x, logits_pos, axis=1, keepdims=False
        )
    logits = last @ params.head  # (B, V)
    return cache, logits


def make_global_decode(
    mesh, comm_dp, comm_tp, cfg, max_len, *, prefill="batched",
    kv_bucket=None, prefill_impl="xla", sampler="greedy",
    temperature=1.0, top_k=None,
):
    """Jitted greedy autoregressive decoder over a ``(dp, tp)`` mesh.

    ``decode(params, prompt)``: ``prompt`` is global ``[B, P]`` int32
    sharded over dp (tp-replicated).  ``prefill="batched"`` (default)
    processes the whole prompt in ONE causal forward pass that fills
    the KV cache — the prompt costs a single MXU-shaped forward instead
    of P sequential steps; ``prefill="stepwise"`` keeps the
    position-at-a-time path (same math, the original formulation — the
    equivalence is pinned by tests/parallel/test_decode.py).  Then
    generates ``max_len - P`` greedy tokens.  Returns global
    ``[B, max_len]`` int32 — prompt followed by the generated
    continuation.  Matches :func:`reference_greedy_decode` exactly
    (same math; tp roundoff only).

    ``sampler="categorical"`` draws each continuation token from the
    (temperature-scaled, optionally top-k-truncated) softmax instead of
    the argmax; the returned callable then takes a third argument,
    ``decode(params, prompt, key)`` (a ``jax.random.PRNGKey``).  The
    draw for a given (row, position) folds the GLOBAL row id and the
    position into the key, so the sharded sampler matches
    :func:`reference_sample_decode` bitwise under any dp sharding.

    ``prefill_impl`` picks the batched prefill's attention kernel:
    ``"xla"`` (default — dense scores; the right choice for short
    prompts, where the flash kernel's block pipeline costs more than it
    saves) or ``"flash"`` (the Pallas blockwise kernel, ops/flash.py)
    for LONG prompts, where the dense [P, P] score tensor dominates the
    prefill — the long-context inference analog of the training-side
    crossover.  Token-identical either way at full matmul precision
    (chip_smoke.py's transformer.decode phase checks it on the chip).

    ``kv_bucket=N`` runs the generate loop in KV-length buckets: the
    scan carry is a cache VIEW whose static length grows by N per
    segment (a python loop of scans inside the same jit), so each step
    reads/attends only ``ceil((pos+1)/N)·N`` cache positions instead of
    the full ``max_len`` budget.  Decode is KV-bandwidth-bound at large
    batch, and with the un-bucketed loop every step pays the PADDED
    budget read (how much that costs: not measured on the current
    chip).
    Token-exact vs the un-bucketed loop (garbage positions beyond
    ``pos`` are causally masked either way).
    """
    dp_ax, tp_ax = comm_dp.axes[0], comm_tp.axes[0]
    tp = comm_tp.size
    _check_tp_divisibility(cfg, tp)
    hq_l, hk_l = cfg.heads // tp, cfg.kv_heads // tp
    specs = param_specs(tp_ax)
    if prefill not in ("batched", "stepwise"):
        raise ValueError(
            f"prefill must be 'batched' or 'stepwise', got {prefill!r}"
        )
    if prefill_impl not in ("xla", "flash"):
        raise ValueError(
            f"prefill_impl must be 'xla' or 'flash', got {prefill_impl!r}"
        )
    _check_sampler(sampler, temperature, top_k, cfg.vocab)
    if kv_bucket is not None and (
        int(kv_bucket) != kv_bucket or not 0 < int(kv_bucket) <= max_len
    ):
        raise ValueError(
            f"kv_bucket must be an integer in (0, max_len={max_len}], "
            f"got {kv_bucket!r}"
        )

    def local_decode(params, prompt, key):
        from mpi4jax_tpu.ops._core import promote_vma

        b, p_len = prompt.shape
        if p_len > max_len:
            raise ValueError(
                f"prompt length {p_len} exceeds max_len={max_len} "
                f"(the decoder's static sequence budget)"
            )
        prompt = promote_vma(prompt, (dp_ax, tp_ax))
        key = promote_vma(key, (dp_ax, tp_ax))
        # global row ids: the sampling key folds these in, so draws are
        # identical under any dp sharding (see _choose_token)
        row_ids = lax.axis_index(dp_ax) * b + jnp.arange(b)
        out = promote_vma(
            jnp.zeros((b, max_len), prompt.dtype), (dp_ax, tp_ax)
        )
        out = lax.dynamic_update_slice(out, prompt, (0, 0))

        def choose(logits, pos):
            return _choose_token(
                logits, pos, key, row_ids, sampler, temperature, top_k
            ).astype(prompt.dtype)

        if prefill == "batched" and p_len > 1:
            cache, pre_logits = _prefill_sharded(
                params, prompt, cfg, comm_tp, hq_l, hk_l, max_len,
                impl=prefill_impl,
            )
            if p_len < max_len:
                # the token at position p_len is chosen from position
                # p_len - 1's logits
                nxt = choose(pre_logits, p_len - 1)
                out = lax.dynamic_update_slice(
                    out, nxt[:, None], (0, p_len)
                )
            start = p_len  # positions start..max_len-2 remain
        else:
            cache = promote_vma(
                jnp.zeros(
                    (cfg.layers, 2, b, max_len, hk_l, cfg.head_dim),
                    params.embed.dtype,
                ),
                (dp_ax, tp_ax),
            )
            start = 0

        def step(carry, pos):
            # pos runs start..max_len-2, so pos+1 is always a valid slot
            cache, out = carry
            last = lax.dynamic_index_in_dim(
                out, pos, axis=1, keepdims=False
            )
            cache, logits = _decode_step_sharded(
                params, cache, last, pos, cfg, comm_tp, hq_l, hk_l
            )
            # inside the prompt, keep the given token; past it, append
            # the chosen (greedy or sampled) token
            nxt = choose(logits, pos)
            cur = lax.dynamic_index_in_dim(out, pos + 1, axis=1, keepdims=False)
            write = jnp.where(pos + 1 < p_len, cur, nxt)
            out = lax.dynamic_update_slice(out, write[:, None], (0, pos + 1))
            return (cache, out), None

        if kv_bucket is None:
            (cache, out), _ = lax.scan(
                step, (cache, out), jnp.arange(start, max_len - 1)
            )
        else:
            # bucketed KV growth: segment s scans positions
            # [prev, min(end_s, max_len-1)) with a cache view of STATIC
            # length end_s (pos < end_s throughout, so the causal mask
            # and the pos-slot write both stay in range); between
            # segments the view is zero-padded to the next bucket
            # boundary.  The python loop is over static bounds — one
            # executable, ~max_len/kv_bucket scan instances.
            bk = int(kv_bucket)
            ends = list(range((start // bk + 1) * bk, max_len, bk))
            ends.append(max_len)
            view = cache[:, :, :, : ends[0]]
            prev = start
            for i, end in enumerate(ends):
                if i:
                    view = jnp.pad(
                        view,
                        (
                            (0, 0), (0, 0), (0, 0),
                            (0, end - ends[i - 1]), (0, 0), (0, 0),
                        ),
                    )
                hi = min(end, max_len - 1)
                (view, out), _ = lax.scan(
                    step, (view, out), jnp.arange(prev, hi)
                )
                prev = hi
        # every tp rank computed the identical sequence, but collective
        # outputs are varying-typed; a masked psum re-establishes the
        # replicated typing the out_specs declare
        tp_rank = lax.axis_index(tp_ax)
        return lax.psum(
            jnp.where(tp_rank == 0, out, jnp.zeros((), out.dtype)), tp_ax
        )

    decode = jax.jit(
        jax.shard_map(
            local_decode,
            mesh=mesh,
            in_specs=(specs, jax.P(dp_ax, None), jax.P(None)),
            out_specs=jax.P(dp_ax, None),
        )
    )
    if sampler == "greedy":
        # greedy ignores the key: keep the two-argument call surface
        _zero_key = jax.random.PRNGKey(0)
        return lambda params, prompt: decode(params, prompt, _zero_key)

    def _raw_key(key):
        # accept both key styles: new-style typed keys (jax.random.key,
        # rank 0 — would trip the rank-1 P(None) spec) unwrap to their
        # uint32 data; legacy PRNGKey arrays pass through
        if jnp.issubdtype(jnp.asarray(key).dtype, jax.dtypes.prng_key):
            return jax.random.key_data(key)
        return key

    return lambda params, prompt, key: decode(params, prompt, _raw_key(key))


def reference_greedy_decode(params, prompt, cfg, max_len):
    """Unsharded oracle: full-sequence recompute per position."""
    b, p_len = prompt.shape
    if p_len > max_len:
        raise ValueError(
            f"prompt length {p_len} exceeds max_len={max_len}"
        )
    out = jnp.zeros((b, max_len), prompt.dtype)
    out = lax.dynamic_update_slice(out, prompt, (0, 0))

    def body(pos, out):
        x = params.embed[out]

        def layer(x, bp):
            return dense_layer(x, bp, cfg), None

        x, _ = lax.scan(layer, x, params.blocks)
        x = _rmsnorm(x, params.ln_f, cfg.eps)
        logits = x @ params.head  # (B, max_len, V)
        step_logits = lax.dynamic_index_in_dim(
            logits, pos, axis=1, keepdims=False
        )
        nxt = jnp.argmax(step_logits, axis=-1).astype(out.dtype)
        cur = lax.dynamic_index_in_dim(out, pos + 1, axis=1, keepdims=False)
        write = jnp.where(pos + 1 < p_len, cur, nxt)
        return lax.dynamic_update_slice(out, write[:, None], (0, pos + 1))

    return lax.fori_loop(0, max_len - 1, body, out)


def reference_sample_decode(
    params, prompt, cfg, max_len, key, *, temperature=1.0, top_k=None
):
    """Unsharded sampling oracle: full-sequence recompute per position,
    next tokens drawn through the SAME :func:`_choose_token` (per-row
    fold_in of position and global row id) as the sharded decoder — so
    ``make_global_decode(..., sampler="categorical")`` must match it
    bitwise given the same key, under any dp/tp sharding."""
    _check_sampler("categorical", temperature, top_k, cfg.vocab)
    b, p_len = prompt.shape
    if p_len > max_len:
        raise ValueError(
            f"prompt length {p_len} exceeds max_len={max_len}"
        )
    row_ids = jnp.arange(b)
    out = jnp.zeros((b, max_len), prompt.dtype)
    out = lax.dynamic_update_slice(out, prompt, (0, 0))

    def body(pos, out):
        x = params.embed[out]

        def layer(x, bp):
            return dense_layer(x, bp, cfg), None

        x, _ = lax.scan(layer, x, params.blocks)
        x = _rmsnorm(x, params.ln_f, cfg.eps)
        logits = x @ params.head  # (B, max_len, V)
        step_logits = lax.dynamic_index_in_dim(
            logits, pos, axis=1, keepdims=False
        )
        nxt = _choose_token(
            step_logits, pos, key, row_ids, "categorical", temperature,
            top_k,
        ).astype(out.dtype)
        cur = lax.dynamic_index_in_dim(out, pos + 1, axis=1, keepdims=False)
        write = jnp.where(pos + 1 < p_len, cur, nxt)
        return lax.dynamic_update_slice(out, write[:, None], (0, pos + 1))

    return lax.fori_loop(0, max_len - 1, body, out)


# -- t4j-lint entries: the DPxTPxSP train step's schedule on the
# smallest composed mesh (2,2,2) — TP Megatron f/g, SP ring attention,
# DP grad sync all in one extracted schedule.


def _lint_train_step():
    import jax as _jax

    from mpi4jax_tpu.parallel.comm import MeshComm

    mesh = _jax.make_mesh(
        (2, 2, 2), ("dp", "tp", "sp"),
        axis_types=(_jax.sharding.AxisType.Auto,) * 3,
    )
    world = MeshComm.from_mesh(mesh)
    cfg = TransformerConfig(
        vocab=32, d_model=16, layers=2, heads=4, kv_heads=2, head_dim=8,
        d_ff=32,
    )
    params = init_params(_jax.random.PRNGKey(0), cfg)
    tokens = _jax.random.randint(
        _jax.random.PRNGKey(1), (4, 16), 0, cfg.vocab
    )
    step = make_global_train_step(
        mesh, world.sub("dp"), world.sub("tp"), world.sub("sp"), cfg,
        lr=1e-1,
    )
    return step(params, (tokens, jnp.roll(tokens, -1, axis=1)))


T4J_LINT_ENTRIES = [("train_step_2x2x2", _lint_train_step)]
