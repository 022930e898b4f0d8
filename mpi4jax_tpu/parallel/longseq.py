"""Long-context sequence/context parallelism built on the comm primitives.

The reference ships the *building blocks* for every named sequence-
parallel scheme but no scheme itself (SURVEY §5.7): the ring step is
``sendrecv`` to rank±1 (mpi4jax/_src/collective_ops/sendrecv.py:366-385,
AD-reversible), and head↔sequence resharding is ``alltoall``
(alltoall.py:35-74).  This module assembles both into first-class,
differentiable context-parallel attention:

* :func:`ring_attention` — blockwise attention with an online softmax;
  KV blocks rotate around the communicator ring via :func:`sendrecv`,
  one ICI nearest-neighbour ``ppermute`` per step (Liu et al. 2023,
  "Ring Attention with Blockwise Transformers", arXiv:2310.01889 —
  public algorithm, implemented here from the paper's math).  Memory per
  device is O(T_local); the full sequence is never materialised.
* :func:`ulysses_attention` — DeepSpeed-Ulysses-style resharding
  (Jacobs et al. 2023, arXiv:2309.14509): all-to-all converts
  sequence-sharding into head-sharding, each device runs dense attention
  over the *full* sequence for its head subset, and a second all-to-all
  restores sequence sharding.  One pair of ICI all-to-alls total; heads
  must divide the ring size.

Both run per-device inside ``shard_map``, are reverse-mode
differentiable end to end (the ring's gradient traverses the ring in
the reverse direction via the sendrecv/ppermute transpose), and thread
the ordering token through every exchange.
"""

import math

import jax
import jax.numpy as jnp
from jax import lax

from mpi4jax_tpu.ops._core import Token, as_token, publishes_token
from mpi4jax_tpu.ops.collectives import alltoall
from mpi4jax_tpu.ops.p2p import sendrecv

__all__ = [
    "ring_attention",
    "ulysses_attention",
    "local_attention",
    "zigzag_indices",
    "zigzag_shard",
    "zigzag_unshard",
]

_NEG = -0.7 * float(jnp.finfo(jnp.float32).max)  # finite mask value


def _check_gqa(hq, hk, where):
    if hq % hk:
        raise ValueError(
            f"{where}: query heads must be a multiple of kv heads "
            f"(grouped-query attention), got Hq={hq}, Hkv={hk}"
        )


def _scores(q, k, scale):
    """q·kᵀ with GQA support: query head h attends kv head ``h // g``
    (g = Hq/Hkv).  Returns [B, Hq, Tq, Tk] f32 scores."""
    b, tq, hq, d = q.shape
    hk = k.shape[2]
    if hq == hk:
        s = jnp.einsum(
            "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
        )
    else:
        _check_gqa(hq, hk, "attention")
        g = hq // hk
        qg = q.reshape(b, tq, hk, g, d)
        s = jnp.einsum(
            "bqhgd,bkhd->bhgqk", qg, k, preferred_element_type=jnp.float32
        ).reshape(b, hq, tq, k.shape[1])
    return s * scale


def _weighted_values(w, v, hq):
    """w·v with GQA support; ``w``: [B, Hq, Tq, Tk], ``v``: [B, Tk, Hkv, D]."""
    hk = v.shape[2]
    if hq == hk:
        return jnp.einsum("bhqk,bkhd->bqhd", w, v)
    g = hq // hk
    b, _, tq, tk = w.shape
    wg = w.reshape(b, hk, g, tq, tk)
    return jnp.einsum("bhgqk,bkhd->bqhgd", wg, v).reshape(
        b, tq, hq, v.shape[-1]
    )


def local_attention(
    q, k, v, *, causal=False, scale=None, q_offset=0, k_offset=0, impl="auto"
):
    """Single-device attention: softmax(q k^T) v.

    ``q``: [B, Tq, Hq, D]; ``k``/``v``: [B, Tk, Hkv, D] with
    ``Hq % Hkv == 0`` — grouped-query attention (query head h attends
    kv head ``h // (Hq/Hkv)``; Hkv == Hq is plain MHA, Hkv == 1 is
    MQA).  ``*_offset`` are the global positions of the first
    row/column (for causal masking of sharded blocks).  Accumulates in
    float32.

    ``impl``: ``"xla"`` — dense (materialises the [Tq, Tk] scores, the
    oracle); ``"flash"`` — the Pallas VMEM-blocked kernel
    (ops/flash.py); ``"auto"`` — flash on TPU, dense elsewhere.
    """
    _check_gqa(q.shape[2], k.shape[2], "local_attention")
    if impl == "auto":
        impl = (
            "flash"
            if jax.default_backend() == "tpu" and q.shape[1] >= 128
            else "xla"
        )
    if impl == "flash":
        from mpi4jax_tpu.ops.flash import flash_attention

        return flash_attention(
            q, k, v, causal=causal, scale=scale,
            q_offset=q_offset, k_offset=k_offset,
        )
    d = q.shape[-1]
    scale = (1.0 / math.sqrt(d)) if scale is None else scale
    s = _scores(q, k, scale)
    if causal:
        qpos = q_offset + jnp.arange(q.shape[1])
        kpos = k_offset + jnp.arange(k.shape[1])
        mask = qpos[:, None] >= kpos[None, :]
        s = jnp.where(mask[None, None], s, _NEG)
    w = jax.nn.softmax(s, axis=-1)
    out = _weighted_values(w.astype(v.dtype), v, q.shape[2])
    return out.astype(q.dtype)


def zigzag_indices(p, t_global):
    """Global sequence positions each rank holds under the zigzag layout.

    Rank r holds chunks ``r`` and ``2p-1-r`` of the 2p equal chunks —
    the standard balanced-causal layout (Megatron context parallelism):
    every rank then owns one "early" and one "late" chunk, so causal
    masking wastes the same ~half of the score blocks on every rank
    instead of idling rank 0 while rank p-1 computes everything.

    Returns an int32 array of shape ``(p, t_global // p)``.
    """
    if t_global % (2 * p):
        raise ValueError(
            f"zigzag layout needs the global sequence divisible by "
            f"2*comm.size = {2 * p}, got T={t_global}"
        )
    c = t_global // (2 * p)
    import numpy as _np

    rows = [
        _np.concatenate(
            [
                _np.arange(r * c, (r + 1) * c),
                _np.arange((2 * p - 1 - r) * c, (2 * p - r) * c),
            ]
        )
        for r in range(p)
    ]
    return _np.stack(rows).astype(_np.int32)


def zigzag_shard(x, p, axis=1):
    """Reorder a globally-ordered array so a plain rank-major shard over
    ``axis`` gives each rank its zigzag chunks (apply before sharding)."""
    idx = zigzag_indices(p, x.shape[axis]).reshape(-1)
    return jnp.take(x, jnp.asarray(idx), axis=axis)


def zigzag_unshard(x, p, axis=1):
    """Inverse of :func:`zigzag_shard` on the gathered global array."""
    import numpy as _np

    idx = zigzag_indices(p, x.shape[axis]).reshape(-1)
    inv = _np.empty_like(idx)
    inv[idx] = _np.arange(idx.size, dtype=_np.int32)
    return jnp.take(x, jnp.asarray(inv), axis=axis)


@publishes_token
def ring_attention(
    q, k, v, comm, *, causal=False, scale=None, token=None,
    layout="contiguous", impl="auto",
):
    """Context-parallel attention over a 1-D ring communicator.

    Every device holds the local sequence block ``q``/``k``/``v`` of
    shape [B, T_local, H, D] (global sequence = ring-rank-major
    concatenation).  Returns ``(out, token)`` with ``out`` the local
    block of softmax(QK^T)V over the *global* sequence.

    Algorithm: ``comm.size`` steps of blockwise attention with running
    (max, sum, accumulator) statistics; after each step the KV pair
    moves to the next rank via :func:`sendrecv` (one ``ppermute``).
    Reverse-mode AD reverses the permutation automatically — gradients
    ride the ring the opposite way, the exact transpose contract of the
    reference's sendrecv (sendrecv.py:366-385).

    ``impl`` selects the single-device attention kernel (see
    :func:`local_attention`) for the ``comm.size == 1`` shortcut; the
    multi-rank ring path always uses its own blockwise online-softmax
    updates (the ring IS the flash-style blocking, at shard granularity).

    ``layout``: ``"contiguous"`` — rank r holds global positions
    ``[r*T_local, (r+1)*T_local)``; ``"zigzag"`` — rank r holds chunks
    ``r`` and ``2p-1-r`` (see :func:`zigzag_indices`), which balances
    the causal-masking work across ranks (with contiguous blocks the
    last rank attends to everything while rank 0 sees one block; the
    ring is a barrier per step, so the slowest rank paces everyone).
    Use :func:`zigzag_shard`/:func:`zigzag_unshard` to convert global
    arrays.
    """
    token = as_token(token)
    p = comm.size
    d = q.shape[-1]
    scale = (1.0 / math.sqrt(d)) if scale is None else scale

    # validate BEFORE the single-rank shortcut, so a bad layout string /
    # GQA mismatch fails in 1-device tests too, not first at scale
    if layout not in ("contiguous", "zigzag"):
        raise ValueError(
            f"layout must be 'contiguous' or 'zigzag', got {layout!r}"
        )
    _check_gqa(q.shape[2], k.shape[2], "ring_attention")

    if comm.backend == "self" or p == 1:
        out = local_attention(q, k, v, causal=causal, scale=scale, impl=impl)
        return out, token

    if comm.backend != "mesh":
        raise NotImplementedError(
            f"ring_attention requires a mesh communicator, got "
            f"{comm.backend!r}"
        )
    if len(comm.axes) != 1:
        raise ValueError(
            f"ring_attention needs a 1-D communicator (one mesh axis), "
            f"got axes {comm.axes}; use comm.sub(axis)"
        )

    rank = comm.rank()
    b, tq, h, _ = q.shape
    tk = k.shape[1]
    if layout == "zigzag":
        if tq != tk:
            raise ValueError(
                f"zigzag layout requires equal q/kv block lengths, got "
                f"Tq={tq}, Tk={tk} (the chunk table is shared)"
            )
        if tq % 2:
            raise ValueError(
                f"zigzag layout needs an even local block length "
                f"(two chunks per rank), got T_local={tq}"
            )
        pos_table = jnp.asarray(zigzag_indices(p, p * tq))
        qpos = pos_table[rank]
    else:
        qpos = rank * tq + jnp.arange(tq)

    # forward ring: the kv block moves to the next rank each step, so at
    # step i this rank holds the block that originated at rank - i
    perm = [(r, (r + 1) % p) for r in range(p)]

    from mpi4jax_tpu.ops._core import promote_vma

    # carries become device-varying after the first step; start them
    # varying so the scan carry type is stable.  The target set is the
    # ring axis PLUS whatever axes the operands already vary on — on a
    # multi-axis mesh (e.g. dp×tp×sp) q/k/v vary on every axis, and a
    # carry promoted to "sp" alone would type-mismatch attend's outputs.
    try:
        operand_vma = (
            jax.typeof(q).vma | jax.typeof(k).vma | jax.typeof(v).vma
        )
    except AttributeError:
        operand_vma = frozenset()
    carry_axes = tuple(dict.fromkeys((*comm.axes, *sorted(operand_vma))))
    acc0 = promote_vma(jnp.zeros((b, tq, h, d), jnp.float32), carry_axes)
    m0 = promote_vma(jnp.full((b, h, tq), _NEG, jnp.float32), carry_axes)
    l0 = promote_vma(jnp.zeros((b, h, tq), jnp.float32), carry_axes)
    token = token.with_stamp(promote_vma(token.stamp, carry_axes))

    def attend(q_sub, qpos_sub, k_blk, v_blk, acc, m, l, kpos, *, mask):
        """Online-softmax update of (acc, m, l) for the q rows in
        ``q_sub``; ``mask=False`` asserts full visibility (no masking
        work, no wasted score FLOPs beyond the block itself)."""
        s = _scores(q_sub, k_blk, scale)
        if mask:
            vis = qpos_sub[:, None] >= kpos[None, :]
            s = jnp.where(vis[None, None], s, _NEG)

        m_new = jnp.maximum(m, s.max(axis=-1))
        corr = jnp.exp(m - m_new)
        w = jnp.exp(s - m_new[..., None])
        l_new = l * corr + w.sum(axis=-1)
        acc_new = acc * corr.transpose(0, 2, 1)[
            ..., None
        ] + _weighted_values(w, v_blk.astype(jnp.float32), q_sub.shape[2])
        return acc_new, m_new, l_new

    c = tq // 2  # zigzag chunk length

    def zigzag_causal_update(i, src, k_blk, v_blk, acc, m, l):
        """Chunk-level causal schedule for the zigzag layout.

        Rank r's q chunks are (r, 2p-1-r); the step-i kv block holds
        src's chunks (src, 2p-1-src).  Chunk-pair visibility collapses
        to three cases, two of which need NO elementwise mask and only
        HALF the block's scores — this is where the zigzag layout's
        balance comes from (every rank does the same half-block of
        work per off-diagonal step, vs the contiguous layout where one
        rank computes a full block while another skips it):

        * i == 0 (src == rank): the local block — diagonal chunks, one
          masked full attend.
        * src < rank: every q row sees ONLY src's early chunk
          (k rows [:c]); late chunk entirely in the future.
        * src > rank: only the late q chunk (rows [c:]) sees anything,
          and it sees the WHOLE kv block.
        """

        def diag():
            return attend(
                q, qpos, k_blk, v_blk, acc, m, l, pos_table[src], mask=True
            )

        def lower():  # src < rank: all q vs early k chunk, unmasked
            return attend(
                q, qpos, k_blk[:, :c], v_blk[:, :c], acc, m, l, None,
                mask=False,
            )

        def upper():  # src > rank: late q chunk vs full kv, unmasked
            a2, m2, l2 = attend(
                q[:, c:], None, k_blk, v_blk,
                acc[:, c:], m[..., c:], l[..., c:], None, mask=False,
            )
            return (
                acc.at[:, c:].set(a2),
                m.at[..., c:].set(m2),
                l.at[..., c:].set(l2),
            )

        return lax.cond(
            i == 0, diag, lambda: lax.cond(src < rank, lower, upper)
        )

    def step(carry, i):
        k_blk, v_blk, acc, m, l, stamp = carry
        src = (rank - i) % p

        if causal and layout == "zigzag":
            acc, m, l = zigzag_causal_update(i, src, k_blk, v_blk, acc, m, l)
        elif causal:
            kpos = src * tk + jnp.arange(tk)
            # blocks entirely in this rank's future contribute nothing:
            # skip the attention math (the communication still happens —
            # the ring must keep rotating). Saves ~half the FLOPs of a
            # causal ring on average, but unevenly: at step i only the
            # ranks with src <= rank do work (the zigzag layout is the
            # balanced alternative).
            block_visible = qpos[-1] >= kpos[0]
            acc, m, l = lax.cond(
                block_visible,
                lambda: attend(q, qpos, k_blk, v_blk, acc, m, l, kpos, mask=True),
                lambda: (acc, m, l),
            )
        else:
            kpos = None
            acc, m, l = attend(
                q, qpos, k_blk, v_blk, acc, m, l, kpos, mask=False
            )

        tok = Token(stamp)
        k_blk, tok = sendrecv(k_blk, k_blk, source=perm, dest=perm, comm=comm, token=tok)
        v_blk, tok = sendrecv(v_blk, v_blk, source=perm, dest=perm, comm=comm, token=tok)
        return (k_blk, v_blk, acc, m, l, tok.stamp), None

    carry0 = (k, v, acc0, m0, l0, token.stamp)
    (k_f, v_f, acc, m, l, stamp), _ = lax.scan(
        step, carry0, jnp.arange(p), length=p
    )
    del k_f, v_f
    out = acc / l.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype), Token(stamp)


@publishes_token
def ulysses_attention(
    q, k, v, comm, *, causal=False, scale=None, token=None, impl="auto"
):
    """Ulysses-style context parallelism: all-to-all head↔sequence
    reshard, dense local attention over the full sequence, reshard back.

    ``q``/``k``/``v``: local [B, T_local, H, D] with ``H % comm.size ==
    0``.  Cheaper than the ring when the full sequence fits in HBM for
    ``H / p`` heads (2 collectives instead of ``p`` permutes); the ring
    wins at extreme lengths.
    """
    token = as_token(token)
    p = comm.size

    if comm.backend == "self" or p == 1:
        out = local_attention(q, k, v, causal=causal, scale=scale, impl=impl)
        return out, token

    if comm.backend != "mesh":
        raise NotImplementedError(
            f"ulysses_attention requires a mesh communicator, got "
            f"{comm.backend!r}"
        )

    b, t, h, d = q.shape
    hk = k.shape[2]
    _check_gqa(h, hk, "ulysses_attention")
    for name, heads in (("query", h), ("kv", hk)):
        if heads % p:
            raise ValueError(
                f"ulysses_attention needs {name} heads divisible by the "
                f"ring size: H={heads}, comm.size={p}"
                + (
                    " (for GQA with fewer kv heads than ranks, repeat kv "
                    "heads to a multiple of comm.size first)"
                    if name == "kv"
                    else ""
                )
            )

    def to_heads(x, tok):
        # [B, T, H, D] -> rows [p, T, B, hp, D] -> alltoall -> full seq
        # for this rank's head subset [B, p*T, hp, D]
        hp = x.shape[2] // p
        blocks = x.reshape(b, t, p, hp, d).transpose(2, 1, 0, 3, 4)
        mixed, tok = alltoall(blocks, comm=comm, token=tok)
        # row j now holds rank j's sequence block for our heads
        return mixed.transpose(2, 0, 1, 3, 4).reshape(b, p * t, hp, d), tok

    def to_seq(x, tok):
        # inverse of to_heads
        hp = x.shape[2]
        blocks = x.reshape(b, p, t, hp, d).transpose(1, 2, 0, 3, 4)
        mixed, tok = alltoall(blocks, comm=comm, token=tok)
        return mixed.transpose(2, 1, 0, 3, 4).reshape(b, t, p * hp, d), tok

    qh, token = to_heads(q, token)
    kh, token = to_heads(k, token)
    vh, token = to_heads(v, token)

    out = local_attention(qh, kh, vh, causal=causal, scale=scale, impl=impl)

    out, token = to_seq(out, token)
    return out, token
