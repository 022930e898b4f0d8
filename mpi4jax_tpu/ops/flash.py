"""Pallas TPU flash attention: the blockwise-local-attention hot op.

``local_attention`` (parallel/longseq.py) is the FLOPs core of both
sequence-parallel schemes; the dense XLA form materialises the [Tq, Tk]
score matrix in HBM.  This kernel streams K/V blocks through VMEM with
online-softmax statistics in scratch, so scores never leave the chip —
the standard flash-attention schedule (Dao et al. 2022) expressed in
Pallas idioms: sequential minormost grid dimension as the K loop, VMEM
scratch carried across grid steps, masking via 2-D iota.

Public entry: :func:`flash_attention` with the same contract as
``local_attention`` ([B, T, H, D] operands, float32 accumulation,
``causal`` with static block offsets).  ``interpret=True`` runs the
kernel on CPU for tests.  Reverse-mode differentiable with a BLOCKWISE
backward (the standard dFlashAttention pair): the forward additionally
saves the per-row log-sum-exp, and two kernels recompute scores per
block — one accumulating (dK, dV) per key block over query blocks, one
accumulating dQ per query block over key blocks — so the backward
never materialises the [Tq, Tk] score matrix either.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mpi4jax_tpu.ops._core import union_vma_struct

__all__ = ["flash_attention"]

_NEG = -0.7 * float(jnp.finfo(jnp.float32).max)
_INF = float("inf")
_LANES = 128  # TPU lane width: scratch statistics are (block_q, _LANES)


def _tri_iq_ik(t):
    """Row-major lower-triangle index: flat ``t`` -> (iq, ik) with
    ik <= iq.  The float sqrt is exact for any realistic block count;
    the two `where` guards absorb boundary roundoff anyway."""
    tf = t.astype(jnp.float32)
    iq = jnp.floor((jnp.sqrt(8.0 * tf + 1.0) - 1.0) / 2.0).astype(jnp.int32)
    tri = iq * (iq + 1) // 2
    iq = jnp.where(t < tri, iq - 1, iq)
    iq = jnp.where(t >= (iq + 1) * (iq + 2) // 2, iq + 1, iq)
    ik = t - iq * (iq + 1) // 2
    return iq, ik


def _tri_gate(causal, q_offset, k_offset, tq, tk, pad_q, pad_k, block_q,
              block_k):
    """True when the squashed-triangle causal grid applies: square
    unsharded causal attention with no padding and equal blocks.  The
    triangle grid visits only the ~half of the blocks the causal mask
    keeps (and masks only the diagonal ones); sharded (offset) and
    padded cases keep the general rectangular path.

    The flat triangle index is inverted with a float32 sqrt
    (:func:`_tri_iq_ik`) whose ±1 boundary guards absorb at most one
    index of error.  At the 2^22 flat-index cap, ``8*t+1`` ≈ 2^25 — a
    couple of f32 ulps of representation error plus the sqrt's
    half-ulp, i.e. an absolute error on ``sqrt ≈ 2^12.5`` of ~1e-3,
    far below the ±1 the guards absorb (the guards would only be
    outrun near ``t ≈ 2^45``).  The static cap keeps that argument
    comfortably valid instead of letting an extreme block count
    (~2896 query blocks — seq ≈ 1.5M at block 512) silently mis-map
    blocks (ADVICE r4)."""
    if not (
        causal
        and q_offset == k_offset
        and tq == tk
        and pad_q == 0
        and pad_k == 0
        and block_q == block_k
    ):
        return False
    nq = tq // block_q
    return nq * (nq + 1) // 2 <= 1 << 22


def _kernel(
    q_ref,
    k_ref,
    v_ref,
    *rest,
    scale,
    causal,
    q_offset,
    k_offset,
    kv_len,
    block_q,
    block_k,
    num_k,
    with_lse,
    triangle,
):
    # triangle runs carry a precomputed additive causal-mask bias as a
    # 4th input (0 on visible entries, ~_NEG on masked, bf16): one VPU
    # add on the diagonal blocks replaces the iota+compare+select
    # stack; masked scores collapse to ~-2.4e38 whose exp underflows to
    # exactly 0, so every OBSERVABLE quantity (w, l, m on rows with a
    # visible entry — every triangle row has one) matches the where()
    # form
    if triangle:
        mask_ref, o_ref, *rest = rest
    else:
        mask_ref = None
        o_ref, *rest = rest
    if with_lse:
        m_out_ref, l_out_ref, acc_ref, m_ref, l_ref = rest
    else:
        m_out_ref, l_out_ref = None, None
        acc_ref, m_ref, l_ref = rest
    if triangle:
        # squashed causal grid: only the lower-triangle blocks are
        # visited (the rest are fully masked anyway), and only the
        # diagonal block pays the mask/iota VPU work — measured ~1.4x
        # at seq 8192 over the rectangular grid + full masking
        iq, ik = _tri_iq_ik(pl.program_id(1))
    else:
        iq = pl.program_id(1)
        ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    def _compute(mask_causal):
        # the softmax scale rides the [bq, D] query block instead of the
        # [bq, bk] score block — one full-block VPU pass saved per visit
        # (bk/D× fewer multiplies); f32 so no operand rounding is added
        q = q_ref[0].astype(jnp.float32) * scale  # [bq, D]
        k = k_ref[0].astype(jnp.float32)  # [bk, D]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [bq, bk] f32, already scaled

        if mask_causal and triangle:
            # diagonal block of the squashed grid: add the precomputed
            # bias (one pass; masked entries collapse to ~_NEG and
            # their exp underflows to exactly 0 — see the signature
            # note)
            s = s + mask_ref[...]
        elif not triangle:
            # local (unpadded-array) positions of this block's rows/cols
            krow = ik * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
        if mask_causal and not triangle:
            qpos = q_offset + iq * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            # causally-masked REAL keys get the finite _NEG (the dense
            # oracle's convention: a fully-masked row degrades to uniform
            # weights over the real keys)
            s = jnp.where(qpos >= k_offset + krow, s, _NEG)
        if not triangle:
            # padded K rows are excluded outright (-inf): exp(-inf - m)
            # == 0 for any finite m, and m stays finite because the
            # scratch starts at _NEG — so padding never contributes to
            # l, matching the unpadded oracle even for fully-masked
            # rows.  (The triangle path is gated on zero padding.)
            s = jnp.where(krow < kv_len, s, -_INF)

        m_prev = m_ref[:, :1]  # [bq, 1] (lanes replicated)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        if q_ref.dtype == jnp.bfloat16:
            # bf16 transcendental: the exp argument is rounded to 8
            # mantissa bits (~0.4% weight error — inside the bf16
            # operands' own precision budget; the backward recomputes
            # the SAME bf16 weights, so fwd/bwd stay self-consistent)
            # and the PV contraction consumes w without a cast pass
            w = jnp.exp((s - m_new).astype(jnp.bfloat16))
        else:
            w = jnp.exp(s - m_new)  # [bq, bk]
        l_ref[...] = l_ref[...] * corr + w.sum(
            axis=1, keepdims=True, dtype=jnp.float32
        )
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        # the weights ride the MXU in the INPUT dtype (f32 accumulate):
        # for bf16 operands that rounds w to 8 mantissa bits — inside
        # the operands' own precision budget (the flash-standard
        # mixed-precision contraction) — and keeps the PV matmul on the
        # fast MXU path; f32 inputs keep exact f32 weights (the tests'
        # oracle-equality mode)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            w.astype(v_ref.dtype),
            v_ref[0],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if triangle:
        @pl.when(ik == iq)
        def _diag():
            _compute(True)

        @pl.when(ik != iq)
        def _interior():
            _compute(False)
    else:
        # NB on this path causal block-SKIPPING (pl.when around the body
        # for fully masked blocks) was measured and rejected at 2048
        # (12.3 vs 11.6 ms); the triangle grid above is the form of
        # skipping that does pay (no visit, no DMA, no conditional on
        # the hot interior blocks).
        _compute(causal)

    last = (ik == iq) if triangle else (ik == num_k - 1)

    @pl.when(last)
    def _finalize():
        o_ref[0] = (acc_ref[...] / l_ref[:, :1]).astype(o_ref.dtype)
        if with_lse:
            # softmax residuals for the backward, stored (rows, 1) —
            # the trailing singleton keeps the block Mosaic-legal.  m
            # and l are saved SEPARATELY, never fused into m + log(l):
            # on fully-masked rows m == _NEG (~-2.4e38) absorbs log(n)
            # entirely in float32, which would inflate the recomputed
            # weights from 1/n to 1 and scale dV by n.
            m_out_ref[0] = m_ref[:, :1]
            l_out_ref[0] = l_ref[:, :1]


def flash_attention(
    q,
    k,
    v,
    *,
    causal=False,
    scale=None,
    q_offset=0,
    k_offset=0,
    block_q=1024,
    block_k=1024,
    interpret=False,
):
    """Blockwise attention, same contract as ``local_attention``.

    Block sizes default to 1024 (1024x2048 and 2048x* exceed VMEM; the
    choice among the feasible pairs is not measured on the current
    chip) and are clamped down for short sequences.

    ``q``: [B, Tq, H, D]; ``k``/``v``: [B, Tk, H, D].  Sequence lengths
    are padded internally to the block sizes (padded K rows are masked
    out of the softmax; padded Q rows are dropped on return).
    ``q_offset``/``k_offset`` are the global positions of the first
    row/column, for causal masking of sequence-sharded blocks.

    ``scale`` and the offsets are trace-time constants (they are baked
    into the kernel); pass Python numbers, not traced values.

    float32 operands are exact in interpret mode only: on the chip the
    kernel's float32 dots run at the MXU's default precision, one bf16
    pass (forward 9.4e-3 from a full-precision dense reference at
    [1, 2048, 4, 128]; chip_smoke.py reports it on every run).

    Grouped-query attention (``k``/``v`` with fewer heads, ``Hq % Hkv
    == 0``) is supported by repeating kv heads before the kernel — the
    VMEM streaming win is kept, at Hq/Hkv× kv HBM footprint; gradients
    flow back through the repeat (summed per kv head).
    """
    d = q.shape[-1]
    hq, hk = q.shape[2], k.shape[2]
    if hq != hk:
        if hq % hk:
            raise ValueError(
                f"flash_attention: query heads must be a multiple of kv "
                f"heads, got Hq={hq}, Hkv={hk}"
            )
        k = jnp.repeat(k, hq // hk, axis=2)
        v = jnp.repeat(v, hq // hk, axis=2)
    scale = (1.0 / math.sqrt(d)) if scale is None else float(scale)
    return _flash_vjp(
        q, k, v, bool(causal), scale, int(q_offset), int(k_offset),
        int(block_q), int(block_k), bool(interpret),
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash_vjp(
    q, k, v, causal, scale, q_offset, k_offset, block_q, block_k, interpret
):
    return _flash_fwd_impl(
        q, k, v, causal, scale, q_offset, k_offset, block_q, block_k,
        interpret,
    )


def _flash_fwd(
    q, k, v, causal, scale, q_offset, k_offset, block_q, block_k, interpret
):
    # NB a checkpoint_name tag on these residuals CANNOT spare the
    # forward replay under jax.checkpoint: linearising the custom_vjp
    # call re-runs this fwd rule regardless of what a save-names policy
    # keeps (measured r5 — the tagged variant still traced 4 kernel
    # classes and paid an extra o-proj recompute).
    out, m_res, l_res = _flash_fwd_impl(
        q, k, v, causal, scale, q_offset, k_offset, block_q, block_k,
        interpret, with_lse=True,
    )
    return out, (q, k, v, out, m_res, l_res)


def _bwd_block(
    q_ref, k_ref, v_ref, g_ref, m_ref, l_ref, delta_ref, *, iq, ik, scale,
    scale_on, mask_causal, mask_kv, q_offset, k_offset, kv_len, block_q,
    block_k, mask_ref=None,
):
    """Shared per-block backward math: recompute masked scores and the
    softmax weights from the saved (m, l) statistics, then form ds —
    the cotangent of the SCALED scores, with the softmax scale folded
    into one [block, D] operand instead of two [bq, bk] passes
    (``scale_on``: the dkv kernel scales q — its dk contraction then
    absorbs the score-cotangent's trailing ·scale through the scaled q
    — the dq kernel scales k, symmetrically).  ``ds`` is zeroed outside
    the visible set exactly as the dense oracle's ``where`` vjp does
    (this is what keeps the fully-masked-row uniform-weights convention
    gradient-exact: those rows produce p == 1/n but ds == 0).

    ``mask_causal``/``mask_kv`` select which mask terms this block
    needs: the triangle grid's interior blocks are fully visible and
    unpadded, so they skip the iota/where VPU work entirely."""
    q = q_ref[0].astype(jnp.float32)  # [bq, D]
    k = k_ref[0].astype(jnp.float32)  # [bk, D]
    v = v_ref[0]  # [bk, D]
    g = g_ref[0]  # [bq, D]
    if scale_on == "q":
        q = q * scale
    else:
        k = k * scale
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # scaled scores
    visible = None
    if mask_causal and mask_ref is not None:
        # triangle diagonal block: one additive pass; masked entries
        # collapse to ~_NEG, so p underflows to exactly 0.0 and ds is
        # exactly 0 there with no visible-mask select at all
        s = s + mask_ref[...]
    else:
        if mask_causal or mask_kv:
            krow = ik * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
        if mask_kv:
            visible = krow < kv_len
        if mask_causal:
            qpos = q_offset + iq * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            causal_ok = qpos >= k_offset + krow
            visible = (
                causal_ok if visible is None else (visible & causal_ok)
            )
            s = jnp.where(causal_ok, s, _NEG)
        if mask_kv:
            s = jnp.where(krow < kv_len, s, -_INF)
    # p from the saved statistics ((rows, 1) columns broadcast across
    # the block): exp(s - m) / l — NOT exp(s - (m + log l)), whose f32
    # fusion loses log(l) against the huge _NEG on fully-masked rows
    # and would inflate those rows' weights from 1/n to 1.  Padded q
    # rows carry m == +inf (host-side padding) so p is exactly 0 there.
    # bf16 operands recompute the forward's own bf16-exp weights (the l
    # statistic summed exactly these), keeping fwd/bwd self-consistent.
    if q_ref.dtype == jnp.bfloat16:
        p = jnp.exp((s - m_ref[0]).astype(jnp.bfloat16)).astype(
            jnp.float32
        ) / l_ref[0]
    else:
        p = jnp.exp(s - m_ref[0]) / l_ref[0]  # [bq, bk]
    dp = jax.lax.dot_general(
        g, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    # NO trailing ·scale: the caller's contraction against the scaled
    # operand (q in dkv, k in dq) supplies it
    ds = p * (dp - delta_ref[0])
    if visible is not None:
        ds = jnp.where(visible, ds, 0.0)
    return q, k, g, p, ds


def _bwd_dkv_kernel(
    q_ref, k_ref, v_ref, g_ref, m_ref, l_ref, delta_ref, *rest, scale,
    causal, q_offset, k_offset, kv_len, block_q, block_k, num_q, triangle,
):
    """dK/dV: one key block per (middle) row, accumulated over the
    sequential query blocks.  On the triangle grid the visible set is
    ``iq >= ik``: the flat index walks key-block rows with iq ascending
    ik..n-1, the diagonal block is the only one needing the mask, and
    the fully-masked iq < ik blocks are never visited at all."""
    if triangle:
        mask_ref, dk_ref, dv_ref, dk_acc, dv_acc = rest
    else:
        mask_ref = None
        dk_ref, dv_ref, dk_acc, dv_acc = rest
    if triangle:
        # reverse the fwd's lower-triangle walk: rows keyed by ik, iq
        # ascending within each row
        n = num_q
        total = n * (n + 1) // 2
        a, bb = _tri_iq_ik(total - 1 - pl.program_id(1))
        ik = n - 1 - a
        iq = n - 1 - bb
    else:
        ik = pl.program_id(1)
        iq = pl.program_id(2)

    first = (iq == ik) if triangle else (iq == 0)
    last = iq == num_q - 1

    @pl.when(first)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def _accumulate(mask_causal):
        q, _k, g, p, ds = _bwd_block(
            q_ref, k_ref, v_ref, g_ref, m_ref, l_ref, delta_ref, iq=iq,
            ik=ik, scale=scale, scale_on="q", mask_causal=mask_causal,
            mask_kv=not triangle, q_offset=q_offset, k_offset=k_offset,
            kv_len=kv_len, block_q=block_q, block_k=block_k,
            mask_ref=mask_ref,
        )
        # dV += P^T @ dO ; dK += dS^T @ Q   (contract the q-block dim).
        # p rides the MXU in g's storage dtype (f32 accumulate); the dK
        # contraction stays f32×f32 — q is already the f32 scaled local
        # (the scale-folding operand), and f32 dots measured the same
        # as bf16 on this kernel (it is DMA-, not MXU-, bound)
        dv_acc[...] += jax.lax.dot_general(
            p.astype(g.dtype), g, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dk_acc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if triangle:
        @pl.when(iq == ik)
        def _diag():
            _accumulate(True)

        @pl.when(iq != ik)
        def _interior():
            _accumulate(False)
    else:
        _accumulate(causal)

    @pl.when(last)
    def _finalize():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _bwd_dq_kernel(
    q_ref, k_ref, v_ref, g_ref, m_ref, l_ref, delta_ref, *rest, scale,
    causal, q_offset, k_offset, kv_len, block_q, block_k, num_k, triangle,
):
    """dQ: one query block per (middle) row, accumulated over the
    sequential key blocks (triangle: ik ascending 0..iq, diagonal
    masked, nothing above it visited)."""
    if triangle:
        mask_ref, dq_ref, dq_acc = rest
    else:
        mask_ref = None
        dq_ref, dq_acc = rest
    if triangle:
        iq, ik = _tri_iq_ik(pl.program_id(1))
    else:
        iq = pl.program_id(1)
        ik = pl.program_id(2)

    first = ik == 0
    last = (ik == iq) if triangle else (ik == num_k - 1)

    @pl.when(first)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def _accumulate(mask_causal):
        _q, k, _g, _p, ds = _bwd_block(
            q_ref, k_ref, v_ref, g_ref, m_ref, l_ref, delta_ref, iq=iq,
            ik=ik, scale=scale, scale_on="k", mask_causal=mask_causal,
            mask_kv=not triangle, q_offset=q_offset, k_offset=k_offset,
            kv_len=kv_len, block_q=block_q, block_k=block_k,
            mask_ref=mask_ref,
        )
        dq_acc[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if triangle:
        @pl.when(ik == iq)
        def _diag():
            _accumulate(True)

        @pl.when(ik != iq)
        def _interior():
            _accumulate(False)
    else:
        _accumulate(causal)

    @pl.when(last)
    def _finalize():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _flash_bwd(
    causal, scale, q_offset, k_offset, block_q, block_k, interpret, res, g
):
    q, k, v, out, m_res, l_res = res
    b, tq, h, d = q.shape
    tk = k.shape[1]
    # scale is a nondiff arg already resolved to a float by
    # flash_attention before the custom_vjp — no re-defaulting here
    block_q, block_k, pad_q, pad_k = _blocks(tq, tk, block_q, block_k)

    qf = _fold(q, pad_q, b, h, d)
    kf = _fold(k, pad_k, b, h, d)
    vf = _fold(v, pad_k, b, h, d)
    gf = _fold(g, pad_q, b, h, d)
    outf = _fold(out, pad_q, b, h, d)
    # the standard softmax-vjp identity: delta_i = Σ_k P_ik dP_ik
    #                                            = rowsum(dO * O);
    # trailing singleton keeps the (1, block_q, 1) blocks Mosaic-legal
    delta = (gf.astype(jnp.float32) * outf.astype(jnp.float32)).sum(
        -1, keepdims=True
    )
    # padded q rows: m == +inf (and l == 1, not 0 — a 0 would turn the
    # harmless p into nan, and 0 * nan poisons the accumulators) makes
    # their softmax weights exactly 0
    m_pad = jnp.pad(
        m_res, ((0, 0), (0, pad_q)), constant_values=_INF
    ).astype(jnp.float32)[..., None]
    l_pad = jnp.pad(
        l_res, ((0, 0), (0, pad_q)), constant_values=1.0
    ).astype(jnp.float32)[..., None]

    nq = qf.shape[1] // block_q
    nk = kf.shape[1] // block_k
    triangle = _tri_gate(
        causal, q_offset, k_offset, tq, tk, pad_q, pad_k, block_q, block_k
    )
    common = dict(
        scale=scale, causal=causal, q_offset=q_offset, k_offset=k_offset,
        kv_len=tk, block_q=block_q, block_k=block_k, triangle=triangle,
    )
    n_tri = nq * (nq + 1) // 2

    if triangle:
        def dkv_qmap(bh, t):
            a, bb = _tri_iq_ik(n_tri - 1 - t)
            return (bh, nq - 1 - bb, 0)

        def dkv_kmap(bh, t):
            a, bb = _tri_iq_ik(n_tri - 1 - t)
            return (bh, nq - 1 - a, 0)

        dkv_grid = (b * h, n_tri)
    else:
        def dkv_qmap(bh, ik, iq):
            return (bh, iq, 0)

        def dkv_kmap(bh, ik, iq):
            return (bh, ik, 0)

        dkv_grid = (b * h, nk, nq)

    bwd_operands = [qf, kf, vf, gf, m_pad, l_pad, delta]
    if triangle:
        bwd_operands.append(_causal_bias(block_q, block_k, qf, kf, vf, gf))

    def specs_for(qmap, kmap):
        specs = [
            pl.BlockSpec((1, block_q, d), qmap),
            pl.BlockSpec((1, block_k, d), kmap),
            pl.BlockSpec((1, block_k, d), kmap),
            pl.BlockSpec((1, block_q, d), qmap),
            pl.BlockSpec((1, block_q, 1), qmap),
            pl.BlockSpec((1, block_q, 1), qmap),
            pl.BlockSpec((1, block_q, 1), qmap),
        ]
        if triangle:
            specs.append(
                pl.BlockSpec((block_q, block_k), lambda *_: (0, 0))
            )
        return specs

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, num_q=nq, **common),
        grid=dkv_grid,
        in_specs=specs_for(dkv_qmap, dkv_kmap),
        out_specs=[
            pl.BlockSpec((1, block_k, d), dkv_kmap),
            pl.BlockSpec((1, block_k, d), dkv_kmap),
        ],
        out_shape=(
            union_vma_struct((b * h, nk * block_k, d), k.dtype, qf, kf, vf, gf),
            union_vma_struct((b * h, nk * block_k, d), v.dtype, qf, kf, vf, gf),
        ),
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=interpret,
    )(*bwd_operands)

    if triangle:
        def dq_qmap(bh, t):
            iq, _ik = _tri_iq_ik(t)
            return (bh, iq, 0)

        def dq_kmap(bh, t):
            _iq, ik = _tri_iq_ik(t)
            return (bh, ik, 0)

        dq_grid = (b * h, n_tri)
    else:
        def dq_qmap(bh, iq, ik):
            return (bh, iq, 0)

        def dq_kmap(bh, iq, ik):
            return (bh, ik, 0)

        dq_grid = (b * h, nq, nk)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, num_k=nk, **common),
        grid=dq_grid,
        in_specs=specs_for(dq_qmap, dq_kmap),
        out_specs=pl.BlockSpec((1, block_q, d), dq_qmap),
        out_shape=union_vma_struct(
            (b * h, nq * block_q, d), q.dtype, qf, kf, vf, gf
        ),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
    )(*bwd_operands)

    return (
        _unfold(dq, tq, b, h, d),
        _unfold(dk, tk, b, h, d),
        _unfold(dv, tk, b, h, d),
    )


_flash_vjp.defvjp(_flash_fwd, _flash_bwd)


def _blocks(tq, tk, block_q, block_k):
    """Clamped block sizes and padding shared by forward and backward
    (they MUST agree: the backward re-pads the forward's residuals).

    (Measured caution, r5: do NOT clamp long sequences down to 512² —
    the 1024² blocks are worth +28% at seq 16384 and +37% at 32768,
    bf16.  FLOAT32 operands at those lengths can push the dq backward
    kernel past the 16 MB scoped-VMEM stack limit under partial-remat
    graph shapes; callers training long context in f32 should pass
    block_q=block_k=512 explicitly — the bench presets train bf16.)"""
    block_q = min(block_q, max(tq, 8))
    block_k = min(block_k, max(tk, 8))
    return block_q, block_k, (-tq) % block_q, (-tk) % block_k


def _causal_bias(block_q, block_k, *arrays):
    """Additive causal mask for the triangle grid's diagonal blocks:
    0 on the visible lower triangle, the finite ``_NEG`` elsewhere.
    Built once per call outside the kernel (XLA folds it to a
    constant); carries the operands' vma union for shard_map."""
    vis = jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0
    ) >= jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    # bf16: same exponent range as f32, so the ~-2.4e38 sentinel
    # survives the rounding, any finite score it is added to still
    # collapses to ~_NEG, and exp underflows to exactly 0 — while the
    # block costs half the VMEM/DMA of an f32 mask (the fused backward
    # kernel is within ~2 MB of the 16 MB scoped-vmem limit at 1024²)
    bias = jnp.where(vis, 0.0, _NEG).astype(jnp.bfloat16)
    from mpi4jax_tpu.ops._core import promote_vma, vma_of

    axes = set()
    for a in arrays:
        axes.update(vma_of(a) or ())
    if axes:
        bias = promote_vma(bias, tuple(sorted(axes)))
    return bias


def _fold(x, pad, b, h, d):
    """[B, T, H, D] -> [B*H, T(+pad), D]."""
    x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
    return x.transpose(0, 2, 1, 3).reshape(b * h, x.shape[1], d)


def _unfold(x, tq, b, h, d):
    """Inverse of :func:`_fold` (drops the padding)."""
    return x[:, :tq, :].reshape(b, h, tq, d).transpose(0, 2, 1, 3)


def _flash_fwd_impl(
    q, k, v, causal, scale, q_offset, k_offset, block_q, block_k,
    interpret, with_lse=False,
):
    b, tq, h, d = q.shape
    tk = k.shape[1]
    # scale arrives as a resolved float (flash_attention defaults it
    # before the custom_vjp) — no re-defaulting here or in _flash_bwd

    block_q, block_k, pad_q, pad_k = _blocks(tq, tk, block_q, block_k)
    qf = _fold(q, pad_q, b, h, d)
    kf = _fold(k, pad_k, b, h, d)
    vf = _fold(v, pad_k, b, h, d)
    nq = qf.shape[1] // block_q
    nk = kf.shape[1] // block_k
    triangle = _tri_gate(
        causal, q_offset, k_offset, tq, tk, pad_q, pad_k, block_q, block_k
    )

    kernel = functools.partial(
        _kernel,
        scale=scale,
        causal=causal,
        q_offset=q_offset,
        k_offset=k_offset,
        kv_len=tk,
        block_q=block_q,
        block_k=block_k,
        num_k=nk,
        with_lse=with_lse,
        triangle=triangle,
    )
    if triangle:
        grid = (b * h, nq * (nq + 1) // 2)

        def qmap(bh, t):
            iq, _ik = _tri_iq_ik(t)
            return (bh, iq, 0)

        def kmap(bh, t):
            _iq, ik = _tri_iq_ik(t)
            return (bh, ik, 0)
    else:
        grid = (b * h, nq, nk)

        def qmap(bh, iq, ik):
            return (bh, iq, 0)

        def kmap(bh, iq, ik):
            return (bh, ik, 0)

    out_specs = [pl.BlockSpec((1, block_q, d), qmap)]
    # inside shard_map the output varies over the union of the
    # operands' varying axes; check_vma requires it spelled out
    out_shape = [
        union_vma_struct((b * h, nq * block_q, d), q.dtype, qf, kf, vf),
    ]
    if with_lse:
        for _ in range(2):  # m and l residuals
            out_specs.append(pl.BlockSpec((1, block_q, 1), qmap))
            out_shape.append(
                union_vma_struct(
                    (b * h, nq * block_q, 1), jnp.float32, qf, kf, vf
                )
            )
    in_specs = [
        pl.BlockSpec((1, block_q, d), qmap),
        pl.BlockSpec((1, block_k, d), kmap),
        pl.BlockSpec((1, block_k, d), kmap),
    ]
    operands = [qf, kf, vf]
    if triangle:
        in_specs.append(
            pl.BlockSpec((block_q, block_k), lambda *_: (0, 0))
        )
        operands.append(_causal_bias(block_q, block_k, qf, kf, vf))
    res = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs if with_lse else out_specs[0],
        out_shape=tuple(out_shape) if with_lse else out_shape[0],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
        ],
        interpret=interpret,
    )(*operands)

    if with_lse:
        out, m_res, l_res = res
        return _unfold(out, tq, b, h, d), m_res[:, :tq, 0], l_res[:, :tq, 0]
    return _unfold(res, tq, b, h, d)
