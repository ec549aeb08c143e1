"""Attention ops: XLA-composed SDPA + Pallas flash-attention TPU kernel.

Reference mapping: the reference has no fused attention — attention exists
only as composed ops (mul/matmul + softmax + dropout) inside models and the
``operators/fused/`` kernel fusions (SURVEY.md §2.3, §5.7). On TPU the hot
path is a Pallas flash-attention kernel (online softmax, O(S) memory, MXU
tiled) — the analog of the reference's ``fused/`` op family, designed for
the MXU rather than translated.

Layout convention: (batch, num_heads, seq, head_dim) — "BHSD".

Dispatch: :func:`dot_product_attention` picks the Pallas kernel on TPU and
the XLA-composed path elsewhere (CPU tests run the kernel in interpret
mode). Both forward and backward are Pallas kernels (FlashAttention-2
style: the backward recomputes p from the forward's logsumexp in two
kernels, dkv and dq); full (Sq,Sk) biases fall back to the XLA backward so
trainable position biases get gradients.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops.nn import keep_mask

NEG_INF = -1e30  # large-negative instead of -inf: keeps softmax NaN-free
                 # for fully-masked rows (padded queries)


# ---------------------------------------------------------------------------
# XLA-composed reference path
# ---------------------------------------------------------------------------

def scaled_dot_product_attention(q, k, v, *, bias=None, causal=False,
                                 scale: Optional[float] = None,
                                 dropout_rate: float = 0.0,
                                 dropout_key=None):
    """Composed attention in fp32 softmax. q,k,v: (B, H, S, D).

    ``bias`` is additive, broadcastable to (B, H, Sq, Sk) (use NEG_INF for
    masked positions). ``causal`` adds a lower-triangular mask.
    """
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if bias is not None:
        s = s + bias.astype(s.dtype)
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        row = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
        s = jnp.where(col <= row + (sk - sq), s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    # fully-masked rows (every key at NEG_INF): emit 0, not the uniform mean
    # of v — keeps this path consistent with the Pallas flash kernel
    alive = jnp.max(s, axis=-1, keepdims=True) > NEG_INF / 2
    p = jnp.where(alive, p, 0.0)
    if dropout_rate > 0.0 and dropout_key is not None:
        keep = keep_mask(dropout_key, 1.0 - dropout_rate, p.shape)
        p = jnp.where(keep, p / (1.0 - dropout_rate), 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)


def make_padding_bias(pad_mask, dtype=jnp.float32):
    """(B, Sk) bool valid-mask -> additive bias (B, 1, 1, Sk)."""
    return jnp.where(pad_mask, 0.0, NEG_INF).astype(dtype)[:, None, None, :]


# ---------------------------------------------------------------------------
# lax fallback with flash-kernel semantics (the shared-harness fallback)
# ---------------------------------------------------------------------------

def _masked_scores(q, k, bias, *, scale, causal):
    """fp32 score block with the SAME masking the Pallas kernel applies."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32),
                   preferred_element_type=jnp.float32) * scale
    if bias is not None:
        s = s + bias.astype(s.dtype)
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        row = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
        s = jnp.where(col <= row + (sk - sq), s, NEG_INF)
    return s


def _lax_flash_fwd(q, k, v, bias=None, *, scale=None, causal=False,
                   return_lse=False):
    """XLA-composed forward with the flash kernel's exact conventions:
    fully-masked rows emit 0 (not a uniform mean of v) and, with
    ``return_lse``, a ~NEG_INF logsumexp — so ring attention's
    streaming logaddexp merge works identically on the fallback path.
    This is the registered lax fallback of the ``flash_attention``
    kernel (:mod:`paddle_tpu.kernels`)."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if bias is not None and bias.ndim < 4:
        bias = bias.reshape((1,) * (4 - bias.ndim) + bias.shape)
    s = _masked_scores(q, k, bias, scale=scale, causal=causal)
    m = jnp.max(s, axis=-1)                         # (B,H,Sq)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    denom = jnp.where(l == 0.0, 1.0, l)
    alive = m > NEG_INF / 2
    out = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    out = jnp.where(alive[..., None], out / denom[..., None], 0.0)
    out = out.astype(q.dtype)
    if return_lse:
        return out, m + jnp.log(denom)              # dead rows: ~NEG_INF
    return out


def _lax_flash_block_bwd(q, k, v, bias, out, lse, g, *, scale, causal):
    """XLA-composed FlashAttention-2 block backward against a GLOBAL
    logsumexp: recompute p = exp(s - lse), then ds = p(dp - delta)scale.
    Mirrors :func:`_flash_bwd`'s two Pallas kernels, so ring attention's
    backward merge is backend-independent (grads accumulate across ring
    blocks against the merged forward's lse on either path)."""
    s = _masked_scores(q, k, bias, scale=scale, causal=causal)
    p = jnp.exp(s - lse[..., None])
    # fully-masked rows: lse ~ NEG_INF would turn exp into garbage ones
    p = jnp.where(lse[..., None] <= NEG_INF / 2, 0.0, p)
    g32 = g.astype(jnp.float32)
    delta = jnp.sum(g32 * out.astype(jnp.float32), axis=-1)   # (B,H,Sq)
    dp = jnp.einsum("bhqd,bhkd->bhqk", g32, v.astype(jnp.float32))
    ds = p * (dp - delta[..., None]) * scale
    dq = jnp.einsum("bhqk,bhkd->bhqd", ds, k.astype(jnp.float32))
    dk = jnp.einsum("bhqk,bhqd->bhkd", ds, q.astype(jnp.float32))
    dv = jnp.einsum("bhqk,bhqd->bhkd", p, g32)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


# ---------------------------------------------------------------------------
# Pallas flash-attention forward kernel
# ---------------------------------------------------------------------------

def _flash_fwd_kernel(q_ref, k_ref, v_ref, bias_ref, o_ref, lse_ref,
                      m_scr, l_scr, acc_scr, *,
                      scale, causal, block_q, block_k, seq_q, seq_k):
    """Grid (BH, nq, nk); online-softmax accumulation over kv blocks.

    Scratch: m (bq,128) running max, l (bq,128) running denom (values
    broadcast across lanes), acc (bq, D) fp32 accumulator.
    """
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def _body():
        q = q_ref[0].astype(jnp.float32)           # (bq, D)
        k = k_ref[0].astype(jnp.float32)           # (bk, D)
        # zero padded kv rows (pallas pads out-of-bounds blocks with
        # garbage/NaN; 0*NaN would poison the p@v contraction)
        kv_valid = (ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_k, 1), 0)) < seq_k
        k = jnp.where(kv_valid, k, 0.0)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (bq, bk)
        if bias_ref is not None:
            s = s + bias_ref[0].astype(jnp.float32)
        row = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        col = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        if causal:
            s = jnp.where(col <= row + (seq_k - seq_q), s, NEG_INF)
        # mask out padding blocks past the true seq end (grid is padded up)
        s = jnp.where(col < seq_k, s, NEG_INF)

        m_prev = m_scr[...]                        # (bq, 128)
        l_prev = l_scr[...]
        m_cur = jnp.max(s, axis=1, keepdims=True)  # (bq, 1)
        m_next = jnp.maximum(m_prev, m_cur)        # broadcast over lanes
        alpha = jnp.exp(m_prev - m_next)           # (bq, 128)
        p = jnp.exp(s - m_next[:, :1])             # (bq, bk)
        l_next = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        m_scr[...] = m_next
        l_scr[...] = l_next
        v = jnp.where(kv_valid, v_ref[0].astype(jnp.float32), 0.0)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)    # (bq, D)
        acc_scr[...] = acc_scr[...] * alpha[:, :1] + pv

    if causal:
        # skip kv blocks fully above the diagonal
        below = ki * block_k <= qi * block_q + (block_q - 1) + (seq_k - seq_q)
        pl.when(below)(_body)
    else:
        _body()

    @pl.when(ki == nk - 1)
    def _finish():
        denom = l_scr[...][:, :1]
        denom = jnp.where(denom == 0.0, 1.0, denom)
        # fully-masked rows (every key at NEG_INF bias): m never rises above
        # ~NEG_INF, p=exp(s-m)=1 and the naive result would be a uniform mean
        # of v. Zero them so the forward matches the backward, which drops
        # those rows' cotangents via the same lse <= NEG_INF/2 test.
        alive = m_scr[...][:, :1] > NEG_INF / 2
        o_ref[0] = jnp.where(alive, acc_scr[...] / denom, 0.0).astype(
            o_ref.dtype)
        if lse_ref is not None:  # logsumexp row stats for the backward
            lse_ref[0, 0] = (m_scr[...][:, 0] + jnp.log(denom[:, 0]))


def _prep_bias(bias, b, h, sq, sk):
    """Normalize bias into (mode, array, BlockSpec-args). Key-only biases
    (Sq dim == 1) get a sublane-padded (bh, 8, sk) layout."""
    bh = b * h
    bias = jnp.broadcast_to(bias, (b, h, sq, sk)) \
        if bias.shape[2] not in (1,) else bias
    if bias.shape[2] == 1:
        br = jnp.broadcast_to(bias, (b, h, 1, sk)).reshape(bh, 1, sk)
        br = jnp.broadcast_to(br[:, 0:1, :], (bh, 8, sk))
        return "key", br
    return "full", bias.reshape(bh, sq, sk)


def _flash_fwd(q, k, v, bias, *, scale, causal, block_q, block_k, interpret,
               return_lse=False):
    b, h, sq, d = q.shape
    sk = k.shape[2]
    bq = min(block_q, sq)
    bk = min(block_k, sk)
    nq = pl.cdiv(sq, bq)
    nk = pl.cdiv(sk, bk)
    bh = b * h
    qr = q.reshape(bh, sq, d)
    kr = k.reshape(bh, sk, d)
    vr = v.reshape(bh, sk, d)

    in_specs = [
        pl.BlockSpec((1, bq, d), lambda g, i, j: (g, i, 0)),
        pl.BlockSpec((1, bk, d), lambda g, i, j: (g, j, 0)),
        pl.BlockSpec((1, bk, d), lambda g, i, j: (g, j, 0)),
    ]
    args = [qr, kr, vr]
    if bias is not None:
        bias_mode, br = _prep_bias(bias, b, h, sq, sk)
        if bias_mode == "key":
            in_specs.append(
                pl.BlockSpec((1, 8, bk), lambda g, i, j: (g, 0, j)))
        else:
            in_specs.append(
                pl.BlockSpec((1, bq, bk), lambda g, i, j: (g, i, j)))
        args.append(br)
    else:
        bias_mode = None

    kernel = functools.partial(
        _flash_kernel_dispatch, bias_mode=bias_mode, with_lse=return_lse,
        scale=scale, causal=causal, block_q=bq, block_k=bk,
        seq_q=sq, seq_k=sk)

    scratch = [
        pltpu.VMEM((bq, 128), jnp.float32),
        pltpu.VMEM((bq, 128), jnp.float32),
        pltpu.VMEM((bq, d), jnp.float32),
    ]
    out_specs = pl.BlockSpec((1, bq, d), lambda g, i, j: (g, i, 0))
    out_shape = jax.ShapeDtypeStruct((bh, sq, d), q.dtype)
    if return_lse:
        # (bh, 1, sq) layout: TPU needs the sublane dim to equal the full
        # array dim when it is not a multiple of 8
        out_specs = [out_specs,
                     pl.BlockSpec((1, 1, bq), lambda g, i, j: (g, 0, i))]
        out_shape = [out_shape,
                     jax.ShapeDtypeStruct((bh, 1, sq), jnp.float32)]
    grid = (bh, nq, nk)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ) if not interpret else None,
        interpret=interpret,
    )(*args)
    if return_lse:
        o, lse = out
        return o.reshape(b, h, sq, d), lse.reshape(b, h, sq)
    return out.reshape(b, h, sq, d)




def _flash_kernel_dispatch(*refs, bias_mode, with_lse, **kw):
    refs = list(refs)
    q_ref, k_ref, v_ref = refs[:3]
    i = 3
    b_ref = None
    if bias_mode is not None:
        b_ref = refs[i]
        i += 1
        if bias_mode == "key":
            b_ref = _KeyBias(b_ref)
    o_ref = refs[i]
    i += 1
    lse_ref = refs[i] if with_lse else None
    if with_lse:
        i += 1
    m, l, acc = refs[i:]
    _flash_fwd_kernel(q_ref, k_ref, v_ref, b_ref, o_ref, lse_ref,
                      m, l, acc, **kw)


class _KeyBias:
    """Adapts a (1, 8, bk) key-bias block to the (bq, bk) read the kernel
    does: row 0 broadcast over queries."""

    def __init__(self, ref):
        self._ref = ref

    def __getitem__(self, idx):
        return self._ref[0][0:1, :]  # (1, bk), broadcasts against (bq, bk)

    def astype(self, dt):  # pragma: no cover - not used
        raise TypeError


# ---------------------------------------------------------------------------
# Pallas flash-attention backward (FlashAttention-2 style two-kernel split)
# ---------------------------------------------------------------------------

def _recompute_p(q, k, bias_blk, lse, ki, qi, *, scale, causal,
                 block_q, block_k, seq_q, seq_k):
    """Recompute the probability block p = exp(s - lse) with the SAME
    masking as the forward (so p matches bit-for-bit up to fp assoc)."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if bias_blk is not None:
        s = s + bias_blk
    row = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    col = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    if causal:
        s = jnp.where(col <= row + (seq_k - seq_q), s, NEG_INF)
    s = jnp.where(col < seq_k, s, NEG_INF)
    p = jnp.exp(s - lse[:, None])
    # zero padded q rows (their lse/do are garbage)
    p = jnp.where(row < seq_q, p, 0.0)
    # fully-masked rows: lse sits at ~NEG_INF (log-denominator cancelled by
    # fp rounding), so exp(s - lse) would come out 1 per column instead of
    # 1/seq_k — inflating dk/dv for every key by seq_k. Zero such rows.
    p = jnp.where(lse[:, None] <= NEG_INF / 2, 0.0, p)
    return p


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          bias_ref, dk_ref, dv_ref,
                          dk_scr, dv_scr, *,
                          scale, causal, block_q, block_k, seq_q, seq_k):
    """Grid (BH, nk, nq): for a fixed kv block, stream q blocks and
    accumulate dk = sum ds^T q, dv = sum p^T do."""
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def _body():
        row_valid = (qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, 1), 0)) < seq_q
        kv_valid = (ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_k, 1), 0)) < seq_k
        q = jnp.where(row_valid, q_ref[0].astype(jnp.float32), 0.0)
        k = jnp.where(kv_valid, k_ref[0].astype(jnp.float32), 0.0)
        v = jnp.where(kv_valid, v_ref[0].astype(jnp.float32), 0.0)
        do = jnp.where(row_valid, do_ref[0].astype(jnp.float32), 0.0)
        lse = jnp.where(row_valid[:, 0], lse_ref[0, 0], 0.0)
        delta = jnp.where(row_valid[:, 0], delta_ref[0, 0], 0.0)
        bias_blk = (bias_ref[0].astype(jnp.float32)
                    if bias_ref is not None else None)

        p = _recompute_p(q, k, bias_blk, lse, ki, qi, scale=scale,
                         causal=causal, block_q=block_q, block_k=block_k,
                         seq_q=seq_q, seq_k=seq_k)
        # dv += p^T do   (contract over q rows)
        dv_scr[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        # dp = do v^T ; ds = p * (dp - delta) * scale
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * scale
        # dk += ds^T q
        dk_scr[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        below = ki * block_k <= qi * block_q + (block_q - 1) + (seq_k - seq_q)
        pl.when(below)(_body)
    else:
        _body()

    @pl.when(qi == nq - 1)
    def _finish():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         bias_ref, dq_ref, dq_scr, *,
                         scale, causal, block_q, block_k, seq_q, seq_k):
    """Grid (BH, nq, nk): for a fixed q block, stream kv blocks and
    accumulate dq = sum ds k."""
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    def _body():
        row_valid = (qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, 1), 0)) < seq_q
        kv_valid = (ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_k, 1), 0)) < seq_k
        q = jnp.where(row_valid, q_ref[0].astype(jnp.float32), 0.0)
        k = jnp.where(kv_valid, k_ref[0].astype(jnp.float32), 0.0)
        v = jnp.where(kv_valid, v_ref[0].astype(jnp.float32), 0.0)
        do = jnp.where(row_valid, do_ref[0].astype(jnp.float32), 0.0)
        lse = jnp.where(row_valid[:, 0], lse_ref[0, 0], 0.0)
        delta = jnp.where(row_valid[:, 0], delta_ref[0, 0], 0.0)
        bias_blk = (bias_ref[0].astype(jnp.float32)
                    if bias_ref is not None else None)

        p = _recompute_p(q, k, bias_blk, lse, ki, qi, scale=scale,
                         causal=causal, block_q=block_q, block_k=block_k,
                         seq_q=seq_q, seq_k=seq_k)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * scale
        dq_scr[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        below = ki * block_k <= qi * block_q + (block_q - 1) + (seq_k - seq_q)
        pl.when(below)(_body)
    else:
        _body()

    @pl.when(ki == nk - 1)
    def _finish():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _flash_bwd(q, k, v, bias, out, lse, g, *, scale, causal,
               block_q, block_k, interpret):
    """Pallas backward: returns (dq, dk, dv). Bias grads are not computed
    here (callers with trainable biases use the XLA path)."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    bq = min(block_q, sq)
    bk = min(block_k, sk)
    nq = pl.cdiv(sq, bq)
    nk = pl.cdiv(sk, bk)
    bh = b * h
    qr = q.reshape(bh, sq, d)
    kr = k.reshape(bh, sk, d)
    vr = v.reshape(bh, sk, d)
    dor = g.reshape(bh, sq, d)
    lser = lse.reshape(bh, 1, sq)
    # delta = rowsum(do * o) — cheap elementwise, let XLA fuse it
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1).reshape(bh, 1, sq)

    bias_mode = None
    bias_args = []
    if bias is not None:
        bias_mode, br = _prep_bias(bias, b, h, sq, sk)
        bias_args = [br]

    def bias_spec(for_dkv):
        if bias_mode == "key":
            return [pl.BlockSpec((1, 8, bk),
                                 (lambda g_, i, j: (g_, 0, i)) if for_dkv
                                 else (lambda g_, i, j: (g_, 0, j)))]
        if bias_mode == "full":
            return [pl.BlockSpec((1, bq, bk),
                                 (lambda g_, i, j: (g_, j, i)) if for_dkv
                                 else (lambda g_, i, j: (g_, i, j)))]
        return []

    common = dict(scale=scale, causal=causal, block_q=bq, block_k=bk,
                  seq_q=sq, seq_k=sk)
    cparams = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
    ) if not interpret else None

    # dk/dv: grid (bh, nk, nq) — i = kv block, j = q block
    dkv_kernel = functools.partial(
        _bwd_dispatch, which="dkv", has_bias=bias_mode is not None,
        mode=bias_mode, **common)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(bh, nk, nq),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda g_, i, j: (g_, j, 0)),   # q
            pl.BlockSpec((1, bk, d), lambda g_, i, j: (g_, i, 0)),   # k
            pl.BlockSpec((1, bk, d), lambda g_, i, j: (g_, i, 0)),   # v
            pl.BlockSpec((1, bq, d), lambda g_, i, j: (g_, j, 0)),   # do
            pl.BlockSpec((1, 1, bq), lambda g_, i, j: (g_, 0, j)),   # lse
            pl.BlockSpec((1, 1, bq), lambda g_, i, j: (g_, 0, j)),   # delta
            *bias_spec(for_dkv=True),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, d), lambda g_, i, j: (g_, i, 0)),
            pl.BlockSpec((1, bk, d), lambda g_, i, j: (g_, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sk, d), q.dtype),
            jax.ShapeDtypeStruct((bh, sk, d), q.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        compiler_params=cparams,
        interpret=interpret,
    )(qr, kr, vr, dor, lser, delta, *bias_args)

    dq_kernel = functools.partial(
        _bwd_dispatch, which="dq", has_bias=bias_mode is not None,
        mode=bias_mode, **common)
    dq = pl.pallas_call(
        dq_kernel,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda g_, i, j: (g_, i, 0)),   # q
            pl.BlockSpec((1, bk, d), lambda g_, i, j: (g_, j, 0)),   # k
            pl.BlockSpec((1, bk, d), lambda g_, i, j: (g_, j, 0)),   # v
            pl.BlockSpec((1, bq, d), lambda g_, i, j: (g_, i, 0)),   # do
            pl.BlockSpec((1, 1, bq), lambda g_, i, j: (g_, 0, i)),   # lse
            pl.BlockSpec((1, 1, bq), lambda g_, i, j: (g_, 0, i)),   # delta
            *bias_spec(for_dkv=False),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda g_, i, j: (g_, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=cparams,
        interpret=interpret,
    )(qr, kr, vr, dor, lser, delta, *bias_args)

    shape4 = (b, h, sq, d)
    return (dq.reshape(shape4), dk.reshape(b, h, sk, d),
            dv.reshape(b, h, sk, d))


def _bwd_dispatch(*refs, which, has_bias, mode, **kw):
    refs = list(refs)
    ins, rest = refs[:6], refs[6:]
    if has_bias:
        b_ref, rest = rest[0], rest[1:]
        if mode == "key":
            b_ref = _KeyBias(b_ref)
    else:
        b_ref = None
    if which == "dkv":
        dk_ref, dv_ref, dk_scr, dv_scr = rest
        _flash_bwd_dkv_kernel(*ins, b_ref, dk_ref, dv_ref, dk_scr, dv_scr,
                              **kw)
    else:
        dq_ref, dq_scr = rest
        _flash_bwd_dq_kernel(*ins, b_ref, dq_ref, dq_scr, **kw)


# ---------------------------------------------------------------------------
# public flash_attention with custom_vjp
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def flash_attention(q, k, v, bias=None, causal=False,
                    scale: Optional[float] = None,
                    block_q: int = 512, block_k: int = 512,
                    interpret: bool = False):
    """Flash attention (Pallas fwd + bwd). q,k,v: (B,H,S,D); bias additive,
    broadcastable to (B,H,Sq,Sk).

    Backward: FlashAttention-2-style Pallas kernels (dkv + dq, recomputing
    p from the forward's logsumexp). Key-padding biases (Sq dim == 1) are
    treated as constants (zero cotangent); full (Sq,Sk) biases take the
    XLA recompute path so trainable relative-position biases get grads."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if bias is not None and bias.ndim < 4:  # accept broadcastable ranks
        bias = bias.reshape((1,) * (4 - bias.ndim) + bias.shape)
    return _flash_fwd(q, k, v, bias, scale=scale, causal=causal,
                      block_q=block_q, block_k=block_k, interpret=interpret)


def _flash_vjp_fwd(q, k, v, bias, causal, scale, block_q, block_k, interpret):
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    bias4 = bias
    if bias is not None and bias.ndim < 4:
        bias4 = bias.reshape((1,) * (4 - bias.ndim) + bias.shape)
    out, lse = _flash_fwd(q, k, v, bias4, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k,
                          interpret=interpret, return_lse=True)
    # save the ORIGINAL bias so its cotangent matches the caller's shape
    return out, (q, k, v, bias, out, lse)


def _flash_vjp_bwd(causal, scale, block_q, block_k, interpret, res, g):
    q, k, v, bias, out, lse = res
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    sq_dim = (bias.shape[-2] if bias is not None and bias.ndim >= 2 else 1)
    if bias is not None and sq_dim != 1:
        # a full (.., Sq, Sk) bias may be trainable (relative-position
        # biases): take the XLA recompute path, which yields its grad in
        # the caller's original bias shape
        def ref(q, k, v, bias):
            return scaled_dot_product_attention(q, k, v, bias=bias,
                                                causal=causal, scale=scale)

        _, vjp = jax.vjp(ref, q, k, v, bias)
        return vjp(g)
    bias4 = bias
    if bias is not None and bias.ndim < 4:
        bias4 = bias.reshape((1,) * (4 - bias.ndim) + bias.shape)
    dq, dk, dv = _flash_bwd(q, k, v, bias4, out, lse, g, scale=scale,
                            causal=causal, block_q=block_q, block_k=block_k,
                            interpret=interpret)
    dbias = jnp.zeros_like(bias) if bias is not None else None
    return dq, dk, dv, dbias


flash_attention.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


# ---------------------------------------------------------------------------
# dispatch
# ---------------------------------------------------------------------------

_dropout_exit_logged = False


def _note_dropout_exit(impl: str, dropout_rate: float):
    """Attention dropout has no kernel path: the composed XLA attention
    runs instead. Say so, once, so a trainer with attention dropout on
    does not pass for a kernel run (``kernel_dispatch_total`` then
    counts no ``flash_attention``)."""
    global _dropout_exit_logged
    if not _dropout_exit_logged:
        _dropout_exit_logged = True
        import logging
        logging.getLogger("paddle_tpu").warning(
            "attention: dropout_rate=%g has no flash-kernel path; "
            "impl=%r runs the composed XLA attention instead",
            dropout_rate, impl)


def resolve_attention_impl(impl: str, dropout_rate: float = 0.0) -> str:
    """What :func:`dot_product_attention` will run for ``impl``:
    ``"xla"`` (the composed path), ``"flash"`` or ``"flash_interpret"``.
    ``"auto"`` is flash on a TPU backend and xla on any other; attention
    dropout always takes the composed path (and says so)."""
    if impl == "auto":
        from paddle_tpu import kernels
        impl = "flash" if kernels.on_tpu() else "xla"
    if impl != "xla" and dropout_rate > 0.0:
        _note_dropout_exit(impl, dropout_rate)
        return "xla"
    return impl


def dot_product_attention(q, k, v, *, bias=None, causal=False,
                          scale=None, dropout_rate=0.0, dropout_key=None,
                          impl: str = "auto"):
    """Attention entry point used by nn layers.

    impl: "auto" (flash on a TPU backend, xla on any other), "flash",
    "xla", "flash_interpret" (tests). The flash impls dispatch through
    the shared kernel registry (:mod:`paddle_tpu.kernels`): block sizes
    resolve from the autotuner cache at trace time, and
    ``kernel_dispatch_total`` counts what ran. Attention dropout always
    takes the composed path (see :func:`_note_dropout_exit`).
    """
    impl = resolve_attention_impl(impl, dropout_rate)
    if impl == "xla":
        return scaled_dot_product_attention(
            q, k, v, bias=bias, causal=causal, scale=scale,
            dropout_rate=dropout_rate, dropout_key=dropout_key)
    return flash_attention_dispatch(q, k, v, bias, impl=impl,
                                    causal=causal, scale=scale)


def flash_attention_dispatch(q, k, v, bias=None, *, impl, causal=False,
                             scale=None):
    """The flash kernel through the kernel registry, for an ``impl``
    already resolved to ``"flash"`` or ``"flash_interpret"``."""
    from paddle_tpu import kernels
    return kernels.dispatch(
        "flash_attention", q, k, v, bias,
        impl="pallas_interpret" if impl == "flash_interpret" else "pallas",
        causal=causal, scale=scale)


# ---------------------------------------------------------------------------
# kernel-registry entry (paddle_tpu.kernels)
# ---------------------------------------------------------------------------

def _flash_kernel_pallas(q, k, v, bias=None, *, block_sizes, interpret,
                         causal=False, scale=None):
    return flash_attention(q, k, v, bias, causal, scale,
                           block_sizes.get("block_q", 512),
                           block_sizes.get("block_k", 512), interpret)


def _flash_kernel_lax(q, k, v, bias=None, *, causal=False, scale=None):
    return _lax_flash_fwd(q, k, v, bias, scale=scale, causal=causal)


def _flash_kernel_reference(q, k, v, bias=None, *, causal=False,
                            scale=None):
    return scaled_dot_product_attention(q, k, v, bias=bias, causal=causal,
                                        scale=scale)


def _flash_sample_inputs(seed):
    b, h, s, d = ((1, 2, 64, 32), (2, 2, 128, 64), (1, 4, 320, 64))[
        seed % 3]
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    return ((jax.random.normal(kq, (b, h, s, d), jnp.float32),
             jax.random.normal(kk, (b, h, s, d), jnp.float32),
             jax.random.normal(kv, (b, h, s, d), jnp.float32)),
            {"causal": True})


def _flash_tune_signature(args, kwargs):
    q, k = args[0], args[1]
    b, h, sq, d = q.shape
    return (("bh", b * h), ("q", sq), ("k", k.shape[2]), ("d", d))


def _flash_vmem_estimate(args, kwargs, blocks):
    d = args[0].shape[-1]
    bq = blocks.get("block_q", 512)
    bk = blocks.get("block_k", 512)
    # fp32 working set: q + acc, k + v, s + p, m/l lane scratch
    return 4 * (2 * bq * d + 2 * bk * d + 2 * bq * bk + 2 * bq * 128)


def _register_flash_kernel():
    from paddle_tpu import kernels
    kernels.register(kernels.KernelSpec(
        name="flash_attention",
        contract=kernels.KernelContract(
            version=1,
            arg_layouts={"q": "(B,H,Sq,D)", "k": "(B,H,Sk,D)",
                         "v": "(B,H,Sk,D)",
                         "bias": "(B,H,Sq,Sk) additive, optional"},
            out_layout="(B,H,Sq,D)",
            grid="(B*H, cdiv(Sq,block_q), cdiv(Sk,block_k)) "
                 "kv-arbitrary online softmax",
            block_candidates={"block_q": (512, 256, 128),
                              "block_k": (512, 256, 128)},
            atol=2e-5, rtol=2e-5),
        pallas_fn=_flash_kernel_pallas,
        lax_fn=_flash_kernel_lax,
        reference_fn=_flash_kernel_reference,
        sample_inputs=_flash_sample_inputs,
        pallas_sites=("paddle_tpu.ops.attention:_flash_fwd",
                      "paddle_tpu.ops.attention:_flash_bwd"),
        tune_signature=_flash_tune_signature,
        vmem_estimate=_flash_vmem_estimate))


_register_flash_kernel()
