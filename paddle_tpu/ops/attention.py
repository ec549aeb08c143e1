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
mode). The forward and the backward are ONE Pallas kernel each. The
backward recomputes p from the forward's logsumexp once a block pair and
takes dV, dK and dQ from it: five products. A body emits only the work
the call's static shape needs (see "Pallas flash-attention kernels"
below): keys in one block take a whole softmax and not an online one, a
head in one block pair writes its gradients without scratch, masks and
iotas exist only where a sequence does not divide into its blocks or the
call is causal, and the matrix unit takes the operands in the dtype they
come in. ``flash_attention_lowerings_total`` counts which body a trace
took. Full (Sq,Sk) biases fall back to the XLA backward so trainable
position biases get gradients.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.observability import registry as _obs_registry
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
    Mirrors :func:`_flash_bwd`'s Pallas kernel, so ring attention's
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
# Pallas flash-attention kernels
# ---------------------------------------------------------------------------
#
# One algorithm, four bodies. What picks a body, and every mask inside
# it, is static when the call is traced: the sequence lengths against
# the blocks, ``causal``, the bias mode and the operands' dtype. A body
# holds only the work its shape needs:
#
# - the matrix unit takes q, k, v and dO in the dtype they come in
#   (bf16 under the trainer's policy) and accumulates in float32; p and
#   dS are rounded to that dtype for their products, as the composed
#   path does. Scores, softmax statistics and accumulators are float32.
# - the softmax scale is folded into q, a (bq, Dh) multiply where the
#   scores would take a (bq, bk) one.
# - key rows are zeroed and columns masked only where ``sk % bk != 0``,
#   query rows only where ``sq % bq != 0``, and an iota exists only
#   where one of those or ``causal`` needs it.
# - rows that must add nothing to a gradient (every key masked, or past
#   the end of q) get p = 0 through their logsumexp, on a (bq, 1)
#   column: exp(s - 1e30) is 0.0 exactly.

_NT = (((1,), (1,)), ((), ()))   # a @ b.T
_NN = (((1,), (0,)), ((), ()))   # a @ b
_TN = (((0,), (0,)), ((), ()))   # a.T @ b

_LOWERINGS = _obs_registry.counter(
    "flash_attention_lowerings_total",
    "flash kernel bodies chosen, by pass, body and masks; counted where "
    "the shape decides, at trace time")


def _dot(a, b, dims):
    """float32 accumulation of operands as they are. Operands narrower
    than float32 are what the matrix unit takes whole, so they say so:
    under a process-wide ``highest`` (the tests' conftest) Mosaic would
    refuse them; float32 operands follow the process's default."""
    narrow = a.dtype.itemsize < 4
    return jax.lax.dot_general(
        a, b, dims, preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.DEFAULT if narrow else None)


@dataclasses.dataclass(frozen=True)
class _Shape:
    """What a flash body may branch on: all of it static."""
    seq_q: int
    seq_k: int
    block_q: int
    block_k: int
    causal: bool

    @classmethod
    def of(cls, q, k, block_q, block_k, causal):
        sq, sk = q.shape[2], k.shape[2]
        return cls(sq, sk, min(block_q, sq), min(block_k, sk), bool(causal))

    @property
    def nq(self):
        return pl.cdiv(self.seq_q, self.block_q)

    @property
    def nk(self):
        return pl.cdiv(self.seq_k, self.block_k)

    @property
    def ragged_q(self):
        return self.seq_q % self.block_q != 0

    @property
    def ragged_k(self):
        return self.seq_k % self.block_k != 0

    @property
    def masks(self):
        if self.causal:
            return "causal"
        return "ragged" if self.ragged_q or self.ragged_k else "none"

    def below_diagonal(self, qi, ki):
        """False for a key block wholly above the causal diagonal."""
        return (ki * self.block_k <= qi * self.block_q + (self.block_q - 1)
                + (self.seq_k - self.seq_q))

    def count(self, which, single):
        _LOWERINGS.inc(**{"pass": which, "masks": self.masks,
                          "body": "single_block" if single else "blocked"})


def _valid(block, seq, i):
    """(block, 1) bool: which rows of block ``i`` lie inside ``seq``."""
    return i * block + jax.lax.broadcasted_iota(
        jnp.int32, (block, 1), 0) < seq


def _scaled(q, scale):
    """The scale folded into q, in float32 and rounded once to q's own
    dtype (exact for a power of two: 1/8 at a head width of 64)."""
    return (q.astype(jnp.float32) * scale).astype(q.dtype)


def _keys(k_ref, v_ref, sh, ki):
    """k and v as they lie; rows past ``seq_k`` zeroed (a block past the
    end is padded with garbage, and 0 * NaN would poison p @ v)."""
    k, v = k_ref[0], v_ref[0]
    if sh.ragged_k:
        valid = _valid(sh.block_k, sh.seq_k, ki)
        k = jnp.where(valid, k, jnp.zeros_like(k))
        v = jnp.where(valid, v, jnp.zeros_like(v))
    return k, v


def _bias_block(bias_ref, bias_mode):
    """float32 (1, bk) for a key bias (row 0 of its sublane-padded
    block), (bq, bk) for a full one, None for none."""
    if bias_mode is None:
        return None
    blk = bias_ref[0][0:1, :] if bias_mode == "key" else bias_ref[0]
    return blk.astype(jnp.float32)


def _scores(q, k, bias, sh, qi, ki):
    """float32 (bq, bk) scores of one block pair; ``q`` carries the
    scale. Masked to NEG_INF above the causal diagonal and past
    ``seq_k``, where the shape has either."""
    s = _dot(q, k, _NT)
    if bias is not None:
        s = s + bias
    if sh.causal or sh.ragged_k:
        shape = (sh.block_q, sh.block_k)
        col = ki * sh.block_k + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        keep = col < sh.seq_k if sh.ragged_k else None
        if sh.causal:
            row = qi * sh.block_q + jax.lax.broadcasted_iota(
                jnp.int32, shape, 0)
            seen = col <= row + (sh.seq_k - sh.seq_q)
            keep = seen if keep is None else keep & seen
        s = jnp.where(keep, s, NEG_INF)
    return s


def _write_rows(o_ref, lse_ref, acc, m, l):
    """acc / l and the logsumexp, from (bq, 1) columns m and l. A row
    with every key masked has m ~ NEG_INF and p = 1 a column, the
    uniform mean of v: it is zeroed, so that the forward agrees with the
    backward, which drops such a row by the same test of its lse."""
    alive = m > NEG_INF / 2
    o_ref[0] = jnp.where(alive, acc / l, 0.0).astype(o_ref.dtype)
    if lse_ref is not None:
        lse_ref[0, 0] = (m + jnp.log(l))[:, 0]


def _flash_fwd_single_kernel(q_ref, k_ref, v_ref, bias_ref, o_ref, lse_ref,
                             *, sh, scale, bias_mode):
    """Grid (BH, nq, 1): the keys are ONE block, so the softmax is
    whole: max, exp, sum, one product, one divide. No scratch."""
    qi = pl.program_id(1)
    k, v = _keys(k_ref, v_ref, sh, 0)
    s = _scores(_scaled(q_ref[0], scale), k,
                _bias_block(bias_ref, bias_mode), sh, qi, 0)
    m = jnp.max(s, axis=1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=1, keepdims=True)       # >= 1: the max is in it
    _write_rows(o_ref, lse_ref, _dot(p.astype(v.dtype), v, _NN), m, l)


def _flash_fwd_blocked_kernel(q_ref, k_ref, v_ref, bias_ref, o_ref, lse_ref,
                              m_scr, l_scr, acc_scr, *, sh, scale, bias_mode):
    """Grid (BH, nq, nk); online-softmax accumulation over kv blocks.

    Scratch: m (bq,128) running max, l (bq,128) running denom (values
    broadcast across lanes), acc (bq, D) fp32 accumulator.
    """
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def _body():
        k, v = _keys(k_ref, v_ref, sh, ki)
        s = _scores(_scaled(q_ref[0], scale), k,
                    _bias_block(bias_ref, bias_mode), sh, qi, ki)
        m_prev = m_scr[...]                        # (bq, 128)
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_next)           # (bq, 128)
        p = jnp.exp(s - m_next[:, :1])             # (bq, bk)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        m_scr[...] = m_next
        acc_scr[...] = (acc_scr[...] * alpha[:, :1]
                        + _dot(p.astype(v.dtype), v, _NN))

    if sh.causal:
        pl.when(sh.below_diagonal(qi, ki))(_body)
    else:
        _body()

    @pl.when(ki == sh.nk - 1)
    def _finish():
        l = l_scr[...][:, :1]
        # a row whose every block was skipped has summed nothing
        _write_rows(o_ref, lse_ref, acc_scr[...], m_scr[...][:, :1],
                    jnp.where(l == 0.0, 1.0, l))


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


def _bias_operand(bias, q, k, sh, key_map, full_map):
    """(mode, specs, arrays) of a call's bias operand: nothing for no
    bias, else its block under the index map of its mode."""
    if bias is None:
        return None, [], []
    b, h, sq, _ = q.shape
    mode, br = _prep_bias(bias, b, h, sq, k.shape[2])
    spec = (pl.BlockSpec((1, 8, sh.block_k), key_map) if mode == "key"
            else pl.BlockSpec((1, sh.block_q, sh.block_k), full_map))
    return mode, [spec], [br]


def _optional_refs(body, n_in, has_bias, n_out):
    """pallas hands a kernel its refs in one flat list: ``n_in`` inputs,
    the bias block if the call has one, ``n_out`` outputs of the
    ``len(n_out)`` the body names (a False is handed over as None), then
    the scratch."""
    def kernel(*refs):
        refs = list(refs)
        ins = [refs.pop(0) for _ in range(n_in)]
        bias_ref = refs.pop(0) if has_bias else None
        outs = [refs.pop(0) if there else None for there in n_out]
        body(*ins, bias_ref, *outs, *refs)
    return kernel


def _flash_fwd(q, k, v, bias, *, scale, causal, block_q, block_k, interpret,
               return_lse=False):
    b, h, sq, d = q.shape
    sk = k.shape[2]
    sh = _Shape.of(q, k, block_q, block_k, causal)
    bq, bk = sh.block_q, sh.block_k
    single = sh.nk == 1
    sh.count("fwd", single)
    bh = b * h

    bias_mode, bias_specs, bias_args = _bias_operand(
        bias, q, k, sh, lambda g, i, j: (g, 0, j), lambda g, i, j: (g, i, j))
    in_specs = [
        pl.BlockSpec((1, bq, d), lambda g, i, j: (g, i, 0)),
        pl.BlockSpec((1, bk, d), lambda g, i, j: (g, j, 0)),
        pl.BlockSpec((1, bk, d), lambda g, i, j: (g, j, 0)),
        *bias_specs]
    args = [q.reshape(bh, sq, d), k.reshape(bh, sk, d), v.reshape(bh, sk, d),
            *bias_args]

    body = functools.partial(
        _flash_fwd_single_kernel if single else _flash_fwd_blocked_kernel,
        sh=sh, scale=scale, bias_mode=bias_mode)
    scratch = [] if single else [
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
    out = pl.pallas_call(
        _optional_refs(body, 3, bias_mode is not None, (True, return_lse)),
        grid=(bh, sh.nq, sh.nk),
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


# ---------------------------------------------------------------------------
# Pallas flash-attention backward: ONE kernel, five products a block pair
# ---------------------------------------------------------------------------

def _bwd_pair(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, bias_ref,
              sh, scale, bias_mode, qi, ki):
    """One block pair of the backward against a given logsumexp: p
    ONCE (the forward's masking, so p matches it up to fp association),
    dP = dO V^T ONCE, dS = p (dP - delta), and the three products they
    feed. Returns this pair's float32 (dq, dk, dv)."""
    q = _scaled(q_ref[0], scale)
    do = do_ref[0]
    k, v = _keys(k_ref, v_ref, sh, ki)
    bias = _bias_block(bias_ref, bias_mode)
    lse = lse_ref[0, 0][:, None]                   # (bq, 1)
    delta = delta_ref[0, 0][:, None]
    # a row with every key masked has lse ~ NEG_INF (the log-denominator
    # lost to rounding): exp(s - lse) would be 1 a column, not 1/seq_k,
    # inflating dk and dv of every key. Such a row gets p = 0.
    dead = lse <= NEG_INF / 2
    if sh.ragged_q:                # rows past seq_q hold garbage
        valid = _valid(sh.block_q, sh.seq_q, qi)
        q = jnp.where(valid, q, jnp.zeros_like(q))
        do = jnp.where(valid, do, jnp.zeros_like(do))
        delta = jnp.where(valid, delta, 0.0)
        if bias_mode == "full":
            bias = jnp.where(valid, bias, 0.0)
        dead = dead | ~valid
    lse = jnp.where(dead, -NEG_INF, lse)
    p = jnp.exp(_scores(q, k, bias, sh, qi, ki) - lse)
    dp = _dot(do, v, _NT)
    ds = (p * (dp - delta)).astype(q.dtype)
    dv = _dot(p.astype(do.dtype), do, _TN)
    dk = _dot(ds, q, _TN)                          # q carries the scale
    dq = _dot(ds, k, _NN) * scale
    return dq, dk, dv


def _flash_bwd_single_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                             bias_ref, dq_ref, dk_ref, dv_ref,
                             *, sh, scale, bias_mode):
    """Grid (BH, 1, 1): a head's sequence is one block pair, so nothing
    accumulates: the three gradients go straight to the outputs."""
    dq, dk, dv = _bwd_pair(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                           bias_ref, sh, scale, bias_mode, 0, 0)
    dq_ref[0] = dq.astype(dq_ref.dtype)
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _flash_bwd_blocked_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref,
                              delta_ref, bias_ref, dq_ref, dk_ref, dv_ref,
                              dq_scr, dk_scr, dv_scr,
                              *, sh, scale, bias_mode):
    """Grid (BH, nk, nq): for a fixed kv block, stream the q blocks and
    accumulate dk += ds^T q, dv += p^T do in (bk, D) float32 scratch,
    and dq += ds k in a float32 scratch that holds dq for the WHOLE
    sequence (nq * bq, D) and is written out at a head's last step."""
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    rows = pl.ds(pl.multiple_of(qi * sh.block_q, sh.block_q), sh.block_q)

    @pl.when(ki == 0)
    def _init_dq():
        dq_scr[rows, :] = jnp.zeros((sh.block_q, dq_scr.shape[1]),
                                    jnp.float32)

    @pl.when(qi == 0)
    def _init_dkv():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def _body():
        dq, dk, dv = _bwd_pair(q_ref, k_ref, v_ref, do_ref, lse_ref,
                               delta_ref, bias_ref, sh, scale, bias_mode,
                               qi, ki)
        dq_scr[rows, :] += dq
        dk_scr[...] += dk
        dv_scr[...] += dv

    if sh.causal:
        pl.when(sh.below_diagonal(qi, ki))(_body)
    else:
        _body()

    @pl.when(qi == sh.nq - 1)
    def _finish_dkv():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)

    @pl.when((qi == sh.nq - 1) & (ki == sh.nk - 1))
    def _finish_dq():
        dq_ref[0] = dq_scr[0:sh.seq_q, :].astype(dq_ref.dtype)


def _flash_bwd(q, k, v, bias, out, lse, g, *, scale, causal,
               block_q, block_k, interpret):
    """Pallas backward against the given ``lse`` and ``out`` (ring
    attention hands the merged forward's): returns (dq, dk, dv). Bias
    grads are not computed here (callers with trainable biases use the
    XLA path)."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    sh = _Shape.of(q, k, block_q, block_k, causal)
    bq, bk = sh.block_q, sh.block_k
    single = sh.nq == 1 and sh.nk == 1
    sh.count("bwd", single)
    bh = b * h
    # delta = rowsum(do * o) stays XLA's: its fusion reads ``out`` in
    # whatever layout the caller keeps it, where a kernel operand would
    # cost a copy of it a layer (PERF.md section 6, PR 41)
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1).reshape(bh, 1, sq)
    bias_mode, bias_specs, bias_args = _bias_operand(
        bias, q, k, sh, lambda g_, i, j: (g_, 0, i),
        lambda g_, i, j: (g_, j, i))
    args = [q.reshape(bh, sq, d), k.reshape(bh, sk, d), v.reshape(bh, sk, d),
            g.reshape(bh, sq, d), lse.reshape(bh, 1, sq), delta, *bias_args]

    # grid (bh, nk, nq): i = kv block, j = q block
    q_rows = pl.BlockSpec((1, bq, d), lambda g_, i, j: (g_, j, 0))
    k_rows = pl.BlockSpec((1, bk, d), lambda g_, i, j: (g_, i, 0))
    q_stat = pl.BlockSpec((1, 1, bq), lambda g_, i, j: (g_, 0, j))
    in_specs = [q_rows, k_rows, k_rows, q_rows, q_stat, q_stat, *bias_specs]

    body = functools.partial(
        _flash_bwd_single_kernel if single else _flash_bwd_blocked_kernel,
        sh=sh, scale=scale, bias_mode=bias_mode)
    scratch = [] if single else [
        pltpu.VMEM((sh.nq * bq, d), jnp.float32),
        pltpu.VMEM((bk, d), jnp.float32),
        pltpu.VMEM((bk, d), jnp.float32),
    ]
    dq, dk, dv = pl.pallas_call(
        _optional_refs(body, 6, bias_mode is not None, (True,) * 3),
        grid=(bh, sh.nk, sh.nq),
        in_specs=in_specs,
        out_specs=[
            # dq for the whole sequence: resident while a head's blocks run
            pl.BlockSpec((1, sq, d), lambda g_, i, j: (g_, 0, 0)),
            k_rows, k_rows,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, sk, d), q.dtype),
            jax.ShapeDtypeStruct((bh, sk, d), q.dtype),
        ],
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        ) if not interpret else None,
        interpret=interpret,
    )(*args)
    return (dq.reshape(b, h, sq, d), dk.reshape(b, h, sk, d),
            dv.reshape(b, h, sk, d))


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

    Backward: one Pallas kernel (:func:`_flash_bwd`, recomputing p from
    the forward's logsumexp). Key-padding biases (Sq dim == 1) are
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
    """The backward's working set, the larger of the two passes': s, p,
    dP and dS in float32 and their two roundings for the products; the
    operand blocks (q, dO; k, v) and dk's and dv's, (block, Dh) each and
    twice for the pipeline's two buffers; dq for the whole sequence as
    an output, twice, and where a head is more than one block pair once
    more as float32 scratch beside dk's and dv's. A row is 128 lanes at
    least."""
    q, k = args[0], args[1]
    sh = _Shape.of(q, k, blocks.get("block_q", 512),
                   blocks.get("block_k", 512), False)
    bq, bk = sh.block_q, sh.block_k
    row = max(q.shape[-1], 128)
    item = jnp.dtype(q.dtype).itemsize
    scores = (4 * 4 + 2 * item) * bq * bk
    moved = 2 * item * row * (2 * bq + 4 * bk)
    if sh.nq == 1 and sh.nk == 1:
        return scores + moved + 2 * item * row * bq
    rows_q = sh.nq * bq
    return scores + moved + 2 * item * row * rows_q + 4 * row * (rows_q
                                                                  + 2 * bk)


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
            grid="fwd (B*H, nq, nk): online softmax over kv blocks, a "
                 "whole softmax where nk == 1; bwd (B*H, nk, nq): one "
                 "kernel, dk/dv a kv block and dq a whole sequence in "
                 "float32 scratch, none where nq == nk == 1",
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
