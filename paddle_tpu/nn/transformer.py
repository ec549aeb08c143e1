"""Transformer building blocks (MHA, encoder/decoder layers).

Reference mapping: the reference composes attention from primitive ops in
model zoos (no nn.MultiHeadAttention in fluid 1.5; e.g. PaddleNLP
transformer builds q/k/v with ``layers/nn.py`` fc:231 + matmul + softmax
:2333). Here attention is a first-class layer backed by the Pallas flash
kernel (``ops/attention.py``) with Megatron-style TP sharding hints:
qkv projections column-parallel over "tp", output projection row-parallel,
so a tp-sharded mesh runs each head group on its own shard with a single
psum at the block output (inserted by GSPMD).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from paddle_tpu.nn.layers import Dropout, LayerNorm, Linear
from paddle_tpu.nn.module import Layer
from paddle_tpu.ops import activation as ops_act
from paddle_tpu.ops import attention as ops_attn

# Activation-sharding convention for transformer blocks:
#   hidden activations (B, S, D): P(("dp","fsdp"), "sp", None)
ACT_SPEC = P(("dp", "fsdp"), "sp", None)
HEADS_SPEC = P(("dp", "fsdp"), "tp", None, None)       # (B, H, S, Dh)
RING_HEADS_SPEC = P(("dp", "fsdp"), "tp", "sp", None)  # seq stays sharded

#: the ``jax.named_scope`` names these blocks open, flat (no layer index,
#: no nesting under a layer's own name): they reach the compiled step's
#: ``op_name`` metadata, where ``observability.scopes`` books device time
#: by them (PERF.md section 3). The flash kernel call stays OUTSIDE every
#: one of them: its Pallas calls are unnamed, XLA names them after the
#: innermost enclosing scope (``jvp_forward_.N``), and the accepted
#: ``kernel.flash_attn_*`` metrics select them by that name. So
#: ``attn_qkv`` and ``attn_out`` are siblings of the kernel call, and
#: ``attn_core`` wraps the composed (non-flash) attention only.
BLOCK_SCOPES = ("attn_qkv", "attn_core", "attn_out", "ffn", "add_norm")


def _constrain(x, spec):
    try:
        return jax.lax.with_sharding_constraint(x, spec)
    except (ValueError, RuntimeError):
        # outside a mesh context (single-device eager) constraints are moot
        return x


def _kernel_spec(mesh, shape):
    """``HEADS_SPEC`` cut to this mesh and this (B, H, S, Dh) shape: an
    axis stays only if the mesh has it, and a dimension is split only
    where its axes divide it — what does not divide stays whole on
    every shard, as ``_constrain`` leaves it."""
    entries = []
    for dim, axes in zip(shape, HEADS_SPEC):
        axes = (axes,) if isinstance(axes, str) else (axes or ())
        axes = tuple(a for a in axes if a in mesh.shape)
        n = math.prod(mesh.shape[a] for a in axes)
        entries.append(axes if n > 1 and dim % n == 0 else None)
    return P(*entries)


def _attend(q, k, v, bias, *, causal, dropout_rate, dropout_key, impl):
    """:func:`ops.attention.dot_product_attention` for (B, H, S, Dh)
    heads laid out by ``HEADS_SPEC``. The flash kernel is a Mosaic
    custom call, which the SPMD partitioner cannot split ("Mosaic
    kernels cannot be automatically partitioned"): where the
    partitioner would see it — a multi-device mesh is current and no
    enclosing ``shard_map`` (a pipeline stage body) has made the axes
    manual already — it runs per (batch, head) shard inside
    ``shard_map``. Batches and heads are independent, so there is no
    collective and each shard's output is what the unsharded kernel
    gives for its slice. The composed XLA path partitions by itself."""
    from paddle_tpu.core import mesh as mesh_lib
    impl = ops_attn.resolve_attention_impl(impl, dropout_rate)
    if impl == "xla":
        with jax.named_scope("attn_core"):
            return ops_attn.scaled_dot_product_attention(
                q, k, v, bias=bias, causal=causal, dropout_rate=dropout_rate,
                dropout_key=dropout_key)

    def kernel(q, k, v, bias=None):
        return ops_attn.flash_attention_dispatch(
            q, k, v, bias, impl=impl, causal=causal)

    mesh = mesh_lib.current_mesh()
    if (mesh is None or mesh.size == 1
            or jax.sharding.get_abstract_mesh().manual_axes):
        return kernel(q, k, v, bias)
    from paddle_tpu.core.compat import shard_map
    spec = _kernel_spec(mesh, q.shape)
    args, specs = (q, k, v), (spec,) * 3
    if bias is not None:
        if bias.ndim < 4:   # accept broadcastable ranks, like the kernel
            bias = bias.reshape((1,) * (4 - bias.ndim) + bias.shape)
        # a broadcast (size-1) batch or head dim stays whole on every shard
        specs += (P(*(ax if bias.shape[d] != 1 else None
                      for d, ax in enumerate(spec))),)
        args += (bias,)
    return shard_map(kernel, mesh=mesh, in_specs=specs,
                     out_specs=spec)(*args)


class MultiHeadAttention(Layer):
    """Multi-head attention with fused-qkv option and flash-kernel backend.

    ``self_attention=True`` uses one fused qkv projection (better MXU
    utilisation than three thin matmuls); cross-attention keeps separate
    q and kv projections (decoder).
    """

    def __init__(self, embed_dim, num_heads, dropout=0.0, bias=True,
                 self_attention=True, causal=False, attn_impl="auto"):
        super().__init__()
        if embed_dim % num_heads:
            raise ValueError("num_heads must divide embed_dim")
        self.embed_dim, self.num_heads = embed_dim, num_heads
        self.head_dim = embed_dim // num_heads
        self.dropout_rate = dropout
        self.causal = causal
        self.attn_impl = attn_impl
        if attn_impl == "ring" and dropout > 0.0:
            raise ValueError(
                "ring attention does not support attention-prob dropout; "
                "set attn_dropout=0 (residual dropout still applies)")
        self.self_attention = self_attention
        if self_attention:
            self.qkv_proj = Linear(embed_dim, 3 * embed_dim, bias=bias,
                                   sharding=P(None, "tp"))
        else:
            self.q_proj = Linear(embed_dim, embed_dim, bias=bias,
                                 sharding=P(None, "tp"))
            self.kv_proj = Linear(embed_dim, 2 * embed_dim, bias=bias,
                                  sharding=P(None, "tp"))
        self.out_proj = Linear(embed_dim, embed_dim, bias=bias,
                               sharding=P("tp", None))

    def _split_heads(self, x):
        b, s, _ = x.shape
        x = x.reshape(b, s, self.num_heads, self.head_dim)
        return x.transpose(0, 2, 1, 3)  # (B, H, S, Dh)

    def _merge_heads(self, x):
        b, h, s, d = x.shape
        return x.transpose(0, 2, 1, 3).reshape(b, s, h * d)

    def qkv_heads(self, params, x, key_value=None):
        """(B, S, D) -> (q, k, v) heads, each (B, H, S, Dh) — the serving
        engine's hook: it owns the attention itself (ragged paged decode
        over the shared page pool) and only needs the projections.
        ``key_value`` (B, Sk, D): what cross-attention projects k and v
        from (``x`` itself when left out)."""
        if self.self_attention:
            qkv = self.qkv_proj(params["qkv_proj"], x)
            q, k, v = jnp.split(qkv, 3, axis=-1)
        else:
            q = self.q_proj(params["q_proj"], x)
            kv = self.kv_proj(params["kv_proj"],
                              x if key_value is None else key_value)
            k, v = jnp.split(kv, 2, axis=-1)
        return tuple(self._split_heads(t) for t in (q, k, v))

    def proj_out(self, params, heads):
        """(B, H, S, Dh) attention output heads -> (B, S, D) through the
        output projection (the other half of the serving hook)."""
        return self.out_proj(params["out_proj"], self._merge_heads(heads))

    def cross_kv(self, params, memory):
        """Precompute cross-attention (k, v) heads from encoder memory —
        done ONCE per sequence; decode steps pass them as ``static_kv``
        (the reference's cached beam-search decoder keeps the same
        per-layer static caches)."""
        kv = self.kv_proj(params["kv_proj"], memory)
        k, v = jnp.split(kv, 2, axis=-1)
        return self._split_heads(k), self._split_heads(v)

    def forward(self, params, query, key_value=None, *, bias=None,
                key=None, training=False, cache=None, cache_pos=None,
                return_kv=False, static_kv=None):
        """query: (B, Sq, D); key_value: (B, Sk, D) for cross-attention.
        ``bias``: additive attention bias broadcastable to (B,H,Sq,Sk).

        Incremental decoding: ``cache=(k_cache, v_cache)`` with leaves
        (B, H, Smax, Dh) and ``cache_pos`` the write position makes this
        a single-token decode step (query Sq=1 attends over the filled
        prefix; O(S) per token instead of refeeding the whole sequence)
        returning (out, new_cache). ``return_kv=True`` on the normal
        path additionally returns this call's (k, v) heads — the
        prefill that seeds the cache. ``static_kv``: precomputed (k, v)
        heads (see :meth:`cross_kv`) — skips the kv projection entirely
        (cross-attention decode)."""
        if static_kv is not None:
            with jax.named_scope("attn_qkv"):
                q = self._split_heads(self.q_proj(params["q_proj"], query))
            k, v = static_kv
            with jax.named_scope("attn_core"):
                out = ops_attn.dot_product_attention(
                    q, k, v, bias=bias, causal=False, impl="xla")
            with jax.named_scope("attn_out"):
                return self.proj_out(params, out)
        with jax.named_scope("attn_qkv"):
            q, k, v = self.qkv_heads(params, query, key_value)

        if cache is not None:
            with jax.named_scope("attn_core"):
                ck, cv = cache
                ck = jax.lax.dynamic_update_slice(
                    ck, k.astype(ck.dtype), (0, 0, cache_pos, 0))
                cv = jax.lax.dynamic_update_slice(
                    cv, v.astype(cv.dtype), (0, 0, cache_pos, 0))
                # static shapes: attend over the whole cache, mask the
                # unfilled tail (positions > cache_pos)
                smax = ck.shape[2]
                mask = jnp.arange(smax)[None, None, None, :] <= cache_pos
                step_bias = jnp.where(mask, 0.0, -1e30).astype(q.dtype)
                if bias is not None:
                    step_bias = step_bias + bias
                out = ops_attn.dot_product_attention(
                    q, ck, cv, bias=step_bias, causal=False, impl="xla")
            with jax.named_scope("attn_out"):
                return self.proj_out(params, out), (ck, cv)
        spec = RING_HEADS_SPEC if self.attn_impl == "ring" else HEADS_SPEC
        with jax.named_scope("attn_qkv"):
            q = _constrain(q, spec)
            k = _constrain(k, spec)
            v = _constrain(v, spec)
        drop_rate = self.dropout_rate if training else 0.0
        if self.attn_impl == "ring":
            # sequence-parallel path: S sharded over "sp", k/v ride the ring
            from paddle_tpu.parallel.ring_attention import ring_attention
            out = ring_attention(q, k, v, bias=bias, causal=self.causal)
        else:
            out = _attend(q, k, v, bias, causal=self.causal,
                          dropout_rate=drop_rate, dropout_key=key,
                          impl=self.attn_impl)
        with jax.named_scope("attn_out"):
            out = _constrain(self.proj_out(params, out), ACT_SPEC)
        if return_kv:
            return out, (k, v)
        return out


class FeedForward(Layer):
    """Position-wise MLP: col-parallel fc1, row-parallel fc2."""

    def __init__(self, embed_dim, ffn_dim, activation="gelu", dropout=0.0):
        super().__init__()
        self.fc1 = Linear(embed_dim, ffn_dim, sharding=P(None, "tp"))
        self.fc2 = Linear(ffn_dim, embed_dim, sharding=P("tp", None))
        self.act = getattr(ops_act, activation)
        self.drop = Dropout(dropout)

    def forward(self, params, x, key=None, training=False):
        with jax.named_scope("ffn"):
            h = self.act(self.fc1(params["fc1"], x))
            h = self.drop(None, h, key=key, training=training)
            return _constrain(self.fc2(params["fc2"], h), ACT_SPEC)


class TransformerEncoderLayer(Layer):
    """Pre/post-LN encoder block (post-LN default: BERT convention)."""

    def __init__(self, embed_dim, num_heads, ffn_dim, dropout=0.1,
                 attn_dropout=None, activation="gelu", pre_ln=False,
                 attn_impl="auto"):
        super().__init__()
        self.attn = MultiHeadAttention(
            embed_dim, num_heads,
            dropout=attn_dropout if attn_dropout is not None else dropout,
            attn_impl=attn_impl)
        self.ffn = FeedForward(embed_dim, ffn_dim, activation, dropout)
        self.ln1 = LayerNorm(embed_dim)
        self.ln2 = LayerNorm(embed_dim)
        self.drop = Dropout(dropout)
        self.pre_ln = pre_ln

    def forward(self, params, x, *, bias=None, key=None, training=False):
        k1 = k2 = k3 = None
        if key is not None:
            with jax.named_scope("add_norm"):   # the dropouts' keys
                k1, k2, k3 = jax.random.split(key, 3)
        # ``add_norm``: the residual, its dropout and the LayerNorm, as
        # siblings of the attention and ffn calls (never around them)
        if self.pre_ln:
            with jax.named_scope("add_norm"):
                h = self.ln1(params["ln1"], x)
            h = self.attn(params["attn"], h, bias=bias, key=k1,
                          training=training)
            with jax.named_scope("add_norm"):
                x = x + self.drop(None, h, key=k2, training=training)
                h = self.ln2(params["ln2"], x)
            h = self.ffn(params["ffn"], h, key=k3, training=training)
            with jax.named_scope("add_norm"):
                if key is not None:
                    h = self.drop(None, h, key=jax.random.fold_in(k3, 1),
                                  training=training)
                return x + h
        h = self.attn(params["attn"], x, bias=bias, key=k1, training=training)
        with jax.named_scope("add_norm"):
            x = self.ln1(params["ln1"],
                         x + self.drop(None, h, key=k2, training=training))
        h = self.ffn(params["ffn"], x, key=k3, training=training)
        with jax.named_scope("add_norm"):
            if key is not None:
                k4 = jax.random.fold_in(k3, 1)
                h = self.drop(None, h, key=k4, training=training)
            return self.ln2(params["ln2"], x + h)


class TransformerDecoderLayer(Layer):
    """Decoder block: causal self-attention + cross-attention + FFN."""

    def __init__(self, embed_dim, num_heads, ffn_dim, dropout=0.1,
                 attn_dropout=None, activation="relu", pre_ln=False,
                 attn_impl="auto"):
        super().__init__()
        if attn_dropout is None:
            attn_dropout = dropout
        self.self_attn = MultiHeadAttention(embed_dim, num_heads,
                                            dropout=attn_dropout,
                                            causal=True,
                                            attn_impl=attn_impl)
        self.cross_attn = MultiHeadAttention(embed_dim, num_heads,
                                             dropout=attn_dropout,
                                             self_attention=False,
                                             attn_impl=attn_impl)
        self.ffn = FeedForward(embed_dim, ffn_dim, activation, dropout)
        self.ln1 = LayerNorm(embed_dim)
        self.ln2 = LayerNorm(embed_dim)
        self.ln3 = LayerNorm(embed_dim)
        self.drop = Dropout(dropout)
        self.pre_ln = pre_ln

    def forward(self, params, x, memory, *, self_bias=None, cross_bias=None,
                key=None, training=False):
        ks = [None] * 3
        if key is not None:
            with jax.named_scope("add_norm"):   # the dropouts' keys
                ks = list(jax.random.split(key, 3))

        def sub(x, ln_name, fn, drop_key):
            ln = getattr(self, ln_name)
            with jax.named_scope("add_norm"):
                dk = (jax.random.fold_in(drop_key, 1)
                      if drop_key is not None else None)
            if self.pre_ln:
                with jax.named_scope("add_norm"):
                    h = ln(params[ln_name], x)
                h = fn(h)
                with jax.named_scope("add_norm"):
                    return x + self.drop(None, h, key=dk, training=training)
            h = fn(x)
            with jax.named_scope("add_norm"):
                h = self.drop(None, h, key=dk, training=training)
                return ln(params[ln_name], x + h)

        x = sub(x, "ln1",
                lambda h: self.self_attn(params["self_attn"], h,
                                         bias=self_bias, key=ks[0],
                                         training=training), ks[0])
        x = sub(x, "ln2",
                lambda h: self.cross_attn(params["cross_attn"], h, memory,
                                          bias=cross_bias, key=ks[1],
                                          training=training), ks[1])
        x = sub(x, "ln3",
                lambda h: self.ffn(params["ffn"], h, key=ks[2],
                                   training=training), ks[2])
        return x

    def decode_step(self, params, x, pos, self_cache, cross_kv, *,
                    cross_bias=None):
        """Single-token cached decode (x (B, 1, D) at position ``pos``):
        self-attention through the KV cache, cross-attention over the
        precomputed memory heads. Inference only (no dropout). Returns
        (x, new_self_cache)."""
        def sub(x, ln_name, fn):
            ln = getattr(self, ln_name)
            if self.pre_ln:
                with jax.named_scope("add_norm"):
                    h = ln(params[ln_name], x)
                h = fn(h)
                with jax.named_scope("add_norm"):
                    return x + h
            h = fn(x)
            with jax.named_scope("add_norm"):
                return ln(params[ln_name], x + h)

        box = {}

        def self_fn(h):
            out, box["cache"] = self.self_attn(
                params["self_attn"], h, cache=self_cache, cache_pos=pos)
            return out

        x = sub(x, "ln1", self_fn)
        x = sub(x, "ln2",
                lambda h: self.cross_attn(params["cross_attn"], h,
                                          bias=cross_bias,
                                          static_kv=cross_kv))
        x = sub(x, "ln3", lambda h: self.ffn(params["ffn"], h))
        return x, box["cache"]
