"""Decoder-only causal language model (GPT-style).

Beyond-reference capability (the reference era predates GPT training
recipes), included because the decoder stack, flash causal attention, and
sp/tp shardings make it free — and it is the canonical long-context
workload for ring attention. Pre-LN, learned positions, tied head.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp

from paddle_tpu.nn import initializer as I
from paddle_tpu.nn.layers import Dropout, Embedding, LayerNorm
from paddle_tpu.nn.module import Layer, LayerList, StackedLayers
from paddle_tpu.nn.transformer import (ACT_SPEC, FeedForward,
                                       MultiHeadAttention, _constrain)


@dataclasses.dataclass
class GPTConfig:
    vocab_size: int = 50304
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    ffn_size: int = 3072
    max_position: int = 1024
    dropout: float = 0.0
    attn_impl: str = "auto"
    # GPipe the block stack over the "pp" mesh axis (parallel/pipeline.py)
    pipeline: bool = False
    pp_microbatches: int = 2
    pp_schedule: str = "gpipe"    # or "circular" (interleaved 1F1B)
    pp_circuits: int = 1
    pp_pre_interleaved: bool = False  # params pre-converted w/
    #   parallel.pipeline.interleave_stack (skips per-step reshuffle)
    # stacked (L, ...) scan-over-layers param layout (see BertConfig);
    # defaults on with pipeline. NOTE: changes the checkpoint tree —
    # migrate older per-layer trees with
    # parallel.pipeline.stack_params_at(params, ("blocks",), L).
    stacked_layers: Optional[bool] = None

    def __post_init__(self):
        if self.stacked_layers is None:
            self.stacked_layers = self.pipeline

    @classmethod
    def tiny(cls, **kw):
        kw.setdefault("vocab_size", 128)
        kw.setdefault("hidden_size", 32)
        kw.setdefault("num_layers", 2)
        kw.setdefault("num_heads", 2)
        kw.setdefault("ffn_size", 64)
        kw.setdefault("max_position", 64)
        return cls(**kw)


class GPTBlock(Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.ln1 = LayerNorm(cfg.hidden_size)
        self.attn = MultiHeadAttention(cfg.hidden_size, cfg.num_heads,
                                       dropout=cfg.dropout, causal=True,
                                       attn_impl=cfg.attn_impl)
        self.ln2 = LayerNorm(cfg.hidden_size)
        self.mlp = FeedForward(cfg.hidden_size, cfg.ffn_size,
                               activation="gelu", dropout=cfg.dropout)

    def forward(self, params, x, *, key=None, training=False, cache=None,
                cache_pos=None, return_kv=False):
        k1 = k2 = None
        if key is not None:
            k1, k2 = jax.random.split(key)
        h = self.ln1(params["ln1"], x)
        if cache is not None:
            a, new_cache = self.attn(params["attn"], h, cache=cache,
                                     cache_pos=cache_pos)
            x = x + a
            x = x + self.mlp(params["mlp"], self.ln2(params["ln2"], x))
            return x, new_cache
        if return_kv:
            a, kv = self.attn(params["attn"], h, key=k1,
                              training=training, return_kv=True)
            x = x + a
            x = x + self.mlp(params["mlp"], self.ln2(params["ln2"], x),
                             key=k2, training=training)
            return x, kv
        x = x + self.attn(params["attn"], h, key=k1, training=training)
        x = x + self.mlp(params["mlp"], self.ln2(params["ln2"], x),
                         key=k2, training=training)
        return x


class GPT(Layer):
    """Causal LM: forward returns logits; loss is shifted next-token NLL."""

    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        self.wte = Embedding(cfg.vocab_size, cfg.hidden_size,
                             weight_init=I.normal(0.0, 0.02))
        self.wpe = Embedding(cfg.max_position, cfg.hidden_size,
                             weight_init=I.normal(0.0, 0.01), sharding=None)
        self.drop = Dropout(cfg.dropout)
        if cfg.stacked_layers:
            self.blocks = StackedLayers(GPTBlock(cfg), cfg.num_layers)
        else:
            self.blocks = LayerList([GPTBlock(cfg)
                                     for _ in range(cfg.num_layers)])
        self.ln_f = LayerNorm(cfg.hidden_size)

    def serving(self, *, tp: int = 1,
                mlp_sharded: bool = False) -> "GPTServing":
        """This model's block as the paged serving engine runs it (see
        :mod:`paddle_tpu.serving.program`)."""
        return GPTServing(self, tp=tp, mlp_sharded=mlp_sharded)

    def forward(self, params, ids, *, key=None, training=False):
        cfg = self.cfg
        keys = [None] * (cfg.num_layers + 1)
        if key is not None:
            keys = list(jax.random.split(key, cfg.num_layers + 1))
        pos = jnp.arange(ids.shape[1], dtype=jnp.int32)[None, :]
        x = self.wte(params["wte"], ids) + self.wpe(params["wpe"], pos)
        x = self.drop(None, x, key=keys[0], training=training)
        x = _constrain(x, ACT_SPEC)
        if cfg.pipeline:
            x = self._blocks_pipelined(params, x, keys[1:], training)
        elif cfg.stacked_layers:
            lkeys = (jnp.stack(keys[1:]) if keys[1] is not None else None)
            x = self.blocks(params["blocks"], x, layer_keys=lkeys,
                            training=training)
        else:
            for i, block in enumerate(self.blocks):
                x = block(params["blocks"][str(i)], x, key=keys[i + 1],
                          training=training)
        x = self.ln_f(params["ln_f"], x)
        return jnp.einsum("bsd,vd->bsv", x, params["wte"]["weight"])

    def _blocks_pipelined(self, params, x, layer_keys, training):
        """GPipe over "pp" (shared schedule wrapper; the decoder-only
        stack has no per-microbatch bias — causality is inside the
        block)."""
        from paddle_tpu.parallel import pipeline as pp_lib

        cfg = self.cfg
        if cfg.stacked_layers:
            block0 = self.blocks.template
            blk_params = params["blocks"]        # pre-stacked (L, ...)
        else:
            block0 = self.blocks[0]
            blk_params = [params["blocks"][str(i)]
                          for i in range(cfg.num_layers)]
        return pp_lib.gpipe_layer_stack(
            lambda lp, h, extra, k: block0(lp, h, key=k,
                                           training=training),
            blk_params, x, num_microbatches=cfg.pp_microbatches,
            layer_keys=layer_keys, schedule=cfg.pp_schedule,
            num_circuits=cfg.pp_circuits,
            pre_interleaved=cfg.pp_pre_interleaved)

    def loss(self, params, ids, *, key=None, training=True):
        """Next-token LM loss over ids (B, S): predict ids[:,1:]."""
        logits = self.forward(params, ids[:, :-1], key=key,
                              training=training)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
        nll = -jnp.take_along_axis(logp, ids[:, 1:, None], -1)[..., 0]
        loss = nll.mean()
        return loss, {"ppl": jnp.exp(loss)}

    # ---- incremental decoding (KV cache) --------------------------------

    def init_cache(self, batch_size, max_len, dtype=jnp.float32):
        """Per-layer (k, v) buffers (B, H, max_len, Dh) for
        :meth:`generate(use_cache=True)`."""
        cfg = self.cfg
        shape = (batch_size, cfg.num_heads, max_len,
                 cfg.hidden_size // cfg.num_heads)
        return [(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))
                for _ in range(cfg.num_layers)]

    def prefill(self, params, ids, cache):
        """Full-attention pass over the prompt that seeds the caches.
        Returns (logits (B, S0, V), cache)."""
        cfg = self.cfg
        s0 = ids.shape[1]
        pos = jnp.arange(s0, dtype=jnp.int32)[None, :]
        x = self.wte(params["wte"], ids) + self.wpe(params["wpe"], pos)
        x = _constrain(x, ACT_SPEC)
        new_cache = []
        for i, block in enumerate(self.blocks):
            x, (k, v) = block(params["blocks"][str(i)], x, return_kv=True)
            ck, cv = cache[i]
            new_cache.append((
                jax.lax.dynamic_update_slice(ck, k.astype(ck.dtype),
                                             (0, 0, 0, 0)),
                jax.lax.dynamic_update_slice(cv, v.astype(cv.dtype),
                                             (0, 0, 0, 0))))
        x = self.ln_f(params["ln_f"], x)
        return jnp.einsum("bsd,vd->bsv", x, params["wte"]["weight"]), \
            new_cache

    def decode_step(self, params, token_ids, pos, cache):
        """One cached decode step: ``token_ids`` (B,) at position ``pos``
        -> (logits (B, V), new_cache). O(S) work per token versus the
        uncached path's O(S^2) full refeed."""
        x = (self.wte(params["wte"], token_ids[:, None])
             + self.wpe(params["wpe"], pos[None, None]))
        new_cache = []
        for i, block in enumerate(self.blocks):
            x, kv = block(params["blocks"][str(i)], x, cache=cache[i],
                          cache_pos=pos)
            new_cache.append(kv)
        x = self.ln_f(params["ln_f"], x)
        return jnp.einsum("bd,vd->bv", x[:, 0],
                          params["wte"]["weight"]), new_cache

    def generate(self, params, prompt_ids, max_new_tokens=32,
                 temperature=1.0, key=None, use_cache=False,
                 cache_dtype=None):
        """Autoregressive sampling (greedy when key is None). Static-shape
        loop; prompt_ids (B, S0) with S0+max_new <= max_position.

        ``use_cache=True`` decodes incrementally through per-layer KV
        caches — same tokens, O(S) per step (LayerList layout only; the
        pipeline/stacked training layouts fall back to the full refeed).
        ``cache_dtype`` defaults to the params' compute dtype, so a bf16
        checkpoint gets a bf16 cache (half the HBM footprint).
        """
        cfg = self.cfg
        b, s0 = prompt_ids.shape
        total = s0 + max_new_tokens
        ids = jnp.concatenate(
            [prompt_ids,
             jnp.zeros((b, max_new_tokens), jnp.int32)], axis=1)

        def sample(logits, key):
            # one shape for both paths: split exactly like the uncached
            # body so cached/uncached sampling consume identical streams
            if key is None:
                return logits.argmax(-1).astype(jnp.int32), None
            key, new_key = jax.random.split(key)
            return jax.random.categorical(
                key, logits / temperature).astype(jnp.int32), new_key

        if use_cache and not (cfg.pipeline or cfg.stacked_layers):
            if cache_dtype is None:
                cache_dtype = params["wte"]["weight"].dtype
            cache = self.init_cache(b, total, dtype=cache_dtype)
            logits, cache = self.prefill(params, prompt_ids, cache)
            nxt, key = sample(logits[:, s0 - 1], key)
            ids = ids.at[:, s0].set(nxt)

            def body(t, carry):
                ids, cache, key = carry
                logits, cache = self.decode_step(
                    params, ids[:, t - 1], jnp.asarray(t - 1), cache)
                nxt, key = sample(logits, key)
                return ids.at[:, t].set(nxt), cache, key

            ids, _, _ = jax.lax.fori_loop(s0 + 1, total, body,
                                          (ids, cache, key))
            return ids

        def body(t, carry):
            ids, key = carry
            logits = self.forward(params, ids)[:, t - 1]
            nxt, key = sample(logits, key)
            return ids.at[:, t].set(nxt), key

        ids, _ = jax.lax.fori_loop(s0, total, body, (ids, key))
        return ids

    # ---- bucketed decoding (recompile cap) ------------------------------

    def _generate_padded_cached(self, params, padded_ids, prompt_len,
                                max_new_bucket):
        """Greedy cached decode where the REAL prompt length is a traced
        scalar: ``padded_ids`` (B, S0b) holds the prompt right-padded to
        the bucket; prefill seeds the cache causally over the padded
        buffer, the first token samples from ``prompt_len - 1``, and the
        decode loop overwrites the pad garbage in cache order (each step
        masks to ``<= cache_pos``, so garbage K/V past the write head is
        never attended). Returns generated tokens (B, max_new_bucket)."""
        b, s0b = padded_ids.shape
        cache = self.init_cache(b, s0b + max_new_bucket,
                                dtype=params["wte"]["weight"].dtype)
        logits, cache = self.prefill(params, padded_ids, cache)
        last = jnp.take_along_axis(
            logits, (prompt_len - 1)[None, None, None].astype(jnp.int32)
            .repeat(b, 0), axis=1)[:, 0]
        gen = jnp.zeros((b, max_new_bucket), jnp.int32)
        gen = gen.at[:, 0].set(jnp.argmax(last, -1).astype(jnp.int32))

        def body(t, carry):
            gen, cache = carry
            logits, cache = self.decode_step(
                params, gen[:, t - 1], prompt_len + t - 1, cache)
            return gen.at[:, t].set(
                jnp.argmax(logits, -1).astype(jnp.int32)), cache

        gen, _ = jax.lax.fori_loop(1, max_new_bucket, body, (gen, cache))
        return gen

    def generate_bucketed(self, params, prompt_ids, max_new_tokens=32,
                          *, min_bucket=8):
        """Greedy :meth:`generate` with power-of-two shape bucketing:
        the prompt is right-padded to the next pow2 length and the
        decode horizon rounded up the same way, so every request whose
        (prompt, horizon) lands in the same bucket reuses ONE compiled
        graph — a serving box sees a handful of compiles total instead
        of one per distinct request shape. Tokens are identical to
        ``generate(use_cache=True)`` because the real prompt length is a
        traced scalar (pad K/V is masked, then overwritten). LayerList
        layout only, greedy only. Returns (B, S0 + max_new_tokens) ids,
        same contract as :meth:`generate`."""
        cfg = self.cfg
        if cfg.pipeline or cfg.stacked_layers:
            raise ValueError("generate_bucketed needs the LayerList "
                             "layout (like generate(use_cache=True))")
        import numpy as np
        prompt_host = np.asarray(prompt_ids)
        b, s0 = prompt_host.shape

        def pow2(n):
            return 1 << max(int(n) - 1, 0).bit_length()

        s0b = min(max(pow2(s0), min_bucket), cfg.max_position)
        nb = max(pow2(max_new_tokens), min_bucket)
        if s0 + max_new_tokens > cfg.max_position:
            raise ValueError("prompt + max_new_tokens exceeds max_position")
        s0b = max(s0b, s0)  # max_position clamp must never truncate
        padded = np.zeros((b, s0b), np.int32)
        padded[:, :s0] = prompt_host
        jits = getattr(self, "_bucket_jit_cache", None)
        if jits is None:
            jits = {}
            object.__setattr__(self, "_bucket_jit_cache", jits)
        fn = jits.get((s0b, nb))
        if fn is None:
            fn = jax.jit(functools.partial(self._generate_padded_cached,
                                           max_new_bucket=nb))
            jits[(s0b, nb)] = fn
        gen = fn(params, jnp.asarray(padded),
                 jnp.asarray(s0, jnp.int32))
        # assemble on host: an eager jnp.concatenate would compile once
        # per prompt length — exactly the retraces bucketing removes
        return jnp.asarray(np.concatenate(
            [prompt_host.astype(np.int32),
             np.asarray(gen)[:, :max_new_tokens]], axis=1))


class GPTServing:
    """GPT's serving program (:mod:`paddle_tpu.serving.program`): learned
    positions added at the embedding, pre-LN blocks, every head with its
    own K and V, the word table as the output head.

    ``tp > 1``: the body is one head shard's — qkv from the head-major
    TP slice of the projections (:meth:`tp_params` lays them out,
    :meth:`tp_plan` shards them), the row-sharded output projection
    closed by ONE psum a layer. ``mlp_sharded`` also splits the MLP the
    Megatron way (the prefill tier), closed by the layer's second psum."""

    def __init__(self, model: GPT, *, tp: int = 1,
                 mlp_sharded: bool = False):
        from paddle_tpu.serving.program import ServingSpec
        cfg = model.cfg
        if cfg.pipeline or cfg.stacked_layers:
            raise ValueError(
                "ServingEngine needs the LayerList GPT layout; convert "
                "stacked/pipeline checkpoints for serving first")
        self.model = model
        self.tp, self.mlp_sharded = tp, mlp_sharded
        self.spec = ServingSpec(
            num_layers=cfg.num_layers, num_heads=cfg.num_heads,
            kv_heads=cfg.num_heads,
            head_dim=cfg.hidden_size // cfg.num_heads,
            vocab_size=cfg.vocab_size, max_position=cfg.max_position)

    def param_dtype(self, params):
        return params["wte"]["weight"].dtype

    def tp_params(self, params):
        """Head-major TP re-layout of the attention projections: fused
        qkv weight ``(D, 3D)`` -> ``(D, 3, H, Dh)`` (bias ``(3D,)`` ->
        ``(3, H, Dh)``), out_proj weight ``(D, D)`` -> ``(H, Dh, D)``.
        Sharding the RAW fused columns over tp would hand each shard a
        slice straddling the q/k/v boundaries; head-major, the "tp"
        shard boundary IS a head boundary — which is exactly what the
        per-shard page pools need. Everything else passes through
        untouched (replicated under ``serving_tp_plan``)."""
        cfg = self.model.cfg
        d, h = cfg.hidden_size, cfg.num_heads
        dh = d // h
        out = dict(params)
        blocks = {}
        for name, bp in params["blocks"].items():
            bp = dict(bp)
            qkv, op = bp["attn"]["qkv_proj"], bp["attn"]["out_proj"]
            attn = {
                "qkv_tp": {"weight": qkv["weight"].reshape(d, 3, h, dh)},
                "out_tp": {"weight": op["weight"].reshape(h, dh, d)},
            }
            if "bias" in qkv:
                attn["qkv_tp"]["bias"] = qkv["bias"].reshape(3, h, dh)
            if "bias" in op:
                attn["out_tp"]["bias"] = op["bias"]
            bp["attn"] = attn
            blocks[name] = bp
        out["blocks"] = blocks
        return out

    def tp_plan(self):
        """The sharding of :meth:`tp_params`' tree over the "tp" axis."""
        from paddle_tpu.parallel import plan
        return (plan.serving_prefill_tp_plan() if self.mlp_sharded
                else plan.serving_tp_plan())

    def embed(self, params, tokens, positions):
        m = self.model
        return (m.wte(params["wte"], tokens)
                + m.wpe(params["wpe"], positions))              # (S,C,D)

    def attn_in(self, params, i, x, positions):
        block, bp = self.model.blocks[i], params["blocks"][str(i)]
        h = block.ln1(bp["ln1"], x)
        if self.tp > 1:
            # per-shard heads (S,Hl,C,Dh) from the head-major projection
            # slice: the col-parallel half of the Megatron split
            ap = bp["attn"]
            qkv = jnp.einsum("scd,dthk->tshck", h, ap["qkv_tp"]["weight"])
            b = ap["qkv_tp"].get("bias")
            if b is not None:
                qkv = qkv + b[:, None, :, None, :]
            q, k, v = qkv[0], qkv[1], qkv[2]
        else:
            q, k, v = block.attn.qkv_heads(bp["attn"], h)       # (S,H,C,Dh)
        s_tot, _, c, _ = k.shape
        # token-major, heads folded the way the pool stores them
        k_tok = k.transpose(0, 2, 1, 3).reshape(s_tot, c, -1)
        v_tok = v.transpose(0, 2, 1, 3).reshape(s_tot, c, -1)
        return q, (k_tok, v_tok), None

    def attn_out(self, params, i, x, att):
        block, bp = self.model.blocks[i], params["blocks"][str(i)]
        if self.tp == 1:
            return x + block.attn.proj_out(bp["attn"],
                                           att.transpose(0, 2, 1, 3))
        # row-sharded output projection + THE one attention-output
        # collective: local heads (S,C,H/tp,Dh) -> (S,C,D) replicated
        ap = bp["attn"]
        part = jax.lax.psum(
            jnp.einsum("schk,hkd->scd", att, ap["out_tp"]["weight"]), "tp")
        b = ap["out_tp"].get("bias")
        return x + (part + b if b is not None else part)

    def ffn(self, params, i, x, valid):
        block, bp = self.model.blocks[i], params["blocks"][str(i)]
        if not self.mlp_sharded:
            return x + block.mlp(bp["mlp"], block.ln2(bp["ln2"], x)), None
        # Megatron MLP shard (prefill tier): fc1 column-split over "tp",
        # fc2 row-split, closed by the layer's SECOND psum; the fc2 bias
        # is added once AFTER the reduce
        mp = bp["mlp"]
        h = block.ln2(bp["ln2"], x)
        h = block.mlp.act(jnp.matmul(h, mp["fc1"]["weight"])
                          + mp["fc1"]["bias"])
        part = jax.lax.psum(jnp.matmul(h, mp["fc2"]["weight"]), "tp")
        return x + (part + mp["fc2"]["bias"]), None

    def head(self, params, x):
        x = self.model.ln_f(params["ln_f"], x)
        return jnp.einsum("...d,vd->...v", x, params["wte"]["weight"])
