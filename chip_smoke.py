"""Does the system still start on the chip?  `python chip_smoke.py`

Drives the two main paths once, through the entry points a user calls,
at the full width of models the repo ships (depth and weights as
configured; weights random from ``--seed``), in ONE process:

1. **paged kernels** — the Pallas ragged paged decode and prefill
   bodies (fp32 pages, bf16 pages, int8 pages) against their ``lax_fn``
   on the chip at GPT-2 widths, within each kernel contract's tolerance;
2. **trainer** — BERT-base, batch 48 x sequence 512, ``bf16`` policy,
   ``make_train_state`` / ``build_train_step`` / ``jax.jit``: five
   optimizer steps on one fixed batch, attention dropout off (hidden
   dropout as configured, a fresh key each step). Every loss finite,
   the fifth below the first, attention on the flash kernel;
3. **serving** — GPT-2 small through ``inference.make_serving_engine``
   -> ``warmup()`` -> ``submit()`` x5 -> ``step()`` until idle. Every
   request finishes, greedy tokens equal ``model.generate(...,
   use_cache=True)`` on the same chip, 0 compiles after warmup, paged
   attention on the Pallas bodies;
4. **sparse family** — ``models/sparse_moe_lm.py`` at its published
   widths (32 query heads over 4 KV heads of 128, 16 indexer heads of 64,
   top-2048, 128 experts of 768, the whole vocabulary; ONE layer): the
   indexer, the sparse paged kernels and the grouped expert kernel
   against their ``lax_fn``, the selection mask against the ``lax.top_k``
   statement of its rule at the docs cell's geometry (0 elements may
   differ) and its kernel timed alone, then one request of 2304 prompt
   tokens through the engine (dense kernels up to 2048 cached tokens,
   selection past them), every chosen token within ``SPARSE_TIE_MARGIN`` of the plain
   float32 reference's best (``benchmark/families/keye_vl2.py``);
5. **hybrid family** — ``models/hybrid_ssm_lm.py`` at its published
   widths (20 query heads over 4 KV heads of 128, 32 mixer heads of 128
   in 2 groups, state 256, MLP 21504; ONE layer, a vocabulary of 8192
   rows): the chunked scan and the one-token state update against their
   ``lax_fn`` on float32 state tiles of ``(256, 128)``, then one request
   of 300 prompt tokens (three chunks of 128, the last ragged) and 12
   new ones through the engine, every chosen token within
   ``HYBRID_TIE_MARGIN`` of the plain float32 reference's best
   (``benchmark/families/falcon_h1.py``), and the slot's state row
   changed while its neighbours' stayed zero;
6. **latent family** — ``models/latent_conv_moe_lm.py`` at its published
   widths (8 query heads over 2 KV heads of 128 in a latent of 1024 + 256
   + 256, 16 experts of 2048 with one a token, router hidden 256; ONE
   layer, a vocabulary of 8192 rows): the grouped expert kernel on ``(T,
   1)`` ids against its ``lax_fn``, then one request of 300 prompt tokens
   (three chunks of 128, the last ragged) and 12 new ones through the
   engine, every chosen token within ``LATENT_TIE_MARGIN`` of the plain
   float32 reference's best (``benchmark/families/zaya.py``), and the
   slot's three tails changed while its neighbours' stayed zero;
7. **keys wider than values** — the dense paged decode and prefill
   bodies at the widths of a model whose two kinds of layer differ in
   their KV heads (64 query heads of 192; a full layer's pool 4 KV heads,
   K 768 lanes beside V 512, query groups of 16; a window layer's 8, K
   1536 beside V 1024, groups of 8, window 128, a learned sink a head in
   the softmax) against their ``lax_fn``, bf16 and fp32 pages, within
   each kernel contract's tolerance. ``--only wide_keys`` runs this phase
   alone.
9. **gated delta** — the two kernels of a state layer
   (``ops/gated_delta.py``) at Qwen3-Next's widths (16 key and 32 value
   heads of 128, chunks of 128 = two tiles of 64; four lanes: one fresh,
   one ragged, one a pad lane on the null row) against their ``lax_fn``,
   rows no lane holds bit for bit; the dense paged decode and prefill
   bodies at heads of 256 (a pool of 2 KV heads x 256 lanes, query groups
   of 8) against theirs; and 16 chained one-token updates over 256 slots
   timed against the state tiles' bytes. ``--only gated_delta`` runs this
   phase alone.

``--chips 4`` runs INSTEAD (no one-chip phase): BERT-base under
``shard_train_step`` on a dp2 x tp2 mesh against the same steps on one
device of that host, and ``ServingEngine(tp=4)`` against a ``tp=1``
engine, each asserting that parameters and page pools are really spread
over all four devices.

Any failed condition raises: no phase is wrapped in a ``try`` that lets
the run go on. Without a TPU the script exits non-zero before any phase.
The LAST line of stdout is the result object and nothing else.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import sys
import time

import numpy as np

#: a greedy-token mismatch against ``model.generate`` is tolerated only
#: where the reference itself is a near-tie: reference top-1 logit minus
#: the reference logit of the engine's token, below this (logit units).
#: Random GPT-2 logits have a typical top-2 gap ~0.1; a broken kernel
#: picks tokens whole logits away, matmul rounding only near-ties.
TIE_MARGIN = 0.05
#: the sparse-attention / sparse-expert family in bf16 against its float32
#: reference: a routed model crosses a routing or selection threshold now
#: and then where the reference does not, and the token after it may fall
#: this far short (benchmark/configs/keye_vl2_30b_a3b.json, tie_margin:
#: largest seen over ten seeds 0.36, with the selection off 0.78)
SPARSE_TIE_MARGIN = 0.6
#: the attention + state-space hybrid in bf16 against its float32
#: reference, in logits that spread by about 0.01 under the published
#: multipliers (benchmark/configs/falcon_h1_34b.json, tie_margin)
HYBRID_TIE_MARGIN = 5e-4
#: the latent-conv attention + top-1 expert model in bf16 against its
#: float32 reference at ONE layer, 12 chosen tokens, in logits that spread
#: by about 0.9. The reference follows the program's expert at a routing
#: tie and keeps a switch that leaves the token at most 0.1 short
#: (benchmark/families/zaya.py, ROUTING_EXPLAINED), so a followed token
#: may read up to 0.1; one it could not follow reads 0.3 to 4. Six seeds
#: at this phase's own size read 0, 0, 0, 0, 1.5e-3, 0 (my chip run, PR 35,
#: chiprun_out/logs/ninth_smoke6.log)
LATENT_TIE_MARGIN = 0.15
#: dp2 x tp2 vs one device: same math, different reduction order, bf16
#: activations — relative tolerance on each step's loss
MESH_LOSS_RTOL = 2e-2


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What the phases run at. ``real()`` is the chip run; ``tiny()``
    is the CPU rehearsal (Pallas bodies through the interpreter)."""
    bert: dict
    bert_batch: int
    bert_seq: int
    gpt: dict
    num_slots: int
    page_size: int
    prefill_chunk: int
    max_tokens_per_slot: int
    prompt_lens: tuple          # request i has prompt_lens[i] tokens
    shared_prefix: int          # the LAST request opens with this many
    #                             tokens of request 0, and is submitted
    #                             once request 0's prompt is in the cache
    new_tokens: int
    sparse: dict                # SparseMoELMConfig overrides
    sparse_page_size: int
    sparse_chunk: int
    sparse_prompt: int          # past topk, so decode selects
    hybrid: dict                # HybridSSMLMConfig overrides
    hybrid_page_size: int
    hybrid_chunk: int
    hybrid_prompt: int          # whole chunks and a ragged one
    latent: dict                # LatentConvMoELMConfig overrides; page,
    #                             chunk and prompt are the hybrid's
    #: keys wider than values: (query heads, key width, value width, page
    #: size, window, (KV heads of a full layer, of a window layer))
    wide_keys: tuple = (64, 192, 128, 128, 128, (4, 8))
    #: a selection over a latent cache: (query heads, latent width, rotary
    #: width, indexer heads, indexer head width, tokens selected, page
    #: size, prefill chunk, pages a slot)
    selecting_latent: tuple = (128, 512, 64, 64, 128, 2048, 128, 256, 40)
    #: a state layer and the full layer beside it: (key heads, value heads,
    #: key width, value width, chunk, slots timed; query heads, KV heads,
    #: head width, page size, pages a slot)
    gated_delta: tuple = (16, 32, 128, 128, 128, 256, 16, 2, 256, 128, 8)
    interpret: bool = False

    @classmethod
    def real(cls):
        # prompts cross a page (16) and a prefill-chunk (32) boundary;
        # five requests over four slots, so the last waits for a slot
        return cls(bert={}, bert_batch=48, bert_seq=512, gpt={},
                   num_slots=4, page_size=16, prefill_chunk=32,
                   max_tokens_per_slot=128, prompt_lens=(40, 13, 70, 40, 40),
                   shared_prefix=32, new_tokens=12,
                   sparse=dict(num_hidden_layers=1), sparse_page_size=128,
                   sparse_chunk=64, sparse_prompt=2304,
                   hybrid=dict(num_hidden_layers=1, vocab_size=8192),
                   hybrid_page_size=128, hybrid_chunk=128, hybrid_prompt=300,
                   latent=dict(num_hidden_layers=1, vocab_size=8192))

    @classmethod
    def tiny(cls):
        return cls(bert=dict(vocab_size=128, hidden_size=32, num_layers=2,
                             num_heads=2, ffn_size=64, max_position=64),
                   bert_batch=2, bert_seq=32,
                   gpt=dict(vocab_size=128, hidden_size=32, num_layers=2,
                            num_heads=4, ffn_size=64, max_position=64),
                   num_slots=2, page_size=4, prefill_chunk=8,
                   max_tokens_per_slot=16, prompt_lens=(10, 3, 9, 10),
                   shared_prefix=8, new_tokens=5,
                   sparse=dict(vocab_size=96, hidden_size=64,
                               num_hidden_layers=1, num_attention_heads=4,
                               num_key_value_heads=2, head_dim=16,
                               max_position_embeddings=256, num_experts=8,
                               num_experts_per_tok=2,
                               moe_intermediate_size=32,
                               indexer_num_heads=2, indexer_head_dim=8,
                               indexer_topk=16),
                   sparse_page_size=4, sparse_chunk=8, sparse_prompt=22,
                   hybrid=dict(vocab_size=96, hidden_size=64,
                               num_hidden_layers=1, num_attention_heads=4,
                               num_key_value_heads=2, head_dim=16,
                               intermediate_size=96,
                               max_position_embeddings=256, mamba_d_ssm=64,
                               mamba_n_heads=4, mamba_d_head=16,
                               mamba_n_groups=2, mamba_d_state=16),
                   hybrid_page_size=4, hybrid_chunk=8, hybrid_prompt=19,
                   latent=dict(vocab_size=96, hidden_size=64,
                               num_hidden_layers=1, num_attention_heads=4,
                               num_key_value_heads=2, head_dim=16,
                               max_position_embeddings=256, num_experts=8,
                               moe_intermediate_size=32,
                               router_hidden_size=16),
                   wide_keys=(4, 24, 16, 4, 8, (1, 2)),
                   selecting_latent=(4, 16, 8, 2, 16, 16, 8, 16, 6),
                   gated_delta=(2, 4, 16, 16, 8, 3, 4, 2, 16, 4, 3),
                   interpret=True)

    @property
    def prefill_steps(self):
        """engine steps until request 0's prompt is wholly cached (one
        chunk per admitted request per step)."""
        return -(-self.prompt_lens[0] // self.prefill_chunk)

    @property
    def kernel_impl(self):
        return "pallas_interpret" if self.interpret else "pallas"


def log(msg):
    print(f"[chip_smoke] {msg}", flush=True)


def _prefill_forms():
    """``paged_prefill_lowerings_total`` by label set: the forms the
    chunked-prefill bodies traced so far took (fold by head or by group,
    operands as stored or float32, terms stacked or a product each)."""
    from paddle_tpu.observability import registry as obs
    counter = obs.counter("paged_prefill_lowerings_total")
    return {",".join(f"{k}={v}" for k, v in labels):
            int(counter.value(**dict(labels)))
            for labels in counter.labels_seen()}


def _dispatched(kernel, impl):
    """How often ``kernel`` resolved to ``impl`` so far (trace-time
    counter of ``kernels.dispatch``)."""
    from paddle_tpu.observability import registry
    return registry.counter("kernel_dispatch_total").value(
        kernel=kernel, impl=impl)


# ---------------------------------------------------------------------------
# phase 1: paged kernels against their lax_fn, on the device
# ---------------------------------------------------------------------------

def phase_paged_kernels(sizes, seed):
    import jax
    import jax.numpy as jnp
    from paddle_tpu import kernels
    from paddle_tpu.models.gpt import GPTConfig
    from paddle_tpu.serving.paged_cache import quantize_kv

    cfg = GPTConfig(**sizes.gpt)
    h, dh = cfg.num_heads, cfg.hidden_size // cfg.num_heads
    s, ps, c = sizes.num_slots, sizes.page_size, sizes.prefill_chunk
    mp = -(-sizes.max_tokens_per_slot // ps)
    num_pages = s * mp + 1
    rng = np.random.default_rng(seed)
    # the pool as the engine stores it: a token's heads folded head-major
    k32 = jnp.asarray(rng.standard_normal((num_pages, ps, h * dh)),
                      jnp.float32)
    v32 = jnp.asarray(rng.standard_normal((num_pages, ps, h * dh)),
                      jnp.float32)
    bt = jnp.asarray(rng.permutation(num_pages - 1)[:s * mp].reshape(s, mp)
                     + 1, jnp.int32)
    # slot 0 inactive, slot 1 mid-page, the rest anywhere up to full
    lengths = rng.integers(1, mp * ps + 1, s)
    lengths[0], lengths[1 % s] = 0, ps + 3
    lengths = jnp.asarray(lengths, jnp.int32)
    starts = jnp.asarray(rng.integers(0, (mp - 1) * ps - c + 1, s),
                         jnp.int32)
    n_valid = rng.integers(1, c + 1, s)
    n_valid[0], n_valid[-1] = 0, c
    n_valid = jnp.asarray(n_valid, jnp.int32)
    q_dec = jnp.asarray(rng.standard_normal((s, h, dh)), jnp.float32)
    q_pre = jnp.asarray(rng.standard_normal((s, c, h, dh)), jnp.float32)
    kq, ks = quantize_kv(k32, (2,))
    vq, vs = quantize_kv(v32, (2,))
    pools = {
        "f32": (k32, v32),
        "bf16": (k32.astype(jnp.bfloat16), v32.astype(jnp.bfloat16)),
        "int8": (kq, vq, ks, vs),
    }
    errs = {}
    for label, pool in pools.items():
        suffix = "_int8" if label == "int8" else ""
        for name, q, geo in (
                ("ragged_paged_decode" + suffix, q_dec, (lengths,)),
                ("ragged_paged_prefill" + suffix, q_pre,
                 (starts, n_valid))):
            args = (q, *pool, bt, *geo)
            contract = kernels.get(name).contract
            out = jax.jit(lambda *a, _n=name: kernels.dispatch(
                _n, *a, impl=sizes.kernel_impl))(*args)
            # the reference in true fp32: the backend's default matmul
            # precision is bf16-class and would drown the comparison
            with jax.default_matmul_precision("highest"):
                ref = jax.jit(lambda *a, _n=name: kernels.dispatch(
                    _n, *a, impl="lax"))(*args)
            out, ref = np.asarray(out), np.asarray(ref)
            assert out.shape == ref.shape and np.isfinite(out).all(), name
            err = float(np.max(np.abs(out - ref)))
            errs[f"{name}[{label}]"] = err
            np.testing.assert_allclose(
                out, ref, atol=contract.atol, rtol=contract.rtol,
                err_msg=f"{name}[{label}] {sizes.kernel_impl} vs lax")
    log("paged kernels vs lax max|err|: " + json.dumps(errs))
    forms = _prefill_forms()
    # the bf16 and int8 pools' operands went to the MXU as stored, the
    # float32 pool's at HIGHEST
    assert any("operands=stored" in f for f in forms) \
        and any("operands=float32" in f for f in forms), forms
    log("paged prefill lowerings: " + json.dumps(forms))


def phase_wide_key_kernels(sizes, seed):
    """The dense paged bodies where a KV head's keys are wider than its
    values and the two kinds of layer differ in KV heads: a full layer's
    pool without a sink, a window layer's under its window with one."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu import kernels

    h, dk, dv, ps, window, kv_heads = sizes.wide_keys
    s, mp = 4, 6
    c = ps                              # a chunk is a page of queries
    num_pages = s * mp + 1
    rng = np.random.default_rng(seed)
    bt = jnp.asarray(rng.permutation(num_pages - 1)[:s * mp].reshape(s, mp)
                     + 1, jnp.int32)
    lengths = rng.integers(1, mp * ps + 1, s)
    lengths[0], lengths[1] = 0, ps + 3
    lengths = jnp.asarray(lengths, jnp.int32)
    starts = jnp.asarray(rng.integers(0, (mp - 1) * ps - c + 1, s),
                         jnp.int32)
    n_valid = rng.integers(1, c + 1, s)
    n_valid[0], n_valid[-1] = 0, c
    n_valid = jnp.asarray(n_valid, jnp.int32)
    errs = {}
    for kv, kw in ((kv_heads[0], {}), (kv_heads[1], dict(
            window=window,
            sinks=jnp.asarray(rng.standard_normal(h) + 2.0, jnp.float32)))):
        k32, v32 = (jnp.asarray(rng.standard_normal(
            (num_pages, ps, kv * width)), jnp.float32) for width in (dk, dv))
        for dtype in (jnp.float32, jnp.bfloat16):
            pool = (k32.astype(dtype), v32.astype(dtype))
            for name, q, geo in (
                    ("ragged_paged_decode", (s, h, dk), (lengths,)),
                    ("ragged_paged_prefill", (s, c, h, dk),
                     (starts, n_valid))):
                q = jnp.asarray(rng.standard_normal(q), jnp.float32)
                args = (q, *pool, bt, *geo)
                contract = kernels.get(name).contract
                out = jax.jit(lambda *a, _n=name: kernels.dispatch(
                    _n, *a, impl=sizes.kernel_impl, **kw))(*args)
                with jax.default_matmul_precision("highest"):
                    ref = jax.jit(lambda *a, _n=name: kernels.dispatch(
                        _n, *a, impl="lax", **kw))(*args)
                out, ref = np.asarray(out), np.asarray(ref)
                label = f"{name}[kv{kv},{jnp.dtype(dtype).name}]"
                assert out.shape == ref.shape == q.shape[:-1] + (dv,) \
                    and np.isfinite(out).all(), label
                errs[label] = float(np.max(np.abs(out - ref)))
                np.testing.assert_allclose(
                    out, ref, atol=contract.atol, rtol=contract.rtol,
                    err_msg=f"{label} {sizes.kernel_impl} vs lax")
    log("wide-key paged kernels vs lax max|err|: " + json.dumps(errs))
    forms = _prefill_forms()
    if h * c >= 4096:       # a wide chunk folds a page once a KV head
        assert "fold=group,operands=stored,terms=each" in forms, forms
    log("wide-key prefill lowerings: " + json.dumps(forms))


# ---------------------------------------------------------------------------
# phase 2: the trainer
# ---------------------------------------------------------------------------

def _bert_setup(sizes, seed, **overrides):
    """BERT-base with attention dropout off (the flash kernel has no
    dropout path) and everything else as configured, unless
    ``overrides`` (``BertConfig`` fields) says otherwise."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu import optimizer as opt
    from paddle_tpu.core import dtypes
    from paddle_tpu.models.bert import BertConfig, BertForPretraining
    from paddle_tpu.train import build_train_step, make_train_state

    kw = dict(sizes.bert, attn_dropout=0.0, **overrides)
    if sizes.interpret:
        kw["attn_impl"] = "flash_interpret"
    cfg = BertConfig.base(**kw)
    model = BertForPretraining(cfg)
    optimizer = opt.AdamW(learning_rate=1e-4)
    state = make_train_state(model, optimizer, jax.random.PRNGKey(seed))

    def loss_fn(params, **batch):
        return model.loss(params, training=True, **batch)

    step = build_train_step(loss_fn, optimizer,
                            policy=dtypes.get_policy("bf16"))
    b, seq = sizes.bert_batch, sizes.bert_seq
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed + 1), 3)
    batch = dict(
        input_ids=jax.random.randint(k1, (b, seq), 0, cfg.vocab_size,
                                     jnp.int32),
        token_type_ids=jnp.zeros((b, seq), jnp.int32),
        attention_mask=jnp.ones((b, seq), bool),
        mlm_labels=jax.random.randint(k2, (b, seq), 0, cfg.vocab_size,
                                      jnp.int32),
        mlm_mask=(jax.random.uniform(k3, (b, seq)) < 0.15
                  ).astype(jnp.float32),
        nsp_labels=jnp.zeros((b,), jnp.int32),
    )
    return model, state, step, batch


def _run_steps(run, state, batch, n, dropout_key=None):
    """``n`` optimizer steps on ``batch``; with ``dropout_key``, step i
    draws its dropout masks from ``fold_in(dropout_key, i)``."""
    import jax
    losses = []
    for i in range(n):
        if dropout_key is not None:
            batch = dict(batch, key=jax.random.fold_in(dropout_key, i))
        state, metrics = run(state, **batch)
        losses.append(float(metrics["loss"]))
    return state, losses


def phase_trainer(sizes, seed):
    import jax
    from paddle_tpu.core import dtypes
    flash_before = _dispatched("flash_attention", sizes.kernel_impl)
    model, state, step, batch = _bert_setup(sizes, seed)
    assert model.cfg.dropout > 0.0      # the dropout/RNG path runs too
    # the batch's loss without dropout, before and after the steps: a
    # training loss swings with the masks it happened to draw by more
    # than five steps move it
    policy = dtypes.get_policy("bf16")
    eval_loss = jax.jit(lambda p: model.loss(
        policy.cast_to_compute(p), training=False, **batch)[0])
    before = float(eval_loss(state["params"]))
    step = jax.jit(step, donate_argnums=(0,))
    t0 = time.perf_counter()
    state, losses = _run_steps(step, state, batch, 5,
                               dropout_key=jax.random.PRNGKey(seed + 3))
    jax.block_until_ready(state)
    dt = time.perf_counter() - t0
    after = float(eval_loss(state["params"]))
    flash = _dispatched("flash_attention", sizes.kernel_impl) - flash_before
    log(f"trainer: losses={[round(x, 4) for x in losses]} evaluation-mode "
        f"loss {before:.4f} -> {after:.4f} "
        f"flash_attention[{sizes.kernel_impl}] dispatches={int(flash)} "
        f"seconds={dt:.1f} (compile included)")
    assert all(math.isfinite(x) for x in losses), losses
    assert after < before, f"loss did not fall: {before} -> {after}"
    assert flash > 0, "BERT attention did not resolve to the flash kernel"


# ---------------------------------------------------------------------------
# phase 3: paged serving
# ---------------------------------------------------------------------------

def _gpt_setup(sizes, seed):
    import jax
    from paddle_tpu.models.gpt import GPT, GPTConfig
    model = GPT(GPTConfig(**sizes.gpt))
    params = model.init(jax.random.PRNGKey(seed))
    return model, params


def _prompts(sizes, vocab, seed):
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, vocab, n).astype(np.int32)
               for n in sizes.prompt_lens]
    prompts[-1][:sizes.shared_prefix] = prompts[0][:sizes.shared_prefix]
    return prompts


def _engine_kwargs(sizes):
    kw = dict(num_slots=sizes.num_slots, page_size=sizes.page_size,
              prefill_chunk=sizes.prefill_chunk,
              max_tokens_per_slot=sizes.max_tokens_per_slot)
    if sizes.interpret:
        kw["attn_impl"] = "pallas_interpret"
    return kw


def _serve(eng, prompts, n, prefill_steps):
    """Submit all but the last prompt, step until request 0's prompt is
    prefilled (``prefill_steps``), submit the last (it shares request
    0's prefix), step to idle; -> {index: tokens}."""
    rids = {eng.submit(p, n): i for i, p in enumerate(prompts[:-1])}
    got = {}
    for _ in range(prefill_steps):
        got.update(eng.step())
    rids[eng.submit(prompts[-1], n)] = len(prompts) - 1
    steps = 0
    while not eng.scheduler.idle():
        got.update(eng.step())
        steps += 1
        assert steps < 10_000, "engine never went idle"
    assert set(got) == set(rids), f"unfinished requests: {set(rids) - set(got)}"
    return {rids[r]: np.asarray(t) for r, t in got.items()}


def _check_tokens(model, params, prompts, served, n, what):
    """Engine tokens vs ``model.generate`` on the same device. A
    mismatch is admitted only at a reference near-tie (TIE_MARGIN);
    tokens after it are not compared (the sequences have forked)."""
    import jax
    gen = jax.jit(lambda p, ids: model.generate(
        p, ids, max_new_tokens=n, use_cache=True))
    fwd = jax.jit(model.forward)
    exact = 0
    for i, prompt in enumerate(prompts):
        ref = np.asarray(gen(params, prompt[None]))[0, len(prompt):]
        out = served[i]
        assert out.shape == ref.shape, (i, out.shape, ref.shape)
        diff = np.nonzero(out != ref)[0]
        if diff.size == 0:
            exact += 1
            continue
        pos = int(diff[0])
        ids = np.concatenate([prompt, ref[:pos]]).astype(np.int32)
        logits = np.asarray(fwd(params, ids[None]))[0, -1].astype(np.float64)
        top = np.sort(logits)[-2:]
        margin = float(logits.max() - logits[out[pos]])
        log(f"{what}: request {i} first differs at new-token {pos}: "
            f"engine={int(out[pos])} reference={int(ref[pos])} "
            f"reference top-2 margin={float(top[1] - top[0]):.3e} "
            f"top-1 minus engine's token={margin:.3e} "
            f"(tolerance {TIE_MARGIN})")
        assert margin < TIE_MARGIN, (
            f"{what}: request {i} token {pos} is not a near-tie")
    log(f"{what}: {exact}/{len(prompts)} requests token-exact vs "
        "model.generate")


def phase_serving(sizes, seed):
    from paddle_tpu import inference
    from paddle_tpu import observability as obs

    model, params = _gpt_setup(sizes, seed)
    prompts = _prompts(sizes, model.cfg.vocab_size, seed + 2)
    n = sizes.new_tokens
    impl = sizes.kernel_impl
    names = ("ragged_paged_decode", "ragged_paged_prefill")
    before = {(k, i): _dispatched(k, i) for k in names
              for i in (impl, "lax")}
    reg = obs.MetricsRegistry()
    eng = inference.make_serving_engine(model, params, registry=reg,
                                        **_engine_kwargs(sizes))
    stats0 = dict(COMPILE_STATS)
    t0 = time.perf_counter()
    eng.warmup()
    t_warm = time.perf_counter() - t0
    log(f"serving: warmup {t_warm:.1f}s, of which JAX reports "
        + json.dumps(_compile_stats_since(stats0)))
    det = obs.RecompileDetector("chip_smoke_serving", warmup=0,
                                registry=reg)
    t0 = time.perf_counter()
    served = _serve(eng, prompts, n, sizes.prefill_steps)
    t_serve = time.perf_counter() - t0
    det.check()
    ran = {k: _dispatched(*k) - v for k, v in before.items()}
    log(f"serving: warmup {len(eng.warmed_signatures)} signatures in "
        f"{t_warm:.1f}s, {len(prompts)} requests in {t_serve:.2f}s, "
        f"compiles after warmup={det.recompiles}, dispatches="
        + json.dumps({f"{k}[{i}]": int(c) for (k, i), c in ran.items()}))
    assert det.recompiles == 0, "serving compiled after warmup"
    for k in names:
        assert ran[(k, impl)] > 0, f"{k} never resolved to {impl}"
        assert ran[(k, "lax")] == 0, f"{k} fell back to lax"
    shared = reg.counter("serving_prompt_tokens_total").value() \
        - reg.counter("serving_prefill_tokens_total").value()
    assert shared > 0, "the shared prefix was prefilled twice"
    _check_tokens(model, params, prompts, served, n, "serving")


# ---------------------------------------------------------------------------
# phase 4: the sparse-attention / sparse-expert family
# ---------------------------------------------------------------------------

def phase_sparse_family(sizes, seed):
    import os

    import jax
    import jax.numpy as jnp
    from paddle_tpu import inference, kernels
    from paddle_tpu.models.sparse_moe_lm import (SparseMoELM,
                                                 SparseMoELMConfig)

    impl = sizes.kernel_impl
    cfg = SparseMoELMConfig(kernel_impl=impl, **sizes.sparse)
    names = ("lightning_indexer", "sparse_paged_decode",
             "sparse_paged_prefill", "moe_grouped_ffn")
    # -- the new kernels against their lax forms, at this model's widths
    errs = {}
    for name in names:
        spec = kernels.get(name)
        args, kw = _sparse_kernel_args(name, cfg, sizes, seed)
        out = jax.jit(lambda *a, _n=name: kernels.dispatch(
            _n, *a, impl=impl, **kw))(*args)
        with jax.default_matmul_precision("highest"):
            ref = jax.jit(lambda *a, _n=name: kernels.dispatch(
                _n, *a, impl="lax", **kw))(*args)
        out, ref = np.asarray(out, np.float32), np.asarray(ref, np.float32)
        assert out.shape == ref.shape and np.isfinite(out).all(), name
        errs[name] = float(np.max(np.abs(out - ref)))
        np.testing.assert_allclose(
            out, ref, atol=spec.contract.atol, rtol=spec.contract.rtol,
            err_msg=f"{name} {impl} vs lax")
    log("sparse family kernels vs lax max|err|: " + json.dumps(errs))
    _selection_at_the_cells_geometry(cfg, sizes, seed)

    # -- a short serve through the engine, against the plain reference
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "benchmark"))
    from families import keye_vl2
    model = SparseMoELM(cfg)
    params = jax.jit(lambda k: model.init(k, dtype=jnp.bfloat16))(
        jax.random.PRNGKey(seed))
    before = {(k, i): _dispatched(k, i) for k in names
              for i in (impl, "lax")}
    n0, n = sizes.sparse_prompt, sizes.new_tokens
    pages = -(-(n0 + n + 8) // sizes.sparse_page_size)
    kw = dict(num_slots=2, page_size=sizes.sparse_page_size,
              prefill_chunk=sizes.sparse_chunk, attn_impl=impl,
              max_tokens_per_slot=pages * sizes.sparse_page_size)
    eng = inference.make_serving_engine(model, params, **kw)
    prompt = np.random.default_rng(seed + 3).integers(
        0, cfg.vocab_size, n0).astype(np.int32)
    t0 = time.perf_counter()
    rid = eng.submit(prompt, n)
    while not eng.scheduler.idle():
        eng.step()
    out = np.asarray(eng.result(rid))
    t_serve = time.perf_counter() - t0
    for k in names:
        assert _dispatched(k, impl) > before[(k, impl)], \
            f"{k} never resolved to {impl}"
        assert _dispatched(k, "lax") == before[(k, "lax")], \
            f"{k} fell back to lax"
    published = keye_vl2.sizes_of(cfg)
    ids = np.concatenate([prompt, out]).astype(np.int32)[None]
    with jax.default_matmul_precision("highest"):
        logits = np.asarray(jax.jit(
            lambda p, i: keye_vl2.reference_logits(
                p, i, published, n0 - 1, n, query_block=128))(
            params, jnp.asarray(ids)))[0].astype(np.float64)
    gaps = logits.max(-1) - logits[np.arange(n), out]
    log(f"sparse family: {n0}-token prompt + {n} tokens in {t_serve:.1f}s "
        f"(compiles included); {int((gaps == 0).sum())}/{n} tokens are the "
        f"float32 reference's argmax, largest shortfall {gaps.max():.3e} "
        f"logits (tolerance {SPARSE_TIE_MARGIN})")
    assert gaps.max() < SPARSE_TIE_MARGIN, \
        "sparse family left the reference"


def _selection_at_the_cells_geometry(cfg, sizes, seed):
    """The engine's selection mask (``topk_selection_mask``) against the
    ``lax.top_k`` statement of the rule at the docs cell's geometry, a
    decode call's 32 rows and a prefill call's 4 x 64 of 128 pages, over
    score sets drawn like index scores (a weighted sum of relu'd
    products: exact zeros), one of them quantised so that ties cross the
    threshold: 0 elements may differ. Then the kernel alone beside
    ``lax.top_k`` on ``(S, T)``: 16 calls in one program, each call's
    scores made from the mask before it, so that what feeds a call is in
    the chain and no call is shared; the feeding alone is timed too."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.serving import sparse_attention as SA
    impl, topk = sizes.kernel_impl, cfg.indexer_topk
    slots, lanes, calls = (4, 2, 2) if sizes.interpret else (32, 4, 16)
    c, t = sizes.sparse_chunk, 128 * sizes.sparse_page_size
    rng = np.random.default_rng(seed)

    def scores_of(rows, kind):
        # four heads: a sixteenth of the scores are zeros, of the sign
        # of the head weights where those agree
        w = rng.standard_normal((rows, 1, 4)).astype(np.float32)
        x = (w * np.maximum(rng.standard_normal((rows, t, 4)).astype(
            np.float32), 0.0)).sum(-1)
        x = np.round(x * 4) / 4 if kind == "ties" else x
        return jnp.asarray(x, jnp.float32)

    decode = jax.jit(lambda x, n: SA.select_decode_mask(x, n, topk,
                                                        impl=impl))
    prefill = jax.jit(lambda x, st: SA.select_prefill(x, st, None, topk,
                                                      impl=impl))
    by_sort = jax.jit(lambda x, n: SA.selected_by_sort(x, n, topk))
    differing = {}
    for kind in ("ties", "drawn", "drawn again"):
        n = rng.integers(topk + 1, t + 1, slots)
        n[:3] = (t, topk + 1, topk)
        x, n = scores_of(slots, kind), jnp.asarray(n, jnp.int32)
        got, want = np.asarray(decode(x, n)), np.asarray(by_sort(x, n))
        assert want.sum(1).tolist() == [topk] * slots
        starts = rng.integers(0, t - c + 1, lanes)
        starts[:2] = (t - c, topk - c // 2)
        x = scores_of(lanes * c, kind).reshape(lanes, c, t)
        st = jnp.asarray(starts, jnp.int32)
        got_p = np.asarray(prefill(x, st))
        want_p = np.asarray(by_sort(x, st[:, None] + jnp.arange(1, c + 1)))
        differing[kind] = [int((got != want).sum()), got.size,
                           int((got_p != want_p).sum()), got_p.size]
    log("selection mask vs lax.top_k, elements differing [decode, of, "
        "prefill, of]: " + json.dumps(differing))
    assert all(d[0] == d[2] == 0 for d in differing.values()), differing

    x0 = scores_of(slots, "drawn")
    n = jnp.full((slots,), t, jnp.int32)

    def chain(select):
        def run(x):
            for _ in range(calls):
                x = x + 0.5 * select(x)
            return x
        return jax.jit(run)

    def top_k_mask(x):
        vals, _ = jax.lax.top_k(x, topk)
        return (x >= vals[:, -1:]).astype(jnp.float32)

    per_call = {}
    for what, select in (
            ("topk_selection_mask", lambda x: SA.select_decode_mask(
                x, n, topk, impl=impl)),
            ("lax.top_k", top_k_mask),
            ("the feeding alone", lambda x: (x > 0).astype(jnp.float32))):
        run = chain(select)
        run(x0).block_until_ready()
        best = math.inf
        for _ in range(5):
            t0 = time.perf_counter()
            run(x0).block_until_ready()
            best = min(best, time.perf_counter() - t0)
        per_call[what] = round(best / calls * 1e6, 1)
    log(f"selection alone, ({slots}, {t}) for {topk}, us a call in a chain "
        f"of {calls}" + (" (interpreted: no device time)" if sizes.interpret
                         else "") + ": " + json.dumps(per_call))


def _serve_slot_state_family(what, model, family, kernel_names, sizes, seed,
                             state_entries, margin):
    """One request of ``sizes.hybrid_prompt`` tokens (whole chunks and a
    ragged one) and ``sizes.new_tokens`` new ones through a two-slot
    engine of a family that keeps state a slot: its kernels ran on
    ``sizes.kernel_impl`` and never on ``lax``, the slot's row of each of
    the last ``state_entries`` pool arrays changed while its neighbour's
    stayed zero, and every chosen token lies within ``margin`` logits of
    the best of ``family.reference_logits`` (plain float32)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu import inference

    impl, cfg = sizes.kernel_impl, model.cfg
    params = jax.jit(lambda k: model.init(k, dtype=jnp.bfloat16))(
        jax.random.PRNGKey(seed))
    before = {(k, i): _dispatched(k, i) for k in kernel_names
              for i in (impl, "lax")}
    n0, n_new = sizes.hybrid_prompt, sizes.new_tokens
    pages = -(-(n0 + n_new + 8) // sizes.hybrid_page_size)
    eng = inference.make_serving_engine(
        model, params, num_slots=2, page_size=sizes.hybrid_page_size,
        prefill_chunk=sizes.hybrid_chunk, attn_impl=impl,
        max_tokens_per_slot=pages * sizes.hybrid_page_size)
    prompt = np.random.default_rng(seed + 5).integers(
        0, cfg.vocab_size, n0).astype(np.int32)
    t0 = time.perf_counter()
    rid = eng.submit(prompt, n_new)
    while not eng.scheduler.idle():
        eng.step()
    out = np.asarray(eng.result(rid))
    t_serve = time.perf_counter() - t0
    for k in kernel_names:
        assert _dispatched(k, impl) > before[(k, impl)], \
            f"{k} never resolved to {impl}"
        assert _dispatched(k, "lax") == before[(k, "lax")], \
            f"{k} fell back to lax"
    for state in eng.cache.pages[0][-state_entries:]:
        state = np.asarray(state)
        assert state.dtype == np.float32 and state[1].any(), \
            "the slot's state row never changed"
        assert not state[2].any(), "a neighbour's state row changed"
    ids = np.concatenate([prompt, out]).astype(np.int32)[None]
    with jax.default_matmul_precision("highest"):
        logits = np.asarray(jax.jit(
            lambda pr, i: family.reference_logits(
                pr, i, family.sizes_of(cfg), n0 - 1, n_new))(
            params, jnp.asarray(ids)))[0].astype(np.float64)
    gaps = logits.max(-1) - logits[np.arange(n_new), out]
    log(f"{what} family: {n0}-token prompt + {n_new} tokens in "
        f"{t_serve:.1f}s (compiles included); {int((gaps == 0).sum())}/"
        f"{n_new} tokens are the float32 reference's argmax, largest "
        f"shortfall {gaps.max():.3e} logits of a spread of "
        f"{logits.std():.3e} (tolerance {margin})")
    assert gaps.max() < margin, f"{what} family left the reference"


# ---------------------------------------------------------------------------
# phase 5: the attention + state-space hybrid
# ---------------------------------------------------------------------------

def phase_hybrid_family(sizes, seed):
    import os

    import jax
    import jax.numpy as jnp
    from paddle_tpu import kernels
    from paddle_tpu.models.hybrid_ssm_lm import (HybridSSMLM,
                                                 HybridSSMLMConfig)

    impl = sizes.kernel_impl
    cfg = HybridSSMLMConfig(kernel_impl=impl, **sizes.hybrid)
    names = ("ssd_chunk_scan", "ssm_decode_update")
    # -- the two kernels against their lax forms, at this model's widths:
    # four lanes (one fresh, one ragged, one a pad lane on the null row)
    rng = np.random.default_rng(seed)
    h, p, g, n = (cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_n_groups,
                  cfg.mamba_d_state)
    s, c = 4, sizes.hybrid_chunk

    def normal(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    dt = np.log1p(np.exp(rng.standard_normal((s, c, h)))).astype(np.float32)
    dt[2, c // 2:] = 0.0
    dt[3] = 0.0
    scan = (normal(s, c, h * p), jnp.asarray(0.1 * dt),
            -jnp.exp(0.5 * normal(h)), normal(s, c, g * n),
            normal(s, c, g * n), normal(s + 2, h, n, p),
            jnp.asarray([2, 5, 3, 0], jnp.int32),
            jnp.asarray([0, 1, 0, 0], jnp.int32))
    one = tuple(a[:, 0] for a in scan[:2]) + (scan[2],) + tuple(
        a[:, 0] for a in scan[3:5]) + scan[5:7]
    errs = {}
    for name, args in (("ssd_chunk_scan", scan), ("ssm_decode_update", one)):
        spec = kernels.get(name)
        kw = dict(n_groups=g)
        out = jax.jit(lambda *a, _n=name: kernels.dispatch(
            _n, *a, impl=impl, **kw))(*args)
        with jax.default_matmul_precision("highest"):
            ref = jax.jit(lambda *a, _n=name: kernels.dispatch(
                _n, *a, impl="lax", **kw))(*args)
        for got, want in zip(out, ref):
            got, want = np.asarray(got), np.asarray(want)
            assert got.shape == want.shape and np.isfinite(got).all(), name
            np.testing.assert_allclose(
                got, want, atol=spec.contract.atol * max(
                    1.0, float(np.abs(want).max())),
                rtol=spec.contract.rtol, err_msg=f"{name} {impl} vs lax")
            errs[name] = max(errs.get(name, 0.0),
                             float(np.max(np.abs(got - want))))
        idle = [r for r in range(1, s + 2) if r not in (2, 5, 3)]
        assert (np.asarray(out[1])[idle] == np.asarray(args[5])[idle]).all(), \
            f"{name} touched a row no lane holds"
    log("hybrid family kernels vs lax max|err|: " + json.dumps(errs))

    # -- a short serve through the engine, against the plain reference
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "benchmark"))
    from families import falcon_h1
    _serve_slot_state_family("hybrid", HybridSSMLM(cfg), falcon_h1, names,
                             sizes, seed, 1, HYBRID_TIE_MARGIN)


# ---------------------------------------------------------------------------
# phase 6: latent conv attention + top-1 experts with a carried router
# ---------------------------------------------------------------------------

def phase_latent_family(sizes, seed):
    import os

    import jax
    import jax.numpy as jnp
    from paddle_tpu import kernels
    from paddle_tpu.models.latent_conv_moe_lm import (LatentConvMoELM,
                                                      LatentConvMoELMConfig)
    from paddle_tpu.ops import grouped_ffn

    impl = sizes.kernel_impl
    cfg = LatentConvMoELMConfig(kernel_impl=impl, **sizes.latent)
    name = "moe_grouped_ffn"
    # -- the grouped expert kernel at one expert a token, at this model's
    # widths: a decode batch (tiles of 16 rows) and a prefill call (32)
    rng = np.random.default_rng(seed)
    e, f, d = cfg.num_experts, cfg.moe_intermediate_size, cfg.hidden_size
    w = [jnp.asarray(rng.standard_normal((e, f, d)) * d ** -0.5,
                     jnp.bfloat16) for _ in range(3)]
    spec, errs = kernels.get(name), {}
    for tokens in (4 * e, 40 * e):
        ids = jnp.asarray(rng.integers(0, e, (tokens, 1)), jnp.int32)
        valid = jnp.asarray(rng.uniform(size=tokens) < 0.9)
        src, _dest, tile_expert, n_used, _ = grouped_ffn.route_tiles(
            ids, valid, e, grouped_ffn.tile_rows(tokens, e))
        x = jnp.asarray(rng.standard_normal((tokens, d)), jnp.bfloat16)
        args = (jnp.where((src >= 0)[:, None], x[jnp.maximum(src, 0)], 0),
                tile_expert, n_used, *w)
        got = np.asarray(jax.jit(lambda *a: kernels.dispatch(
            name, *a, impl=impl))(*args), np.float32)
        want = np.asarray(jax.jit(lambda *a: kernels.dispatch(
            name, *a, impl="lax"))(*args), np.float32)
        assert got.shape == want.shape and np.isfinite(got).all(), name
        # bf16 operands and a bf16 result on both sides: one rounding
        np.testing.assert_allclose(
            got, want, atol=2 ** -7 * max(1.0, float(np.abs(want).max())),
            rtol=spec.contract.rtol, err_msg=f"{name} {impl} vs lax")
        errs[f"{tokens} tokens"] = float(np.max(np.abs(got - want)))
    log("latent family kernels vs lax max|err|: " + json.dumps(errs))

    # -- a short serve through the engine, against the plain reference
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "benchmark"))
    from families import zaya
    _serve_slot_state_family("latent", LatentConvMoELM(cfg), zaya, (name,),
                             sizes, seed, 3, LATENT_TIE_MARGIN)


# ---------------------------------------------------------------------------
# phase 8: a selection over a latent cache, at DeepSeek-V3.2's widths
# ---------------------------------------------------------------------------

def phase_selecting_latent_kernels(sizes, seed):
    """The kernels of ``layer_kinds.SelectingLatent`` against their ``lax``
    forms, and the selection's mask against ``lax.top_k``, element for
    element: decode's grouped walk that compacts the selected rows out of
    whole pages and folds them (a shared document, slots walked alone,
    whole pages selected), the same two parts for a chunk's tokens eight
    of a lane a walk, the indexer where a decode step's few rows meet a
    block's key pages in one product and where a chunk's heads are
    summed a few queries at a time."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu import kernels
    from paddle_tpu.serving import sparse_attention as SA

    h, dl, dr, j, di, topk, ps, c, mp = sizes.selecting_latent
    impl = sizes.kernel_impl
    dt = jnp.float32 if sizes.interpret else jnp.bfloat16
    s = 8
    num_pages = s * mp + 1
    rng = np.random.default_rng(seed)

    def normal(*shape, scale=1.0):
        return jnp.asarray(scale * rng.standard_normal(shape),
                           jnp.float32).astype(dt)

    tables = (1 + rng.permutation(num_pages - 1)[:s * mp]).reshape(
        s, mp).astype(np.int32)
    shared = mp - 4
    tables[:5, :shared] = tables[0, :shared]      # five slots, one document
    lengths = rng.integers(shared * ps, mp * ps + 1, s).astype(np.int32)
    lengths[-1] = topk // 2                       # sees fewer than it takes
    bt, lens = jnp.asarray(tables), jnp.asarray(lengths)
    c_pages = normal(num_pages, ps, dl)
    r_pages = normal(num_pages, ps, dr + -dr % 128)
    ik_pages = normal(num_pages, di, ps)
    errs = {}

    def close(name, got, want, atol):
        got, want = (np.asarray(a, np.float32) for a in (got, want))
        assert got.shape == want.shape and np.isfinite(got).all(), name
        np.testing.assert_allclose(got, want, atol=atol, rtol=2e-2,
                                   err_msg=f"{name} {impl} vs lax")
        errs[name] = float(np.max(np.abs(got - want)))

    # -- the indexer: a decode step's few rows against a block's pages in
    # one product, a wide chunk summed a few queries at a time
    q_idx, w_idx = normal(s, 1, j, di), jnp.asarray(
        rng.standard_normal((s, 1, j)), jnp.float32)
    alone = jax.jit(lambda *a: SA.lightning_index_scores(*a, impl=impl))(
        q_idx, w_idx, ik_pages, bt, lens)[:, 0]
    close("indexer decode", alone, jax.jit(
        lambda *a: SA.lightning_index_scores(*a, impl="lax"))(
            q_idx, w_idx, ik_pages, bt, lens)[:, 0], 2e-2 * di ** 0.5)
    starts = jnp.asarray(np.maximum(lengths - c, 0))
    n_valid = jnp.asarray(rng.integers(1, c + 1, s), jnp.int32)
    qc, wc = normal(s, c, j, di), jnp.asarray(
        rng.standard_normal((s, c, j)), jnp.float32)
    close("indexer chunk", *(jax.jit(
        lambda *a, i=i: SA.lightning_index_scores(*a, impl=i))(
            qc, wc, ik_pages, bt, starts + n_valid)
        for i in (impl, "lax")), 2e-2 * di ** 0.5)

    # -- the selection: the counting mask both kernels take, against the
    # sort, element for element
    mask = jax.jit(lambda a, n: SA.select_decode_mask(
        a, n, topk, impl=impl))(alone, lens)
    want = np.asarray(SA.selected_by_sort(alone, lens, topk))
    assert ((np.asarray(mask) > 0) == (want > 0)).all()
    assert int(want[-1].sum()) == topk // 2
    log(f"selection mask vs lax.top_k: {int(want.sum())} of "
        f"{want.size} marked, 0 differ")

    # -- the grouped decode: five slots over one document's pages walked
    # once, three walked alone (the last selects whole pages: 8 passes of
    # 16 rows each), compacted and folded on the chip, against the ``lax``
    # form, the NumPy reference and the same slots walked alone
    scale = (dl + dr) ** -0.5 / 4
    q = normal(s, h, dl + dr, scale=scale)
    groups = SA.DA.decode_groups(tables, lengths, np.arange(s), ps)
    folded = shared // SA.DA.GROUP_SHARED_PAGES * SA.DA.GROUP_SHARED_PAGES
    assert (groups[2][:5] == folded).all() and not groups[2][5:].any()
    a_page = np.asarray(mask).reshape(s, mp, ps).sum(-1)
    assert a_page[-1].max() == ps and a_page[-1].sum() == topk // 2

    def decode(i, groups):
        groups = None if groups is None else tuple(map(jnp.asarray, groups))
        return jax.jit(lambda *a: SA.selected_latent_decode_attention(
            *a, groups, impl=i))(q, c_pages, r_pages, bt, mask, lens)

    grouped = decode(impl, groups)
    close("sparse_latent_decode", grouped, decode("lax", groups), 2 ** -6)
    close("sparse_latent_decode, every slot alone", decode(impl, None),
          grouped, 2 ** -6)
    probe = np.asarray([0, 4, 5, s - 1])       # members, a lone slot, whole pages
    close("sparse_latent_decode vs NumPy", grouped[probe],
          SA._sparse_latent_decode_reference(
              *(jnp.asarray(a, jnp.float32)[probe] if a.shape[0] == s
                else jnp.asarray(a, jnp.float32)
                for a in (q, c_pages, r_pages)), bt[probe], mask[probe],
              lens[probe]), 2 ** -6)
    lanes = 2
    qp = normal(lanes, c, h, dl + dr, scale=scale)
    scores = jax.jit(lambda *a: SA.lightning_index_scores(*a, impl=impl))(
        qc[:lanes], wc[:lanes], ik_pages, bt[:lanes],
        (starts + n_valid)[:lanes])
    # -- the prefill: a chunk's tokens through the same two parts under
    # the prefill's name, the second lane part dead
    seen = SA._chunk_extents(starts[:lanes], n_valid[:lanes], c)
    if mp * ps > topk:
        marks = jax.jit(lambda a, n: SA.select_decode_mask(
            a, n, topk, impl=impl))(scores.reshape(lanes * c, -1),
                                    seen.reshape(-1))
    else:
        marks = (jnp.arange(mp * ps)[None, :]
                 < seen.reshape(-1, 1)).astype(jnp.float32)
    close("sparse_latent_prefill", *(jax.jit(
        lambda *a, i=i: SA.sparse_latent_prefill_attention(*a, impl=i))(
            qp, c_pages, r_pages, bt[:lanes], starts[:lanes],
            n_valid[:lanes], marks.reshape(lanes, c, -1))
        for i in (impl, "lax")), 2 ** -6)
    for name in ("sparse_latent_decode", "sparse_latent_prefill",
                 "lightning_indexer"):
        assert _dispatched(name, impl) > 0
    log("selecting latent kernels vs lax max|err|: " + json.dumps(errs))


# ---------------------------------------------------------------------------
# phase 9: a state layer's two kernels and the full layer beside it, at
# Qwen3-Next's widths
# ---------------------------------------------------------------------------

def phase_gated_delta_kernels(sizes, seed):
    """``gated_delta_chunk_scan`` and ``gated_delta_decode_update`` against
    their ``lax`` forms at the published tile sizes, the dense paged
    kernels at heads of 256 against theirs, and the one-token update over
    every slot of the cell timed against its state tiles' bytes."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu import kernels
    from paddle_tpu.serving import decode_attention as DA

    hk, hv, dk, dv, c, slots, hq, kv, dh, ps, mp = sizes.gated_delta
    impl = sizes.kernel_impl
    rng = np.random.default_rng(seed)

    def normal(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    def unit(a):
        return a * jax.lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + 1e-6)

    # -- the two kernels: four lanes, one fresh, one ragged, one a pad
    # lane on the null row; neighbouring keys share a direction
    s = 4
    g = -np.exp(rng.standard_normal(hv)) * 0.05 * np.log1p(np.exp(
        rng.standard_normal((s, c, hv))))
    beta = 1.0 / (1.0 + np.exp(-rng.standard_normal((s, c, hv))))
    g[2, c // 2:], beta[2, c // 2:] = 0.0, 0.0
    g[3], beta[3] = 0.0, 0.0
    scan = (unit(normal(s, c, hk, dk)) * dk ** -0.5,
            unit(normal(s, c, hk, dk) + 0.7 * normal(s, 1, hk, dk)),
            normal(s, c, hv, dv), jnp.asarray(g, jnp.float32),
            jnp.asarray(beta, jnp.float32), normal(s + 2, hv, dk, dv),
            jnp.asarray([2, 5, 3, 0], jnp.int32),
            jnp.asarray([0, 1, 0, 0], jnp.int32))
    one = tuple(a[:, 0] for a in scan[:3]) + (
        jnp.exp(scan[3][:, 0]), scan[4][:, 0]) + scan[5:7]
    errs = {}
    for name, args in (("gated_delta_chunk_scan", scan),
                       ("gated_delta_decode_update", one)):
        spec = kernels.get(name)
        out = jax.jit(lambda *a, _n=name: kernels.dispatch(
            _n, *a, impl=impl))(*args)
        with jax.default_matmul_precision("highest"):
            ref = jax.jit(lambda *a, _n=name: kernels.dispatch(
                _n, *a, impl="lax"))(*args)
        for got, want in zip(out, ref):
            got, want = np.asarray(got), np.asarray(want)
            assert got.shape == want.shape and np.isfinite(got).all(), name
            np.testing.assert_allclose(
                got, want, atol=spec.contract.atol * max(
                    1.0, float(np.abs(want).max())),
                rtol=spec.contract.rtol, err_msg=f"{name} {impl} vs lax")
            errs[name] = max(errs.get(name, 0.0),
                             float(np.max(np.abs(got - want))))
        idle = [r for r in range(1, s + 2) if r not in (2, 5, 3)]
        assert (np.asarray(out[1])[idle] == np.asarray(args[5])[idle]).all(), \
            f"{name} touched a row no lane holds"
        assert _dispatched(name, impl) > 0

    # -- the full layer beside them: heads of 256, 8 query heads a KV head
    dt = jnp.float32 if sizes.interpret else jnp.bfloat16
    n_slots = 8
    num_pages = n_slots * mp + 1
    tables = jnp.asarray((1 + rng.permutation(num_pages - 1)[:n_slots * mp]
                          ).reshape(n_slots, mp), jnp.int32)
    k_pages = normal(num_pages, ps, kv * dh).astype(dt)
    v_pages = normal(num_pages, ps, kv * dh).astype(dt)
    lengths = jnp.asarray(rng.integers(1, mp * ps + 1, n_slots), jnp.int32)
    q = (normal(n_slots, hq, dh) * dh ** -0.25).astype(dt)
    qc = (normal(n_slots, ps, hq, dh) * dh ** -0.25).astype(dt)
    starts = jnp.maximum(lengths - ps, 0)
    n_valid = jnp.asarray(rng.integers(1, ps + 1, n_slots), jnp.int32)
    for name, fn, args in (
            ("ragged_paged_decode", DA.ragged_paged_decode_attention,
             (q, k_pages, v_pages, tables, lengths)),
            ("ragged_paged_prefill", DA.ragged_paged_prefill_attention,
             (qc, k_pages, v_pages, tables, starts, n_valid))):
        got, want = (np.asarray(jax.jit(
            lambda *a, i=i: fn(*a, impl=i))(*args), np.float32)
            for i in (impl, "lax"))
        assert np.isfinite(got).all(), name
        np.testing.assert_allclose(got, want, atol=2 ** -6, rtol=2e-2,
                                   err_msg=f"{name} {impl} vs lax")
        errs[f"{name}[kv{kv}x{dh}]"] = float(np.max(np.abs(got - want)))
        assert _dispatched(name, impl) > 0
    log("gated delta kernels vs lax max|err|: " + json.dumps(errs))

    # -- 16 chained one-token updates over every slot, least of 5
    chain = 16
    rows = jnp.arange(1, slots + 1, dtype=jnp.int32)
    wide = tuple(jnp.broadcast_to(a[:1], (slots,) + a.shape[1:])
                 + 0.01 * normal(slots, *a.shape[1:]) for a in one[:5])

    @jax.jit
    def steps(pool, *args):
        total = 0.0
        for _ in range(chain):
            o, pool = kernels.dispatch("gated_delta_decode_update", *args,
                                       pool, rows, impl=impl)
            total = total + o.sum()
        return total, pool

    pool = normal(slots + 1, hv, dk, dv)
    jax.block_until_ready(steps(pool, *wide))
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        jax.block_until_ready(steps(pool, *wide))
        best = min(best, time.perf_counter() - t0)
    moved = 2.0 * 4 * slots * hv * dk * dv * chain
    log(f"gated_delta_decode_update alone, {slots} slots x {chain} calls: "
        f"{best / chain / slots * 1e6:.2f} us a slot and layer, "
        f"{moved / best / 1e9:.0f} GB/s of state read and written")


def _sparse_kernel_args(name, cfg, sizes, seed):
    """One call's arguments of kernel ``name`` at ``cfg``'s widths: bf16
    pools of 2 slots x 20 pages under float32 queries (as phase 1: the
    outputs are float32, so a difference is the kernel's and not one
    rounding to bf16), the selection from random scores; the expert
    kernel in float32 throughout (its hidden activations round to the
    weights' type inside the body)."""
    import jax.numpy as jnp
    from paddle_tpu.ops import grouped_ffn
    from paddle_tpu.serving import sparse_attention as SA
    rng = np.random.default_rng(seed)
    dt = jnp.float32 if sizes.interpret else jnp.bfloat16
    s, ps, c = 2, sizes.sparse_page_size, sizes.sparse_chunk
    topk = cfg.indexer_topk
    mp = topk // ps + 4
    h, kv, dh = (cfg.num_attention_heads, cfg.num_key_value_heads,
                 cfg.head_dim)
    j, di = cfg.indexer_num_heads, cfg.indexer_head_dim
    num_pages = s * mp + 1

    def normal(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32).astype(dt)

    bt = jnp.asarray(rng.permutation(num_pages - 1)[:s * mp].reshape(s, mp)
                     + 1, jnp.int32)
    lengths = jnp.asarray([mp * ps - 3, topk + ps + 1], jnp.int32)
    if name == "moe_grouped_ffn":
        e, f, d = (cfg.num_experts, cfg.moe_intermediate_size,
                   cfg.hidden_size)
        t, k = 32, cfg.num_experts_per_tok
        ids = jnp.asarray(np.stack([rng.permutation(e)[:k]
                                    for _ in range(t)]), jnp.int32)
        tm = grouped_ffn.tile_rows(t * k, e)
        src, _dest, tile_expert, n_used, _ = grouped_ffn.route_tiles(
            ids, jnp.ones((t,), bool), e, tm)
        x = jnp.asarray(rng.standard_normal((t, d)), jnp.float32)
        x_pad = jnp.where((src >= 0)[:, None], x[jnp.maximum(src, 0)], 0)
        w = [jnp.asarray(rng.standard_normal((e, f, d)), jnp.float32)
             * d ** -0.5 for _ in range(3)]
        return (x_pad, tile_expert, n_used, *w), {}
    if name == "lightning_indexer":
        return (normal(s, c, j, di), jnp.asarray(rng.standard_normal(
            (s, c, j)), jnp.float32), normal(num_pages, di, ps), bt,
            lengths), {}
    kp, vp = normal(num_pages, ps, kv * dh), normal(num_pages, ps, kv * dh)
    if name == "sparse_paged_decode":
        # the two slots open with the same eight pages: one group
        from paddle_tpu.serving import decode_attention as DA
        tables = np.asarray(bt).copy()
        tables[1, :8] = tables[0, :8]
        groups = DA.decode_groups(tables, np.asarray(lengths),
                                         np.arange(s), ps)
        scores = jnp.asarray(rng.standard_normal((s, mp * ps)), jnp.float32)
        selected = SA.select_decode_mask(scores, lengths, topk)
        return (jnp.asarray(rng.standard_normal((s, h, dh)), jnp.float32),
                kp, vp, jnp.asarray(tables), selected, lengths,
                *map(jnp.asarray, groups)), {}
    starts = lengths - c
    n_valid = jnp.asarray([c, c - 3], jnp.int32)
    scores = jnp.asarray(rng.standard_normal((s, c, mp * ps)), jnp.float32)
    selected = SA.select_prefill(scores, starts, n_valid, topk)
    return (jnp.asarray(rng.standard_normal((s, c, h, dh)), jnp.float32),
            kp, vp, bt, starts, n_valid, selected), {}


# ---------------------------------------------------------------------------
# --chips 4: the sharded paths and what they are compared with
# ---------------------------------------------------------------------------

def _assert_spread(tree, devices, what):
    """Some array of ``tree`` is sharded; every sharded one has shards
    on ALL ``devices`` and no device holds the whole of it."""
    import jax
    sharded = 0
    for x in jax.tree_util.tree_leaves(tree):
        if x.sharding.is_fully_replicated:
            continue
        sharded += 1
        held = {s.device for s in x.addressable_shards}
        assert held == set(devices), (what, x.shape, held)
        for s in x.addressable_shards:
            assert math.prod(s.data.shape) < math.prod(x.shape), (
                f"{what}: {s.device} holds all of a sharded {x.shape}")
    assert sharded > 0, f"{what}: nothing is sharded"
    return sharded


def phase_sharded_trainer(sizes, seed, devices):
    import jax
    from paddle_tpu.core.mesh import MeshConfig, make_mesh, mesh_context
    from paddle_tpu.parallel import api as papi, plan as plan_lib

    # no dropout at all: the two runs must be the same function
    model, state, step, batch = _bert_setup(sizes, seed, dropout=0.0)
    mesh = make_mesh(MeshConfig(dp=2, tp=2), devices=devices)
    with mesh_context(mesh):
        run, state = papi.shard_train_step(
            step, mesh, state, plan=plan_lib.megatron_plan(),
            hints=model.sharding_specs(state["params"]),
            batch_spec=papi.batch_specs(batch))
        n = _assert_spread(state["params"], devices, "BERT params")
        state, losses = _run_steps(run, state, batch, 3)
        _assert_spread(state["params"], devices, "BERT params after steps")
    # the same three steps from the same seed on ONE device of this host
    # (a fresh state: placement may alias the buffers the steps donated)
    _, state, step, batch = _bert_setup(sizes, seed, dropout=0.0)
    assert {d for x in jax.tree_util.tree_leaves(state)
            for d in x.devices()} == {devices[0]}
    state, ref_losses = _run_steps(jax.jit(step, donate_argnums=(0,)),
                                   state, batch, 3)
    log(f"sharded trainer: dp2 x tp2 losses={[round(x, 4) for x in losses]}"
        f" one-device losses={[round(x, 4) for x in ref_losses]} "
        f"sharded param arrays={n}")
    assert all(math.isfinite(x) for x in losses + ref_losses)
    np.testing.assert_allclose(losses, ref_losses, rtol=MESH_LOSS_RTOL)


def phase_tp_serving(sizes, seed, devices):
    from paddle_tpu import inference

    model, params = _gpt_setup(sizes, seed)
    prompts = _prompts(sizes, model.cfg.vocab_size, seed + 2)
    n = sizes.new_tokens
    kw = _engine_kwargs(sizes)
    served = {}
    for tp in (1, 4):       # tp=4 takes the host's first four devices
        eng = inference.make_serving_engine(model, params, tp=tp, **kw)
        eng.warmup()
        if tp > 1:
            _assert_spread(eng.cache.pages, devices, "tp=4 page pool")
            _assert_spread(eng._step_params, devices, "tp=4 step params")
        served[tp] = _serve(eng, prompts, n, sizes.prefill_steps)
    same = sum(bool(np.array_equal(served[1][i], served[4][i]))
               for i in range(len(prompts)))
    log(f"tp serving: {same}/{len(prompts)} requests token-equal, "
        "tp=4 vs tp=1")
    assert same == len(prompts), "tp=4 tokens differ from tp=1"


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------

def run_one_chip(sizes, seed=0):
    """The one-chip phases, in order; raises on the first failure."""
    phase_paged_kernels(sizes, seed)
    phase_trainer(sizes, seed)
    phase_serving(sizes, seed)
    phase_sparse_family(sizes, seed)
    phase_hybrid_family(sizes, seed)
    phase_latent_family(sizes, seed)
    phase_wide_key_kernels(sizes, seed)
    phase_selecting_latent_kernels(sizes, seed)
    phase_gated_delta_kernels(sizes, seed)


def run_four_chips(sizes, seed=0, devices=None):
    """The ``--chips 4`` phases and nothing of the one-chip run."""
    import jax
    devices = list(devices or jax.devices())[:4]
    assert len(devices) == 4, f"need 4 devices, have {len(devices)}"
    phase_sharded_trainer(sizes, seed, devices)
    phase_tp_serving(sizes, seed, devices)


#: what JAX itself reports while it compiles (``jax.monitoring``), summed
#: over the process once :func:`_watch_compiles` is on: persistent-cache
#: hits and misses, and seconds spent tracing, lowering to MLIR, in the
#: backend compile (on a cache hit that is the read) and reading the
#: cache. A jit traced inside another is counted in both.
COMPILE_STATS = {"hits": 0, "misses": 0, "trace_s": 0.0, "lower_s": 0.0,
                 "backend_compile_s": 0.0, "cache_read_s": 0.0}
_EVENT_KEYS = {
    "/jax/compilation_cache/cache_hits": "hits",
    "/jax/compilation_cache/cache_misses": "misses",
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "backend_compile_s",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_read_s",
}


def _watch_compiles():
    import jax.monitoring

    def on_event(event, **kw):
        if event in _EVENT_KEYS:
            COMPILE_STATS[_EVENT_KEYS[event]] += 1

    def on_duration(event, seconds, **kw):
        if event in _EVENT_KEYS:
            COMPILE_STATS[_EVENT_KEYS[event]] += seconds

    jax.monitoring.register_event_listener(on_event)
    jax.monitoring.register_event_duration_secs_listener(on_duration)


def _compile_stats_since(before):
    return {k: round(v - before[k], 1) for k, v in COMPILE_STATS.items()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--only", choices=("wide_keys", "selecting_latent",
                                       "gated_delta"),
                    default=None,
                    help="one chip: this phase alone")
    args = ap.parse_args(argv)

    import jax
    found = jax.devices()
    if found[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found "
              f"{found[0].platform!r}", file=sys.stderr)
        return 1
    if len(found) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(found)} device(s)", file=sys.stderr)
        return 1
    # the chips this run uses: the one-chip phases live on the default
    # device (the first), --chips 4 on the first four
    devices = found[:args.chips]
    from paddle_tpu.core.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    _watch_compiles()
    log(f"device={devices[0].device_kind} count={len(devices)} "
        f"(JAX found {len(found)}) compile cache={cache_dir}")
    t0 = time.perf_counter()
    if args.chips == 4:
        run_four_chips(Sizes.real(), args.seed, devices)
    elif args.only:
        {"wide_keys": phase_wide_key_kernels,
         "selecting_latent": phase_selecting_latent_kernels,
         "gated_delta": phase_gated_delta_kernels}[args.only](
             Sizes.real(), args.seed)
    else:
        run_one_chip(Sizes.real(), args.seed)
    log(f"all phases passed in {time.perf_counter() - t0:.1f}s; compile "
        f"cache hits={COMPILE_STATS['hits']} "
        f"misses={COMPILE_STATS['misses']}; peak_bytes_in_use="
        f"{(devices[0].memory_stats() or {}).get('peak_bytes_in_use')}")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
