"""GPT-2's greedy tokens through the serving engine's decode and prefill
loops, held to what commit b130ff6 produced.

PR 28 rewrote ``ServingEngine._decode_loop`` / ``_prefill_loop`` against a
model-supplied serving program (``models/gpt.py``'s ``GPTServing``). The
tokens below were printed by the SAME script on the parent commit
(spelled-out GPT-2 block, before the rewrite): a tiny seeded GPT, five
prompts sharing a 9-token prefix (one repeated verbatim, so a tail page is
copied on write), outputs of 9..13 tokens, Pallas bodies interpreted. Any
change of arithmetic order in the rewritten loops shows up as a changed
token somewhere in these 52.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import inference
from paddle_tpu import observability as obs
from paddle_tpu.models.gpt import GPT, GPTConfig

from serving_taps import once

#: the same 52 tokens from every kind of engine: full-precision pages, int8
#: pages, heads over two devices, a one-layer draft
PARENT_TOKENS = dict.fromkeys(("fp", "int8", "tp2", "spec"), [
    [116, 63, 28, 66, 50, 66, 50, 50, 50],
    [66, 70, 4, 50, 4, 50, 4, 50, 4, 50],
    [70, 10, 116, 116, 116, 116, 70, 10, 116, 116, 116],
    [4, 4, 4, 50, 4, 50, 4, 50, 66, 50, 4, 50],
    [116, 63, 28, 66, 50, 66, 50, 50, 50, 50, 50, 50, 50],
])


def _serve(kind):
    if kind == "tp2" and len(jax.devices()) < 2:
        pytest.skip("needs two devices")
    cfg = GPTConfig.tiny(num_heads=4, attn_impl="xla")
    model = GPT(cfg)
    params = model.init(jax.random.PRNGKey(7))
    kw = dict(num_slots=3, page_size=4, prefill_chunk=8, decode_block=3,
              attn_impl="pallas_interpret", registry=obs.MetricsRegistry())
    if kind == "int8":
        kw["cache_dtype"] = jnp.int8
    if kind == "tp2":
        kw["tp"] = 2
    if kind == "spec":
        draft = GPT(GPTConfig.tiny(num_layers=1, num_heads=4,
                                   attn_impl="xla"))
        kw.update(draft_model=draft,
                  draft_params=draft.init(jax.random.PRNGKey(8)), spec_k=3)
    eng = inference.make_serving_engine(model, params, **kw)
    rng = np.random.default_rng(11)
    shared = rng.integers(0, cfg.vocab_size, 9)
    prompts = [np.concatenate([shared, rng.integers(0, cfg.vocab_size, n)])
               .astype(np.int32) for n in (3, 7, 1, 12)]
    prompts.append(prompts[0].copy())
    rids = [eng.submit(p, 9 + i) for i, p in enumerate(prompts)]
    out = {}
    while not eng.scheduler.idle():
        out.update(eng.step())
    return [np.asarray(out[r]).tolist() for r in rids], eng


@pytest.fixture(scope="module")
def served():
    """``kind -> (tokens, engine)``: each kind served once for the two
    tests below."""
    return once(_serve)


@pytest.mark.parametrize("kind", sorted(PARENT_TOKENS))
def test_greedy_tokens_are_the_parents(served, kind):
    assert served(kind)[0] == PARENT_TOKENS[kind]


@pytest.mark.parametrize("kind", ["fp", "int8", "tp2"])
def test_overlapped_loop_goldens_are_the_parents(served, kind):
    """The same run: its blocks went out one ahead of their read-back
    (since ISSUE 34 the only loop there is), and none is left in flight."""
    tokens, eng = served(kind)
    assert tokens == PARENT_TOKENS[kind]
    assert eng._reg.snapshot()["serving_decode_blocks_overlapped_total"] > 0
    assert eng._pending is None
