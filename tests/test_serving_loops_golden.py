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


#: the prefill calls ``[lanes_live, lanes, width, tokens]`` of each step of
#: ``tests/prefill_call_sequences.py``'s seeded run AT THE PARENT OF PR 54
#: (run on a checkout of ad2c7ff), for the programs whose calls carry one
#: chunk a slot whatever the engine: a mixer's or a projection's state
#: carried from chunk to chunk outside the pages, a kind that selects by
#: chunk, a latent row's rotary pool written a page tile a lane
CALLS_BEFORE_A_CALL_CARRIED_RUNS = {
    'hybrid_ssm': [
        [[3, 4, 2, 21]],
        [[2, 2, 4, 16], [1, 1, 8, 1]],
        [[1, 1, 4, 8], [1, 1, 8, 8], [1, 1, 8, 5]],
        [[1, 1, 2, 8], [1, 1, 4, 8], [1, 1, 8, 8]],
        [[1, 1, 8, 8], [1, 1, 16, 8]],
    ],
    'latent_conv_moe': [
        [[3, 4, 2, 21]],
        [[2, 2, 4, 16], [1, 1, 8, 1]],
        [[1, 1, 4, 8], [1, 1, 8, 8], [1, 1, 8, 5]],
        [[1, 1, 2, 8], [1, 1, 4, 8], [1, 1, 8, 8]],
        [[1, 1, 8, 8], [1, 1, 16, 8]],
    ],
    'sparse_moe': [
        [[3, 4, 4, 25]],
        [[2, 2, 8, 17], [1, 1, 8, 12]],
        [[1, 1, 8, 5]],
        [[1, 1, 4, 12], [1, 1, 8, 12], [1, 1, 16, 12]],
        [[1, 1, 16, 4]],
    ],
    'mla_moe': [
        [[3, 4, 1, 21]],
        [[2, 2, 2, 16], [1, 1, 4, 1]],
        [[1, 1, 2, 8], [1, 1, 4, 8], [1, 1, 4, 5]],
        [[1, 1, 1, 8], [1, 1, 2, 8], [1, 1, 4, 8]],
        [[1, 1, 4, 8], [1, 1, 8, 8]],
    ],
}


@pytest.mark.parametrize("program", sorted(CALLS_BEFORE_A_CALL_CARRIED_RUNS))
def test_a_program_whose_run_limit_is_one_forms_the_calls_it_formed(program):
    """ISSUE 54: the round of an engine whose program answers a run of 1
    is the loop it was, call for call (regenerate on the PARENT commit with
    ``tests/prefill_call_sequences.py``, never on the change)."""
    import prefill_call_sequences
    assert prefill_call_sequences.call_sequence(program) \
        == CALLS_BEFORE_A_CALL_CARRIED_RUNS[program]
