"""ISSUE 31: inside one ``step()`` the host reads back from the device
once, after everything the step can dispatch has been dispatched.

A prefill call whose lanes all continue is not read; one that finishes a
prompt keeps its first token on the device for the decode block of the
same step (``ServingEngine._owed``, ``first_token_step``); the block's
read-back settles both. ``serving_device_readbacks_total`` counts the
waits. The tokens below were printed by the same prompts on the parent
commit (b498ca3), where every prefill call ended in a read-back.

Since ISSUE 34 that one read-back is of the block the step BEFORE
dispatched (``tests/test_serving_overlap.py``): a step dispatches its
block and settles the last, so tokens reach the host one step after the
step that queued them, and a run from idle makes one step more than
blocks.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import analysis, inference
from paddle_tpu import observability as obs
from paddle_tpu.models.gpt import GPT, GPTConfig

#: seven prompts over one 10-token prefix (the last repeats the second),
#: 10 tokens each; the same for every kind of engine below
PARENT_TOKENS = [
    [89, 124, 124, 124, 124, 49, 124, 49, 49, 49],
    [39, 49, 120, 39, 120, 34, 120, 2, 39, 39],
    [49, 42, 49, 124, 39, 124, 49, 124, 39, 27],
    [36, 36, 36, 36, 36, 36, 36, 36, 36, 89],
    [124, 124, 124, 124, 124, 124, 49, 49, 49, 49],
    [27, 27, 42, 27, 60, 27, 60, 27, 60, 89],
    [39, 49, 120, 39, 120, 34, 120, 2, 39, 39],
]
ENGINE_KINDS = {
    "plain": dict(prefix_sharing=False),
    "int8": dict(cache_dtype=jnp.int8),
    "tp2": dict(tp=2),
    "prefix_sharing": dict(prefix_sharing=True),
}


@pytest.fixture(scope="module")
def model_params():
    model = GPT(GPTConfig.tiny(num_heads=4, attn_impl="xla"))
    return model, model.init(jax.random.PRNGKey(5))


def _engine(model_params, **over):
    kw = dict(num_slots=4, page_size=8, max_tokens_per_slot=56,
              prefill_chunk=8, decode_block=3, attn_impl="pallas_interpret",
              registry=obs.MetricsRegistry())
    kw.update(over)
    return inference.make_serving_engine(*model_params, **kw)


def _prompts(vocab):
    rng = np.random.default_rng(23)
    shared = rng.integers(0, vocab, 10)
    tails = [rng.integers(0, vocab, n) for n in (2, 19, 7, 30, 1, 12)]
    ps = [np.concatenate([shared, t]).astype(np.int32) for t in tails]
    return ps + [ps[1].copy()]


def _readbacks(eng):
    return sum(v for k, v in eng._reg.snapshot().items()
               if k.startswith("serving_device_readbacks_total"))


@pytest.mark.parametrize("kind", sorted(ENGINE_KINDS))
def test_tokens_are_the_parents_and_nothing_recompiles(model_params, kind):
    if kind == "tp2" and len(jax.devices()) < 2:
        pytest.skip("needs two devices")
    # a budget of two calls a step: both can finish prompts
    eng = _engine(model_params, prefill_budget=64, **ENGINE_KINDS[kind])
    eng.warmup(cost_gauges=False)
    assert analysis.serving_bucket_coverage(eng) == []
    assert eng.warmed_signatures == set(eng.warmup_plan())
    merged, merge = [], eng.first_token_step
    eng.first_token_step = lambda *a: (merged.append(eng._anat_steps),
                                       merge(*a))[1]
    det = obs.RecompileDetector("readback", warmup=0, registry=eng._reg)
    outs = eng.generate_many(_prompts(model_params[0].cfg.vocab_size), 10)
    det.check()
    assert [o.tolist() for o in outs] == PARENT_TOKENS
    # some step merged the first tokens of several calls, one after the other
    assert max(merged.count(k) for k in set(merged)) >= 2
    # first tokens went to their decode blocks on the device: compiled
    # in warm-up like every other program (under tp on the whole mesh)
    assert det.recompiles == 0
    snap = eng._reg.snapshot()
    assert snap['serving_device_readbacks_total{phase="prefill"}'] == 0
    # every block is read once, by the step after the one that sent it
    assert snap['serving_device_readbacks_total{phase="decode"}'] \
        == snap["serving_decode_rounds_total"] \
        == snap["serving_steps_total"] - 1


def test_a_four_chunk_prompt_waits_once_for_the_step_that_takes_it_whole(
        model_params):
    """Four prefill calls and the decode block of one step: one wait (the
    parent of ISSUE 31 waited five times there), made by the next step,
    which is when the host learns the first token and stamps TTFT."""
    eng = _engine(model_params, prefill_budget=32)
    eng.submit(np.arange(1, 30, dtype=np.int32), 7)       # 29 tokens
    before = _readbacks(eng)
    assert eng.step() == {}
    snap = eng._reg.snapshot()
    assert snap["serving_prefill_calls_total"] == 4
    assert snap["serving_decode_rounds_total"] == 1
    (st,) = [s for s in eng.scheduler.slots if s is not None]
    # four prefill calls and a block went out, nothing was waited for
    assert _readbacks(eng) == before and eng._owed == []
    assert st.prefill_done and st.generated == [] \
        and st.first_token_at is None
    assert eng._pending.started_from is not None
    # known at dispatch: the slot's length holds the block already
    assert eng.cache.lengths[0] == 29 + eng.decode_block
    assert eng.step() == {}
    assert _readbacks(eng) - before == 1
    assert len(st.generated) == 1 + eng.decode_block      # first + block
    assert st.first_token_at is not None


def test_a_chunk_that_continues_waits_for_nothing(model_params):
    """One chunk a step: the three steps whose chunk continues read
    nothing back; the fourth finishes the prompt and sends its block,
    which the fifth reads."""
    reg = obs.MetricsRegistry()
    eng = _engine(model_params, prefill_budget=8, registry=reg)
    rid = eng.submit(np.arange(1, 30, dtype=np.int32), 7)
    before = _readbacks(eng)
    for k in range(3):
        assert eng.step() == {}
        assert _readbacks(eng) == before, k
        (st,) = [s for s in eng.scheduler.slots if s is not None]
        assert st.prefilled == 8 * (k + 1) and st.generated == []
    assert reg.snapshot()["serving_steps_total"] == 0
    eng.step()
    assert _readbacks(eng) == before
    eng.step()
    assert _readbacks(eng) - before == 1
    while not eng.scheduler.idle():
        eng.step()
    stats = eng.request_stats(rid)
    assert stats["ttft_s"] >= stats["prefill_s"] >= 0
    assert stats["prefill_chunks"] == 4 and stats["tokens"] == 7


def test_nothing_is_owed_when_a_step_returns(model_params):
    eng = _engine(model_params, prefill_budget=16)
    for p in _prompts(model_params[0].cfg.vocab_size):
        eng.submit(p, 5)
    steps = 0
    while not eng.scheduler.idle():
        eng.step()
        steps += 1
        assert eng._owed == []
        for i, st in enumerate(eng.scheduler.slots):
            if st is not None and st.prefill_done:
                # the host holds the first token, or the block in flight
                # carries the debt to its settle
                if st.generated:
                    assert st.first_token_at is not None
                else:
                    assert eng._pending.rows[i][2]
    assert steps > 3
    assert eng._unread_counts == []


def test_eos_and_one_token_requests_are_read_in_the_parents_step(
        model_params):
    """The admission cascade evicts on a first token that ends its
    request, so a finishing lane with an ``eos_id`` or a budget of one
    token is read at once: a request that ends on its first token ends in
    the parent's step, with the parent's tokens. One that ends inside a
    block ends a step later than there: when the block is read."""
    eng = _engine(model_params)
    p = _prompts(model_params[0].cfg.vocab_size)
    reqs = [(p[0], 6, 89), (p[3], 1, None), (p[2], 6, None), (p[1], 8, 120)]
    rids = [eng.submit(q, n, eos_id=e) for q, n, e in reqs]
    came, k = {}, 0
    while not eng.scheduler.idle():
        k += 1
        for rid, toks in eng.step().items():
            came[rid] = (k, np.asarray(toks).tolist())
    assert [came[r] for r in rids] == [
        (2, [89]), (4, [36]), (5, [49, 42, 49, 124, 39, 124]),
        (4, [39, 49, 120])]
    snap = eng._reg.snapshot()
    # three of the four prompts end in a call that reads back
    assert snap['serving_device_readbacks_total{phase="prefill"}'] == 3


@pytest.mark.parametrize("how,over", [
    ("speculative", None),
    ("prefill_tier", dict(tier="prefill")),
])
def test_engines_that_read_at_once_warm_no_merge_program(model_params, how,
                                                         over):
    """A speculative round reads ``generated`` on the host and a prefill
    tier parks the slot for handoff: their finishing calls read back as
    before, and their plan holds no ``first_token`` signature."""
    if over is None:
        draft = GPT(GPTConfig.tiny(num_layers=1, num_heads=4,
                                   attn_impl="xla"))
        over = dict(draft_model=draft,
                    draft_params=draft.init(jax.random.PRNGKey(8)), spec_k=3)
    eng = _engine(model_params, **over)
    assert not [s for s in eng.warmup_plan() if s[0] == "first_token"]
    assert analysis.serving_bucket_coverage(eng) == []
    eng.submit(np.arange(1, 20, dtype=np.int32), 4)
    before = _readbacks(eng)
    eng.step()                      # chunks 1-3 of 19 tokens: all of it
    (st,) = [s for s in eng.scheduler.slots if s is not None]
    assert st.prefill_done and st.generated and eng._owed == []
    snap = eng._reg.snapshot()
    assert snap['serving_device_readbacks_total{phase="prefill"}'] == 1
    # two continuing calls read nothing; the speculative round waits on
    # the draft's proposals and on the verifier's tokens
    assert _readbacks(eng) - before == (3 if how == "speculative" else 1)
