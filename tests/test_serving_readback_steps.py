"""ISSUE 31's one read-back a step (``tests/test_serving_readback.py``),
step by step: what the host holds after each ``step()`` of a prompt
prefilled in one step or in four, of requests that end on their first
token, of engines that read at once. Its own file for ``--dist loadfile``."""

import types

import jax
import numpy as np
import pytest

from paddle_tpu import analysis
from paddle_tpu import observability as obs
from paddle_tpu.models.gpt import GPT, GPTConfig

from serving_taps import readback_gpt, readbacks as _readbacks
from serving_taps import readback_engine as _engine
from serving_taps import readback_prompts as _prompts


@pytest.fixture(scope="module")
def model_params():
    return readback_gpt()


@pytest.fixture(scope="module")
def four_chunk_steps(model_params):
    """The two steps of a 29-token prompt whose four chunks (one prefill
    call that carries them as a run of four lanes, since PR 54) and
    decode block go out in ONE step, made once for the two tests below:
    -> the engine and what the host held after each step."""
    eng = _engine(model_params, prefill_budget=32)
    eng.submit(np.arange(1, 30, dtype=np.int32), 7)
    seen = []
    for _ in range(2):
        before = _readbacks(eng)
        out = eng.step()
        (st,) = [s for s in eng.scheduler.slots if s is not None]
        snap = eng._reg.snapshot()
        seen.append(types.SimpleNamespace(
            out=out, readbacks=_readbacks(eng) - before,
            owed=list(eng._owed), prefill_done=st.prefill_done,
            generated=list(st.generated), first_token_at=st.first_token_at,
            started_from=eng._pending and eng._pending.started_from,
            length=int(eng.cache.lengths[0]),
            calls=snap["serving_prefill_calls_total"],
            rounds=snap["serving_decode_rounds_total"]))
    return eng, seen


def test_a_four_chunk_prompt_waits_once_for_the_step_that_takes_it_whole(
        four_chunk_steps):
    """The prompt's four chunks, one call's run, and the decode block of
    one step: one wait (the parent of ISSUE 31 waited five times there,
    once a chunk's call), made by the next step, which is when the host
    learns the first token and stamps TTFT."""
    eng, (first, second) = four_chunk_steps
    assert first.out == {} and first.calls == 1 and first.rounds == 1
    assert eng.anatomy.records()[0]["prefill_calls"][0][:4] + \
        eng.anatomy.records()[0]["prefill_calls"][0][5:] == [4, 4, 4, 29, 4]
    # the prefill call and a block went out, nothing was waited for
    assert first.readbacks == 0 and first.owed == []
    assert first.prefill_done and first.generated == [] \
        and first.first_token_at is None
    assert first.started_from is not None
    # known at dispatch: the slot's length holds the block already
    assert first.length == 29 + eng.decode_block
    assert second.out == {} and second.readbacks == 1
    assert len(second.generated) == 1 + eng.decode_block  # first + block
    assert second.first_token_at is not None


def test_first_tokens_come_with_the_settle_of_their_block(four_chunk_steps):
    """The same two steps, read for ISSUE 34: a prompt that ends in step k
    decodes in block k, which is in flight when step k returns, and the
    host learns the first token with that block's settle: TTFT is stamped
    then."""
    eng, (first, second) = four_chunk_steps
    assert first.prefill_done and first.started_from is not None
    assert first.generated == [] and first.first_token_at is None
    assert first.readbacks == 0
    assert second.readbacks == 1 and second.first_token_at is not None
    assert len(second.generated) == 1 + eng.decode_block


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
    # (the parent's tokens in the parent's steps: a call that carries runs
    # gives each prompt of a step what the calls of one chunk a slot did)
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
