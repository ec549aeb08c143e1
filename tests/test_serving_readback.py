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
blocks. The cases that look at single steps are in
``tests/test_serving_readback_steps.py``.
"""

import jax
import jax.numpy as jnp
import pytest

from paddle_tpu import analysis
from paddle_tpu import observability as obs

from serving_taps import PARENT_TOKENS, once, readback_gpt, readbacks
from serving_taps import readback_engine as _engine
from serving_taps import readback_prompts as _prompts

ENGINE_KINDS = {
    "plain": dict(prefix_sharing=False),
    "int8": dict(cache_dtype=jnp.int8),
    "tp2": dict(tp=2),
    "prefix_sharing": dict(prefix_sharing=True),
}


@pytest.fixture(scope="module")
def model_params():
    return readback_gpt()


@pytest.fixture(scope="module")
def served(model_params):
    """``kind -> `` what ONE run of the seven prompts through a warmed
    engine of that kind left, for the two tests below: the engine, what
    warm-up covered, the steps that merged first tokens, the tokens, the
    compiles after warm-up and the most read-backs any step made."""
    def serve(kind):
        if kind == "tp2" and len(jax.devices()) < 2:
            pytest.skip("needs two devices")
        # a budget of two calls a step: both can finish prompts
        eng = _engine(model_params, prefill_budget=64, **ENGINE_KINDS[kind])
        eng.warmup(cost_gauges=False)
        run = dict(eng=eng, uncovered=analysis.serving_bucket_coverage(eng),
                   warmed=set(eng.warmed_signatures), merged=[])
        merge = eng.first_token_step
        eng.first_token_step = lambda *a: (
            run["merged"].append(eng._anat_steps), merge(*a))[1]
        step, most = eng.step, [0]

        def counted_step():
            before = readbacks(eng)
            out = step()
            most.append(readbacks(eng) - before)
            return out
        eng.step = counted_step
        det = obs.RecompileDetector("readback", warmup=0, registry=eng._reg)
        try:
            outs = eng.generate_many(
                _prompts(model_params[0].cfg.vocab_size), 10)
        finally:
            del eng.step                    # the class's own again
        det.check()
        return dict(run, recompiles=det.recompiles, most=max(most),
                    tokens=[o.tolist() for o in outs])
    return once(serve)


@pytest.mark.parametrize("kind", sorted(ENGINE_KINDS))
def test_tokens_are_the_parents_and_nothing_recompiles(served, kind):
    run = served(kind)
    eng, merged = run["eng"], run["merged"]
    assert run["uncovered"] == []
    assert run["warmed"] == set(eng.warmup_plan())
    assert run["tokens"] == PARENT_TOKENS
    # some step merged the first tokens of several calls, one after the other
    assert max(merged.count(k) for k in set(merged)) >= 2
    # first tokens went to their decode blocks on the device: compiled
    # in warm-up like every other program (under tp on the whole mesh)
    assert run["recompiles"] == 0
    snap = eng._reg.snapshot()
    assert snap['serving_device_readbacks_total{phase="prefill"}'] == 0
    # every block is read once, by the step after the one that sent it
    assert snap['serving_device_readbacks_total{phase="decode"}'] \
        == snap["serving_decode_rounds_total"] \
        == snap["serving_steps_total"] - 1


@pytest.mark.parametrize("kind", sorted(ENGINE_KINDS))
def test_overlapped_tokens_are_the_parents(served, kind):
    """The same run, read for what ISSUE 34 added: every block but the
    first went out while the one before it was unread, none of their
    tokens was dropped, and no step waited twice."""
    run = served(kind)
    assert run["tokens"] == PARENT_TOKENS
    snap = run["eng"]._reg.snapshot()
    assert snap["serving_decode_blocks_overlapped_total"] > 0
    assert snap["serving_decode_discarded_tokens_total"] == 0
    assert run["most"] == 1 and run["eng"]._pending is None


def test_the_merge_program_is_warmed_and_nothing_recompiles(served):
    """The run of the engine that shares prefixes, read for ISSUE 34: the
    program that merges first tokens into their block is in the plan."""
    run = served("prefix_sharing")
    assert ("last_token",) in run["eng"].warmup_plan()
    assert run["uncovered"] == [] and run["recompiles"] == 0
    assert run["tokens"] == PARENT_TOKENS


