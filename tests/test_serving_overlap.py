"""ISSUE 34: at most one decode block is in flight across ``step()`` calls.

``step()`` dispatches block k, then settles block k-1 (the step's one
read-back), so the host's per-step work runs beside the device. Decided
at dispatch: who is in the block, how many of its tokens each slot keeps,
``cache.lengths``; learned at settle: the tokens, first tokens, an
``eos_id`` hit, who is finished. The same two calls back to back are the
synchronous engine, which is what a speculative engine, a tier and
``snapshot_every_blocks`` get, and every call that reads a slot between
two steps settles on entry.

The GPT cases take their engines from ``engines`` below, one a set of
options for the module: what a case may assume of such an engine is in
``tests/serving_taps.py``. The GPT tokens are those of
``test_serving_readback.py`` (where the cases that serve the seven prompts
whole live beside their twins of ISSUE 31; the one whose first token
comes with its block's settle is in ``test_serving_readback_steps.py``);
the hybrid and sparse ones
were printed by ``_battery`` below on ISSUE 34's parent commit (fe44bd4),
where every block was read in the step that dispatched it.
"""

import jax
import numpy as np
import pytest

from paddle_tpu import inference
from paddle_tpu import observability as obs
from paddle_tpu.models.gpt import GPT, GPTConfig
from paddle_tpu.serving.engine import SlotMigrationError

from serving_taps import moved, shared_engines, traced, wipe  # noqa: E402
from serving_taps import PARENT_TOKENS, readback_gpt  # noqa: E402
from serving_taps import drain_counting as _drain  # noqa: E402
from serving_taps import readback_engine as _engine  # noqa: E402
from serving_taps import readback_prompts as _prompts  # noqa: E402

#: five requests of 7..15 tokens through three slots, block of 3
PARENT_HYBRID = [
    [68, 53, 9, 26, 82, 76, 84],
    [26, 82, 76, 84, 5, 48, 82, 76, 84],
    [9, 26, 82, 76, 84, 5, 48, 82, 76, 84, 5],
    [76, 84, 5, 48, 82, 76, 84, 5, 48, 82, 76, 84, 5],
    [70, 39, 62, 9, 26, 82, 76, 84, 5, 48, 82, 76, 84, 5, 48],
]
#: five requests of 6..18 tokens over a 9-token prefix (one repeated)
PARENT_SPARSE = [
    [26, 78, 33, 72, 6, 28],
    [66, 33, 47, 63, 79, 52, 72, 6, 50],
    [77, 77, 77, 41, 4, 74, 11, 33, 72, 7, 63, 90],
    [60, 61, 37, 31, 72, 7, 70, 39, 25, 30, 89, 48, 7, 70, 74],
    [66, 33, 47, 63, 79, 52, 72, 6, 50, 84, 72, 6, 50, 84, 30, 12, 72, 6],
]


@pytest.fixture(scope="module")
def model_params():
    return readback_gpt()


@pytest.fixture(scope="module")
def engines(model_params):
    """``get(**options) -> engine``: ``serving_taps.readback_engine`` with
    these options, once for the module, idle, its tracer its own and off."""
    return shared_engines(lambda **over: _engine(
        model_params, tracer=obs.Tracer(capacity=4096, enabled=False),
        **over))


def _battery(family):
    """The tiny hybrid / sparse program, three slots, a block of 3."""
    if family == "hybrid":
        from paddle_tpu.models.hybrid_ssm_lm import (HybridSSMLM,
                                                     HybridSSMLMConfig)
        model = HybridSSMLM(HybridSSMLMConfig.tiny(
            kernel_impl="lax", a_init_range=(0.02, 0.2),
            dt_init_range=(0.1, 0.7)))
        rng, chunk = np.random.default_rng(17), 8
        prompts = [rng.integers(0, 96, n).astype(np.int32)
                   for n in (5, 19, 9, 30, 3)]
        budgets = [7 + 2 * i for i in range(5)]
    else:
        from paddle_tpu.models.sparse_moe_lm import (SparseMoELM,
                                                     SparseMoELMConfig)
        model = SparseMoELM(SparseMoELMConfig.tiny(kernel_impl="lax"))
        rng, chunk = np.random.default_rng(19), 12
        vocab = model.cfg.vocab_size
        shared = rng.integers(0, vocab, 9)
        prompts = [np.concatenate([shared, rng.integers(0, vocab, n)])
                   .astype(np.int32) for n in (3, 25, 12, 40)]
        prompts.append(prompts[1].copy())
        budgets = [6 + 3 * i for i in range(5)]
    eng = inference.make_serving_engine(
        model, model.init(jax.random.PRNGKey(5)), num_slots=3, page_size=4,
        prefill_chunk=chunk, max_tokens_per_slot=96, decode_block=3,
        attn_impl="lax", registry=obs.MetricsRegistry())
    rids = [eng.submit(p, n) for p, n in zip(prompts, budgets)]
    out, _, most = _drain(eng)
    return eng, [np.asarray(out[r]).tolist() for r in rids], most


# -- (a) the parent's tokens (GPT's: ``test_serving_readback.py``) ---------------

@pytest.mark.parametrize("family,parent", [("hybrid", PARENT_HYBRID),
                                           ("sparse", PARENT_SPARSE)])
def test_programs_with_state_or_counts_give_the_parents_tokens(family,
                                                               parent):
    """A state row a slot, and counts that ride the read-back: a block's
    counts come over with the block, never with the one before it."""
    eng, tokens, most = _battery(family)
    assert tokens == parent
    snap = eng._reg.snapshot()
    assert snap["serving_decode_blocks_overlapped_total"] > 0
    assert most == 1 and eng._unread_counts == [] and eng._pending is None
    if family == "sparse":      # every dispatched block's counts were read
        assert snap["serving_moe_assignments_total"] > 0
        assert snap["serving_attn_context_tokens_total"] > 0


# -- (b) the order of a steady state ---------------------------------------------

def test_block_k_is_dispatched_before_block_k_minus_1_is_read(engines):
    eng = engines(prefix_sharing=False)
    before = eng._reg.snapshot()
    with traced(eng) as tracer:
        rids = [eng.submit(np.arange(1, 9 + k, dtype=np.int32), 12)
                for k in range(3)]
        out, steps, most = _drain(eng)
    assert sorted(out) == sorted(rids) and most == 1
    snap = moved(eng._reg, before)
    rounds = snap["serving_decode_rounds_total"]
    assert rounds >= 4
    # a run that starts idle: every block but the first went out while
    # the one before it was unread
    assert snap["serving_decode_blocks_overlapped_total"] == rounds - 1
    assert snap['serving_device_readbacks_total{phase="decode"}'] == rounds
    # the last step only settles: one more step than blocks
    assert snap["serving_steps_total"] == rounds + 1
    spans = sorted((s for s in tracer.spans()
                    if s.name.startswith("serving.decode")),
                   key=lambda s: s.start)
    rnd = [s for s in spans if s.name == "serving.decode_round"]
    disp = [s for s in spans if s.name == "serving.decode.dispatch"]
    sync = [s for s in spans if s.name == "serving.decode.sync"]
    book = [s for s in spans if s.name == "serving.decode.book"]
    assert len(disp) == len(sync) == len(book) == rounds
    for k in range(1, int(rounds)):
        # dispatch of block k ends before the sync of block k-1 starts
        assert disp[k].end <= sync[k - 1].start
        # ... inside round k's span, whose children they all are, and the
        # settle names the round that dispatched its block
        assert sync[k - 1].parent_id == book[k - 1].parent_id \
            == disp[k].parent_id == rnd[k].span_id
        assert sync[k - 1].attrs["block"] == book[k - 1].attrs["block"] \
            == rnd[k - 1].span_id
    # the block each request saw: an interval that ends at a read-back
    blocks = [s for s in tracer.spans()
              if s.name == "serving.decode_block"]
    assert {round(s.end, 9) for s in blocks} \
        == {round(s.end, 9) for s in sync}


# -- (c) an eos_id inside block k-1 with block k in flight ------------------------

def test_eos_inside_a_block_drops_the_block_in_flight(model_params, engines):
    p = _prompts(model_params[0].cfg.vocab_size)
    # alone, prompt 1 gives [39, 49, 120, 39, 120, 34, 120, 2, 39, 39]:
    # its first 120 ends block 0 (tokens 1-3 after the first token)
    ref = engines(num_slots=1)
    alone = {i: ref.generate_many([p[i]], 10)[0].tolist() for i in (1, 2)}
    assert alone[1][:4] == [39, 49, 120, 39]

    eng = engines(num_slots=1, prefill_budget=32)
    before = eng._reg.snapshot()
    r1 = eng.submit(p[1], 10, eos_id=120)
    r2 = eng.submit(p[2], 10)
    freed = []
    free_slot = eng.cache.free_slot
    eng.cache.free_slot = lambda s: (freed.append(s), free_slot(s))[1]
    came, k = {}, 0
    try:
        while not eng.scheduler.idle():
            k += 1
            for rid, toks in eng.step().items():
                came[rid] = (k, np.asarray(toks).tolist())
    finally:
        del eng.cache.free_slot             # the class's own again
    # an eos_id request's first token is read in its prefill call (39);
    # step 1 dispatches block 0 (49 120 39), step 2 block 1 and settles
    # block 0: the request ends on 120 with block 1 in flight
    assert came[r1] == (2, [39, 49, 120])
    # the newcomer took the freed slot behind the stale block and gives
    # the tokens it gives alone
    assert came[r2][1] == alone[2]
    snap = moved(eng._reg, before)
    # one token of block 0 after the eos, all three of block 1
    assert snap["serving_decode_discarded_tokens_total"] == 1 + 3
    assert freed == [0, 0]          # once a request
    eng.cache.check_invariants()
    assert eng._pending is None


def test_eos_on_a_program_with_slot_state_resets_the_row_once():
    """The stale block updates the freed slot's state row; the newcomer
    starts from zeros all the same, and gives what it gives alone."""
    from paddle_tpu.models.hybrid_ssm_lm import (HybridSSMLM,
                                                 HybridSSMLMConfig)
    model = HybridSSMLM(HybridSSMLMConfig.tiny(
        kernel_impl="lax", a_init_range=(0.02, 0.2),
        dt_init_range=(0.1, 0.7)))
    params = model.init(jax.random.PRNGKey(5))

    eng = inference.make_serving_engine(
        model, params, num_slots=1, page_size=4, prefill_chunk=8,
        max_tokens_per_slot=96, decode_block=3, attn_impl="lax",
        registry=obs.MetricsRegistry())
    rng = np.random.default_rng(17)
    a, b = (rng.integers(0, 96, n).astype(np.int32) for n in (5, 19))
    # each alone, the slot's rows zeroed in between as a new engine's are
    alone_a = eng.generate_many([a], 9)[0].tolist()
    wipe(eng)
    alone_b = eng.generate_many([b], 9)[0].tolist()
    wipe(eng)
    eos = alone_a[2]                # ends inside block 0
    assert eos not in alone_a[:2]
    before = eng._reg.snapshot()
    r1, r2 = eng.submit(a, 9, eos_id=eos), eng.submit(b, 9)
    out, _, _ = _drain(eng)
    assert out[r1].tolist() == alone_a[:3]
    assert out[r2].tolist() == alone_b
    snap = moved(eng._reg, before)
    assert snap["serving_decode_discarded_tokens_total"] == 1 + 3
    assert snap["serving_ssm_state_resets_total"] == 2


# -- (d) a budget that ends inside a block ----------------------------------------

@pytest.mark.parametrize("budget", [2, 4, 5, 7])
def test_a_budget_that_ends_mid_block_is_not_dispatched_again(model_params,
                                                              engines,
                                                              budget):
    """Every finish is known in advance without an ``eos_id``: the slot
    joins exactly the blocks its budget needs, and no row is wasted."""
    eng = engines(num_slots=2)
    before = eng._reg.snapshot()
    n = eng.decode_block                                    # 3
    p = _prompts(model_params[0].cfg.vocab_size)
    rid = eng.submit(p[0], budget)
    live = []
    dispatch = eng._dispatch_block
    eng._dispatch_block = lambda dslots, w, rnd: (
        live.append(list(dslots)), dispatch(dslots, w, rnd))[1]
    try:
        out, _, most = _drain(eng)
    finally:
        del eng._dispatch_block             # the class's own again
    assert out[rid].tolist() == PARENT_TOKENS[0][:budget]
    # the first token comes from prefill; the rest in blocks of 3
    assert live == [[0]] * -(-(budget - 1) // n)
    assert moved(eng._reg, before)[
        "serving_decode_discarded_tokens_total"] == 0
    assert most == 1 and not eng.cache.lengths.any()


# -- (e) who sees no block in flight ----------------------------------------------

def _in_flight(model_params, engines, **over):
    """The idle engine of these options three steps into two requests of
    20 tokens: both slots hold tokens, and a block of three more each is
    in flight."""
    eng = engines(prefix_sharing=False, **over)
    p = _prompts(model_params[0].cfg.vocab_size)
    rids = [eng.submit(p[0], 20), eng.submit(p[2], 20)]
    for _ in range(3):
        eng.step()
    assert [len(st.generated) for st in eng.scheduler.slots[:2]] == [7, 4]
    return eng, rids


@pytest.mark.parametrize("call", ["snapshot_slot", "release_slot",
                                  "restore_slot", "cancel_queued",
                                  "export_prefix_pages",
                                  "import_prefix_pages", "poll_handoffs",
                                  "poll_micro_snapshots"])
def test_calls_between_steps_settle_the_block_in_flight(model_params,
                                                        engines, call):
    """(The restore and the import are refused, or have nothing to do,
    before anything of theirs lands: the engine serves on.)"""
    eng, _ = _in_flight(model_params, engines)
    assert eng._pending is not None
    st = eng.scheduler.slots[1]
    args = {"snapshot_slot": (0,), "release_slot": (0,),
            "restore_slot": ({"format": "nothing"},),
            "export_prefix_pages": ([],),
            "import_prefix_pages": (None,)}.get(call, ())
    try:
        getattr(eng, call)(*args)
    except SlotMigrationError:
        assert call == "restore_slot"       # refused, after the settle
    assert eng._pending is None
    assert len(st.generated) == 4 + eng.decode_block


def test_a_migrated_slot_carries_the_tokens_of_the_block_in_flight(
        model_params, engines):
    """Drain between two steps: the snapshot holds every token the device
    had computed, and the peer (an engine of its own) finishes the
    request bit-identically."""
    p = _prompts(model_params[0].cfg.vocab_size)
    whole = engines(prefix_sharing=False).generate_many([p[0], p[2]], 20)
    assert whole[0].tolist()[:10] == PARENT_TOKENS[0]
    src, _ = _in_flight(model_params, engines)
    dst = _engine(model_params, prefix_sharing=False)
    snap = src.snapshot_slot(0)
    assert snap["state"]["generated"] == whole[0].tolist()[:10]
    assert snap["state"]["length"] == len(p[0]) + 10 - 1
    src.release_slot(0)
    new = dst.restore_slot(snap)
    out, _, _ = _drain(dst)
    assert out[new].tolist() == whole[0].tolist()
    out, _, _ = _drain(src)
    assert [v.tolist() for v in out.values()] == [whole[1].tolist()]


def test_a_spill_read_settles_first(model_params, engines):
    eng, _ = _in_flight(model_params, engines, host_spill_pages=4)
    assert eng._pending is not None
    eng._spill_read(1)
    assert eng._pending is None


@pytest.mark.parametrize("how", ["speculative", "prefill_tier",
                                 "decode_tier", "snapshot_every_blocks"])
def test_engines_that_settle_at_once_leave_nothing_in_flight(model_params,
                                                             engines, how):
    over = {"prefill_tier": dict(tier="prefill"),
            "decode_tier": dict(tier="decode"),
            "snapshot_every_blocks": dict(snapshot_every_blocks=1)}.get(how)
    if over is None:
        draft = GPT(GPTConfig.tiny(num_layers=1, num_heads=4,
                                   attn_impl="xla"))
        over = dict(draft_model=draft,
                    draft_params=draft.init(jax.random.PRNGKey(8)), spec_k=3)
    eng = _engine(model_params, prefix_sharing=False, **over)
    assert eng._settles_at_once
    assert ("last_token",) not in eng.warmup_plan()
    assert ("last_token",) not in eng.reachable_signatures()
    p = _prompts(model_params[0].cfg.vocab_size)
    if how == "decode_tier":
        # a decode tier takes restored slots only: two steps of a
        # colocated engine, handed over
        src, _ = _in_flight(model_params, engines)
        snap = src.snapshot_slot(0)
        eng.restore_slot(snap)
        held = len(snap["state"]["generated"])
    else:
        eng.submit(p[0], 20)
        held = 0
    for _ in range(3):
        eng.step()
        assert eng._pending is None
        for st in eng.scheduler.slots:
            if st is not None and st.prefill_done \
                    and how != "prefill_tier":
                # tokens are handed back in the step that computed them
                assert len(st.generated) > held
                held = len(st.generated)
    assert eng._reg.snapshot()["serving_decode_blocks_overlapped_total"] == 0
    if how == "snapshot_every_blocks":
        assert eng.poll_micro_snapshots()
    if how == "prefill_tier":
        # the slot parks with the first token its prefill call read
        ((_, snap),) = eng.poll_handoffs()
        assert snap["state"]["generated"] == PARENT_TOKENS[0][:1]


# -- (f) loops end ----------------------------------------------------------------

def test_generate_many_and_a_drain_return_every_request(model_params,
                                                        engines):
    eng = engines(prefill_budget=16)    # a prompt's chunks over several steps
    p = _prompts(model_params[0].cfg.vocab_size)
    outs = eng.generate_many(p, 10)
    assert [o.tolist() for o in outs] == PARENT_TOKENS
    assert eng.scheduler.idle() and eng._pending is None
    # again, through step(): every rid comes back exactly once
    rids = [eng.submit(q, 5) for q in p]
    seen = []
    while not eng.scheduler.idle():
        seen += list(eng.step())
    assert sorted(seen) == sorted(rids)
    assert eng._pending is None and eng._owed == [] \
        and eng._unread_counts == []
    assert eng.step() == {}                 # an idle tick reads nothing


def test_decode_block_seconds_are_the_cadence_of_read_backs(engines):
    """``serving_decode_step_seconds`` of an overlapped block is the
    interval between two read-backs: the blocks of a run tile the time
    from the first dispatch to the last read-back, none counted twice,
    and what the caller does between two steps is in none of them."""
    import time
    eng = engines(num_slots=1)
    before = eng._reg.snapshot()
    with traced(eng) as tracer:
        eng.submit(np.arange(1, 9, dtype=np.int32), 13)
        while not eng.scheduler.idle():
            eng.step()
            time.sleep(0.05)    # a caller that pauses (a compile, a trace)
    spans = tracer.spans()
    sync = [s for s in spans if s.name == "serving.decode.sync"]
    asm = min(s.start for s in spans if s.name == "serving.decode.assemble")
    steps = sorted((s for s in spans if s.name == "serving.step"
                    and s.end > asm), key=lambda s: s.start)
    away = sum(b.start - a.end for a, b in zip(steps, steps[1:]))
    assert away >= 0.05 * (len(steps) - 1)
    hist = moved(eng._reg, before)
    assert hist["serving_decode_step_seconds_count"] == len(sync) == 4
    assert hist["serving_decode_step_seconds_sum"] == pytest.approx(
        max(s.end for s in sync) - asm - away, rel=1e-6)
