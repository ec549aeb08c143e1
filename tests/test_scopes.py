"""Device time by model part (PR 38): the named scopes the step programs
open, the compile listener's catalogue of loaded programs, and the
tables ``observability.scopes`` builds from their HLO text on demand."""

import gc
import glob
import os

import jax
import jax.numpy as jnp
import pytest

from paddle_tpu import inference, observability as obs, optimizer as opt
from paddle_tpu import profiler
from paddle_tpu.core import dtypes
from paddle_tpu.models import bert
from paddle_tpu.nn import transformer
from paddle_tpu.observability import recompile, scopes
from paddle_tpu.serving import engine as engine_mod
from paddle_tpu.train import build_train_step, make_train_state


# -- the split of one op_name ---------------------------------------------------

@pytest.mark.parametrize("op_name, phase, scope, rest", [
    ("jit(step)/jvp(forward)/ffn/dot_general", "forward", "ffn",
     "dot_general"),
    ("jit(step)/transpose(jvp(forward))/mlm_head/jit(inner)/mul", "backward",
     "mlm_head", "jit(inner)/mul"),
    # the walk over the labelled positions (ops.labelled_nll, PR 47): a
    # loop under the scope, and in its backward a vjp taken inside the body
    ("jit(step)/jvp(forward)/mlm_head/while/body/gbcd,vd->gbcv/dot_general",
     "forward", "mlm_head", "while/body/gbcd,vd->gbcv/dot_general"),
    ("jit(step)/transpose(jvp(forward))/mlm_head/while/body/"
     "vmap(transpose(jvp()))/dot_general", "backward", "mlm_head",
     "while/body/vmap(transpose(jvp()))/dot_general"),
    ("jit(step)/optimizer/mul", "optimizer", "", "mul"),
    ("jit(f)/while/body/attend/jit(_take)/gather", "", "attend",
     "jit(_take)/gather"),
    # a jitted function is a call, not a scope, whatever its name
    ("jit(step)/jit(ffn)/mul", "", "", "jit(step)/jit(ffn)/mul"),
    ("jit(step)/jvp(forward)/jit(_threefry_split)/slice", "forward", "",
     "jit(_threefry_split)/slice"),
    # the first scope wins; one nested under it is the rest
    ("jit(f)/attn_in/ffn/add", "", "attn_in", "ffn/add"),
    ("", "", "", ""),
], ids=["forward", "backward", "forward-loop", "backward-loop-vjp",
        "optimizer", "nested-jit", "jit-named-like-a-scope", "phase-only",
        "first-scope-wins", "no-metadata"])
def test_an_op_name_splits_into_phase_scope_and_rest(op_name, phase, scope,
                                                     rest):
    got = scopes.split_op_name(op_name)
    assert (got.phase, got.scope, got.rest) == (phase, scope, rest)
    assert got.key == (f"{phase}/{scope}" if phase else scope)


def test_the_names_are_the_tuples_beside_the_code():
    names, phases = scopes.scope_names()
    assert names == set(engine_mod.STEP_SCOPES + transformer.BLOCK_SCOPES
                        + bert.MODEL_SCOPES)
    assert phases == {"forward", "optimizer"}
    assert not names & phases


HLO = """HloModule jit_step, is_scheduled=true, entry_computation_layout={()->f32[]}

%fused_computation.1 (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  %mul.1 = f32[8]{0} multiply(%p0, %p0), metadata={op_name="jit(step)/transpose(jvp(forward))/ffn/mul"}
  ROOT %sub.2 = f32[8]{0} subtract(%mul.1, %p0), metadata={op_name="jit(step)/optimizer/sub"}
}

%fused_computation.2 (p0: f32[8]) -> f32[8] {
  %p0.1 = f32[8]{0} parameter(0)
  ROOT %tanh.1 = f32[8]{0} tanh(%p0.1), metadata={op_name="jit(step)/jvp(forward)/ffn/tanh"}
}

ENTRY %main.9 (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  %fusion.2 = f32[8]{0} fusion(%x), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(step)/jvp(forward)/ffn/tanh"}
  %copy.3 = f32[8]{0} copy(%fusion.2)
  ROOT %multiply_subtract_fusion = f32[8]{0} fusion(%copy.3), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/optimizer/sub"}
}
"""


def test_a_fusion_is_booked_to_its_own_metadata_and_says_if_it_is_mixed():
    table = scopes.parse_hlo(HLO)
    assert table.module == "jit_step"
    assert table.scopes["fusion.2"].key == "forward/ffn"
    assert not table.scopes["fusion.2"].mixed
    # the weight-gradient multiply fused with the update: the optimizer's
    booked = table.scopes["multiply_subtract_fusion"]
    assert booked.key == "optimizer/" and booked.mixed
    assert table.scopes["copy.3"].key == ""         # no metadata at all
    assert (table.fusions, table.mixed_fusions) == (2, 1)
    assert table.mixed_share == 0.5
    name, op, sig = scopes.parse_instruction(
        "%fusion.2 = f32[8]{0} fusion(f32[8]{0} %x), kind=kLoop")
    assert (name, op, sig) == ("fusion.2", "fusion", table.shapes["fusion.2"])
    assert scopes.parse_instruction("fusion.2") is None


def test_programs_of_one_name_are_told_apart_or_left_unattributed():
    """Two programs named alike (one a gather width) whose ``fusion.2`` is
    different code: an execution is keyed by what was seen in it, and an
    instruction both could be is booked only where they agree."""
    other = HLO.replace('jvp(forward)/ffn/tanh"}\n  %copy',
                        'jvp(forward)/add_norm/tanh"}\n  %copy') \
        .replace("%copy.3 = f32[8]{0} copy(", "%copy.4 = f32[8]{0} copy(") \
        .replace("fusion(%copy.3)", "fusion(%copy.4)")
    a, b = scopes.parse_hlo(HLO), scopes.parse_hlo(other)
    assert b.scopes["fusion.2"].key == "forward/add_norm"
    tabs = scopes.Tables([a, b])
    both = tabs.candidates("jit_step", [("fusion.2", None)])
    assert both == [a, b]
    assert tabs.find(both, "fusion.2") is None              # they disagree
    assert tabs.find(both, "multiply_subtract_fusion").key == "optimizer/"
    only_a = tabs.candidates("jit_step", [("fusion.2", None),
                                          ("copy.3", a.shapes["copy.3"])])
    assert only_a == [a]
    assert tabs.find(only_a, "fusion.2").key == "forward/ffn"
    # a shape no program writes that way: the names decide alone; a name
    # no program has: every program of that module name is left
    assert tabs.candidates("jit_step", [("copy.3", 12345)]) == [a]
    assert tabs.candidates("jit_step", [("copy.9", None)]) == [a, b]
    assert tabs.candidates("jit_other") == []
    assert tabs.find([], "fusion.2") is None
    assert tabs.mixed_share() == {"jit_step": 0.5}


# -- the catalogue ---------------------------------------------------------------

def _tiny_bert_step():
    cfg = bert.BertConfig(vocab_size=128, hidden_size=32, num_layers=2,
                          num_heads=2, ffn_size=64, max_position=16,
                          dropout=0.1, attn_dropout=0.0, attn_impl="xla")
    model = bert.BertForPretraining(cfg)
    optimizer = opt.AdamW(learning_rate=1e-3)
    state = make_train_state(model, optimizer, jax.random.PRNGKey(0))
    step = build_train_step(
        lambda params, **batch: model.loss(params, training=True, **batch),
        optimizer, policy=dtypes.get_policy("bf16"))
    b, s = 2, 16
    z = jnp.zeros((b, s), jnp.int32)
    batch = dict(input_ids=z, token_type_ids=z,
                 attention_mask=jnp.ones((b, s), bool), mlm_labels=z,
                 mlm_mask=jnp.ones((b, s), jnp.float32),
                 nsp_labels=jnp.zeros((b,), jnp.int32),
                 key=jax.random.PRNGKey(1))
    return jax.jit(lambda st, bt: step(st, **bt)), state, batch


def _module_named(tabs, name):
    return [p for p in tabs.programs if p.module == name]


def test_the_catalogue_holds_a_program_the_caller_compiled_and_dropped():
    """``build_train_step`` installs the listener; the caller's own
    ``jit(...).lower().compile()`` is heard, and its table can still be
    built after the caller let go of ``compiled``."""
    build_train_step(lambda params: jnp.sum(params), opt.SGD(0.1))

    def catalogue_probe_fn(x):
        with jax.named_scope("ffn"):
            return jnp.tanh(x) * 3.0
    compiled = jax.jit(catalogue_probe_fn).lower(jnp.ones((8, 8))).compile()
    held = [p for p in recompile.loaded_programs()
            if p.module == "jit_catalogue_probe_fn"]
    assert len(held) == 1 and held[0].table is None     # a handle, no text
    del compiled
    gc.collect()
    (table,) = _module_named(scopes.tables(), "jit_catalogue_probe_fn")
    assert "ffn" in {sc.key for sc in table.scopes.values()}
    assert held[0].table is table                       # built once
    assert _module_named(scopes.tables(),
                         "jit_catalogue_probe_fn") == [table]


def test_the_catalogue_holds_only_the_last_few_programs_nobody_else_does():
    """A held handle keeps a program's code mapped, so the listener keeps
    the last ``_RECENT`` it saw and no more: a process that compiles
    thousands (this suite, a retracing trainer) does not keep them all."""
    obs.install_compile_listener()
    for i in range(recompile._RECENT + 6):
        jax.jit(lambda x, i=i: x * i + 1.0)(jnp.ones((2,)))     # dropped
    gc.collect()
    assert len(recompile._recent) == recompile._RECENT
    alive = [p for p in recompile.loaded_programs()
             if p.module == "jit__lambda"]
    assert 0 < len(alive) <= recompile._RECENT
    # what died is forgotten: ids, and tables
    assert len(recompile._seen) == len(recompile.loaded_programs())
    assert set(recompile._tables) <= set(recompile._seen.values())


def test_held_programs_fit_a_budget_of_device_code(monkeypatch):
    """A held program's generated code stays in device memory: the held
    ones fit ``_HELD_CODE_BYTES`` together, and one larger than that is
    not held at all (the BERT cell's reference programs)."""
    import collections
    monkeypatch.setattr(recompile, "_recent", collections.deque(maxlen=64))
    monkeypatch.setattr(recompile, "_held_code_bytes",
                        recompile._HELD_CODE_BYTES)
    mb = 1 << 20
    for seq, size in enumerate([20 * mb, 8 * mb, 40 * mb, 10 * mb], 1):
        recompile._hold(recompile.LoadedProgram(object(), seq, size))
    assert [r.seq for r in recompile._recent] == [2, 4]
    assert sum(r.code_bytes for r in recompile._recent) \
        <= recompile._HELD_CODE_BYTES
    # a serving engine's step programs are large and its own: it lifts
    # the budget (they are loaded while it serves whoever holds them)
    recompile.hold_step_programs()
    recompile._hold(recompile.LoadedProgram(object(), 5, 40 * mb))
    assert [r.seq for r in recompile._recent] == [2, 4, 5]


def test_a_one_off_program_never_pushes_a_step_program_out(monkeypatch):
    """The BERT cell's order of programs: set-up, the train step, then an
    evaluation program just under the budget, run once and dropped. The
    step program is the one whose table is asked for after the run."""
    import collections
    monkeypatch.setattr(recompile, "_recent", collections.deque(maxlen=64))
    monkeypatch.setattr(recompile, "_held_code_bytes",
                        recompile._HELD_CODE_BYTES)
    mb = 1 << 20
    for seq, (size, step) in enumerate(
            [(10 * mb, False), (29 * mb, True), (31 * mb, False),
             (2 * mb, False)], 1):
        recompile._hold(recompile.LoadedProgram(object(), seq, size, step))
    assert [r.seq for r in recompile._recent] == [2, 4]
    # among step programs the oldest goes first
    recompile._hold(recompile.LoadedProgram(object(), 5, 20 * mb, True))
    assert [r.seq for r in recompile._recent] == [5]
    # the newest step program is held whatever its size (the BERT cell's
    # is 133 MB of code once the chip has memory to spare), alone
    recompile._hold(recompile.LoadedProgram(object(), 6, 133 * mb, True))
    assert [r.seq for r in recompile._recent] == [6]
    recompile._hold(recompile.LoadedProgram(object(), 7, 2 * mb, False))
    assert [r.seq for r in recompile._recent] == [6]
    recompile._hold(recompile.LoadedProgram(object(), 8, 30 * mb, True))
    assert [r.seq for r in recompile._recent] == [8]


def test_a_traced_train_step_marks_the_program_compiled_from_it():
    """In a process of its own: this one's catalogue is shared with every
    test that ran before, and its ids are reused as their programs die."""
    import os
    import subprocess
    import sys
    script = """
import jax, jax.numpy as jnp
from paddle_tpu import optimizer as opt
from paddle_tpu.observability import recompile
from paddle_tpu.train import build_train_step
sgd = opt.SGD(0.1)
params = jnp.ones((2,))
state = {"params": params, "opt": sgd.init(params),
         "step": jnp.zeros((), jnp.int32)}
# a set-up program, loaded before the listener is there and nothing
# compiled between: met together with the step program, and marked
# like it, if the trace did not look at what is loaded first
early = jax.jit(lambda x: x - 3.0).lower(params).compile()
step = build_train_step(lambda params: jnp.sum(params * params), sgd)
compiled = jax.jit(step).lower(state).compile()
other = jax.jit(lambda x: x * 2.0 + 1.0).lower(params).compile()
marks = {id(r.handle): r.step for r in recompile._recent}
print("MARKS", marks[id(compiled.runtime_executable())],
      marks[id(other.runtime_executable())],
      marks[id(early.runtime_executable())])
"""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        timeout=300, env={**os.environ, "JAX_PLATFORMS": "cpu",
                          "PYTHONPATH": root})
    assert "MARKS True False False" in out.stdout, \
        out.stdout + out.stderr[-2000:]


def test_no_text_is_produced_unless_a_table_is_asked_for(monkeypatch):
    """Compiling, running and checking for recompiles read no HLO text:
    only ``scopes.tables()`` does, once a program."""
    calls = []
    real = scopes.parse_hlo
    monkeypatch.setattr(scopes, "parse_hlo",
                        lambda text, names=None: calls.append(len(text))
                        or real(text, names))
    det = obs.RecompileDetector("scopes_test", warmup=0,
                                registry=obs.MetricsRegistry(),
                                log_fn=lambda msg: None)

    def quiet_probe_fn(x):
        return x + 1.0
    f = jax.jit(quiet_probe_fn)
    f(jnp.ones((4,))).block_until_ready()
    det.check()
    assert any(p.module == "jit_quiet_probe_fn" and p.table is None
               for p in recompile.loaded_programs())
    assert calls == []
    scopes.tables()
    assert calls
    n = len(calls)
    scopes.tables()                      # every table is already built
    assert len(calls) == n


def test_the_recompile_warning_names_the_program_that_appeared():
    msgs = []
    det = obs.RecompileDetector("named", warmup=0,
                                registry=obs.MetricsRegistry(),
                                log_fn=msgs.append)

    def retraced_probe_fn(x):
        return x * 2.0
    f = jax.jit(retraced_probe_fn)
    f(jnp.ones((3,)))
    assert det.check() >= 1
    assert "jit_retraced_probe_fn" in msgs[-1]


# -- the trainer's scopes ------------------------------------------------------------

def test_a_tiny_bert_step_has_every_scope_forward_and_backward(tmp_path):
    jitted, state, batch = _tiny_bert_step()
    compiled = jitted.lower(state, batch).compile()
    table = scopes.parse_hlo(compiled.as_text())
    keys = {sc.key for sc in table.scopes.values()}
    names = set(transformer.BLOCK_SCOPES + bert.MODEL_SCOPES)
    for phase in ("forward", "backward"):
        assert {f"{phase}/{n}" for n in names} <= keys, phase
    assert "optimizer/" in keys
    # the MLM head is a walk over the labelled positions: both loops'
    # bodies are keyed to the scope their caller opened, the backward's
    # (a custom_vjp's, traced at transposition) under ``backward``
    for phase in ("forward", "backward"):
        assert any(sc.key == f"{phase}/mlm_head"
                   and sc.rest.startswith("while/body/")
                   for sc in table.scopes.values()), phase
    # nothing of the encoder under no scope: what the forward leaves
    # bare is the model's own key split and padding bias and the sum of
    # the two losses; ``jit(<lambda>)`` leads the policy's cast of the
    # gradients back to float32 (``transpose(jvp())``, outside ``forward``),
    # which stands alone where it follows the head's backward loop
    bare = {sc.rest.split("/")[0] for sc in table.scopes.values()
            if sc.key in ("forward/", "backward/")}
    assert bare <= {"jit(_threefry_split)", "jit(_where)", "slice",
                    "squeeze", "broadcast_in_dim", "add", "jit(<lambda>)",
                    ""}, bare
    # the flash dispatch itself is under none of the blocks' scopes: a
    # scope AROUND it would rename the unnamed kernels after itself
    # (tests/test_chip_compile.py compiles the real one)

    # and a profiler session books the step's device time by them
    st = state
    with profiler.profiler(str(tmp_path), summary=False):
        for _ in range(2):
            st, _m = compiled(st, batch)
        jax.block_until_ready(st)
    by_scope = profiler.device_time_by_scope(str(tmp_path))
    assert by_scope["backward/ffn"] > 0
    assert by_scope["forward/mlm_head"] > 0
    assert by_scope["backward/mlm_head"] > 0
    assert by_scope.get(scopes.UNATTRIBUTED, 0.0) \
        < 0.05 * sum(by_scope.values())
    assert "backward/ffn" in profiler.format_by_scope(by_scope)
    assert glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                  "*.xplane.pb"))


# -- the serving loops' scopes ----------------------------------------------------

def _tiny_engine(family):
    if family == "gpt2":
        from paddle_tpu.models.gpt import GPT, GPTConfig
        model = GPT(GPTConfig.tiny())
        params = model.init(jax.random.PRNGKey(0))
    else:
        from paddle_tpu.models import (hybrid_ssm_lm, latent_conv_moe_lm,
                                       sparse_moe_lm)
        cls, cfg = {
            "sparse": (sparse_moe_lm.SparseMoELM,
                       sparse_moe_lm.SparseMoELMConfig),
            "hybrid": (hybrid_ssm_lm.HybridSSMLM,
                       hybrid_ssm_lm.HybridSSMLMConfig),
            "latent": (latent_conv_moe_lm.LatentConvMoELM,
                       latent_conv_moe_lm.LatentConvMoELMConfig)}[family]
        model = cls(cfg.tiny(kernel_impl="lax"))
        params = model.init(jax.random.PRNGKey(0))
    return inference.make_serving_engine(
        model, params, num_slots=2, page_size=8, prefill_chunk=16,
        max_tokens_per_slot=64, decode_block=2, attn_impl="lax",
        registry=obs.MetricsRegistry())


@pytest.mark.parametrize("family", ["gpt2", "sparse", "hybrid", "latent"])
def test_each_serving_program_has_the_hooks_scopes(family):
    eng = _tiny_engine(family)
    z = jnp.zeros((2,), jnp.int32)
    state_col = 1 if eng.program.spec.slot_state else 0
    decode = eng.decode_step.lower(
        eng._step_params, eng.cache.pages, jnp.zeros((2, 2), jnp.int32),
        z, z, z).compile().as_text()
    prefill = eng.prefill_step.lower(
        eng._step_params, eng.cache.pages,
        jnp.zeros((2, 2 + state_col), jnp.int32), z,
        jnp.zeros((2, 16), jnp.int32), z).compile().as_text()
    for text in (decode, prefill):
        table = scopes.parse_hlo(text)
        keys = {sc.key for sc in table.scopes.values()}
        assert {"embed", "attn_in", "attend", "ffn", "head"} <= keys
        assert ("mixer" in keys) == (family == "hybrid")
        assert keys <= set(engine_mod.STEP_SCOPES) | {""}
