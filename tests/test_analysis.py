"""Static-analysis subsystem tests (ISSUE 3 acceptance criteria).

Every rule must BOTH fire on a minimal repro step function AND stay
silent on the equivalent clean code; the PRNG key-reuse rule is
additionally exercised against the real surfaces it protects
(``nn.distributions`` sampling, the models' fold_in dropout paths); the
``Trainer.fit(lint=...)`` / ``Executor(lint=...)`` gates enforce at the
right severities; and the CI self-lint preset stays green.
"""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

import paddle_tpu as pt
from paddle_tpu import analysis, debug, observability
from paddle_tpu import optimizer as opt
from paddle_tpu.analysis import (Finding, LintError, Report, Suppressions,
                                 lint_fn, lint_train_step)
from paddle_tpu.nn import ImgConvGroup
from paddle_tpu.nn.distributions import Normal
from paddle_tpu.parallel import plan as plan_lib
from paddle_tpu.train import build_train_step, make_train_state


def _rules(report):
    return sorted({f.rule for f in report})


# ---------------------------------------------------------------------------
# jaxpr rules: each fires on a minimal repro AND is silent on clean code
# ---------------------------------------------------------------------------

class TestHostCallbackRule:
    def test_fires_on_pure_callback(self):
        def step(x):
            return jax.pure_callback(
                lambda a: a, jax.ShapeDtypeStruct((4,), jnp.float32),
                x).sum()
        rep = lint_fn(step, jnp.ones((4,)), registry=False)
        assert "host-callback" in _rules(rep)
        assert rep.errors                      # host syncs are errors

    def test_fires_on_debug_print_as_warning(self):
        def step(x):
            jax.debug.print("x={x}", x=x)
            return x.sum()
        rep = lint_fn(step, jnp.ones((4,)), registry=False)
        assert "debug-callback" in _rules(rep)
        assert not rep.errors                  # warning, not error

    def test_silent_on_pure_step(self):
        def step(x):
            return (x * 2).sum()
        assert _rules(lint_fn(step, jnp.ones((4,)), registry=False)) == []


class TestF64Rule:
    def test_fires_under_x64(self):
        from jax import enable_x64
        with enable_x64():
            rep = lint_fn(lambda x: x * np.float64(2.0),
                          jnp.ones((4,), jnp.float64), registry=False)
        assert "f64-promotion" in _rules(rep)

    def test_silent_on_f32(self):
        rep = lint_fn(lambda x: x * 2.0, jnp.ones((4,)), registry=False)
        assert "f64-promotion" not in _rules(rep)


class TestDonationRule:
    def _step(self):
        def step(state, x):
            return {"w": state["w"] + x.sum()}, x.sum()
        return step, {"w": jnp.zeros((256, 256))}, jnp.ones((8,))

    def test_fires_when_state_not_donated(self):
        step, state, x = self._step()
        rep = lint_fn(jax.jit(step), state, x, registry=False)
        assert "undonated-buffer" in _rules(rep)

    def test_silent_when_donated(self):
        step, state, x = self._step()
        rep = lint_fn(jax.jit(step, donate_argnums=0), state, x,
                      registry=False)
        assert "undonated-buffer" not in _rules(rep)

    def test_silent_when_donation_unknown(self):
        # plain python fn, no donate_argnums: rule cannot judge -> silent
        step, state, x = self._step()
        rep = lint_fn(step, state, x, registry=False)
        assert "undonated-buffer" not in _rules(rep)

    def test_small_buffers_ignored(self):
        def step(state, x):
            return {"w": state["w"] + x.sum()}, x.sum()
        rep = lint_fn(jax.jit(step), {"w": jnp.zeros((4,))}, jnp.ones((8,)),
                      registry=False)
        assert "undonated-buffer" not in _rules(rep)


class TestKeyReuseRule:
    def test_fires_on_double_draw(self):
        def step(key, x):
            a = jax.random.normal(key, x.shape)
            b = jax.random.uniform(key, x.shape)
            return (a + b + x).sum()
        rep = lint_fn(step, jax.random.PRNGKey(0), jnp.ones((8,)),
                      registry=False)
        assert "prng-key-reuse" in _rules(rep)
        assert rep.errors

    def test_silent_with_split(self):
        def step(key, x):
            k1, k2 = jax.random.split(key)
            return (jax.random.normal(k1, x.shape)
                    + jax.random.uniform(k2, x.shape) + x).sum()
        rep = lint_fn(step, jax.random.PRNGKey(0), jnp.ones((8,)),
                      registry=False)
        assert "prng-key-reuse" not in _rules(rep)

    def test_silent_with_fold_in_per_consumer(self):
        def step(key, x):
            h = x
            for i in range(3):
                h = h + jax.random.bernoulli(
                    jax.random.fold_in(key, i), 0.5, h.shape)
            return h.sum()
        rep = lint_fn(step, jax.random.PRNGKey(0), jnp.ones((8,)),
                      registry=False)
        assert "prng-key-reuse" not in _rules(rep)

    def test_fires_on_key_closed_over_scan(self):
        def step(key, xs):
            def body(c, x):
                return c + jax.random.normal(key, x.shape).sum(), None
            out, _ = jax.lax.scan(body, 0.0, xs)
            return out
        rep = lint_fn(step, jax.random.PRNGKey(0), jnp.ones((4, 3)),
                      registry=False)
        assert "prng-key-reuse" in _rules(rep)
        assert any("scan/while" in f.message for f in rep)

    def test_silent_on_per_iteration_keys_through_scan(self):
        def step(key, xs):
            ks = jax.random.split(key, xs.shape[0])
            def body(c, kx):
                k, x = kx
                return c + jax.random.normal(k, x.shape).sum(), None
            out, _ = jax.lax.scan(body, 0.0, (ks, xs))
            return out
        rep = lint_fn(step, jax.random.PRNGKey(0), jnp.ones((4, 3)),
                      registry=False)
        assert "prng-key-reuse" not in _rules(rep)

    def test_new_style_typed_keys_tracked(self):
        def step(key, x):
            return (jax.random.normal(key, x.shape)
                    + jax.random.normal(key, x.shape)).sum()
        key = jax.random.key(0)                 # typed key array
        rep = lint_fn(step, key, jnp.ones((4,)), registry=False)
        assert "prng-key-reuse" in _rules(rep)


class TestReplicatedLargeRule:
    def _state(self):
        return {"params": {"w": jnp.zeros((1024, 512))},  # 2 MiB
                "opt": {}, "step": jnp.zeros((), jnp.int32)}

    def test_fires_under_replicated_plan(self):
        rep = lint_fn(lambda s, x: (s, x.sum()), self._state(),
                      jnp.ones((4,)), plan=plan_lib.replicated_plan(),
                      registry=False)
        assert "replicated-large" in _rules(rep)
        assert not rep.errors                    # warning severity

    def test_silent_under_fsdp_plan(self):
        rep = lint_fn(lambda s, x: (s, x.sum()), self._state(),
                      jnp.ones((4,)), plan=plan_lib.fsdp_plan(),
                      registry=False)
        assert "replicated-large" not in _rules(rep)

    def test_silent_without_plan(self):
        rep = lint_fn(lambda s, x: (s, x.sum()), self._state(),
                      jnp.ones((4,)), registry=False)
        assert "replicated-large" not in _rules(rep)

    def test_fires_on_replicated_sharding_constraint(self, mesh8):
        repl = NamedSharding(mesh8, P())
        def step(x):
            y = jax.lax.with_sharding_constraint(x * 2, repl)
            return y.sum()
        rep = lint_fn(step, jnp.ones((1024, 512)), registry=False)
        assert "replicated-large" in _rules(rep)

    def test_silent_on_partitioned_constraint(self, mesh8):
        sharded = NamedSharding(mesh8, P("dp"))
        def step(x):
            y = jax.lax.with_sharding_constraint(x * 2, sharded)
            return y.sum()
        rep = lint_fn(step, jnp.ones((1024, 512)), registry=False)
        assert "replicated-large" not in _rules(rep)


# ---------------------------------------------------------------------------
# AST rules
# ---------------------------------------------------------------------------

def _ast_bad_step(state, x):
    import random
    import time
    y = x * 2
    if y.sum() > 0:                       # tracer branch
        y = y + 1
    while y.mean() < 1:                   # tracer while
        y = y + 1
    v = y.item()                          # host sync
    a = np.asarray(y)                     # host materialization
    t = time.time()                       # trace-time constant
    r = random.random()                   # stdlib random
    f = float(y[0])                       # host conversion
    return state, {"v": v, "a": a, "t": t, "r": r, "f": f}


def _ast_clean_step(state, x, training=False, key=None):
    if training:                          # static flag: fine
        x = x * 2
    if key is None:                       # None-compare: fine
        x = x + 1
    y = jnp.where(x > 0, x, 0.0)          # traced branch: fine
    return state, {"y": y.sum()}


class TestAstRules:
    def test_bad_step_fires_everything(self):
        findings = analysis.lint_callable(_ast_bad_step)
        rules = {f.rule for f in findings}
        assert rules == {"ast-tracer-branch", "ast-host-sync"}
        branch = [f for f in findings if f.rule == "ast-tracer-branch"]
        assert len(branch) == 2               # the if AND the while
        sync = [f for f in findings if f.rule == "ast-host-sync"]
        assert len(sync) == 5                 # item/asarray/time/random/float
        assert all("test_analysis.py" in f.location for f in findings)

    def test_clean_step_is_silent(self):
        assert analysis.lint_callable(_ast_clean_step) == []

    def test_source_unavailable_is_silent(self):
        assert analysis.lint_callable(jnp.sum) == []


# ---------------------------------------------------------------------------
# key-reuse vs the REAL surfaces it protects
# ---------------------------------------------------------------------------

class TestPrngSurfaces:
    def test_distributions_keyed_sample_clean(self):
        def step(key, x):
            return Normal(0.0, 1.0).sample((4,), key=key).sum() + x.sum()
        rep = lint_fn(step, jax.random.PRNGKey(0), jnp.ones((3,)),
                      registry=False)
        assert "prng-key-reuse" not in _rules(rep)

    def test_distributions_double_sample_trips(self):
        def step(key, x):
            n = Normal(0.0, 1.0)
            return (n.sample((4,), key=key).sum()
                    + n.sample((4,), key=key).sum() + x.sum())
        rep = lint_fn(step, jax.random.PRNGKey(0), jnp.ones((3,)),
                      registry=False)
        assert "prng-key-reuse" in _rules(rep)

    def test_img_conv_group_dropout_clean(self):
        """The fold_in-per-layer dropout keys from PR 1 must lint clean."""
        m = ImgConvGroup(3, [8, 8], pool_size=2, conv_with_batchnorm=True,
                         conv_batchnorm_drop_rate=0.3, conv_act="relu")
        params = m.init(jax.random.PRNGKey(0))
        def fwd(params, key, x):
            return m(params, x, training=True, dropout_key=key).sum()
        rep = lint_fn(fwd, analysis.abstractify(params),
                      jax.random.PRNGKey(1),
                      jax.ShapeDtypeStruct((2, 8, 8, 3), jnp.float32),
                      registry=False)
        assert _rules(rep) == []

    @pytest.mark.parametrize("make_key", [jax.random.PRNGKey, jax.random.key],
                             ids=["raw", "typed"])
    def test_shared_dropout_key_trips(self, make_key):
        """The anti-pattern ImgConvGroup avoids: one key for every layer's
        dropout correlates the masks — the rule must catch it, through the
        bit generator's state that dropout widens the key into."""
        from paddle_tpu.ops import nn as F
        def fwd(key, x):
            h = F.dropout(x, key, rate=0.3, training=True)
            h = F.dropout(h, key, rate=0.3, training=True)
            return h.sum()
        rep = lint_fn(fwd, make_key(0),
                      jnp.ones((2, 8, 8, 3)), registry=False)
        assert "prng-key-reuse" in _rules(rep)

    def test_dropout_per_site_fold_in_clean(self):
        from paddle_tpu.ops import nn as F
        def fwd(key, x):
            for i in range(3):
                x = F.dropout(x, jax.random.fold_in(key, i), rate=0.3)
            return x.sum()
        rep = lint_fn(fwd, jax.random.PRNGKey(0),
                      jnp.ones((2, 8, 8, 3)), registry=False)
        assert "prng-key-reuse" not in _rules(rep)


# ---------------------------------------------------------------------------
# report / suppressions / registry / enforce
# ---------------------------------------------------------------------------

class TestReporting:
    def _finding(self, rule="host-callback", sev="error"):
        return Finding(rule, sev, "msg here", location="loc.py:1")

    def test_render_text_and_json(self):
        rep = Report("demo", [self._finding()])
        assert "demo" in rep.render_text()
        assert "host-callback" in rep.render_text()
        import json
        data = json.loads(rep.render_json())
        assert data["findings"][0]["rule"] == "host-callback"

    def test_ok_thresholds(self):
        rep = Report("demo", [self._finding(sev="warning")])
        assert rep.ok("error") and not rep.ok("warning")

    def test_suppressions_file_roundtrip(self, tmp_path):
        p = tmp_path / "sup.txt"
        p.write_text("# comment\nhost-callback  loc.py\n")
        sup = Suppressions.load(str(p))
        rep = Report("demo", [self._finding()], suppressions=sup)
        assert len(rep) == 0 and len(rep.suppressed) == 1
        assert rep.ok("error")

    def test_findings_counted_into_registry(self):
        reg = observability.default()
        c = reg.counter("analysis_findings_total")
        before = c.value(rule="host-callback", severity="error")
        Report("demo", [self._finding()]).count_into_registry()
        assert c.value(rule="host-callback",
                       severity="error") == before + 1

    def test_enforce_modes(self):
        bad = Report("demo", [self._finding()])
        with pytest.raises(LintError):
            analysis.enforce(bad, "error", log_fn=lambda s: None)
        logs = []
        analysis.enforce(bad, "warn", log_fn=logs.append)   # no raise
        assert logs and "host-callback" in logs[0]
        analysis.enforce(bad, "off", log_fn=logs.append)
        with pytest.raises(ValueError):
            analysis.enforce(bad, "loud")


# ---------------------------------------------------------------------------
# Trainer / Executor gates
# ---------------------------------------------------------------------------

def _mnist_trainer(**kw):
    from paddle_tpu.data import datasets, reader as rd, device_iterator
    from paddle_tpu.models import LeNet
    from paddle_tpu.ops import nn as F

    model = LeNet()
    optim = opt.Adam(learning_rate=1e-3)
    state = make_train_state(model, optim, jax.random.PRNGKey(0))

    def loss_fn(params, image, label):
        logits = model(params, image)
        return jnp.mean(F.softmax_with_cross_entropy(logits, label))

    step = jax.jit(build_train_step(loss_fn, optim), donate_argnums=0)
    data = rd.batch(datasets.synthetic_mnist(n=128), 64)
    batches = list(device_iterator(data, ["image", "label"]))
    return pt.Trainer(step, state, log_every=0, telemetry=False, **kw), \
        batches


def _key_reusing_trainer():
    def bad_step(state, x, key):
        noise = (jax.random.normal(key, x.shape)
                 + jax.random.uniform(key, x.shape))
        w = state["w"] + (x + noise).mean()
        return {"w": w, "step": state["step"] + 1}, {"loss": w.sum()}

    state = {"w": jnp.zeros((4,)), "step": jnp.zeros((), jnp.int32)}
    batches = [{"x": jnp.ones((4,)), "key": jax.random.PRNGKey(i)}
               for i in range(2)]
    return pt.Trainer(jax.jit(bad_step, donate_argnums=0), state,
                      log_every=0, telemetry=False), batches


class TestTrainerGate:
    def test_error_mode_passes_on_clean_model(self):
        """Acceptance: Trainer.fit(lint='error') on the book-mnist model."""
        trainer, batches = _mnist_trainer()
        metrics = trainer.fit(batches, lint="error")
        assert "loss" in metrics

    def test_error_mode_raises_on_key_reuse(self):
        trainer, batches = _key_reusing_trainer()
        with pytest.raises(LintError) as e:
            trainer.fit(batches, lint="error")
        assert "prng-key-reuse" in str(e.value)

    def test_warn_mode_logs_and_trains(self):
        logs = []
        trainer, batches = _key_reusing_trainer()
        trainer.log_fn = logs.append
        trainer.fit(batches, lint="warn")      # trains despite findings
        assert any("prng-key-reuse" in s for s in logs)
        assert trainer.step_count == len(batches)

    def test_off_is_default_and_silent(self):
        trainer, batches = _key_reusing_trainer()
        trainer.fit(batches)                   # no lint, no raise
        assert trainer.step_count == len(batches)


class TestExecutorGate:
    def _bad_program(self):
        def fn(state, x, key):
            noise = (jax.random.normal(key, x.shape)
                     + jax.random.uniform(key, x.shape))
            return {"w": state["w"] + noise.mean()}, {"out": noise.sum()}
        return pt.Program(fn=fn, name="bad_prog", donate_state=True)

    def test_error_mode_raises_at_first_run(self):
        exe = pt.Executor(lint="error")
        state = {"w": jnp.zeros((4,))}
        feed = {"x": jnp.ones((4,)), "key": jax.random.PRNGKey(0)}
        with pytest.raises(LintError):
            exe.run(self._bad_program(), state, feed=feed)

    def test_error_gate_stays_armed_after_caught_error(self):
        """A caught LintError must not disarm the gate: the next run of
        the same defective Program raises again."""
        exe = pt.Executor(lint="error")
        prog = self._bad_program()
        state = {"w": jnp.zeros((4,))}
        feed = {"x": jnp.ones((4,)), "key": jax.random.PRNGKey(0)}
        for _ in range(2):
            with pytest.raises(LintError):
                exe.run(prog, state, feed=feed)

    def test_warn_mode_runs_and_warns_once(self):
        exe = pt.Executor(lint="warn")
        state = {"w": jnp.zeros((4,))}
        prog = self._bad_program()
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            state, fetches = exe.run(
                prog, state, feed={"x": jnp.ones((4,)),
                                   "key": jax.random.PRNGKey(0)})
            state, fetches = exe.run(
                prog, state, feed={"x": jnp.ones((4,)),
                                   "key": jax.random.PRNGKey(1)})
        lint_warnings = [x for x in w if "prng-key-reuse" in str(x.message)]
        assert len(lint_warnings) == 1         # linted once per Program
        assert "out" in fetches

    def test_off_default_unchanged(self):
        exe = pt.Executor()
        state = {"w": jnp.zeros((4,))}
        state, fetches = exe.run(
            self._bad_program(), state,
            feed={"x": jnp.ones((4,)), "key": jax.random.PRNGKey(0)})
        assert "out" in fetches


# ---------------------------------------------------------------------------
# CLI / CI self-lint
# ---------------------------------------------------------------------------

class TestCli:
    def test_list_rules(self, graph_lint_cli, capsys):
        assert graph_lint_cli.main(["--list-rules"]) == 0
        out = capsys.readouterr().out
        assert "prng-key-reuse" in out and "host-callback" in out

    def test_lenet_preset_entry_green(self, graph_lint_cli):
        mod = graph_lint_cli
        rep = mod.lint_lenet(None)
        assert rep.ok("error"), rep.render_text()

    @pytest.mark.slow
    def test_framework_preset_green(self, graph_lint_cli):
        """The CI self-lint stage (run_ci.sh) must pass."""
        assert graph_lint_cli.main(["--preset", "framework"]) == 0


# ---------------------------------------------------------------------------
# satellite: debug.nan_checks context manager
# ---------------------------------------------------------------------------

class TestNanChecks:
    def test_restores_prior_value_and_nests(self):
        prev = jax.config.jax_debug_nans
        try:
            with debug.nan_checks():
                assert jax.config.jax_debug_nans is True
                with debug.nan_checks(False):
                    assert jax.config.jax_debug_nans is False
                    with debug.nan_checks(True):
                        assert jax.config.jax_debug_nans is True
                    assert jax.config.jax_debug_nans is False
                assert jax.config.jax_debug_nans is True
            assert jax.config.jax_debug_nans == prev
        finally:
            jax.config.update("jax_debug_nans", prev)

    def test_restores_on_exception(self):
        prev = jax.config.jax_debug_nans
        with pytest.raises(RuntimeError):
            with debug.nan_checks():
                raise RuntimeError("boom")
        assert jax.config.jax_debug_nans == prev

    def test_traps_nan(self):
        with debug.nan_checks():
            with pytest.raises(FloatingPointError):
                jnp.log(jnp.zeros(())) * 0.0   # 0 * -inf -> NaN

    def test_thin_wrapper_still_works(self):
        prev = jax.config.jax_debug_nans
        try:
            debug.enable_nan_checks(True)
            assert jax.config.jax_debug_nans is True
        finally:
            jax.config.update("jax_debug_nans", prev)


# ---------------------------------------------------------------------------
# HLO tier (ISSUE 9): cost model, HLO rules, bucket coverage, cost CLI
# ---------------------------------------------------------------------------

class TestCostModel:
    def test_matmul_flops_exact(self):
        from paddle_tpu.analysis import cost_model
        r = cost_model.estimate_cost(
            lambda x, w: x @ w,
            jax.ShapeDtypeStruct((8, 16), jnp.float32),
            jax.ShapeDtypeStruct((16, 32), jnp.float32))
        assert r.per_op["dot_general"].flops == 2 * 8 * 16 * 32
        assert r.arg_bytes == (8 * 16 + 16 * 32) * 4
        assert r.out_bytes == 8 * 32 * 4
        assert r.collective_bytes == 0 and not r.collectives

    def test_donation_lowers_peak_hbm(self):
        """Donated state aliases into the output: old+new copies must
        not both count (the static face of donate_argnums)."""
        from paddle_tpu.analysis import cost_model
        def step(s, x):
            return s + x.sum()
        a = jax.ShapeDtypeStruct((512, 512), jnp.float32)
        b = jax.ShapeDtypeStruct((8,), jnp.float32)
        undonated = cost_model.estimate_cost(step, a, b)
        donated = cost_model.estimate_cost(step, a, b, donate_argnums=0)
        assert donated.peak_hbm_bytes < undonated.peak_hbm_bytes
        assert donated.donated_bytes == 512 * 512 * 4

    def test_report_roundtrip_and_summary(self):
        from paddle_tpu.analysis import cost_model
        r = cost_model.estimate_cost(
            lambda x: jnp.tanh(x).sum(),
            jax.ShapeDtypeStruct((64, 64), jnp.float32))
        d = r.as_dict()
        assert set(r.summary()) == {"flops", "peak_hbm_bytes",
                                    "traffic_bytes", "collective_bytes"}
        assert d["per_op"]["tanh"]["count"] == 1
        assert "tanh" in r.render_text() or "flops" in r.render_text()

    def test_lint_fn_attaches_cost(self):
        rep = lint_fn(lambda x: x * 2.0, jnp.ones((16,)), cost=True,
                      registry=False)
        assert rep.cost is not None
        assert rep.cost.summary()["flops"] > 0
        assert "cost" in rep.render_json()


class TestUnexpectedCollectiveRule:
    def _psum_fn(self, mesh):
        from paddle_tpu.core import compat
        return compat.shard_map(
            lambda x: jax.lax.psum(x, "tp"), mesh=mesh,
            in_specs=P("dp", "tp"), out_specs=P("dp", None))

    def test_fires_on_undeclared_psum(self, mesh_dp2_tp4):
        rep = lint_fn(self._psum_fn(mesh_dp2_tp4),
                      jax.ShapeDtypeStruct((8, 16), jnp.float32),
                      collective_allowlist=[], registry=False,
                      mesh_axes={"dp": 2, "tp": 4})
        assert "unexpected-collective" in _rules(rep)
        assert rep.errors
        [c] = rep.cost.collectives
        assert c.kind == "all_reduce" and c.axis == "tp"

    def test_silent_when_allowlisted(self, mesh_dp2_tp4):
        rep = lint_fn(self._psum_fn(mesh_dp2_tp4),
                      jax.ShapeDtypeStruct((8, 16), jnp.float32),
                      collective_allowlist=["all_reduce"],
                      registry=False)
        assert "unexpected-collective" not in _rules(rep)

    def test_silent_on_collective_free_twin(self):
        rep = lint_fn(lambda x: (x * 2).sum(), jnp.ones((8, 16)),
                      collective_allowlist=[], registry=False)
        assert "unexpected-collective" not in _rules(rep)
        assert rep.cost.collective_bytes == 0


class TestReshardingChurnRule:
    def test_fires_on_disagreeing_constraints(self, mesh_dp2_tp4):
        s1 = NamedSharding(mesh_dp2_tp4, P("dp", None))
        s2 = NamedSharding(mesh_dp2_tp4, P(None, "dp"))

        def churn(x):
            x = jax.lax.with_sharding_constraint(x, s1)
            x = x * 2.0
            return jax.lax.with_sharding_constraint(x, s2)

        rep = lint_fn(churn, jax.ShapeDtypeStruct((512, 512), jnp.float32),
                      cost=True, registry=False)
        assert "resharding-churn" in _rules(rep)
        assert rep.cost.resharding[0].bytes == 512 * 512 * 4

    def test_silent_on_agreeing_constraints(self, mesh_dp2_tp4):
        s1 = NamedSharding(mesh_dp2_tp4, P("dp", None))

        def steady(x):
            x = jax.lax.with_sharding_constraint(x, s1)
            x = x * 2.0
            return jax.lax.with_sharding_constraint(x, s1)

        rep = lint_fn(steady, jax.ShapeDtypeStruct((512, 512), jnp.float32),
                      cost=True, registry=False)
        assert "resharding-churn" not in _rules(rep)

    def test_small_values_ignored(self, mesh_dp2_tp4):
        s1 = NamedSharding(mesh_dp2_tp4, P("dp"))
        s2 = NamedSharding(mesh_dp2_tp4, P(None))

        def churn(x):
            x = jax.lax.with_sharding_constraint(x, s1)
            return jax.lax.with_sharding_constraint(x * 2.0, s2)

        rep = lint_fn(churn, jax.ShapeDtypeStruct((8,), jnp.float32),
                      cost=True, registry=False)
        assert "resharding-churn" not in _rules(rep)


class TestPeakHbmBudgetRule:
    def test_fires_over_budget(self):
        rep = lint_fn(lambda x: x * 2.0, jnp.ones((256, 256)),
                      hbm_budget_bytes=1024, registry=False)
        assert "peak-hbm-budget" in _rules(rep)
        assert rep.errors

    def test_silent_under_budget(self):
        rep = lint_fn(lambda x: x * 2.0, jnp.ones((256, 256)),
                      hbm_budget_bytes=1 << 30, registry=False)
        assert "peak-hbm-budget" not in _rules(rep)

    def test_flops_budget_fires_cost_regression(self):
        rep = lint_fn(lambda x, w: x @ w,
                      jnp.ones((64, 64)), jnp.ones((64, 64)),
                      flops_budget=10, registry=False)
        assert "cost-regression" in _rules(rep)


class TestBucketCoverage:
    def _engine(self, **kw):
        from paddle_tpu import serving
        from paddle_tpu.models.gpt import GPT, GPTConfig
        model = GPT(GPTConfig.tiny())
        params = model.init(jax.random.PRNGKey(0))
        kw.setdefault("num_slots", 4)
        kw.setdefault("page_size", 8)
        kw.setdefault("max_tokens_per_slot", 64)
        return serving.ServingEngine(model, params, attn_impl="lax", **kw)

    def test_serving_plan_covers_reachable(self):
        eng = self._engine()
        assert analysis.serving_bucket_coverage(eng) == []
        # the two derivations agree exactly (plan has no dead buckets)
        assert set(eng.warmup_plan()) == set(eng.reachable_signatures())

    def test_serving_nonpow2_config_covered(self):
        eng = self._engine(num_slots=6, max_tokens_per_slot=72)
        assert analysis.serving_bucket_coverage(eng) == []

    def test_skipped_warmup_bucket_fires(self):
        """ISSUE acceptance: deliberately skip one warmup bucket and the
        rule must prove the gap."""
        eng = self._engine()
        plan = set(eng.warmup_plan())
        skipped = sorted(plan, key=str)[0]
        findings = analysis.serving_bucket_coverage(
            eng, warmed=plan - {skipped})
        assert [f.rule for f in findings] == ["bucket-coverage"]
        assert str(skipped) in findings[0].message \
            or str(skipped) in findings[0].location

    def test_embedding_plan_covers_reachable(self):
        from paddle_tpu.embedding_serving import DeviceEmbeddingCache
        for capacity, max_uniq in ((64, 48), (50, 50), (64, 64)):
            cache = DeviceEmbeddingCache(capacity, 9, min_gather_bucket=8)
            assert analysis.embedding_bucket_coverage(
                cache, max_uniq) == [], (capacity, max_uniq)

    def test_embedding_skipped_bucket_fires(self):
        from paddle_tpu.embedding_serving import DeviceEmbeddingCache
        cache = DeviceEmbeddingCache(64, 9, min_gather_bucket=8)
        plan = set(cache.warmup_plan(48))
        skipped = sorted(plan, key=str)[-1]
        findings = analysis.embedding_bucket_coverage(
            cache, 48, warmed=plan - {skipped})
        assert [f.rule for f in findings] == ["bucket-coverage"]

    def test_dispatch_helper(self):
        from paddle_tpu.embedding_serving import DeviceEmbeddingCache
        eng = self._engine()
        assert analysis.check_bucket_coverage(eng) == []
        cache = DeviceEmbeddingCache(64, 9, min_gather_bucket=8)
        assert analysis.check_bucket_coverage(cache, max_uniq=48) == []
        with pytest.raises(ValueError):
            analysis.check_bucket_coverage(cache)

    def test_warmup_records_signatures_and_cost_gauges(self):
        reg = observability.MetricsRegistry()
        eng = self._engine(num_slots=2, page_size=8,
                           max_tokens_per_slot=16, registry=reg)
        eng.warmup()
        assert eng.warmed_signatures == set(eng.warmup_plan())
        # per-bucket static cost gauges published during warmup
        g = reg.gauge("serving_bucket_cost_flops")
        assert g.value(phase="decode", width="1", lanes="2") > 0
        assert ("decode", 1) in eng.bucket_costs
        assert eng.bucket_costs[("decode", 1)].summary()["flops"] > 0


class TestRematRecursion:
    """Satellite: rules must see through jax.checkpoint/remat scopes
    (the remat body is stored as an OPEN jaxpr the recursion previously
    skipped)."""

    def test_key_reuse_inside_remat_fires(self):
        def bad(x, key):
            def inner(x):
                a = jax.random.normal(key, x.shape)
                b = jax.random.uniform(key, x.shape)
                return jnp.sum(x * a * b)
            return jax.checkpoint(inner)(x)
        rep = lint_fn(bad, jnp.ones((4,)), jax.random.PRNGKey(0),
                      registry=False)
        assert "prng-key-reuse" in _rules(rep)

    def test_split_inside_remat_is_silent(self):
        def good(x, key):
            def inner(x):
                k1, k2 = jax.random.split(key)
                return jnp.sum(x * jax.random.normal(k1, x.shape)
                               * jax.random.uniform(k2, x.shape))
            return jax.checkpoint(inner)(x)
        rep = lint_fn(good, jnp.ones((4,)), jax.random.PRNGKey(0),
                      registry=False)
        assert "prng-key-reuse" not in _rules(rep)

    def test_host_callback_inside_remat_fires(self):
        def cb(x):
            def inner(x):
                return jax.pure_callback(
                    lambda a: a, jax.ShapeDtypeStruct((4,), jnp.float32),
                    x).sum()
            return jax.checkpoint(inner)(x)
        rep = lint_fn(cb, jnp.ones((4,)), registry=False)
        assert "host-callback" in _rules(rep)


class TestStaleSuppressions:
    def test_used_entry_not_stale(self):
        sup = Suppressions([("f64-promotion", "*")])
        rep = Report("fn", suppressions=sup)
        rep.add(Finding("f64-promotion", "warning", "m"))
        assert rep.suppressed and sup.stale() == []

    def test_unused_entry_is_stale(self):
        sup = Suppressions([("f64-promotion", "*"),
                            ("prng-key-reuse", "never_matches")])
        rep = Report("fn", suppressions=sup)
        rep.add(Finding("f64-promotion", "warning", "m"))
        assert sup.stale() == [("prng-key-reuse", "never_matches")]


class TestCostCli:
    def test_cost_diff_flags_regression(self, graph_lint_cli):
        mod = graph_lint_cli
        budgets = {"tolerance": 0.10, "surfaces": {
            "s": {"flops": 100, "peak_hbm_bytes": 1000,
                  "collective_bytes": 0}}}
        ok = {"s": {"flops": 105, "peak_hbm_bytes": 1000,
                    "collective_bytes": 0}}
        bad = {"s": {"flops": 150, "peak_hbm_bytes": 1000,
                     "collective_bytes": 0}}
        sink = []
        assert mod.cost_diff(ok, budgets, out=sink.append) == 0
        assert mod.cost_diff(bad, budgets, out=sink.append) == 1
        assert any("REGRESSION" in s for s in sink)

    def test_cost_diff_collectives_from_zero_fail(self, graph_lint_cli):
        mod = graph_lint_cli
        budgets = {"tolerance": 0.10, "surfaces": {
            "s": {"flops": 100, "peak_hbm_bytes": 1000,
                  "collective_bytes": 0}}}
        grew = {"s": {"flops": 100, "peak_hbm_bytes": 1000,
                      "collective_bytes": 4096}}
        assert mod.cost_diff(grew, budgets, out=lambda *_: None) == 1

    def test_cost_diff_missing_baseline_fails(self, graph_lint_cli):
        mod = graph_lint_cli
        budgets = {"tolerance": 0.10, "surfaces": {}}
        assert mod.cost_diff(
            {"new": {"flops": 1, "peak_hbm_bytes": 1,
                     "collective_bytes": 0}},
            budgets, out=lambda *_: None) == 1

    def test_bucket_coverage_report_green(self, graph_lint_cli):
        rep = graph_lint_cli.bucket_coverage_report(None)
        assert rep.ok("error"), rep.render_text()

    @pytest.mark.slow
    def test_cost_preset_green(self, graph_lint_cli):
        """The CI cost stage (run_ci.sh): --cost --cost-diff must pass
        against the committed tools/cost_budgets.json."""
        assert graph_lint_cli.main(
            ["--preset", "framework", "--cost", "--cost-diff"]) == 0

    @pytest.mark.slow
    def test_injected_regression_fails_cost_diff(self, graph_lint_cli,
                                                 tmp_path):
        """ISSUE acceptance: --cost-diff demonstrably fails on an
        injected >10% budget regression."""
        import json
        mod = graph_lint_cli
        with open(mod.DEFAULT_BUDGETS) as f:
            budgets = json.load(f)
        # shrink one committed baseline so the measured value reads as
        # a +50% regression
        budgets["surfaces"]["serving_decode"]["flops"] = int(
            budgets["surfaces"]["serving_decode"]["flops"] / 1.5)
        doctored = tmp_path / "budgets.json"
        doctored.write_text(json.dumps(budgets))
        assert mod.main(["--preset", "framework", "--cost-diff",
                         "--budgets", str(doctored)]) == 1


class TestTrainerCostGate:
    def test_lint_cost_budget_enforced(self):
        trainer, batches = _mnist_trainer()
        with pytest.raises(LintError) as e:
            trainer.fit(batches, lint="error",
                        lint_cost={"hbm_budget_bytes": 1024})
        assert "peak-hbm-budget" in str(e.value)

    def test_lint_cost_clean_trains(self):
        trainer, batches = _mnist_trainer()
        metrics = trainer.fit(batches, lint="error",
                              lint_cost={"hbm_budget_bytes": 1 << 30,
                                         "collective_allowlist": []})
        assert "loss" in metrics
