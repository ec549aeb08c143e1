"""chip_smoke.py's CPU rehearsal: the same phase functions the chip run
calls, at a tiny size, Pallas bodies through the interpreter — and the
script's refusal to produce a result without a TPU."""

import importlib.util
import json
import os
import sys

import jax
import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(_ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod     # dataclasses resolve the module
    spec.loader.exec_module(mod)
    return mod


def test_one_chip_phases_at_tiny_size(chip_smoke, capsys):
    chip_smoke._watch_compiles()
    chip_smoke.run_one_chip(chip_smoke.Sizes.tiny())
    out = capsys.readouterr().out
    # JAX's own account of where warmup's seconds went
    assert "of which JAX reports" in out
    assert chip_smoke.COMPILE_STATS["trace_s"] > 0
    assert chip_smoke.COMPILE_STATS["backend_compile_s"] > 0
    assert "paged kernels vs lax" in out
    assert "flash_attention[pallas_interpret]" in out
    assert "compiles after warmup=0" in out
    assert "token-exact vs model.generate" in out
    assert "sparse family kernels vs lax" in out
    assert "tokens are the float32 reference's argmax" in out
    assert "hybrid family kernels vs lax" in out
    assert "hybrid family: 19-token prompt" in out
    assert "latent family kernels vs lax" in out
    assert "latent family: 19-token prompt" in out


def test_four_chip_phases_on_virtual_devices(chip_smoke, capsys):
    chip_smoke.run_four_chips(chip_smoke.Sizes.tiny(),
                              devices=jax.devices()[:4])
    out = capsys.readouterr().out
    assert "dp2 x tp2 losses" in out
    assert "requests token-equal, tp=4 vs tp=1" in out


def test_real_sizes_are_full_width(chip_smoke):
    """The chip run is at the models' own widths: nothing of BertConfig
    .base() / GPTConfig() is overridden, batch 48 x sequence 512."""
    real = chip_smoke.Sizes.real()
    assert real.bert == {} and real.gpt == {} and not real.interpret
    assert (real.bert_batch, real.bert_seq) == (48, 512)
    page, chunk = real.page_size, real.prefill_chunk
    assert any(n > chunk and n % page for n in real.prompt_lens)
    assert real.shared_prefix % page == 0 and real.shared_prefix > 0


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_main_fails_without_a_tpu(chip_smoke, capsys, argv):
    """No accelerator: non-zero exit and NO result line."""
    assert jax.devices()[0].platform == "cpu"
    assert chip_smoke.main(argv) != 0
    captured = capsys.readouterr()
    assert "needs a TPU" in captured.err
    for line in captured.out.splitlines():
        assert not line.startswith("{"), line
        with pytest.raises(ValueError):
            json.loads(line)


# -- the entry points do not hide the device ---------------------------------

@pytest.fixture(scope="module")
def bench():
    spec = importlib.util.spec_from_file_location(
        "bench_mod", os.path.join(_ROOT, "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class _Dev:
    def __init__(self, platform, kind):
        self.platform, self.device_kind = platform, kind


def test_bench_needs_a_tpu_or_an_explicit_cpu(bench, monkeypatch):
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with pytest.raises(RuntimeError, match="needs a TPU"):
        bench.acquire_device()
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert bench.acquire_device().platform == "cpu"


def test_bench_peak_flops_refuses_an_unknown_device(bench):
    assert bench.device_peak_flops(_Dev("tpu", "TPU v5 lite")) == 197e12
    with pytest.raises(ValueError, match="no published peak"):
        bench.device_peak_flops(_Dev("tpu", "TPU v99"))
    with pytest.raises(ValueError, match="no published peak"):
        bench.device_peak_flops(_Dev("cpu", "cpu"))
    # a CPU smoke run reports no MFU rather than a made-up one
    assert bench.mfu_fields(_Dev("cpu", "cpu"), 1e12) == {
        "mfu": None, "vs_baseline": None}
    assert bench.mfu_fields(_Dev("tpu", "TPU v5 lite"), 98.5e12) == {
        "mfu": 0.5, "vs_baseline": round(0.5 / 0.35, 4)}


def test_compile_cache_is_placed_from_outside_or_fixed(monkeypatch):
    from paddle_tpu.core import compile_cache as cc
    before = jax.config.jax_compilation_cache_dir
    # a CPU backend: nothing is switched on
    assert cc.enable_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.setattr(cc.jax, "devices",
                        lambda *a: [_Dev("tpu", "TPU v5 lite")])
    # placed from outside: JAX's own handling is left alone
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert cc.enable_compile_cache() == "/somewhere/else"
    assert jax.config.jax_compilation_cache_dir == before
    # not placed: ONE fixed path inside the checkout
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        assert cc.enable_compile_cache() == cc.DEFAULT_DIR
        assert jax.config.jax_compilation_cache_dir == cc.DEFAULT_DIR
        assert cc.DEFAULT_DIR == os.path.join(_ROOT, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_attention_dropout_exit_is_visible(caplog, monkeypatch):
    from paddle_tpu.ops import attention
    monkeypatch.setattr(attention, "_dropout_exit_logged", False)
    with caplog.at_level("WARNING", logger="paddle_tpu"):
        for _ in range(2):
            assert attention.resolve_attention_impl(
                "flash_interpret", 0.1) == "xla"
        # no dropout: the kernel stays, nothing is said
        assert attention.resolve_attention_impl("flash_interpret", 0.0) \
            == "flash_interpret"
    assert sum("no flash-kernel path" in r.message
               for r in caplog.records) == 1        # logged once


def test_kernel_dispatch_counts_the_resolved_impl():
    from paddle_tpu import kernels
    from paddle_tpu.observability import registry
    counter = registry.counter("kernel_dispatch_total")
    args, kwargs = kernels.get("ragged_paged_decode").sample_inputs(0)
    before = counter.value(kernel="ragged_paged_decode", impl="lax")
    kernels.dispatch("ragged_paged_decode", *args, impl="auto", **kwargs)
    # on a CPU backend "auto" is the lax path — and says so
    assert counter.value(kernel="ragged_paged_decode",
                         impl="lax") == before + 1


# -- the flash kernel under a mesh (nn.transformer._attend) ------------------
# A TPU's "auto" is the flash kernel, which the SPMD partitioner refuses:
# under a mesh it runs per shard in a shard_map — unless a pipeline stage
# body already is one. The CPU's "auto" is xla, so these name the kernel.

_TINY_BERT = dict(vocab_size=64, hidden_size=16, num_layers=4, num_heads=2,
                  ffn_size=32, max_position=32, dropout=0.0,
                  attn_dropout=0.0)


def _bert_batch(b, s=16):
    import jax.numpy as jnp
    k1, k2 = jax.random.split(jax.random.PRNGKey(1))
    mask = jnp.arange(s)[None, :] < jax.random.randint(
        k2, (b, 1), s // 2, s + 1)               # ragged padding
    return dict(
        input_ids=jax.random.randint(k1, (b, s), 0, 64, jnp.int32),
        token_type_ids=jnp.zeros((b, s), jnp.int32),
        attention_mask=mask,
        mlm_labels=jnp.zeros((b, s), jnp.int32),
        mlm_mask=jnp.ones((b, s), jnp.float32),
        nsp_labels=jnp.zeros((b,), jnp.int32))


@pytest.mark.parametrize("mesh_kw, model_kw, batch_size", [
    pytest.param(dict(config=dict(dp=2, fsdp=2, pp=2)),
                 dict(pipeline=True, pp_microbatches=4,
                      stacked_layers=False), 16, id="inside-pipeline-stage"),
    pytest.param(dict(axis_names=("dp",), shape=(8,)), {}, 16,
                 id="mesh-without-fsdp-tp-axes"),
    pytest.param(dict(config=dict(dp=4, fsdp=2)), {}, 6,
                 id="batch-not-divisible"),
    pytest.param(dict(config=dict(dp=2, tp=4)), {}, 16,
                 id="heads-not-divisible"),
])
def test_flash_kernel_under_a_mesh(mesh_kw, model_kw, batch_size):
    import numpy as np
    from paddle_tpu.core.mesh import MeshConfig, make_mesh, mesh_context
    from paddle_tpu.models.bert import BertConfig, BertForPretraining

    mesh_kw = dict(mesh_kw)
    if "config" in mesh_kw:
        mesh_kw["config"] = MeshConfig(**mesh_kw["config"])
    m_ref = BertForPretraining(BertConfig.tiny(**_TINY_BERT,
                                               attn_impl="xla"))
    m = BertForPretraining(BertConfig.tiny(
        **_TINY_BERT, attn_impl="flash_interpret", **model_kw))
    params = m_ref.init(jax.random.PRNGKey(0))
    batch = _bert_batch(batch_size)
    l_ref, g_ref = jax.value_and_grad(
        lambda p: m_ref.loss(p, training=False, **batch)[0])(params)
    with mesh_context(make_mesh(**mesh_kw)):
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p: m.loss(p, training=False, **batch)[0]))(params)
    assert float(loss) == pytest.approx(float(l_ref), rel=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(grads),
                    jax.tree_util.tree_leaves(g_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=1e-3)
