"""chip_smoke.py's CPU rehearsal: the same phase functions the chip run
calls, at a tiny size, Pallas bodies through the interpreter — and the
script's refusal to produce a result without a TPU."""

import json
import os

import jax
import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: a phase of ``chip_smoke.run_one_chip`` and what it says when it passed
ONE_CHIP_PHASES = {
    "phase_paged_kernels": ["paged kernels vs lax",
                            "paged prefill lowerings",
                            "fold=head,operands=stored,terms=stacked"],
    "phase_trainer": ["flash_attention[pallas_interpret]"],
    # JAX's own account of where warmup's seconds went
    "phase_serving": ["of which JAX reports", "compiles after warmup=0",
                      "token-exact vs model.generate"],
    "phase_sparse_family": ["sparse family kernels vs lax",
                            "selection mask vs lax.top_k",
                            "selection alone, (4, 512) for 16",
                            "tokens are the float32 reference's argmax"],
    "phase_hybrid_family": ["hybrid family kernels vs lax",
                            "hybrid family: 19-token prompt"],
    "phase_latent_family": ["latent family kernels vs lax",
                            "latent family: 19-token prompt"],
    "phase_wide_key_kernels": ["wide-key paged kernels vs lax",
                               "ragged_paged_prefill[kv2,float32]",
                               "wide-key prefill lowerings"],
    "phase_selecting_latent_kernels": [
        "selection mask vs lax.top_k", "0 differ",
        "selecting latent kernels vs lax", "indexer chunk",
        "sparse_latent_decode, every slot alone",
        "sparse_latent_decode vs NumPy"],
    "phase_gated_delta_kernels": [
        "gated delta kernels vs lax", "ragged_paged_prefill[kv2x16]",
        "gated_delta_decode_update alone, 3 slots x 16 calls"],
}


def test_run_one_chip_is_these_phases(chip_smoke, monkeypatch):
    called = []
    for name in [n for n in dir(chip_smoke) if n.startswith("phase_")]:
        monkeypatch.setattr(chip_smoke, name, lambda sizes, seed, name=name:
                            called.append(name))
    chip_smoke.run_one_chip(chip_smoke.Sizes.tiny())
    assert called == list(ONE_CHIP_PHASES)


@pytest.mark.parametrize("phase", list(ONE_CHIP_PHASES))
def test_one_chip_phases_at_tiny_size(chip_smoke, capsys, phase):
    """Each phase alone, as ``run_one_chip`` calls it: a phase reads the
    counters it asserts on before and after itself."""
    if phase == "phase_serving":
        chip_smoke._watch_compiles()
    getattr(chip_smoke, phase)(chip_smoke.Sizes.tiny(), 0)
    out = capsys.readouterr().out
    for line in ONE_CHIP_PHASES[phase]:
        assert line in out
    if phase == "phase_serving":
        assert chip_smoke.COMPILE_STATS["trace_s"] > 0
        assert chip_smoke.COMPILE_STATS["backend_compile_s"] > 0


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
def bench(script):
    return script("bench")


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
