"""The shared Pallas kernel layer (ISSUE 12): registry, autotuner,
fallback harness, and registry lint.

The parity battery here is THE acceptance surface for every registered
kernel: pallas-interpret (the real kernel body under the interpreter) vs
the lax fallback vs an independent dense reference, at each contract's
declared tolerances. Plus: byte parity against the pre-refactor call
paths. The tuner-cache contracts, the zero-steady-state-recompile
invariant with the autotuner active and the registry's lint are in
``tests/test_kernels_registry.py``; the paged and flash bodies beyond the
battery in ``tests/test_kernels_paged.py`` / ``test_kernels_flash.py``.
"""

import json
import os

import numpy as np
import pytest

from paddle_tpu import kernels
from paddle_tpu.kernels import autotune

KERNEL_NAMES = kernels.load_all()


@pytest.fixture(scope="module", autouse=True)
def _programs_released_after_the_file():
    """The battery's interpreted programs map about 20,000 memory
    regions of the 65,530 a process may hold (``vm.max_map_count``), and
    a test worker keeps what it compiled: give them back for the files
    the worker runs next."""
    import jax
    yield
    jax.clear_caches()


# ---------------------------------------------------------------------------
# parity battery — every registered kernel, one harness
# ---------------------------------------------------------------------------

class TestParityBattery:
    @pytest.mark.parametrize("name", KERNEL_NAMES)
    @pytest.mark.parametrize("seed", [0, 1])
    def test_interpret_vs_lax_vs_reference(self, name, seed):
        errs = kernels.parity_check(name, seed)
        # parity_check asserts tolerances internally; a mesh kernel on a
        # single-device box returns {} (skipped), every other kernel
        # must have produced both comparisons
        if errs:
            assert set(errs) >= {"lax", "pallas_interpret"} or \
                set(errs) >= {"xla", "flash_interpret"}, errs


# ---------------------------------------------------------------------------
# head shards — what tensor-parallel serving leans on
# ---------------------------------------------------------------------------

class TestHeadShardsAreIndependent:
    """A tp engine shards its whole step and calls the plain paged
    kernels on each shard's heads (there is no tp kernel). That is sound
    because heads are independent and the pool's lanes are head-major: a
    head shard of the folded pool through the plain kernel is those
    heads of the whole call."""

    @pytest.mark.parametrize("name", [
        "ragged_paged_decode", "ragged_paged_prefill",
        "ragged_paged_decode_int8", "ragged_paged_prefill_int8"])
    @pytest.mark.parametrize("seed", [0, 1])
    def test_shard_equals_those_heads_of_the_whole_call(self, name, seed):
        spec = kernels.get(name)
        args, kw = spec.sample_inputs(seed)
        q, k_pages, v_pages, rest = args[0], args[1], args[2], args[3:]
        h, dh = q.shape[-2:]
        hl = h // 2                 # the two shards of tp=2
        whole = {impl: np.asarray(kernels.dispatch(
            name, *args, impl=impl, **kw))
            for impl in ("lax", "pallas_interpret")}
        for lo, hi in ((0, hl), (hl, h)):
            # scale rows (int8) and the block-table geometry go whole:
            # a token's scale is taken over all its heads
            shard = (q[..., lo:hi, :], k_pages[..., lo * dh:hi * dh],
                     v_pages[..., lo * dh:hi * dh]) + rest
            lax_out = np.asarray(kernels.dispatch(
                name, *shard, impl="lax", **kw))
            want = whole["lax"][..., lo:hi, :]
            if name.endswith("_int8"):
                # XLA's codegen for the fused cast-dequant dot
                # reassociates differently at different head counts, so
                # the per-shard dequant einsum can drift a last ulp from
                # the full-head one (greedy tokens identical to tp=1 are
                # pinned exactly in tests/test_serving_tp.py)
                np.testing.assert_allclose(lax_out, want,
                                           rtol=1e-6, atol=1e-6)
            else:
                np.testing.assert_array_equal(lax_out, want)
            np.testing.assert_allclose(
                np.asarray(kernels.dispatch(
                    name, *shard, impl="pallas_interpret", **kw)),
                whole["pallas_interpret"][..., lo:hi, :],
                atol=spec.contract.atol, rtol=spec.contract.rtol)


# ---------------------------------------------------------------------------
# byte parity vs the pre-refactor call paths
# ---------------------------------------------------------------------------

class TestByteParity:
    def test_flash_dispatch_equals_direct_kernel_call(self):
        """dispatch() with the tuner's default prior must reproduce the
        pre-refactor flash_attention(block=512) output BIT-FOR-BIT."""
        from paddle_tpu.ops.attention import flash_attention
        spec = kernels.get("flash_attention")
        (q, k, v), kw = spec.sample_inputs(0)
        via_registry = np.asarray(kernels.dispatch(
            "flash_attention", q, k, v, None, impl="pallas_interpret",
            tuner=kernels.KernelTuner(path=None), **kw))
        direct = np.asarray(flash_attention(
            q, k, v, None, kw["causal"], None, 512, 512, True))
        np.testing.assert_array_equal(via_registry, direct)

    @pytest.mark.parametrize("name", ["ragged_paged_decode",
                                      "ragged_paged_prefill"])
    def test_pages_per_block_bit_exact(self, name):
        """The pipelined bodies keep the per-page accumulation ORDER
        whatever ``pages_per_block``, so every setting is bit-equal. The
        dense decode body folds a block of pages as one softmax update:
        there every setting is within the contract's tolerance of the
        reference."""
        spec = kernels.get(name)
        args, kw = spec.sample_inputs(1)
        outs = [np.asarray(kernels.dispatch(
            name, *args, impl="pallas_interpret",
            block_sizes={"pages_per_block": pb}, **kw))
            for pb in (1, 2, 4)]
        if name == "ragged_paged_decode":     # (one update a block)
            want = np.asarray(spec.reference_fn(*args, **kw))
            for o in outs:
                np.testing.assert_allclose(o, want, atol=spec.contract.atol,
                                           rtol=spec.contract.rtol)
            return
        for o in outs[1:]:
            np.testing.assert_array_equal(outs[0], o)

    def test_decode_dispatch_equals_private_lax(self):
        from paddle_tpu.serving.decode_attention import _paged_decode_lax
        spec = kernels.get("ragged_paged_decode")
        (q, kp, vp, bt, lens), _ = spec.sample_inputs(0)
        via_registry = np.asarray(kernels.dispatch(
            "ragged_paged_decode", q, kp, vp, bt, lens, impl="lax"))
        direct = np.asarray(_paged_decode_lax(
            q, kp, vp, bt, lens, 1.0 / np.sqrt(q.shape[-1])))
        np.testing.assert_array_equal(via_registry, direct)

    def test_flash_prior_is_the_historic_default(self):
        """The static prior must resolve to the pre-refactor 512/512 so
        auto-dispatched flash is byte-identical to the old hard-coded
        path on every bucket."""
        spec = kernels.get("flash_attention")
        for seed in (0, 1, 2):
            args, kw = spec.sample_inputs(seed)
            assert autotune.static_prior(spec, args, kw) == \
                {"block_q": 512, "block_k": 512}


# ---------------------------------------------------------------------------
# bench artifact
# ---------------------------------------------------------------------------

class TestBenchArtifact:
    def test_committed_bench_kernels_schema(self):
        path = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "BENCH_KERNELS.json")
        with open(path) as f:
            r = json.load(f)
        for k in ("metric", "value", "kernels", "tuner_cache_hits",
                  "committed_cache_entries", "committed_cache_stale"):
            assert k in r, f"BENCH_KERNELS.json missing {k}"
        assert r["committed_cache_stale"] == 0
        assert set(r["kernels"]) == {"flash_attention",
                                     "ragged_paged_decode",
                                     "ragged_paged_prefill"}
        for buckets in r["kernels"].values():
            assert len(buckets) == 3
