"""The selecting latent family's Pallas bodies compiled for the chip, here:
for a ``v5e:2x2`` topology description, which refuses what the interpreter
hides (block shapes off the tiling, VMEM, a layout the compiler re-lays),
at DeepSeek-V3.2's published widths and the long-document cell's geometry
(64 slots, 261 pages of 128, 2048 of 33408 tokens selected). A file of its
own (ROADMAP Design 16): a family's compile cases cost about a minute of a
worker, and ``tests/test_chip_compile.py`` is already the longest file.

Everything that touches ``jax.experimental.topologies`` lives in the
module-scoped fixture below, never at import.
"""

import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest

from paddle_tpu import kernels
from paddle_tpu.kernels import autotune
from paddle_tpu.serving import sparse_attention as SA

S, H, DL, DR, PS, MP, P, K, J, DI, C, G = (64, 128, 512, 64, 128, 261,
                                           2433, 2048, 64, 128, 256, 8)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(desc.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _compiled(fn, one_chip, *args):
    args = tuple(jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
                 for shape, dtype in args)
    return jax.jit(fn).lower(*args).compile()


def _custom_calls(text):
    return sorted(set(re.sub(r"\.\d+$", "", c) for c in re.findall(
        r"%([\w.\-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"", text)))


BF, I32, F32 = jnp.bfloat16, jnp.int32, jnp.float32
POOLS = (((P, PS, DL), BF), ((P, PS, 128), BF))


DECODE = ((((S, H, DL + DR), BF),) + POOLS + (
    ((S, MP), I32), ((S, MP * PS), F32), ((S,), I32), ((S // 2, G), I32),
    ((S // 2,), I32), ((S,), I32)))
PREFILL = ((((2, C, H, DL + DR), BF),) + POOLS + (
    ((2, MP), I32), ((2,), I32), ((2,), I32), ((2, C, MP * PS), F32)))


@pytest.mark.parametrize("name, args", [("sparse_latent_decode", DECODE),
                                        ("sparse_latent_prefill", PREFILL)])
def test_selecting_latent_kernels_compile_and_copy_no_pool(name, args,
                                                           one_chip):
    """Decode, groups of 8 over tables of 261 pages: the two parts that
    walk whole pages of both token-major pools where they lie, compact
    the selected rows in VMEM and fold them are the only custom calls,
    both under the kernel's own name, and no gathered copy is left in the
    program. Prefill, two lanes of a chunk of 256 from the mask: the same
    two parts under ITS name, 64 rows a pair of calls, no gather and no
    copy of a pool (a rotary pool of 64 lanes would be re-laid whole:
    the reason its rows are 128 lanes wide)."""
    spec = kernels.get(name)
    blocks = autotune.static_prior(
        spec, tuple(jax.ShapeDtypeStruct(*a) for a in args), {})
    compiled = _compiled(functools.partial(
        spec.pallas_fn, block_sizes=blocks, interpret=False), one_chip,
        *args)
    text = compiled.as_text()
    assert _custom_calls(text) == [name]
    assert not re.findall(r"= bf16\[%d,%d,\d+\]\S* copy\(" % (P, PS), text)
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30
    assert not re.findall(r" gather\(", text)
    assert f",{K}," not in text


def test_the_decode_of_a_layer_compiles_from_the_mask(one_chip):
    """Indexer, counting mask and the grouped decode as the engine's step
    runs them: three kernels' custom calls, no sort, no scatter and no
    gather in the program."""
    text = _compiled(
        lambda q, c, r, ik, bt, n, qi, wi, *groups:
        SA.latent_indexed_decode_attention(
            q, c, r, ik, bt, n, qi, wi, K, groups=groups, impl="pallas")[0],
        one_chip, ((S, H, DL + DR), BF), *POOLS, ((P, DI, PS), BF),
        ((S, MP), I32), ((S,), I32), ((S, J, DI), BF), ((S, J), F32),
        ((S // 2, G), I32), ((S // 2,), I32), ((S,), I32)).as_text()
    assert _custom_calls(text) == ["lightning_indexer",
                                   "sparse_latent_decode",
                                   "topk_selection_mask"]
    assert not re.findall(r" sort\(| scatter\(| gather\(", text)


def test_the_prefill_of_a_layer_compiles_from_the_mask(one_chip):
    """The same for a prefill call of two lanes of 256 over 33,408 rows:
    the indexer, the mask 64 rows at a time and the two parts under the
    prefill's name; no positions are read out of the mask and no row is
    gathered (no sort, scatter or gather), and no pool is copied."""
    text = _compiled(
        lambda q, c, r, ik, bt, st, nv, qi, wi:
        SA.latent_indexed_prefill_attention(
            q, c, r, ik, bt, st, nv, qi, wi, K, impl="pallas"),
        one_chip, ((2, C, H, DL + DR), BF), *POOLS, ((P, DI, PS), BF),
        ((2, MP), I32), ((2,), I32), ((2,), I32), ((2, C, J, DI), BF),
        ((2, C, J), F32)).as_text()
    assert _custom_calls(text) == ["lightning_indexer",
                                   "sparse_latent_prefill",
                                   "topk_selection_mask"]
    assert not re.findall(r" sort\(| scatter\(| gather\(", text)
    assert not re.findall(r"= bf16\[%d,%d,\d+\]\S* copy\(" % (P, PS), text)


@pytest.mark.parametrize("chunk", [1, C], ids=["decode", "chunk_of_256"])
def test_the_indexer_compiles_at_64_heads_of_128(chunk, one_chip):
    """One query a slot against ONE block-diagonal; a chunk of 256 queries
    x 64 heads summed 8 queries at a time (one block-diagonal for all of
    them would be a 16.8 MB float32 operand a lane); a table of 261 pages,
    no multiple of the page block, padded with the null page."""
    lanes = S if chunk == 1 else 8
    text = _compiled(
        lambda *a: SA.lightning_index_scores(*a, impl="pallas"), one_chip,
        ((lanes, chunk, J, DI), BF), ((lanes, chunk, J), F32),
        ((P, DI, PS), BF), ((lanes, MP), I32), ((lanes,), I32)).as_text()
    assert _custom_calls(text) == ["lightning_indexer"]
    assert f"f32[{lanes},{chunk},{MP * PS}]" in text


def test_the_selection_compiles_over_rows_of_33408(one_chip):
    """The counting mask over a slot's whole table (261 chunks of 128
    lanes a pass), what both attention kernels take: no sort and no
    scatter in the program."""
    text = _compiled(
        lambda a, n: SA.select_decode_mask(a, n, K, impl="pallas"), one_chip,
        ((S, MP * PS), F32), ((S,), I32)).as_text()
    assert _custom_calls(text) == ["topk_selection_mask"]
    assert not re.findall(r" sort\(| scatter\(", text)
