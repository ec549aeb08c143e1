"""The gated delta-rule family's Pallas bodies compiled for the chip, here:
for a ``v5e:2x2`` topology description, which refuses what the interpreter
hides (block shapes off the tiling, VMEM), at Qwen3-Next's published widths
and the long-answer cell's geometry (256 slots, 5121 pages of 128, chunks
of 128): the two delta-rule kernels, and the dense paged kernels at the full
layers' heads of 256. A file of its own (ROADMAP Design 16): a family's
compile cases cost about half a minute of a worker, and
``tests/test_chip_compile.py`` is already the longest file.

Everything that touches ``jax.experimental.topologies`` lives in the
module-scoped fixture below, never at import.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest

from paddle_tpu import kernels
from paddle_tpu.kernels import autotune

QN_SLOTS, QN_PS, QN_PAGES, QN_CHUNK = 256, 128, 5121, 128
QN_HK, QN_HV, QN_DK, QN_DV = 16, 32, 128, 128


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


def _compile_kernel(name, args, one_chip):
    """Compile kernel ``name``'s Pallas body for the described chip at
    the static prior's block sizes."""
    spec = kernels.get(name)
    blocks = autotune.static_prior(spec, args, {})
    args = tuple(jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
                 for a in args)
    body = functools.partial(spec.pallas_fn, block_sizes=blocks,
                             interpret=False)
    assert "tpu_custom_call" in jax.jit(body).lower(*args).compile().as_text()


@pytest.mark.parametrize("name, lanes, chunk", [
    ("gated_delta_chunk_scan", 4, QN_CHUNK), ("gated_delta_chunk_scan", 1, 64),
    ("gated_delta_decode_update", QN_SLOTS, None)],
    ids=["scan-4lanes-2tiles", "scan-1lane-1tile", "decode-256slots"])
def test_gated_delta_kernel_compiles_for_v5e(name, lanes, chunk, one_chip):
    """The two delta-rule kernels at the published tile sizes: 16 key and
    32 value heads, state tiles of (128, 128) float32, a pool of 257
    rows; tiles of 64 tokens (blocks of 64 lanes where a tile's tokens
    lie along them), all 32 tiles of a slot in one decode grid step."""
    sds = jax.ShapeDtypeStruct
    tok = (lanes,) if chunk is None else (lanes, chunk)
    args = (sds(tok + (QN_HK, QN_DK), jnp.float32),
            sds(tok + (QN_HK, QN_DK), jnp.float32),
            sds(tok + (QN_HV, QN_DV), jnp.float32),
            sds(tok + (QN_HV,), jnp.float32),
            sds(tok + (QN_HV,), jnp.float32),
            sds((QN_SLOTS + 1, QN_HV, QN_DK, QN_DV), jnp.float32),
            sds((lanes,), jnp.int32))
    if chunk is not None:
        args += (sds((lanes,), jnp.int32),)
    _compile_kernel(name, args, one_chip)


@pytest.mark.parametrize("name", ["ragged_paged_decode",
                                  "ragged_paged_prefill"])
def test_dense_paged_kernels_compile_at_heads_of_256(name, one_chip):
    """The full layer's pool: 2 KV heads of 256 lanes, 16 query heads in
    groups of 8, pages of 128 tokens, a slot's whole table of 40 pages
    (the widest head these kernels had met was 192 for keys)."""
    sds = jax.ShapeDtypeStruct
    chunked = "prefill" in name
    lanes = 32 if chunked else QN_SLOTS
    q = sds((lanes, QN_CHUNK, 16, 256) if chunked else (lanes, 16, 256),
            jnp.bfloat16)
    pool = sds((QN_PAGES, QN_PS, 2 * 256), jnp.bfloat16)
    i32 = sds((lanes,), jnp.int32)
    _compile_kernel(name, (q, pool, pool, sds((lanes, 40), jnp.int32), i32)
                    + ((i32,) if chunked else ()), one_chip)
