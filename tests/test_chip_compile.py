"""Compile the main path's Pallas kernels for a DESCRIBED TPU v5e.

No chip is attached here: the installed TPU compiler lowers and compiles
for a `v5e:2x2` topology description, which refuses what the Pallas
interpreter never sees (block shapes off the (8, 128) tiling, too much
VMEM, a kernel that cannot be partitioned). Nothing runs, so these
tests say nothing about results or times — ``chip_smoke.py`` does that
on the chip.

Everything that touches ``jax.experimental.topologies`` lives in the
module-scoped fixture below, never at import: only the one xdist worker
that is handed this file loads the TPU library, and every worker
collects the same tests. The kernels are compiled directly (not through
``kernels.dispatch``), with the block sizes the tuner's static prior
picks for these shapes — the tuner's cache key asks ``jax.devices()``
for the device kind, which is the CPU here.
"""

import contextlib
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest

from paddle_tpu import kernels
from paddle_tpu.kernels import autotune

# GPT-2 small serving widths (GPTConfig() defaults) at the smoke's geometry
S, H, DH, PS, MP, P, C = 32, 12, 64, 16, 8, 257, 32
# BERT-base training shapes (BertConfig.base(), batch 48 x seq 512)
B, BERT_H, SEQ, BERT_DH = 48, 12, 512, 64


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _compile_kernel(name, args, one_chip, fn=None, **kwargs):
    """Compile kernel ``name``'s Pallas body for the described chip at
    the static prior's block sizes; return the compiled executable."""
    spec = kernels.get(name)
    blocks = autotune.static_prior(spec, args, kwargs)
    assert all(blocks[b] in cands for b, cands in
               spec.contract.block_candidates.items())
    args = tuple(jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
                 if a is not None else None for a in args)
    body = functools.partial(spec.pallas_fn, block_sizes=blocks,
                             interpret=False, **kwargs)
    compiled = jax.jit(fn(body) if fn else body).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _paged_args(name, page_dtype, slots=S, max_pages=MP, num_pages=P):
    sds = jax.ShapeDtypeStruct
    chunked = "prefill" in name
    quantized = name.endswith("int8")
    q_dtype = jnp.float32 if quantized else page_dtype
    q = sds((slots, C, H, DH) if chunked else (slots, H, DH), q_dtype)
    pages = sds((num_pages, PS, H * DH),
                jnp.int8 if quantized else page_dtype)
    scales = (sds((num_pages, PS), jnp.float32),) * 2 if quantized else ()
    i32 = sds((slots,), jnp.int32)
    geometry = (i32, i32) if chunked else (i32,)
    return (q, pages, pages, *scales,
            sds((slots, max_pages), jnp.int32), *geometry)


def _decode_cell_args(slots, heads, kv_heads, dh, width):
    """The decode call of a benchmark cell: bf16 queries and pool,
    pages of 128, ``width`` pages a slot."""
    sds = jax.ShapeDtypeStruct
    pages = sds((slots * width + 1, 128, kv_heads * dh), jnp.bfloat16)
    return (sds((slots, heads, dh), jnp.bfloat16), pages, pages,
            sds((slots, width), jnp.int32), sds((slots,), jnp.int32))


# the dense decode call at the benchmark's geometries: the backlog
# cell's 64 slots of 12 heads x 64 at each table width it warms, the
# reasoning cell's 256 slots of 8 query heads over 2 KV heads x 128, the
# hybrid cell's 64 slots of 20 over 4 x 128 (groups of 5), and the docs
# cell's heads (32 over 4 x 128 on the 16 pages of its widest bucket
# that does not select; past it that cell's decode call is
# `sparse_paged_decode`, compiled with its whole step below)
_DECODE_CELLS = {
    **{f"backlog-w{w}": _decode_cell_args(64, 12, 12, 64, w)
       for w in (1, 2, 4, 8)},
    "docs-w16": _decode_cell_args(32, 32, 4, 128, 16),
    **{f"reasoning-w{w}": _decode_cell_args(256, 8, 2, 128, w)
       for w in (8, 16, 24)},
    "hybrid-w16": _decode_cell_args(64, 20, 4, 128, 16)}


@pytest.mark.parametrize("name, args", [
    pytest.param(name, _paged_args(name, dtype), id=f"{name}-{tag}")
    for name in ("ragged_paged_decode", "ragged_paged_prefill")
    for tag, dtype in (("bf16", jnp.bfloat16), ("f32", jnp.float32))] + [
    pytest.param("ragged_paged_decode", args, id=cell)
    for cell, args in _DECODE_CELLS.items()])
def test_paged_kernel_compiles_for_v5e(name, args, one_chip):
    """Under the default scoped-VMEM limit where the call asks for none
    (decode never does)."""
    text = _compile_kernel(name, args, one_chip).as_text()
    if "decode" in name:       # a `vmem_limit_bytes` is listed here
        assert '"scoped_memory_configs":[],"custom_call_config"' in text


def _wide_key_args(chunked, kv_heads, lanes, width, pool_pages):
    """A call of the long-prompt cell (MiMo-V2-Flash's widths): 64 query
    heads of 192 over ``kv_heads`` KV heads, a K pool of ``kv_heads *
    192`` lanes beside a V pool of ``kv_heads * 128``, pages of 128."""
    sds = jax.ShapeDtypeStruct
    bf, i32 = jnp.bfloat16, jnp.int32
    q = sds((lanes, 128, 64, 192) if chunked else (lanes, 64, 192), bf)
    geometry = (sds((lanes,), i32),) * (2 if chunked else 1)
    return (q, sds((pool_pages, 128, kv_heads * 192), bf),
            sds((pool_pages, 128, kv_heads * 128), bf),
            sds((lanes, width), i32), *geometry)


# a full layer's pool (4 KV heads: K 768 lanes, V 512, groups of 16) at the
# 64 pages of the widest bucket but one, and a window layer's ring (8 KV
# heads: K 1536, V 1024, window 128, a sink a head; 2 ring pages a decode
# and 3 a prefill call)
@pytest.mark.parametrize("name, args, window", [
    pytest.param("ragged_paged_decode",
                 _wide_key_args(False, 4, 64, 64, 4609), None,
                 id="decode-full"),
    pytest.param("ragged_paged_decode",
                 _wide_key_args(False, 8, 64, 2, 129), 128,
                 id="decode-window-sink"),
    pytest.param("ragged_paged_prefill",
                 _wide_key_args(True, 4, 8, 64, 4609), None,
                 id="prefill-full"),
    pytest.param("ragged_paged_prefill",
                 _wide_key_args(True, 8, 8, 3, 129), 128,
                 id="prefill-window-sink")])
def test_paged_kernels_with_keys_wider_than_values_compile_for_v5e(
        name, args, window, one_chip):
    """192-wide keys are one and a half lane tiles: the prefill body's
    group fold loads two KV heads' K lanes (384) at a tile boundary and
    slices a head out of what it loaded, which the interpreter never
    questions and the chip's compiler has to accept."""
    if window is None:
        _compile_kernel(name, args, one_chip)
        return
    spec = kernels.get(name)
    sinks = jax.ShapeDtypeStruct((64,), jnp.float32, sharding=one_chip)
    blocks = autotune.static_prior(spec, args, {"window": window,
                                                "sinks": sinks})
    args = tuple(jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
                 for a in args)
    compiled = jax.jit(lambda *a: spec.pallas_fn(
        *a[:-1], sinks=a[-1], window=window, block_sizes=blocks,
        interpret=False)).lower(*args, sinks).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _eqns(jaxpr):
    """Every equation under ``jaxpr``, those of the kernels' bodies and of
    their loops and branches among them."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


def _float32_work_on_bf16_pages(jaxpr):
    """What a chunked-prefill call traced on a bf16 pool may not hold: a
    ``dot_general`` with an operand that is not bf16, and a page block (a
    load from a 3-D bf16 ref, sliced or not) cast to float32."""
    found, producers = [], {}
    for eqn in _eqns(jaxpr):
        for out in eqn.outvars:
            producers[out] = eqn
    for eqn in _eqns(jaxpr):
        name = eqn.primitive.name
        if name == "dot_general":
            dtypes = [str(v.aval.dtype) for v in eqn.invars]
            if dtypes != ["bfloat16", "bfloat16"]:
                found.append(("dot_general", dtypes))
        elif name == "convert_element_type" \
                and eqn.params["new_dtype"] == jnp.float32:
            src = producers.get(eqn.invars[0])
            while src is not None and src.primitive.name in (
                    "slice", "reshape", "squeeze"):
                src = producers.get(src.invars[0])
            if src is not None and src.primitive.name == "get":
                ref = src.invars[0].aval
                if ref.dtype == jnp.bfloat16 and len(ref.shape) == 3:
                    found.append(("page block to float32", ref.shape))
    return found


def _docs_prefill_args():
    """The docs cell's chunked-prefill call under a selection: 4 lanes of
    64 queries, 32 heads over 4 KV heads of 128, tables of 128 pages."""
    sds = jax.ShapeDtypeStruct
    bf, i32 = jnp.bfloat16, jnp.int32
    pages = sds((1280, 128, 4 * 128), bf)
    return (sds((4, 64, 32, 128), bf), pages, pages, sds((4, 128), i32),
            sds((4,), i32), sds((4,), i32),
            sds((4, 64, 128 * 128), jnp.float32))


def _plain_prefill_args(lanes, chunk, heads, head_dim, width, pool_pages,
                        pool_dtype):
    """A chunked-prefill call of one query head a KV head (GPT-2's
    widths: 12 heads of 64, a chunk of 32) over pages of 128."""
    sds = jax.ShapeDtypeStruct
    pages = sds((pool_pages, 128, heads * head_dim), pool_dtype)
    return (sds((lanes, chunk, heads, head_dim), pool_dtype), pages, pages,
            sds((lanes, width), jnp.int32), sds((lanes,), jnp.int32),
            sds((lanes,), jnp.int32))


def _prefill_call(name, args, window):
    """``(call, operands)``: kernel ``name``'s Pallas entry at the static
    prior's blocks, a window with a sink a head where ``window`` is set."""
    spec = kernels.get(name)
    kw = {} if window is None else {
        "window": window, "sinks": jax.ShapeDtypeStruct((64,), jnp.float32)}
    blocks = autotune.static_prior(spec, args, kw)
    sinks = kw.pop("sinks", None)

    def call(*a):
        extra = {} if sinks is None else {"sinks": a[-1]}
        return spec.pallas_fn(*a[:len(args)], **kw, **extra,
                              block_sizes=blocks, interpret=False)

    return call, args if sinks is None else (*args, sinks)


def _trace_prefill(name, args, window):
    """The call's jaxpr, its body traced anew (the jitted call keeps the
    jaxpr of shapes it has met), and what the trace added to
    ``paged_prefill_lowerings_total``, by label set."""
    from paddle_tpu.observability import registry as obs
    from paddle_tpu.serving import decode_attention as DA
    counter = obs.counter("paged_prefill_lowerings_total")

    def counts():
        return {lb: counter.value(**dict(lb)) for lb in counter.labels_seen()}

    call, operands = _prefill_call(name, args, window)
    DA._paged_attend_pallas.clear_cache()
    before = counts()
    jaxpr = jax.make_jaxpr(call)(*operands).jaxpr
    added = {lb: n - before.get(lb, 0) for lb, n in counts().items()
             if n != before.get(lb, 0)}
    return jaxpr, added


# the long-prompt cell's two prefill calls (the group fold, spans of two
# 192-lane heads), the docs cell's (the per-head fold under a selection)
# and GPT-2's (a chunk of 32 rows: three terms fit the MXU's rows stacked)
_BF16_PREFILL_CALLS = [
    pytest.param("ragged_paged_prefill",
                 _wide_key_args(True, 4, 8, 64, 4609), None,
                 ("group", "each"), id="long-full"),
    pytest.param("ragged_paged_prefill",
                 _wide_key_args(True, 8, 8, 3, 129), 128,
                 ("group", "each"), id="long-window-sink"),
    pytest.param("sparse_paged_prefill", _docs_prefill_args(), None,
                 ("head", "each"), id="docs-selected"),
    pytest.param("ragged_paged_prefill",
                 _plain_prefill_args(8, 32, 12, 64, 8, 513, jnp.bfloat16),
                 None, ("head", "stacked"), id="gpt2")]


@pytest.mark.parametrize("name, args, window, form", _BF16_PREFILL_CALLS)
def test_prefill_folds_take_a_bf16_pool_as_it_is_stored(name, args, window,
                                                        form):
    """Traced on a bf16 pool the body holds no product with a float32
    operand (the softmax weights go in as bf16 terms) and casts no page
    block to float32, and the trace is counted once, under the form the
    call's shapes decide. No chip is described: the jaxpr is enough."""
    jaxpr, added = _trace_prefill(name, args, window)
    assert sum(e.primitive.name == "dot_general"
               for e in _eqns(jaxpr)) >= 2
    assert _float32_work_on_bf16_pages(jaxpr) == []
    fold, terms = form
    assert added == {(("fold", fold), ("operands", "stored"),
                      ("terms", terms)): 1}


def test_a_float32_pool_is_counted_as_float32_and_the_detector_sees_it(
        monkeypatch):
    """The CPU tests' pools multiply at ``HIGHEST`` and are counted so;
    and the detector flags the body this kernel had before PR 51 (every
    operand cast to float32 for a ``HIGHEST`` product) on a bf16 pool."""
    from paddle_tpu.serving import decode_attention as DA
    args = _plain_prefill_args(2, 32, 12, 64, 8, 17, jnp.float32)
    jaxpr, added = _trace_prefill("ragged_paged_prefill", args, None)
    assert added == {(("fold", "head"), ("operands", "float32"),
                      ("terms", "stacked")): 1}
    assert all(str(v.aval.dtype) == "float32" for e in _eqns(jaxpr)
               if e.primitive.name == "dot_general" for v in e.invars)

    def float32_product(x, page, contract_page_dim, stack=True):
        return jax.lax.dot_general(
            x.astype(jnp.float32), page.astype(jnp.float32),
            (((1,), (contract_page_dim,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)

    monkeypatch.setattr(DA, "_exact_page_dot", float32_product)
    try:
        jaxpr, _ = _trace_prefill(*_BF16_PREFILL_CALLS[3].values[:3])
    finally:
        DA._paged_attend_pallas.clear_cache()
    kinds = {kind for kind, _ in _float32_work_on_bf16_pages(jaxpr)}
    assert kinds == {"dot_general", "page block to float32"}


def test_the_docs_cells_selected_prefill_compiles_for_v5e(one_chip):
    """The per-head fold under a selection with its operands as they are
    stored (the long-prompt cell's two calls compile above)."""
    call, operands = _prefill_call("sparse_paged_prefill",
                                   _docs_prefill_args(), None)
    operands = tuple(jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
                     for a in operands)
    compiled = jax.jit(call).lower(*operands).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("pool", [{}, dict(slots=1, max_pages=4,
                                           num_pages=5)],
                         ids=["P257", "P5"])
@pytest.mark.parametrize("name", ["ragged_paged_decode_int8",
                                  "ragged_paged_prefill_int8"])
def test_paged_int8_kernel_compiles_for_v5e(name, pool, one_chip):
    """P5: a pool smaller than one 8-row group of scale rows."""
    _compile_kernel(name, _paged_args(name, jnp.int8, **pool), one_chip)


# the serving cell's geometry (benchmark/configs/gpt2_small.json: 64 slots
# x 1024 tokens in pages of 128, prefill chunk 32, decode block 8)
CELL_SLOTS, CELL_PS, CELL_PAGES, CELL_CHUNK = 64, 128, 513, 32
_POOL_TEMP_BOUND = 128 << 20


def _cell_engine(variant):
    """A ServingEngine at the cell's widths whose jitted steps are only
    ever lowered: bf16 is the cell itself (whole GPT-2 small, abstract
    weights), int8 and tp2 are two layers of it (what is asserted is
    per layer). The engine's own pool is nine pages; the steps are
    lowered on a 513-page pool of the same page shape."""
    from paddle_tpu import inference
    from paddle_tpu.models.gpt import GPT, GPTConfig
    kw = dict(num_slots=CELL_SLOTS, page_size=CELL_PS, num_pages=9,
              max_tokens_per_slot=1024, prefill_chunk=CELL_CHUNK,
              decode_block=8, attn_impl="pallas",
              cache_dtype=jnp.int8 if variant == "int8" else jnp.bfloat16)
    if variant == "tp2":
        # the sharded engine re-lays its weights out and places them on
        # its mesh, so they are real here: two layers, a small vocabulary
        model = GPT(GPTConfig(num_layers=2, vocab_size=512))
        params = jax.tree_util.tree_map(
            lambda x: x.astype(jnp.bfloat16),
            model.init(jax.random.PRNGKey(0)))
        return inference.make_serving_engine(model, params, tp=2, **kw)
    model = GPT(GPTConfig(num_layers=12 if variant == "bf16" else 2))
    params = jax.eval_shape(
        lambda k: jax.tree_util.tree_map(
            lambda x: x.astype(jnp.bfloat16), model.init(k)),
        jax.random.PRNGKey(0))
    return inference.make_serving_engine(model, params, **kw)


@pytest.fixture(scope="module", params=["bf16", "int8", "tp2"])
def cell_steps(request, topo):
    return _cell_steps(request.param, topo)


def _cell_steps(variant, topo):
    """(variant, lower(step, lanes, width) -> compiled) for one engine:
    its decode and prefill steps compiled for the described chip — for
    tp2 the same step bodies under ``shard_map`` over two of the
    described chips, arguments sharded as the engine shards them."""
    from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding
    eng = _cell_engine(variant)
    steps = {"decode": eng.decode_step, "prefill": eng.prefill_step}
    if variant == "tp2":
        from paddle_tpu.core.compat import shard_map
        from paddle_tpu.core.mesh import MeshConfig, make_mesh
        mesh = make_mesh(MeshConfig(tp=2), devices=topo.devices[:2])
        rep = PartitionSpec()
        specs = (eng._param_specs, eng._page_specs, rep, rep, rep, rep)
        steps = {
            name: jax.jit(shard_map(
                impl, mesh=mesh, in_specs=specs,
                out_specs=(rep, eng._page_specs), check_vma=False),
                donate_argnums=(1,))
            for name, impl in (("decode", eng._decode_step_impl),
                               ("prefill", eng._prefill_step_impl))}

        def place(spec):
            return NamedSharding(mesh, spec)
        param_s = jax.tree_util.tree_map(
            place, eng._param_specs,
            is_leaf=lambda x: isinstance(x, PartitionSpec))
        page_s = jax.tree_util.tree_map(
            place, eng._page_specs,
            is_leaf=lambda x: isinstance(x, PartitionSpec))
        rep_s = place(rep)
    else:
        rep_s = SingleDeviceSharding(topo.devices[0])
        param_s = jax.tree_util.tree_map(lambda _: rep_s, eng._step_params)
        page_s = jax.tree_util.tree_map(lambda _: rep_s, eng.cache.pages)

    sds = jax.ShapeDtypeStruct
    params = jax.tree_util.tree_map(
        lambda a, sh: sds(a.shape, a.dtype, sharding=sh),
        eng._step_params, param_s)
    pages = jax.tree_util.tree_map(
        lambda a, sh: sds((CELL_PAGES,) + a.shape[1:], a.dtype,
                          sharding=sh),
        eng.cache.pages, page_s)

    def i32(*shape):
        return sds(shape, jnp.int32, sharding=rep_s)

    def lower(step, lanes, width):
        if step == "decode":       # block tables, lengths, tokens, active
            args = (i32(lanes, width), i32(lanes), i32(lanes), i32(lanes))
        else:                      # block tables, starts, tokens, n_valid
            args = (i32(lanes, width), i32(lanes),
                    i32(lanes, CELL_CHUNK), i32(lanes))
        return steps[step].lower(params, pages, *args).compile()

    return variant, lower


@pytest.mark.parametrize("step, lanes, width", [
    ("decode", CELL_SLOTS, 1), ("decode", CELL_SLOTS, 8),
    ("prefill", 1, 1), ("prefill", 32, 8)],
    ids=["decode-w1", "decode-w8", "prefill-1lane-w1", "prefill-32lanes-w8"])
def test_serving_step_keeps_the_pool_where_it_lies(step, lanes, width,
                                                   cell_steps):
    """The page pool is stored the way the paged kernels read it, so a
    compiled serving step (a) copies no pool-shaped array, (b) takes the
    pool in as a row-major entry parameter, (c) needs temporaries far
    under one pool array (a relayout of a 4-D pool took 2.67x the pool,
    in and out of every call)."""
    import re
    variant, lower = cell_steps
    compiled = lower(step, lanes, width)
    text = compiled.as_text()
    lanes_wide = H * DH // (2 if variant == "tp2" else 1)
    pool = (f"{'s8' if variant == 'int8' else 'bf16'}"
            f"[{CELL_PAGES},{CELL_PS},{lanes_wide}]")
    assert "tpu_custom_call" in text
    copies = [line for line in text.splitlines()
              if re.search(r"= " + re.escape(pool) + r"\S* copy\(", line)]
    assert not copies, copies[:2]
    entry = text[text.index("ENTRY "):]
    layouts = set(re.findall(
        re.escape(pool) + r"(\{[\d,]+)[^ ]* parameter\(", entry))
    assert layouts == {"{2,1,0"}, layouts
    assert compiled.memory_analysis().temp_size_in_bytes < _POOL_TEMP_BOUND


def _custom_calls(text):
    """Names of the Pallas custom calls of a compiled program, without
    their number."""
    return sorted(re.sub(r"\.\d+$", "", c) for c in re.findall(
        r"%([\w.\-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"", text))


def _flash_args(dtype, key_bias):
    sds = jax.ShapeDtypeStruct
    qkv = sds((B, BERT_H, SEQ, BERT_DH), dtype)
    bias = sds((B, 1, 1, SEQ), jnp.float32) if key_bias else None
    return (qkv, qkv, qkv, bias)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("variant", ["key_bias", "causal"])
def test_flash_forward_compiles_for_v5e(variant, dtype, one_chip):
    key_bias = variant == "key_bias"
    _compile_kernel("flash_attention", _flash_args(dtype, key_bias),
                    one_chip, causal=not key_bias)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("variant", ["key_bias", "causal"])
def test_flash_backward_compiles_for_v5e(variant, dtype, one_chip):
    key_bias = variant == "key_bias"

    def grads(body):
        def loss(q, k, v, bias):
            return jnp.sum(body(q, k, v, bias).astype(jnp.float32))
        return jax.grad(loss, argnums=(0, 1, 2))

    compiled = _compile_kernel("flash_attention",
                               _flash_args(dtype, key_bias), one_chip,
                               fn=grads, causal=not key_bias)
    # forward (residuals) + the ONE backward kernel
    assert len(_custom_calls(compiled.as_text())) == 2


def test_flash_kernels_are_named_after_the_innermost_scope(one_chip):
    """The flash ``pallas_call``s carry no ``name=``, and XLA names a
    Pallas custom call after the innermost entry of JAX's name stack:
    under ``build_train_step``'s ``forward`` scope the forward kernel is
    ``jvp_forward_.N`` and the one backward kernel
    ``transpose_jvp_forward__.N``. Readers of a device trace find the
    kernels by these names, so a scope opened between the train step and
    the kernel (one per module, say) renames both after itself."""
    def compiled_names(*scopes):
        def grads(body):
            def loss(q, k, v, bias):
                with contextlib.ExitStack() as stack:
                    for name in scopes:
                        stack.enter_context(jax.named_scope(name))
                    return jnp.sum(body(q, k, v, bias).astype(jnp.float32))
            return jax.grad(loss, argnums=(0, 1, 2))
        return _custom_calls(_compile_kernel(
            "flash_attention", _flash_args(jnp.bfloat16, True), one_chip,
            fn=grads, causal=False).as_text())

    assert compiled_names("forward") == [
        "jvp_forward_", "transpose_jvp_forward__"]
    assert compiled_names("forward", "attn") == ["attn"] * 2


@pytest.mark.parametrize("mesh_axes, model_kw, flash_calls", [
    # forward + the one backward kernel, in each of the two layers
    pytest.param(dict(dp=2, tp=2), {}, 4, id="dp2xtp2"),
    # a stage's layer is one scanned body: its forward, the forward
    # again where the backward rematerialises it, and the one backward
    pytest.param(dict(dp=2, pp=2), dict(pipeline=True, pp_microbatches=2,
                                        stacked_layers=False), 3,
                 id="inside-pipeline-stage"),
])
def test_flash_under_a_mesh_compiles_for_v5e(mesh_axes, model_kw,
                                             flash_calls, topo):
    """The partitioner refuses a Mosaic kernel ("cannot be automatically
    partitioned"), and the interpreter never meets the partitioner: a
    BERT loss+grad with the compiled flash kernel under a mesh of the
    described chips — per shard in a shard_map, or inside a pipeline
    stage body that already is one."""
    from jax.sharding import NamedSharding, PartitionSpec
    from paddle_tpu.core.mesh import (BATCH_AXES, MeshConfig, make_mesh,
                                      mesh_context)
    from paddle_tpu.models.bert import BertConfig, BertForPretraining

    mesh = make_mesh(MeshConfig(**mesh_axes), devices=topo.devices)
    model = BertForPretraining(BertConfig.tiny(
        vocab_size=512, hidden_size=256, num_layers=2, num_heads=4,
        ffn_size=512, max_position=128, dropout=0.0, attn_dropout=0.0,
        attn_impl="flash", **model_kw))
    b, s = 8, 128
    replicated = NamedSharding(mesh, PartitionSpec())
    by_batch = NamedSharding(mesh, PartitionSpec(BATCH_AXES))
    params = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                       sharding=replicated),
        jax.eval_shape(model.init, jax.random.PRNGKey(0)))

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=by_batch)

    batch = dict(input_ids=sds((b, s), jnp.int32),
                 token_type_ids=sds((b, s), jnp.int32),
                 attention_mask=sds((b, s), jnp.bool_),
                 mlm_labels=sds((b, s), jnp.int32),
                 mlm_mask=sds((b, s), jnp.float32),
                 nsp_labels=sds((b,), jnp.int32))

    def loss(p, batch):
        return model.loss(p, training=True, **batch)[0]

    with mesh_context(mesh):
        compiled = jax.jit(jax.value_and_grad(loss)).lower(
            params, batch).compile()
    assert len(_custom_calls(compiled.as_text())) == flash_calls


# the long-document cell's geometry (benchmark/configs/
# keye_vl2_30b_a3b.json): 32 slots x 16384 tokens in pages of 128, prefill
# chunk 64 at 4 lanes, decode block 8, a pool of 1280 pages; published
# widths, all 128 experts, the whole vocabulary, TWO of the six layers
# (what is asserted is per layer, and two layers compile in a third of
# the time)
DOC_SLOTS, DOC_PS, DOC_PAGES, DOC_CHUNK, DOC_LAYERS = 32, 128, 1280, 64, 2
#: what a step may keep beside the pools and the weights: the widest
#: needs 28 MB (the decode block at width 128: index scores, the
#: selection as a mask, the groups' states), where the gathered copies of
#: the selected rows made it 134 MB more
DOC_TEMP = 64 << 20


@pytest.fixture(scope="module")
def doc_steps(topo):
    """lower(step, lanes, width) -> compiled, for the sparse-attention /
    sparse-expert family at its published widths: abstract bf16 weights,
    an engine with a nine-page pool, the steps lowered on the cell's
    pool of shapes."""
    from jax.sharding import SingleDeviceSharding
    from paddle_tpu import inference
    from paddle_tpu.models.sparse_moe_lm import (SparseMoELM,
                                                 SparseMoELMConfig)
    model = SparseMoELM(SparseMoELMConfig(num_hidden_layers=DOC_LAYERS,
                                          kernel_impl="pallas"))
    params = jax.eval_shape(lambda k: model.init(k, dtype=jnp.bfloat16),
                            jax.random.PRNGKey(0))
    eng = inference.make_serving_engine(
        model, params, num_slots=DOC_SLOTS, page_size=DOC_PS, num_pages=9,
        max_tokens_per_slot=16384, prefill_chunk=DOC_CHUNK, decode_block=8,
        attn_impl="pallas", cache_dtype=jnp.bfloat16)
    dev = SingleDeviceSharding(topo.devices[0])
    sds = jax.ShapeDtypeStruct
    weights = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype, sharding=dev), params)
    pages = jax.tree_util.tree_map(
        lambda a: sds((DOC_PAGES,) + a.shape[1:], a.dtype, sharding=dev),
        eng.cache.pages)

    def i32(*shape):
        return sds(shape, jnp.int32, sharding=dev)

    # the groups of slots whose tables open with the same pages, as the
    # engine hands them to every decode block
    groups = tuple(i32(*a.shape) for a in eng._shared_groups([])[0])

    @functools.lru_cache(maxsize=None)
    def lower(step, lanes, width):
        if step == "decode":
            return eng.decode_step.lower(
                weights, pages, i32(lanes, width), i32(lanes), i32(lanes),
                i32(lanes), groups).compile()
        return eng.prefill_step.lower(
            weights, pages, i32(lanes, width), i32(lanes),
            i32(lanes, DOC_CHUNK), i32(lanes)).compile()

    return lower


@pytest.mark.parametrize("rows", [DOC_SLOTS, 4 * DOC_CHUNK],
                         ids=["decode-32rows", "prefill-4lanes-256rows"])
def test_selection_kernel_compiles_for_v5e(rows, one_chip):
    """``topk_selection_mask`` at the docs cell's shapes, 2,048 of
    16,384 a row for a decode call's 32 rows and a prefill call's 4 x 64,
    at the static prior's block: rows of whole 16,384 scores, their keys
    and the mask resident under the default scoped-VMEM limit."""
    sds = jax.ShapeDtypeStruct
    args = (sds((rows, 128 * DOC_PS), jnp.float32), sds((rows,), jnp.int32))
    text = _compile_kernel("topk_selection_mask", args, one_chip,
                           topk=2048).as_text()
    assert _custom_calls(text) == ["topk_selection_mask"]
    assert '"scoped_memory_configs":[],"custom_call_config"' in text


@pytest.mark.parametrize("step, lanes, width, kernels_in", [
    ("decode", DOC_SLOTS, 128,
     {"lightning_indexer", "topk_selection_mask", "sparse_paged_decode",
      "moe_grouped_ffn"}),
    ("prefill", 4, 128,
     {"lightning_indexer", "topk_selection_mask", "sparse_paged_prefill",
      "moe_grouped_ffn"}),
    ("decode", DOC_SLOTS, 16, {"ragged_paged_decode", "moe_grouped_ffn"}),
    ("prefill", 4, 2, {"ragged_paged_prefill", "moe_grouped_ffn"})],
    ids=["decode-w128-selects", "prefill-4lanes-w128-selects",
         "decode-w16-dense", "prefill-4lanes-w2-dense"])
def test_sparse_family_steps_compile_and_keep_the_pools(
        step, lanes, width, kernels_in, doc_steps):
    """The family's decode block and prefill step compile for the chip
    at the cell's geometry: grouped-query heads in the paged kernels, the
    indexer, the sparse kernels past ``topk`` cached tokens (the dense
    ones up to it), the grouped expert kernel at 128 experts x 768 x
    2048. No step copies a K, V or indexer-key pool; each pool comes in
    row-major; temporaries stay far under one K pool (``DOC_TEMP``)."""
    import re
    compiled = doc_steps(step, lanes, width)
    text = compiled.as_text()
    names = {re.sub(r"\.\d+$", "", n) for n in re.findall(
        r"%([\w.\-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"", text)}
    assert names == kernels_in
    # the selection is counted, never sorted: the sorts left are the
    # expert layer's (the router's 8 of 128, the pairs by expert)
    sorts = [line for line in text.splitlines()
             if re.search(r" (?:sort|topk)\(|custom_call_target=\"TopK\"",
                          line)]
    assert sorts and all("/ffn/" in line for line in sorts), sorts[:2]
    pool = rf"bf16\[{DOC_PAGES},(?:{DOC_PS},512|64,{DOC_PS})\]"
    copies = [line for line in text.splitlines()
              if re.search(r"= " + pool + r"\S* copy\(", line)]
    assert not copies, copies[:2]
    entry = text[text.index("ENTRY "):]
    layouts = set(re.findall(pool + r"(\{[\d,]+)[^ ]* parameter\(", entry))
    assert layouts == {"{2,1,0"}, layouts
    assert compiled.memory_analysis().temp_size_in_bytes < DOC_TEMP


def test_sparse_decode_reads_the_pools_themselves(doc_steps):
    """The decode step at the width that selects makes no copy of the
    selected rows: no gather out of a K or V pool (the kernel walks whole
    pages of the pools under the selection), no temporary of gathered
    rows (32 slots x 16 pages of them were 67 MB a pool)."""
    import re
    compiled = doc_steps("decode", DOC_SLOTS, 128)
    text = compiled.as_text()
    assert not re.findall(rf"bf16\[{DOC_SLOTS * 16},{DOC_PS},512\]", text)
    # rows of a pool: 512 lanes of bf16 each
    gathers = [line for line in text.splitlines()
               if re.search(r"= bf16\[[\d,]*512\]\S* gather\(", line)]
    assert not gathers, gathers[:2]


# -- the attention + state-space hybrid at the chat cell's geometry -----------

H1_SLOTS, H1_PS, H1_PAGES, H1_CHUNK, H1_LAYERS = 64, 128, 1025, 128, 2
H1_HEADS, H1_P, H1_GROUPS, H1_STATE = 32, 128, 2, 256


@pytest.mark.parametrize("name, lanes, chunk", [
    ("ssd_chunk_scan", 4, 128), ("ssd_chunk_scan", 1, 256),
    ("ssm_decode_update", H1_SLOTS, None)],
    ids=["scan-4lanes-c128", "scan-1lane-2tiles", "decode-64slots"])
def test_state_space_kernel_compiles_for_v5e(name, lanes, chunk, one_chip):
    """The two state-space kernels at the published tile sizes: 32 heads,
    state tiles of (256, 128) float32 (the published (128, 256),
    state-major), a pool of 65 rows (that a step which donates it gets
    it back in place is read off the whole steps below)."""
    sds = jax.ShapeDtypeStruct
    tok = (lanes,) if chunk is None else (lanes, chunk)
    args = (sds(tok + (H1_HEADS * H1_P,), jnp.float32),
            sds(tok + (H1_HEADS,), jnp.float32),
            sds((H1_HEADS,), jnp.float32),
            sds(tok + (H1_GROUPS * H1_STATE,), jnp.float32),
            sds(tok + (H1_GROUPS * H1_STATE,), jnp.float32),
            sds((H1_SLOTS + 1, H1_HEADS, H1_STATE, H1_P), jnp.float32),
            sds((lanes,), jnp.int32))
    if chunk is not None:
        args += (sds((lanes,), jnp.int32),)
    _compile_kernel(name, args, one_chip, n_groups=H1_GROUPS)


def _slot_state_steps(topo, model, slots, page_size, num_pages, chunk,
                      max_tokens):
    """lower(step, lanes, width) -> compiled, for a family that keeps
    state a slot: abstract bf16 weights, an engine with a nine-page pool
    and two slots, the steps lowered on the cell's pools of shapes
    (``num_pages`` pages, ``slots`` + 1 state rows)."""
    from jax.sharding import SingleDeviceSharding
    from paddle_tpu import inference
    params = jax.eval_shape(lambda k: model.init(k, dtype=jnp.bfloat16),
                            jax.random.PRNGKey(0))
    eng = inference.make_serving_engine(
        model, params, num_slots=2, page_size=page_size, num_pages=9,
        max_tokens_per_slot=max_tokens, prefill_chunk=chunk, decode_block=8,
        attn_impl="pallas", cache_dtype=jnp.bfloat16)
    dev = SingleDeviceSharding(topo.devices[0])
    sds = jax.ShapeDtypeStruct
    weights = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype, sharding=dev), params)
    paged = len(eng.cache.config.kinds[0].pools)
    pages = [tuple(sds(((num_pages if k < paged else slots + 1),)
                       + a.shape[1:], a.dtype, sharding=dev)
                   for k, a in enumerate(ent)) for ent in eng.cache.pages]

    def i32(*shape):
        return sds(shape, jnp.int32, sharding=dev)

    @functools.lru_cache(maxsize=None)
    def lower(step, lanes, width):
        if step == "decode":
            return eng.decode_step.lower(
                weights, pages, i32(lanes, width), i32(lanes), i32(lanes),
                i32(lanes)).compile()
        # a prefill lane's table carries its state row in one more column
        return eng.prefill_step.lower(
            weights, pages, i32(lanes, width + 1), i32(lanes),
            i32(lanes, chunk), i32(lanes)).compile()

    return lower


def _assert_step_keeps_its_pools(compiled, kernels_in, pools, temp_limit):
    """The compiled step holds exactly the Pallas calls ``kernels_in``,
    copies none of ``pools`` (shape pattern -> entry layout), takes each in
    that layout, and keeps its temporaries under ``temp_limit`` bytes."""
    import re
    text = compiled.as_text()
    names = {re.sub(r"\.\d+$", "", n) for n in re.findall(
        r"%([\w.\-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"", text)}
    assert names == kernels_in
    entry = text[text.index("ENTRY "):]
    for pool, layout in pools.items():
        copies = [line for line in text.splitlines()
                  if re.search(r"= " + pool + r"\S* copy\(", line)]
        assert not copies, copies[:2]
        layouts = set(re.findall(pool + r"(\{[\d,]+)[^ ]* parameter\(",
                                 entry))
        assert layouts == {layout}, (pool, layouts)
    assert compiled.memory_analysis().temp_size_in_bytes < temp_limit


@pytest.fixture(scope="module")
def h1_steps(topo):
    """The hybrid family at its published widths, two layers, on the chat
    cell's pools (1025 pages, 65 state rows)."""
    from paddle_tpu.models.hybrid_ssm_lm import (HybridSSMLM,
                                                 HybridSSMLMConfig)
    model = HybridSSMLM(HybridSSMLMConfig(num_hidden_layers=H1_LAYERS,
                                          kernel_impl="pallas"))
    return _slot_state_steps(topo, model, H1_SLOTS, H1_PS, H1_PAGES,
                             H1_CHUNK, 2048)


@pytest.mark.parametrize("step, lanes, width, kernels_in", [
    ("decode", H1_SLOTS, 16, {"ragged_paged_decode", "ssm_decode_update"}),
    ("prefill", 4, 8, {"ragged_paged_prefill", "ssd_chunk_scan"})],
    ids=["decode-w16", "prefill-4lanes-w8"])
def test_hybrid_family_steps_compile_and_keep_the_pools(
        step, lanes, width, kernels_in, h1_steps):
    """The hybrid's decode block and prefill step compile for the chip at
    the chat cell's geometry: 20 query heads over 4 KV heads in the dense
    paged kernels (a group of 5), chunks of 128, the state update over 64
    slots inside the 8-token loop. No step copies a K or V pool, a state
    pool or a conv-window pool; each comes in row-major; temporaries stay
    under one layer's state pool (272 MB)."""
    _assert_step_keeps_its_pools(h1_steps(step, lanes, width), kernels_in, {
        rf"bf16\[{H1_PAGES},{H1_PS},512\]": "{2,1,0",
        rf"f32\[{H1_SLOTS + 1},{H1_HEADS},{H1_STATE},{H1_P}\]": "{3,2,1,0",
        rf"f32\[{H1_SLOTS + 1},15360\]": "{1,0"}, 272 << 20)


# -- latent conv attention + top-1 experts at the reasoning cell's geometry ---

ZY_SLOTS, ZY_PS, ZY_PAGES, ZY_CHUNK, ZY_LAYERS = 256, 128, 3585, 128, 10


@pytest.fixture(scope="module")
def zaya_steps(topo):
    """The latent-conv family at its published widths and the cell's TEN
    layers, on the reasoning cell's pools (3585 pages, 257 tail rows)."""
    from paddle_tpu.models.latent_conv_moe_lm import (LatentConvMoELM,
                                                      LatentConvMoELMConfig)
    model = LatentConvMoELM(LatentConvMoELMConfig(
        num_hidden_layers=ZY_LAYERS, kernel_impl="pallas"))
    return _slot_state_steps(topo, model, ZY_SLOTS, ZY_PS, ZY_PAGES,
                             ZY_CHUNK, 3072)


@pytest.mark.parametrize("step, lanes, width, kernels_in", [
    ("decode", ZY_SLOTS, 32, {"ragged_paged_decode", "moe_grouped_ffn"}),
    ("prefill", 16, 16, {"ragged_paged_prefill", "moe_grouped_ffn"})],
    ids=["decode-w32", "prefill-16lanes-w16"])
def test_latent_family_steps_compile_and_keep_the_pools(
        step, lanes, width, kernels_in, zaya_steps):
    """The ten-layer decode block and prefill step compile for the chip at
    the reasoning cell's geometry: 8 query heads over 2 KV heads in the
    dense paged kernels (a page row of 256 lanes), 256 slots inside the
    8-token loop, 16 lanes of 128 queries, the grouped expert kernel at
    16 experts of 2048 x 2048 with one a token. No step copies a K or V
    pool or a tail pool; each comes in row-major; temporaries stay under
    the decode logits' 269 MB (the head's matmul and argmax fuse)."""
    _assert_step_keeps_its_pools(
        zaya_steps(step, lanes, width), kernels_in, {
            rf"bf16\[{ZY_PAGES},{ZY_PS},256\]": "{2,1,0",
            rf"f32\[{ZY_SLOTS + 1},1280\]": "{1,0",
            rf"f32\[{ZY_SLOTS + 1},128\]": "{1,0"}, 269 << 20)


# -- window and full attention layers + an expert share at the mixed cell's geometry

KX_SLOTS, KX_PS, KX_PAGES, KX_CHUNK = 128, 128, 3073, 128


@pytest.fixture(scope="module")
def k_exaone_steps(topo):
    """The window / full family at its published widths and the cell's
    five layers (window, window, window, full, window; 16 of 128 experts
    held), on the mixed cell's pools: 3073 pages of the full layer, a
    ring of 2 pages a slot (257 pages) for each window layer."""
    from jax.sharding import SingleDeviceSharding
    from paddle_tpu import inference
    from paddle_tpu.models.window_moe_lm import (WindowMoELM,
                                                 WindowMoELMConfig)
    from paddle_tpu.serving import layer_kinds
    model = WindowMoELM(WindowMoELMConfig(
        num_hidden_layers=5, vocab_size=19200, num_experts=16,
        num_routed_experts=128, kernel_impl="pallas"))
    params = jax.eval_shape(lambda k: model.init(k, dtype=jnp.bfloat16),
                            jax.random.PRNGKey(0))
    eng = inference.make_serving_engine(
        model, params, num_slots=2, page_size=KX_PS, num_pages=9,
        max_tokens_per_slot=9216, prefill_chunk=KX_CHUNK, decode_block=8,
        attn_impl="pallas", cache_dtype=jnp.bfloat16)
    dev = SingleDeviceSharding(topo.devices[0])
    sds = jax.ShapeDtypeStruct
    weights = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype, sharding=dev), params)
    c = eng.cache.config
    # the cell's pools: what each layer's kind lays out at its geometry
    pages = [tuple(sds(shape, dtype, sharding=dev)
                   for shape, dtype, _ in kind.pools)
             for kind in layer_kinds.build(
                 eng.program.spec, num_slots=KX_SLOTS, page_size=KX_PS,
                 num_pages=KX_PAGES, dtype=c.dtype,
                 share_prefix=c.share_prefix)]

    def i32(*shape):
        return sds(shape, jnp.int32, sharding=dev)

    @functools.lru_cache(maxsize=None)
    def lower(step, lanes, width):
        if step == "decode":
            return eng.decode_step.lower(
                weights, pages, i32(lanes, width), i32(lanes), i32(lanes),
                i32(lanes)).compile()
        # a prefill lane's table carries its slot in one more column
        return eng.prefill_step.lower(
            weights, pages, i32(lanes, width + 1), i32(lanes),
            i32(lanes, KX_CHUNK), i32(lanes)).compile()

    return lower


@pytest.mark.parametrize("step, lanes, width, kernels_in", [
    ("decode", KX_SLOTS, 72, {"ragged_paged_decode", "moe_grouped_ffn"}),
    ("prefill", 16, 64, {"ragged_paged_prefill", "moe_grouped_ffn"})],
    ids=["decode-w72", "prefill-16lanes-w64"])
def test_window_family_steps_compile_and_keep_the_pools(
        step, lanes, width, kernels_in, k_exaone_steps):
    """The five-layer decode block and prefill step compile for the chip
    at the mixed cell's geometry: 64 query heads over 8 KV heads (a page
    row of 1024 lanes, a group of 8: the widest the dense kernels run),
    the window layers' calls over tables of 2 and 3 ring pages beside the
    full layer's 72 and 64, 16 lanes of 128 queries at one page a grid
    step, the grouped expert kernel at 16 held experts of 2048 x 6144 in
    128-wide hidden blocks. No step copies the full layer's pool or a
    ring pool; each comes in row-major; temporaries stay under 640 MB
    (the expert layer's rows of a 2048-token call: every pair may land
    on the held experts, so the table is sized for all of them)."""
    _assert_step_keeps_its_pools(
        k_exaone_steps(step, lanes, width), kernels_in, {
            rf"bf16\[{KX_PAGES},{KX_PS},1024\]": "{2,1,0",
            rf"bf16\[{KX_SLOTS * 2 + 1},{KX_PS},1024\]": "{2,1,0"},
        640 << 20)


# -- latent rows + an expert share at the shared-context cell's geometry ---------

MS_SLOTS, MS_PS, MS_PAGES, MS_CHUNK, MS_WIDTH = 128, 128, 4993, 256, 134


@pytest.fixture(scope="module")
def mistral4_steps(topo):
    """The latent-row family at its published widths and the cell's six
    layers (16 of 128 experts held, 16384 rows of the vocabulary), on the
    sessions cell's pools: 4993 pages of (128, 256) latents and of (64,
    128) rotary keys a layer."""
    from jax.sharding import SingleDeviceSharding
    from paddle_tpu import inference
    from paddle_tpu.models.mla_moe_lm import MLAMoELM, MLAMoELMConfig
    model = MLAMoELM(MLAMoELMConfig(
        num_hidden_layers=6, vocab_size=16384, n_routed_experts=16,
        num_routed_experts=128, kernel_impl="pallas"))
    params = jax.eval_shape(lambda k: model.init(k, dtype=jnp.bfloat16),
                            jax.random.PRNGKey(0))
    assert sum(a.size for a in jax.tree.leaves(params)) == 2_872_634_880
    eng = inference.make_serving_engine(
        model, params, num_slots=2, page_size=MS_PS, num_pages=9,
        max_tokens_per_slot=17152, prefill_chunk=MS_CHUNK, decode_block=8,
        attn_impl="pallas", cache_dtype=jnp.bfloat16)
    dev = SingleDeviceSharding(topo.devices[0])
    sds = jax.ShapeDtypeStruct
    weights = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype, sharding=dev), params)
    pages = [tuple(sds((MS_PAGES,) + a.shape[1:], a.dtype, sharding=dev)
                   for a in ent) for ent in eng.cache.pages]

    def i32(*shape):
        return sds(shape, jnp.int32, sharding=dev)

    @functools.lru_cache(maxsize=None)
    def lower(step, lanes, width):
        if step == "decode":
            return eng.decode_step.lower(
                weights, pages, i32(lanes, width), i32(lanes), i32(lanes),
                i32(lanes)).compile()
        return eng.prefill_step.lower(
            weights, pages, i32(lanes, width), i32(lanes),
            i32(lanes, MS_CHUNK), i32(lanes)).compile()

    return lower


@pytest.mark.parametrize("step, lanes, kernels_in", [
    ("decode", MS_SLOTS, {"latent_paged_decode", "moe_grouped_ffn"}),
    ("prefill", 8, {"latent_paged_prefill", "moe_grouped_ffn"})],
    ids=["decode-w134", "prefill-8lanes-w134"])
def test_latent_row_family_steps_compile_and_keep_the_pools(
        step, lanes, kernels_in, mistral4_steps):
    """The six-layer decode block and prefill step compile for the chip at
    the sessions cell's geometry: 32 absorbed queries of 320 a slot over
    tables of 134 pages, 128 slots inside the 8-token loop; 8 lanes of 256
    queries x 32 heads as tiles of 1024 rows; the grouped expert kernel at
    16 held experts of 2048 x 4096. No step copies a latent pool or a
    rotary-key pool; each comes in row-major, whole tiles, nothing padded;
    temporaries stay under 512 MB."""
    _assert_step_keeps_its_pools(
        mistral4_steps(step, lanes, MS_WIDTH), kernels_in, {
            rf"bf16\[{MS_PAGES},{MS_PS},256\]": "{2,1,0",
            rf"bf16\[{MS_PAGES},64,{MS_PS}\]": "{2,1,0"}, 512 << 20)


@pytest.mark.parametrize("name, q_shape, geometry", [
    ("latent_paged_decode", (MS_SLOTS, 32, 320), 1),
    ("latent_paged_prefill", (8, MS_CHUNK, 32, 320), 2)])
def test_latent_kernels_vmem_estimates_hold_for_v5e(name, q_shape, geometry,
                                                    one_chip):
    """Each latent kernel alone at the cell's shapes and the static
    prior's block sizes: it compiles for the chip, and the estimate that
    chose the blocks is not under what the compiler scoped."""
    from paddle_tpu.serving.decode_attention import DECODE_GROUP
    sds = jax.ShapeDtypeStruct
    args = (sds(q_shape, jnp.bfloat16),
            sds((MS_PAGES, MS_PS, 256), jnp.bfloat16),
            sds((MS_PAGES, 64, MS_PS), jnp.bfloat16),
            sds((q_shape[0], MS_WIDTH), jnp.int32)) + tuple(
                sds((q_shape[0],), jnp.int32) for _ in range(geometry))
    if name == "latent_paged_decode":       # the groups: who shares what
        args += (sds((MS_SLOTS // 2, DECODE_GROUP), jnp.int32),
                 sds((MS_SLOTS // 2,), jnp.int32), sds((MS_SLOTS,), jnp.int32))
    spec = kernels.get(name)
    blocks = autotune.static_prior(spec, args, {})
    assert spec.vmem_estimate(args, {}, blocks) <= autotune.VMEM_BUDGET_BYTES
    _compile_kernel(name, args, one_chip)


# -- the named scopes of PR 38 leave every name a metric selects as it was ------

def _kernel_counts(text):
    """Pallas custom calls of a compiled program by name, and its
    ``rng-bit-generator`` instructions."""
    import collections
    counts = collections.Counter(_custom_calls(text))
    counts["rng-bit-generator"] = len(re.findall(r" rng-bit-generator\(",
                                                 text))
    return dict(counts)


def _scope_keys(text):
    """Every ``phase/scope`` the compiled program's instructions are
    booked to."""
    from paddle_tpu.observability import scopes
    return {sc.key for sc in scopes.parse_hlo(text).scopes.values()}


def test_bert_base_step_keeps_the_names_the_metrics_select(one_chip):
    """The BERT-base cell's train step (batch 48 x 512, bf16 policy, flash
    kernel, a pool of batches) compiled for the chip WITH the model's
    named scopes: the flash kernels are still the 12 ``jvp_forward_`` and
    12 ``transpose_jvp_forward__`` custom calls ``kernel.flash_attn_*``
    select (a scope that ENCLOSED the kernel call would have renamed
    them), the dropout draws still 37 ``rng-bit-generator`` instructions
    (``kernel.dropout_bits_time_pct.train``), and every scope reaches
    the compiled program's metadata in the forward and the backward."""
    from paddle_tpu import optimizer as opt
    from paddle_tpu.core import dtypes
    from paddle_tpu.models import bert
    from paddle_tpu.nn import transformer
    from paddle_tpu.train import build_train_step, make_train_state
    model = bert.BertForPretraining(bert.BertConfig(
        attn_dropout=0.0, attn_impl="flash"))
    optimizer = opt.AdamW(learning_rate=1e-4)
    step = build_train_step(
        lambda params, **batch: model.loss(params, training=True, **batch),
        optimizer, policy=dtypes.get_policy("bf16"))
    sds = jax.ShapeDtypeStruct
    state = jax.tree_util.tree_map(
        lambda x: sds(x.shape, x.dtype, sharding=one_chip), jax.eval_shape(
            lambda k: make_train_state(model, optimizer, k),
            jax.random.PRNGKey(0)))

    def arr(dtype, *shape):
        return sds(shape, dtype, sharding=one_chip)
    batch = dict(
        input_ids=arr(jnp.int32, B, SEQ), token_type_ids=arr(jnp.int32, B, SEQ),
        attention_mask=arr(bool, B, SEQ), mlm_labels=arr(jnp.int32, B, SEQ),
        mlm_mask=arr(jnp.float32, B, SEQ), nsp_labels=arr(jnp.int32, B),
        key=arr(jnp.uint32, 2))
    text = jax.jit(lambda st, b: step(st, **b), donate_argnums=(0,)).lower(
        state, batch).compile().as_text()
    assert _kernel_counts(text) == {
        "jvp_forward_": 12, "transpose_jvp_forward__": 12,
        "rng-bit-generator": 37}
    keys = _scope_keys(text)
    model_scopes = set(transformer.BLOCK_SCOPES + bert.MODEL_SCOPES) \
        - {"attn_core"}                 # the composed attention: not here
    for phase in ("forward", "backward"):
        assert {f"{phase}/{name}" for name in model_scopes} <= keys
    assert "optimizer/" in keys


@pytest.mark.parametrize("family, step_args, kernels_in", [
    ("gpt2", ("decode", 64, 8), {"ragged_paged_decode": 12}),
    ("sparse", ("decode", 32, 128),
     {"lightning_indexer": 2, "topk_selection_mask": 2,
      "sparse_paged_decode": 4,                          # two calls a layer
      "moe_grouped_ffn": 2}),
    ("hybrid", ("decode", 64, 16),
     {"ragged_paged_decode": 2, "ssm_decode_update": 2}),
    ("latent", ("decode", 256, 32),
     {"ragged_paged_decode": 10, "moe_grouped_ffn": 10})],
    ids=["gpt2", "sparse", "hybrid", "latent"])
def test_serving_decode_steps_keep_the_names_the_metrics_select(
        family, step_args, kernels_in, request):
    """One decode block of each serving family compiled for the chip
    WITH the engine's scopes around the program's hooks: every Pallas
    kernel keeps its own name and count (a named ``pallas_call`` is not
    renamed by an enclosing scope; ``kernel.*_time_pct.*`` and the
    rooflines select them by these names), and the hooks' scopes are in
    the compiled program's metadata."""
    if family == "gpt2":
        _variant, lower = _cell_steps("bf16", request.getfixturevalue("topo"))
    else:
        lower = request.getfixturevalue(
            {"sparse": "doc_steps", "hybrid": "h1_steps",
             "latent": "zaya_steps"}[family])
    text = lower(*step_args).as_text()
    assert _kernel_counts(text) == {**kernels_in, "rng-bit-generator": 0}
    keys = _scope_keys(text)
    assert {"embed", "attn_in", "write_rows", "attend", "attn_out", "ffn",
            "head"} <= keys
    assert ("mixer" in keys) == (family == "hybrid")
