"""One hash a serving step program: every serving cell of ``BENCHMARK.json``
has its decode block and its widest prefill step lowered for a described
v5e at the cell's own geometry (the configuration file's ``engine``: slots,
page size, pool, chunk, lanes of a prefill call), Pallas kernels in, and
the text hashed with every source location stripped (the XLA text carries
none; a kernel's Mosaic module is parsed and printed without debug info).

A PR that must leave the other cells' programs alone runs this on its
parent and on itself and compares the lines::

    python tools/step_program_hashes.py > change.txt
    python tools/step_program_hashes.py --root /path/to/parent > parent.txt
    diff parent.txt change.txt      # only the cells the PR adds may differ

Nothing runs and no chip is needed: the TPU compiler's description of the
chip is enough to lower for it. ``--cells`` takes a comma-separated list
of cell names; ``--keep DIR`` writes each program's stripped text there
(for a diff when a hash moves).
"""

import argparse
import base64
import hashlib
import importlib
import inspect
import json
import os
import re
import sys

_BODY = re.compile(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22')


def _stripped(text: str) -> str:
    """``text`` with each kernel's serialized Mosaic module replaced by
    its assembly without debug info."""
    from jax._src import tpu_custom_call  # noqa: F401  (registers dialects)
    from jax._src.interpreters import mlir as jmlir
    from jax._src.lib.mlir import ir

    def asm(match):
        ctx = jmlir.make_ir_context()
        with ctx:
            ctx.allow_unregistered_dialects = True
            module = ir.Module.parse(base64.b64decode(match.group(1)))
            return module.operation.get_asm(enable_debug_info=False)
    return _BODY.sub(asm, text)


def _abstract_params(model, dtype):
    import jax
    import jax.numpy as jnp
    key = jax.random.PRNGKey(0)
    try:
        return jax.eval_shape(lambda k: model.init(k, dtype=dtype), key)
    except TypeError:       # an ``init`` that draws float32 only
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, dtype)
            if a.dtype == jnp.float32 else a,
            jax.eval_shape(model.init, key))


def cell_programs(root: str, config: dict, device):
    """``[(step, lanes, width, lowered text), ...]`` of one configuration's
    engine: the decode block over every slot and the prefill step at the
    most lanes a call takes, both at the slot's whole table."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu import inference
    from paddle_tpu.serving import engine as serving_engine
    from paddle_tpu.serving import layer_kinds
    family = importlib.import_module(f"families.{config['family']}")
    model = family.build(config["sizes"])
    ekw = dict(config["engine"])
    slots, chunk = ekw.pop("num_slots"), ekw["prefill_chunk"]
    num_pages = ekw.pop("num_pages", None)
    ekw["cache_dtype"] = jnp.dtype(ekw["cache_dtype"])
    params = _abstract_params(
        model, jnp.dtype(config["assumed"]["weights_dtype"]))
    # a small engine stands in for the cell's: the steps are lowered on
    # shapes, so its own pools are never the cell's size
    eng = inference.make_serving_engine(
        model, params, num_slots=2, num_pages=9, attn_impl="pallas", **ekw)
    c = eng.cache.config
    width = c.max_pages_per_slot
    if num_pages is None:
        num_pages = slots * width + 1
    sds = jax.ShapeDtypeStruct
    # the cell's pools: what each layer's kind lays out at the cell's
    # geometry, then the program's state a slot
    lanes = min(max(eng.prefill_budget // chunk, 1), slots)
    # (a ring's room follows the lanes of a call since PR 54; a checkout
    # from before takes no such argument and gives a ring one page)
    room = {"prefill_room": min(lanes, serving_engine._LANE_STEP)} \
        if "prefill_room" in inspect.signature(
            layer_kinds.build).parameters else {}
    kinds = layer_kinds.build(
        eng.program.spec, num_slots=slots, page_size=c.page_size,
        num_pages=num_pages, dtype=c.dtype, share_prefix=c.share_prefix,
        impl=eng.attn_impl, prefill_chunk=chunk, **room)
    # the steps place rows and attend as the CELL's kinds do (a ring's
    # pages follow its slots and its room), not the stand-in's
    eng.cache.config.kinds = kinds
    weights = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype, sharding=device), params)
    pages = [tuple(sds(shape, dtype, sharding=device)
                   for shape, dtype, _ in kind.pools)
             + tuple(sds((slots + 1,) + a.shape[1:], a.dtype,
                         sharding=device) for a in ent[len(kind.pools):])
             for kind, ent in zip(kinds, eng.cache.pages)]

    def i32(*shape):
        return sds(shape, jnp.int32, sharding=device)

    yield "decode", slots, width, eng.decode_step.lower(
        weights, pages, i32(slots, width), i32(slots), i32(slots),
        i32(slots)).as_text()
    yield "prefill", lanes, width, eng.prefill_step.lower(
        weights, pages, i32(lanes, width + eng._lane_slot_column),
        i32(lanes), i32(lanes, chunk), i32(lanes)).as_text()


def main(argv=None) -> int:
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--root", default=here,
                    help="the checkout whose programs are lowered")
    ap.add_argument("--cells", default=None)
    ap.add_argument("--keep", default=None)
    args = ap.parse_args(argv)
    root = os.path.abspath(args.root)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path[:0] = [root, os.path.join(root, "benchmark")]
    import jax
    jax.config.update("jax_enable_compilation_cache", False)
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    import paddle_tpu
    assert os.path.abspath(paddle_tpu.__file__).startswith(root), \
        paddle_tpu.__file__
    device = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    files = {c["name"]: c["file"] for c in bench["configs"]}
    wanted = set(args.cells.split(",")) if args.cells else None
    done = set()
    for wl in bench["workloads"]:
        if wanted is not None and wl["name"] not in wanted:
            continue
        with open(os.path.join(root, files[wl["config"]])) as f:
            config = json.load(f)
        if "engine" not in config or wl["config"] in done:
            continue                    # a training cell; one engine a config
        done.add(wl["config"])
        for step, lanes, width, text in cell_programs(root, config, device):
            text = _stripped(text)
            print(f"{wl['name']} {step} lanes={lanes} width={width} "
                  f"{hashlib.sha256(text.encode()).hexdigest()}", flush=True)
            if args.keep:
                os.makedirs(args.keep, exist_ok=True)
                with open(os.path.join(
                        args.keep, f"{wl['name']}.{step}.txt"), "w") as f:
                    f.write(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
