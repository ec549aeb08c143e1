"""Controls of a ``serve_lm`` configuration's two limits: does the comparison
that decides ``correct`` tell a sound program from a reference with one
mechanism taken out?

    python3 benchmark/controls.py --config qwen3_next_80b_a3b --seed N \
        [--control NAME ...] [--rehearse]

A small engine (``SLOTS``) at the configuration's widths and depth serves
three requests of ``NEW_TOKENS`` new tokens; their tokens go through
``runners/serve_lm.py``'s own ``_reference_check`` under the
configuration's ``tie_margin`` and ``mean_shortfall_max``: once against
the family's reference as it is (``sound``, which must come out
correct), then once a control: each entry
of the family's ``CONTROLS`` (a mechanism left out of the reference) and
``float8_reference_weights`` (the reference's weights rounded to
float8_e4m3, outside any jitted call). One JSON line a comparison, with
``correct`` as the runner would print it. Exit code 0 where ``sound`` is
correct and every control asked for is not, 1 otherwise (``--rehearse``,
tiny sizes on the CPU with kernels interpreted, shows that the driver
runs: a tiny model's controls need not fail, and the code is 0).

Why a few slots do: the comparison teacher-forces each request alone
through the reference, and a request's tokens do not depend on how many
slots are served beside it (every row of a step is computed alone; the
cell's 256 slots change which rows share a tile of the expert kernel, not
what a row computes). The sound reading at 4 slots lies inside the range
the cell's own runs give (the configuration's ``tie_margin_why``).
"""

import argparse
import importlib
import json
import os
import sys
import types

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path[:0] = [ROOT, BENCH_DIR]

import numpy as np  # noqa: E402

import common  # noqa: E402

FLOAT8 = "float8_reference_weights"
SLOTS, NEW_TOKENS = 4, 384
#: prompt lengths of the three requests, as shares of what a slot holds
#: less the new tokens (700, 1335 and 2500 of 5120 - 384 at the first
#: configuration this was written for)
PROMPT_SHARES = (0.148, 0.282, 0.528)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--control", action="append", default=None,
                    help="a name of the family's CONTROLS, or "
                         f"{FLOAT8}; left out: all of them")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on the CPU, kernels interpreted, 8 "
                         "new tokens a request")
    args = ap.parse_args(argv)

    cfg = common.load_json(BENCH_DIR, "configs", args.config + ".json")
    if cfg["runner"] != "serve_lm":
        raise SystemExit("controls.py drives serve_lm configurations only")
    family = importlib.import_module(f"families.{cfg['family']}")
    named = dict(getattr(family, "CONTROLS", {}))
    asked = args.control or list(named) + [FLOAT8]
    unknown = [c for c in asked if c not in named and c != FLOAT8]
    if unknown:
        raise SystemExit(f"{args.config} names no control {unknown}; it "
                         f"has {sorted(named) + [FLOAT8]}")

    import jax
    import jax.numpy as jnp
    if args.rehearse:
        jax.config.update("jax_platforms", "cpu")
    elif jax.devices()[0].platform != "tpu":
        print("controls.py: needs a TPU (or --rehearse)", file=sys.stderr)
        return 1
    from paddle_tpu import inference
    from paddle_tpu import observability as obs
    from paddle_tpu.core.compile_cache import enable_compile_cache
    from runners import serve_lm
    enable_compile_cache()

    sizes = dict(cfg["sizes"])
    ekw = dict(cfg["engine"])
    if args.rehearse:
        sizes.update(cfg["rehearsal"]["sizes"])
        ekw.update(cfg["rehearsal"]["engine"])
    n_new = 8 if args.rehearse else NEW_TOKENS
    per_slot = ekw["max_tokens_per_slot"]
    ekw.update(
        num_slots=SLOTS, attn_impl="pallas_interpret"
        if args.rehearse else "pallas",
        num_pages=SLOTS * -(-per_slot // ekw["page_size"]) + 1,
        prefill_budget=SLOTS * ekw["prefill_chunk"],
        cache_dtype=jnp.dtype(ekw["cache_dtype"]))
    model = family.build(sizes, interpret=args.rehearse)
    params = jax.jit(lambda k: model.init(
        k, dtype=jnp.dtype(cfg["assumed"]["weights_dtype"])))(
            jax.random.PRNGKey(args.seed % 2147483647))
    eng = inference.make_serving_engine(
        model, params, registry=obs.MetricsRegistry(), **ekw)

    rng = np.random.default_rng(args.seed)
    room = min(per_slot, family.positions(sizes)) - n_new
    prompts = [rng.integers(0, family.vocabulary(sizes),
                            max(int(share * room), 2)).astype(np.int32)
               for share in PROMPT_SHARES]
    rids = [eng.submit(p, n_new) for p in prompts]
    while not eng.scheduler.idle():
        eng.step()
    records = [types.SimpleNamespace(
        req=types.SimpleNamespace(prompt=p, shared_prefix=0),
        tokens=np.asarray(eng.result(r))) for p, r in zip(prompts, rids)]
    common.log(f"served {[len(p) for p in prompts]} prompt tokens and "
               f"{n_new} new ones a request on {SLOTS} slots")

    pad_to = -(-(len(prompts[-1]) + n_new) // 256) * 256
    margin = cfg["assumed"]["tie_margin"]
    mean_max = cfg["assumed"]["mean_shortfall_max"]
    chunk = ekw["prefill_chunk"]

    def compare(name, controls, weights):
        fwd = jax.jit(lambda p, ids, lo, probe: family.reference_logits(
            p, ids, sizes, lo, n_new, probe=probe, **controls(lo, chunk)))
        with jax.default_matmul_precision("highest"):
            fwd = fwd.lower(
                weights, jax.ShapeDtypeStruct((1, pad_to), jnp.int32),
                jax.ShapeDtypeStruct((), jnp.int32), jax.ShapeDtypeStruct(
                    (serve_lm.PROBE_QUERIES,), jnp.int32)).compile()
        ok, worst, mean, _ = serve_lm._reference_check(
            fwd, weights, pad_to, n_new, records, margin, mean_max, eng, None)
        print(json.dumps({
            "config": args.config, "seed": args.seed, "control": name,
            "correct": bool(ok), "reference_shortfall": worst,
            "tie_margin": margin, "reference_mean_shortfall": mean,
            "mean_shortfall_max": mean_max}), flush=True)
        return ok

    sound = compare("sound", lambda lo, chunk: {}, params)
    failed = []
    for name in asked:
        weights, controls = params, named.get(name, lambda lo, chunk: {})
        if name == FLOAT8:
            # outside any jitted call: XLA may remove a bf16 -> float8 ->
            # bf16 pair inside one program
            weights = jax.tree.map(
                lambda a: a.astype(jnp.float8_e4m3fn).astype(a.dtype)
                if a.dtype == jnp.bfloat16 else a, params)
        if not compare(name, controls, weights):
            failed.append(name)
    if args.rehearse:
        return 0
    return 0 if sound and failed == asked else 1


if __name__ == "__main__":
    sys.exit(main())
