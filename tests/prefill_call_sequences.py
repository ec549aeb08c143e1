"""The prefill calls a tiny engine forms for a seeded set of prompts, for
the guard that holds a PR to "a program whose run limit is 1 forms the
calls it formed before" (``tests/test_serving_loops_golden.py``). Run as a
script on another checkout to print the sequences it forms::

    PYTHONPATH=/path/to/parent python tests/prefill_call_sequences.py
"""

import json

#: program -> (page size, prefill chunk): a state-carrying mixer, a
#: state-carrying attention projection, a selecting kind, a latent kind
PROGRAMS = {"hybrid_ssm": (4, 8), "latent_conv_moe": (4, 8),
            "sparse_moe": (4, 12), "mla_moe": (8, 8)}
#: prompt lengths: one shorter than a chunk, one of several chunks that
#: ends inside a page, more prompts than the budget has lanes
PROMPTS = (5, 29, 17, 8, 40)


def _model(name):
    from paddle_tpu import models
    cls = {"hybrid_ssm": "HybridSSMLM", "latent_conv_moe": "LatentConvMoELM",
           "sparse_moe": "SparseMoELM", "mla_moe": "MLAMoELM"}[name]
    return getattr(models, cls)(
        getattr(models, cls + "Config").tiny(kernel_impl="lax"))


def call_sequence(name):
    """``[[[lanes_live, lanes, width, tokens], ...] a step that made a
    call]`` of ``name``'s tiny model in a 4-slot engine whose budget is
    three chunks a step, the five ``PROMPTS`` submitted at once."""
    import jax
    import numpy as np
    from paddle_tpu import inference
    from paddle_tpu import observability as obs
    model = _model(name)
    params = model.init(jax.random.PRNGKey(3))
    page, chunk = PROGRAMS[name]
    eng = inference.make_serving_engine(
        model, params, num_slots=4, page_size=page, prefill_chunk=chunk,
        prefill_budget=3 * chunk, max_tokens_per_slot=64, decode_block=2,
        attn_impl="lax", registry=obs.MetricsRegistry())
    rng = np.random.default_rng(54)
    for n in PROMPTS:
        eng.submit(rng.integers(0, 90, n).astype(np.int32), 4)
    while not eng.scheduler.idle():
        eng.step()
    return [[call[:4] for call in rec["prefill_calls"]]
            for rec in eng.anatomy.records() if rec.get("prefill_calls")]


if __name__ == "__main__":
    import jax
    # what ``tests/conftest.py`` sets
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 8)
    jax.config.update("jax_threefry_partitionable", True)
    jax.config.update("jax_default_matmul_precision", "highest")
    print(json.dumps({name: call_sequence(name) for name in PROGRAMS}))
