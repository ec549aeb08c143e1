"""The lowered text of a tiny program's decode and prefill steps, for the
guards that hold a PR to "the other programs did not move"
(``tests/test_layer_kinds.py``). Run as a script on another checkout to
print the hashes it lowers::

    PYTHONPATH=/path/to/parent python tests/step_texts.py
"""

import hashlib
import json

PROGRAMS = ("gpt", "latent_conv_moe", "window_moe")


def _model(name):
    from paddle_tpu import models
    if name == "gpt":
        from paddle_tpu.models.gpt import GPT, GPTConfig
        return GPT(GPTConfig.tiny())
    if name == "latent_conv_moe":
        return models.LatentConvMoELM(
            models.LatentConvMoELMConfig.tiny(kernel_impl="lax"))
    return models.WindowMoELM(models.WindowMoELMConfig.tiny(
        kernel_impl="lax"))


def step_hashes(name, impl):
    """{"decode": sha256, "prefill": sha256} of the two step programs of
    ``name``'s tiny model in a 2-slot engine with pages of 4, lowered on
    shapes (nothing compiles or runs)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu import inference
    from paddle_tpu import observability as obs
    model = _model(name)
    params = model.init(jax.random.PRNGKey(0))
    # a budget of one chunk a step: a call of one lane, so a window
    # layer's ring has the one page of room it had before calls carried
    # runs (PR 54), and the pools the shapes they had
    eng = inference.make_serving_engine(
        model, params, num_slots=2, page_size=4, prefill_chunk=4,
        prefill_budget=4, max_tokens_per_slot=32, attn_impl=impl,
        registry=obs.MetricsRegistry())
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    width = eng.cache.config.max_pages_per_slot
    texts = {
        "decode": eng.decode_step.lower(
            eng._step_params, eng.cache.pages, i32(2, width), i32(2),
            i32(2), i32(2)).as_text(),
        "prefill": eng.prefill_step.lower(
            eng._step_params, eng.cache.pages,
            i32(2, width + eng._lane_slot_column), i32(2), i32(2, 4),
            i32(2)).as_text()}
    return {step: hashlib.sha256(text.encode()).hexdigest()[:16]
            for step, text in texts.items()}


if __name__ == "__main__":
    import jax
    # what ``tests/conftest.py`` sets: it is part of the lowered text
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 8)
    jax.config.update("jax_threefry_partitionable", True)
    jax.config.update("jax_default_matmul_precision", "highest")
    print(json.dumps({f"{name}[{impl}]": step_hashes(name, impl)
                      for name in PROGRAMS
                      for impl in ("lax", "pallas_interpret")}, indent=1))
