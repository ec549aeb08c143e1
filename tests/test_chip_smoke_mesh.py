"""What ``tests/test_chip_smoke.py`` rehearses across devices (a file of
its own for ``--dist loadfile``): the four-chip phases of ``chip_smoke.py``
on four virtual devices, and the flash kernel under a mesh."""

import jax
import pytest


def test_four_chip_phases_on_virtual_devices(chip_smoke, capsys):
    chip_smoke.run_four_chips(chip_smoke.Sizes.tiny(),
                              devices=jax.devices()[:4])
    out = capsys.readouterr().out
    assert "dp2 x tp2 losses" in out
    assert "requests token-equal, tp=4 vs tp=1" in out


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
