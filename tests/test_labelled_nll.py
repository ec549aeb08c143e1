"""``ops.labelled_nll`` (PR 47): BERT's MLM loss computed at the labelled
positions only, against the dense path (logits at every position, masked
afterwards) kept here as the plain formula: the loss and the gradient of
EVERY parameter for any mask, on one device and under the dp2 x tp2 mesh
of ``tests/test_chip_smoke_mesh.py``, and the counter that says how many
rows the walk computed."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.models.bert import BertConfig, BertForPretraining
from paddle_tpu.ops import labelled_nll

B, S, VOCAB = 4, 32, 128


def dense_loss(model, params, batch):
    """The plain formula: the heads' logits at every position, float32
    log-softmax, the label's column, THEN the mask."""
    seq, pooled = model.bert(params["bert"], batch["input_ids"],
                             batch["token_type_ids"], batch["attention_mask"])
    table = params["bert"]["embeddings"]["word"]["weight"]
    mlm_logits, nsp_logits = model.heads(params["heads"], seq, pooled, table)
    lp = jax.nn.log_softmax(mlm_logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(lp, batch["mlm_labels"][..., None],
                               axis=-1)[..., 0]
    mask = batch["mlm_mask"]
    mlm = (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    nsp_lp = jax.nn.log_softmax(nsp_logits.astype(jnp.float32), axis=-1)
    return mlm - jnp.take_along_axis(
        nsp_lp, batch["nsp_labels"][:, None], axis=-1).mean()


def _first(n):
    """A row of S with its first ``n`` positions labelled."""
    return (jnp.arange(S) < n).astype(jnp.float32)


def _mask(kind, chunk):
    """-> (mask (B, S), chunks the walk must take)."""
    if kind == "bernoulli":
        mask = (jax.random.uniform(jax.random.PRNGKey(7), (B, S))
                < 0.15).astype(jnp.float32)
        return mask, -(-int(mask.sum(1).max()) // chunk)
    if kind == "zeros":
        return jnp.zeros((B, S)), 0
    if kind == "ones":
        return jnp.ones((B, S)), S // chunk
    if kind == "one-sequence-full":
        return jnp.zeros((B, S)).at[2].set(1.0), S // chunk
    # the labels of the fullest sequence end a chunk exactly / open one
    # more; scattered, so the order has work to do
    n = {"count-a-multiple-of-the-chunk": 2 * chunk,
         "count-one-more-than-a-multiple": 2 * chunk + 1}[kind]
    rows = [jnp.roll(_first(n), 3), _first(1), jnp.zeros((S,)),
            _first(n - 1)[::-1]]
    return jnp.stack(rows), -(-n // chunk)


def _model_and_batch():
    model = BertForPretraining(BertConfig.tiny(
        vocab_size=VOCAB, max_position=S, dropout=0.0, attn_dropout=0.0,
        attn_impl="xla"))
    params = model.init(jax.random.PRNGKey(0))
    # the bias is drawn at zero: give it a gradient that depends on it
    params["heads"]["decoder_bias"] = 0.1 * jax.random.normal(
        jax.random.PRNGKey(5), (VOCAB,))
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(1), 3)
    batch = dict(
        input_ids=jax.random.randint(k1, (B, S), 0, VOCAB, jnp.int32),
        token_type_ids=jnp.zeros((B, S), jnp.int32),
        attention_mask=jnp.ones((B, S), bool),
        mlm_labels=jax.random.randint(k2, (B, S), 0, VOCAB, jnp.int32),
        nsp_labels=jax.random.randint(k3, (B,), 0, 2, jnp.int32))
    return model, params, batch


@pytest.mark.parametrize("mesh_axes", [None, dict(dp=2, tp=2)],
                         ids=["one-device", "dp2xtp2"])
@pytest.mark.parametrize("kind", [
    "bernoulli", "zeros", "ones", "one-sequence-full",
    "count-a-multiple-of-the-chunk", "count-one-more-than-a-multiple"])
def test_loss_and_every_gradient_are_the_dense_paths(kind, mesh_axes,
                                                     monkeypatch):
    # chunks of 4 columns on a batch shard of 4 / of 2 sequences
    shards = mesh_axes["dp"] if mesh_axes else 1
    monkeypatch.setattr(labelled_nll, "_CHUNK_ROWS", 4 * B // shards)
    chunk = labelled_nll.chunk_columns(B // shards, S)
    assert chunk == 4
    model, params, batch = _model_and_batch()
    batch["mlm_mask"], chunks = _mask(kind, chunk)

    def loss(p, b):
        return model.loss(p, training=False, **b)

    want, want_grads = jax.jit(jax.value_and_grad(
        lambda p, b: dense_loss(model, p, b)))(params, batch)
    if mesh_axes is None:
        (got, metrics), grads = jax.jit(jax.value_and_grad(
            loss, has_aux=True))(params, batch)
    else:
        from paddle_tpu.core.mesh import MeshConfig, make_mesh, mesh_context
        from paddle_tpu.parallel import api as papi, plan as plan_lib
        mesh = make_mesh(MeshConfig(**mesh_axes), devices=jax.devices()[:4])
        p_sh = plan_lib.named_shardings(mesh, plan_lib.megatron_plan()
                                        .params_specs(
            params, model.sharding_specs(params)))
        b_sh = plan_lib.named_shardings(mesh, papi.batch_specs(batch))
        with mesh_context(mesh):
            (got, metrics), grads = jax.jit(
                jax.value_and_grad(loss, has_aux=True),
                in_shardings=(p_sh, b_sh))(params, batch)
        # the vocabulary really is cut over tp, the batch over dp
        table = grads["bert"]["embeddings"]["word"]["weight"]
        assert {s.data.shape for s in table.addressable_shards} \
            == {(VOCAB // 2, table.shape[1])}
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, g), w in zip(flat, jax.tree_util.tree_leaves(want_grads)):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), atol=1e-5, rtol=1e-5,
            err_msg=jax.tree_util.keystr(path))
    assert float(metrics["mlm_head_rows_share"]) \
        == pytest.approx(chunks / (S // chunk))


def test_a_fractional_mask_weighs_its_rows():
    """The mask is a row's weight, as in the dense formula."""
    model, params, batch = _model_and_batch()
    batch["mlm_mask"] = jnp.where(
        jax.random.uniform(jax.random.PRNGKey(3), (B, S)) < 0.3,
        jax.random.uniform(jax.random.PRNGKey(4), (B, S)), 0.0)
    got, grads = jax.value_and_grad(
        lambda p: model.loss(p, training=False, **batch)[0])(params)
    want, want_grads = jax.value_and_grad(
        lambda p: dense_loss(model, p, batch))(params)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for g, w in zip(jax.tree_util.tree_leaves(grads),
                    jax.tree_util.tree_leaves(want_grads)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=1e-5, rtol=1e-5)


def test_without_a_head_the_hidden_rows_meet_the_table():
    """A causal LM's case: no transform before the decoder, every
    position labelled but the padding."""
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(2), 3)
    hidden = jax.random.normal(k1, (3, 12, 8))
    table = jax.random.normal(k2, (40, 8))
    bias = jnp.linspace(-1.0, 1.0, 40)
    labels = jax.random.randint(k3, (3, 12), 0, 40)
    mask = (jnp.arange(12)[None] < jnp.array([12, 5, 0])[:, None]) \
        .astype(jnp.float32)

    def dense(hidden, table, bias):
        lp = jax.nn.log_softmax(hidden @ table.T + bias, axis=-1)
        nll = -jnp.take_along_axis(lp, labels[..., None], axis=-1)[..., 0]
        return (nll * mask).sum()

    def walked(hidden, table, bias):
        return labelled_nll.labelled_nll(hidden, table, bias, labels,
                                         mask)[0]

    want, want_grads = jax.value_and_grad(dense, (0, 1, 2))(
        hidden, table, bias)
    got, grads = jax.value_and_grad(walked, (0, 1, 2))(hidden, table, bias)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for g, w in zip(grads, want_grads):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=1e-5, rtol=1e-5)
    _, count, share = labelled_nll.labelled_nll(hidden, table, bias, labels,
                                                mask)
    assert (float(count), float(share)) == (17.0, 1.0)


@pytest.mark.parametrize("shard_batch, seq, columns", [
    (48, 512, 16), (96, 512, 8), (8, 512, 128), (2, 16, 16), (4, 30, 30),
    (2048, 512, 1), (48, 17, 17), (100, 34, 2)])
def test_a_chunk_is_a_divisor_of_the_sequence_near_a_thousand_rows(
        shard_batch, seq, columns):
    assert labelled_nll.chunk_columns(shard_batch, seq) == columns


def test_the_training_loss_never_holds_the_logits_at_every_position(
        monkeypatch):
    """The step's program has no array of ``B x S x vocab`` elements, a
    chunk's logits at most; the inference surface still gives the logits
    at every position."""
    monkeypatch.setattr(labelled_nll, "_CHUNK_ROWS", 4 * B)
    model, params, batch = _model_and_batch()
    batch["mlm_mask"] = jnp.ones((B, S))
    text = jax.jit(jax.value_and_grad(
        lambda p: model.loss(p, training=True, **batch)[0])).lower(
            params).as_text()
    assert f"{B}x{S}x{VOCAB}x" not in text and f"{B}x4x{VOCAB}x" in text
    mlm_logits, nsp_logits = model(
        params, batch["input_ids"], batch["token_type_ids"],
        batch["attention_mask"])
    assert mlm_logits.shape == (B, S, VOCAB) and nsp_logits.shape == (B, 2)
