"""BERT (Devlin et al. 2018) with the MLM + NSP pre-training heads: the
program's model from the published ``config.json`` keys, and a plain
reference of the evaluation-mode loss.

Departures of the PROGRAM from the published model, which the reference
follows so that the two compute the same function: LayerNorm epsilon is
1e-5 (published 1e-12), GELU is the tanh form (published: erf), and the
MLM head's decoder is the word table (as published) applied at every
position. The reference reads the program's parameter tree but shares
no code with it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def build(sizes: dict, *, interpret: bool = False):
    """The program's ``BertForPretraining`` for the published ``sizes``."""
    from paddle_tpu.models.bert import BertConfig, BertForPretraining
    kw = dict(
        vocab_size=sizes["vocab_size"], hidden_size=sizes["hidden_size"],
        num_layers=sizes["num_hidden_layers"],
        num_heads=sizes["num_attention_heads"],
        ffn_size=sizes["intermediate_size"],
        max_position=sizes["max_position_embeddings"],
        type_vocab_size=sizes["type_vocab_size"],
        dropout=sizes["hidden_dropout_prob"],
        attn_dropout=sizes["attention_probs_dropout_prob"])
    if interpret:
        kw["attn_impl"] = "flash_interpret"
    return BertForPretraining(BertConfig(**kw))


def _layer_norm(p, x, eps=1e-5):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)))


def _dense(p, x):
    return x @ p["weight"] + p["bias"]


def reference_loss(params, batch, n_head: int):
    """Evaluation-mode (no dropout) MLM + NSP loss in float32 with
    composed attention. Call under
    ``jax.default_matmul_precision("highest")``."""
    p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    ids = batch["input_ids"]
    b, s = ids.shape
    emb = p["bert"]["embeddings"]
    x = emb["word"]["weight"][ids] \
        + emb["position"]["weight"][jnp.arange(s)][None] \
        + emb["token_type"]["weight"][batch["token_type_ids"]]
    x = _layer_norm(emb["ln"], x)
    d = x.shape[-1]
    dh = d // n_head
    keep = batch["attention_mask"].astype(bool)[:, None, None, :]
    enc = p["bert"]["encoder"]
    for i in range(len(enc)):
        lp = enc[str(i)]
        qkv = _dense(lp["attn"]["qkv_proj"], x)
        q, k, v = (t.reshape(b, s, n_head, dh).transpose(0, 2, 1, 3)
                   for t in jnp.split(qkv, 3, axis=-1))
        att = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(float(dh))
        att = jax.nn.softmax(jnp.where(keep, att, -jnp.inf), axis=-1)
        o = jnp.einsum("bhqk,bhkd->bhqd", att, v)
        o = o.transpose(0, 2, 1, 3).reshape(b, s, d)
        x = _layer_norm(lp["ln1"], x + _dense(lp["attn"]["out_proj"], o))
        h = _dense(lp["ffn"]["fc2"], _gelu_tanh(_dense(lp["ffn"]["fc1"], x)))
        x = _layer_norm(lp["ln2"], x + h)
    pooled = jnp.tanh(_dense(p["bert"]["pooler"], x[:, 0]))
    heads = p["heads"]
    h = _layer_norm(heads["ln"], _gelu_tanh(_dense(heads["transform"], x)))
    mlm_logits = h @ emb["word"]["weight"].T + heads["decoder_bias"]
    nsp_logits = _dense(heads["nsp"], pooled)
    mlm_lp = jax.nn.log_softmax(mlm_logits, axis=-1)
    nll = -jnp.take_along_axis(mlm_lp, batch["mlm_labels"][..., None],
                               axis=-1)[..., 0]
    mask = batch["mlm_mask"].astype(jnp.float32)
    mlm_loss = (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    nsp_lp = jax.nn.log_softmax(nsp_logits, axis=-1)
    nsp_loss = -jnp.take_along_axis(
        nsp_lp, batch["nsp_labels"][:, None], axis=-1).mean()
    return mlm_loss + nsp_loss
